"""OP_RHIZOME_FWD actions executed (sibling broadcasts and link acks) per
thousand edges ingested in the window, from the engine's IncrementResult
counts (the same on every platform); nothing where the engine has no
such count."""
from bench.readings import done_batches


def read(view):
    done = done_batches(view)
    edges = sum(r["edges"] for r in done)
    fwds = [getattr(r["result"], "rfwds", None) for r in done]
    if not edges or None in fwds:
        return None
    return 1000.0 * sum(fwds) / edges
