"""OP_LINK_RHIZOME requests emitted (an insert reaching a secondary
rhizome root that was neither active nor linking) per thousand edges
ingested in the window, from the engine's IncrementResult counts (the
same on every platform); nothing where the engine has no such count."""
from bench.readings import done_batches


def read(view):
    done = done_batches(view)
    edges = sum(r["edges"] for r in done)
    links = [getattr(r["result"], "rlinks", None) for r in done]
    if not edges or None in links:
        return None
    return 1000.0 * sum(links) / edges
