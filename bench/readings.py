"""The arithmetic of the metric readers under ``bench/metrics/``, shared
by readers that report one quantity in different cells.  Each function
takes the run's :class:`bench.harness.RunView` and returns ``None`` where
the run holds nothing to read."""
from __future__ import annotations


def done_batches(view) -> list:
    return [r for r in view.window["batches"] if r["done"] is not None]


def edges_per_s(view) -> float | None:
    """Edges of every batch done in the window over the whole window,
    from its start to the end of its last batch."""
    done = done_batches(view)
    if not done:
        return None
    return sum(r["edges"] for r in done) / (
        max(r["done"] for r in done) - view.window["t0"])


def machine_cycles_per_kedge(view) -> float | None:
    """Machine cycles the window's batches took per thousand edges."""
    done = done_batches(view)
    edges = sum(r["edges"] for r in done)
    if not edges:
        return None
    return 1000.0 * sum(r["result"].cycles for r in done) / edges


def traced_batches(view) -> list:
    """The done batches of the window's traced tail."""
    k = view.window.get("tail_from")
    if k is None:
        return []
    return [r for r in view.window["batches"][k:view.window["tail_to"]]
            if r["done"] is not None]


def device_ms_per_cycle(view) -> float | None:
    """Device time of the engine's device loop in the traced tail per
    machine cycle that the tail's batches ran."""
    if view.trace is None or not view.trace["loop_ns"]:
        return None
    cycles = sum(r["result"].cycles for r in traced_batches(view))
    if not cycles:
        return None
    return view.trace["loop_ns"] / 1e6 / cycles


def device_idle_pct(view) -> float | None:
    """Share of the traced tail in which no program ran on the device."""
    if view.trace is None or not view.trace["window_ns"]:
        return None
    return 100.0 * (1.0 - view.trace["busy_ns"] / view.trace["window_ns"])
