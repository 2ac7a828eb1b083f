"""``repro.obs`` — sync-free on-device telemetry (DESIGN §8).

The observability layer rides the engine's fast path instead of
bypassing it:

* ``frames``  — the per-chunk snapshot schema (:class:`Frame` fields),
  the fixed-size on-device :class:`FrameRing` carried through the
  sync-free device loop, and the host-side :class:`FrameLog` readback;
* ``flight``  — the livelock flight recorder: post-mortem wedge
  analysis over the last recorded frames and the rendered
  "who is wedged" report attached to :class:`LivelockError`;
* ``export``  — the congestion-heatmap dump consumed by
  ``benchmarks/report.py``;
* ``spans``   — host spans on the ``jax.profiler`` clock around the
  steps of each increment (the device side carries one
  ``jax.named_scope`` per machine stage, ``cca.*``);
* ``metrics`` — small latency/throughput summary helpers used by the
  serving surface (``launch/serve.py``).

The telemetry planes themselves live in ``core.state.MachineState``
(``tm_cell`` / ``tm_lane`` / ``tm_hiw``) and are accumulated inside the
cycle stages when ``EngineConfig.telemetry`` is on — both backends (jnp
chunk runners and the Pallas cycle megakernel) inherit them through
``cycle_body`` with zero extra host syncs.
"""
from repro.obs.export import congestion_heatmap, write_heatmap
from repro.obs.flight import (render_wedge_report, wedged_cells,
                              wedged_lanes)
from repro.obs.frames import (FS_ALLOCS, FS_BACKLOG, FS_CYCLE, FS_EXEC,
                              FS_HOPS, FS_INFLIGHT, FS_QUIESCENT, FS_STALL,
                              FrameLog, FrameRing, init_ring, ring_store,
                              snapshot)
from repro.obs.metrics import engine_rates, render_summary, summarize
from repro.obs.spans import increment, span

__all__ = [
    "FrameLog", "FrameRing", "init_ring", "ring_store", "snapshot",
    "FS_CYCLE", "FS_HOPS", "FS_EXEC", "FS_STALL", "FS_ALLOCS",
    "FS_BACKLOG", "FS_INFLIGHT", "FS_QUIESCENT",
    "congestion_heatmap", "write_heatmap",
    "render_wedge_report", "wedged_cells", "wedged_lanes",
    "engine_rates", "render_summary", "summarize", "increment", "span",
]
