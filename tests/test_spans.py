"""The engine's own instrumentation on the profiler's clock (DESIGN §8).

* every machine stage of ``cycle_body``, the quiescence test and the
  chunk bookkeeping run under a ``cca.*`` ``jax.named_scope``, which the
  compiled device loop keeps as ``op_name`` metadata of its ops;
* an increment opens the host spans ``repro.increment`` ⊃
  ``repro.load_stream`` ⊃ ``.fetch`` / ``.upload``, then
  ``repro.reset_counters``, ``repro.dispatch`` and ``repro.wait``, all
  tagged with the same ``inc``; an ``MQSession`` increment adds
  ``repro.mq.fold``.
"""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import EngineConfig, StreamingEngine
from repro.core.engine import _increment_device_loop
from repro.graph.streams import StreamSpec, make_stream
from repro.mq.session import MQSession

TINY = dict(height=8, width=8, n_vertices=64, ghost_slots=16,
            io_stream_cap=256, chunk=32, lanes=2)
MACHINE_SCOPES = {"cca.hop", "cca.park", "cca.staging", "cca.phase0",
                  "cca.io", "cca.quiescent", "cca.chunk"}


def _stream(n_inc=2):
    return make_stream(StreamSpec(n_vertices=64, n_edges=256,
                                  increments=n_inc, seed=3))


def _compiled_scopes(eng) -> set:
    txt = _increment_device_loop.lower(
        eng.cfg, eng.app, eng.state, jnp.int32(1000)).compile().as_text()
    return {c for name in re.findall(r'op_name="([^"]*)"', txt)
            for c in name.split("/") if c.startswith("cca.")}


@pytest.mark.parametrize("kind,telemetry", [("engine", False),
                                            ("mq2", False),
                                            ("engine", True)])
def test_compiled_device_loop_carries_every_stage_scope(kind, telemetry):
    cfg = EngineConfig(**TINY, telemetry=telemetry)
    eng = (StreamingEngine(cfg, "bfs") if kind == "engine"
           else MQSession(cfg, qbatch=2, apps=["bfs", "sssp"]).eng)
    want = MACHINE_SCOPES | ({"cca.telemetry"} if telemetry else set())
    assert _compiled_scopes(eng) == want


def _host_spans(run) -> list:
    """``(start, end, name, stats)`` of every ``repro.*`` host event that
    ``run()`` emits under a profiler trace, in start order."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        (pb,) = pathlib.Path(d).rglob("*.xplane.pb")
        pd = ProfileData.from_file(str(pb))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("repro."))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_increment_spans_nest_and_share_the_increment_id():
    eng = StreamingEngine(EngineConfig(**TINY), "bfs")
    eng.seed(0, 0.0)
    incs = _stream()
    eng.run_increment(incs[0])              # compile outside the trace
    spans = _host_spans(lambda: eng.run_increment(incs[1]))
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)
    (inc,) = by["repro.increment"]
    assert inc[3] == dict(inc=1, edges=len(incs[1]))
    (load,) = by["repro.load_stream"]
    (fetch,) = by["repro.load_stream.fetch"]
    (upload,) = by["repro.load_stream.upload"]
    assert _inside(load, inc) and _inside(fetch, load)
    assert _inside(upload, load) and fetch[1] <= upload[0]
    (reset,) = by["repro.reset_counters"]
    assert _inside(reset, inc) and load[1] <= reset[0]
    passes = list(zip(by["repro.dispatch"], by["repro.wait"]))
    assert passes
    for dispatch, wait in passes:
        assert _inside(dispatch, inc) and _inside(wait, inc)
        assert reset[1] <= dispatch[0] and dispatch[1] <= wait[0]
    assert all(s[3].get("inc") == 1 for s in spans)
    assert "repro.mq.fold" not in by


def test_mq_increment_emits_the_fold_span():
    ses = MQSession(EngineConfig(**TINY), qbatch=2, apps=["bfs", "sssp"])
    ses.admit("bfs", 0, slot=0)
    ses.admit("sssp", 1, slot=1)
    incs = _stream()
    ses.run_increment(incs[0])
    spans = _host_spans(lambda: ses.run_increment(incs[1]))
    (inc,) = [s for s in spans if s[2] == "repro.increment"]
    (fold,) = [s for s in spans if s[2] == "repro.mq.fold"]
    assert inc[1] <= fold[0] and fold[3] == dict(inc=1)
    assert {s[3].get("inc") for s in spans} == {1}
    assert np.isfinite(ses.values(0)).any()
