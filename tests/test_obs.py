"""Observability layer (DESIGN §8): telemetry planes, frame ring,
flight recorder, heatmap exporter.

Pins the four contracts of ``repro.obs``:

* ``telemetry=False`` (the default) is bit-exact with the recorded
  pre-PR engine on both backends — the planes collapse to 1x1 dummies
  and the cycle graph is unchanged;
* ``telemetry=True`` changes no semantics: same counters and values,
  and the FINAL frame of each increment reconciles EXACTLY with the
  scalar counters (cumulative planes reset with ``stat_*``) — on both
  backends and on both drivers (sync-free device loop and traced host
  loop);
* the livelock flight recorder raises a structured
  :class:`LivelockError` carrying the frame log and naming the wedged
  cells/lanes of the known §4.2 hub deadlock;
* the congestion-heatmap exporter preserves the totals it
  re-aggregates.
"""
import json
import pathlib

import numpy as np
import pytest

from repro.core import EngineConfig, StreamingEngine
from repro.core.engine import LivelockError
from repro.core.state import TM_EXEC, TM_HOP, TM_IO
from repro.graph.streams import StreamSpec, hub_edges, make_stream
from repro.obs import (FS_CYCLE, FrameLog, congestion_heatmap, engine_rates,
                       summarize, wedged_cells, wedged_lanes)

ONE = np.float32(1.0).view(np.int32)
REF = json.loads((pathlib.Path(__file__).parent
                  / "data" / "pre_lanes_reference.json").read_text())


def _ref_engine(backend, **kw):
    eng = StreamingEngine(
        EngineConfig(backend=backend, **REF["cfg"], **kw), "bfs")
    eng.seed(0, 0.0)
    return eng, make_stream(StreamSpec(**REF["spec"]))


# ---------------- telemetry=False stays bit-exact (both backends) --------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_telemetry_off_bit_exact_vs_pre_pr(backend):
    """With telemetry off (explicit) the engine replays the recorded
    pre-PR fingerprint exactly — the telemetry refactor is free."""
    eng, incs = _ref_engine(backend, telemetry=False)
    rows = []
    for e in incs:
        r = eng.run_increment(e, max_cycles=500_000)
        rows.append(dict(cycles=r.cycles, hops=r.hops, execs=r.execs,
                         stalls=r.stalls, allocs=r.allocs))
        assert r.frames is None
    want = REF["backends"][backend]
    assert rows == want["increments"]
    np.testing.assert_array_equal(eng.values(128), np.array(want["values"]))


# ------------- telemetry=True: same semantics + exact reconcile ----------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_telemetry_on_counters_and_frames_reconcile(backend):
    """Telemetry on: identical counters/values as the fingerprint, and
    every increment's final frame reconciles exactly with its scalar
    counters (DESIGN §8 invariants)."""
    eng, incs = _ref_engine(backend, telemetry=True, frame_ring=16)
    want = REF["backends"][backend]
    for e, w in zip(incs, want["increments"]):
        r = eng.run_increment(e, max_cycles=500_000)
        got = dict(cycles=r.cycles, hops=r.hops, execs=r.execs,
                   stalls=r.stalls, allocs=r.allocs)
        assert got == w
        assert isinstance(r.frames, FrameLog) and len(r.frames) >= 2
        t = r.frames.totals()
        assert t["quiescent"] and t["backlog"] == 0 and t["in_flight"] == 0
        assert (t["hops"], t["execs"], t["stalls"], t["allocs"]) == \
            (r.hops, r.execs, r.stalls, r.allocs)
        # the per-cell planes reconcile with the same counters: every
        # hop/exec/insert is attributed to exactly one cell
        last = r.frames.last()
        assert int(last["cell"][..., TM_HOP].sum()) == r.hops
        assert int(last["cell"][..., TM_EXEC].sum()) == r.execs
        assert int(last["cell"][..., TM_IO].sum()) == len(e)
    np.testing.assert_array_equal(eng.values(128), np.array(want["values"]))


def test_device_loop_frames_match_traced_host_loop():
    """The sync-free device loop and the traced host loop record the
    same frame totals over the full BFS stream (same snapshot schema,
    different drivers)."""
    eng_d, incs = _ref_engine("jnp", telemetry=True, frame_ring=16)
    eng_t, _ = _ref_engine("jnp", telemetry=True, frame_ring=16)
    for e in incs:
        rd = eng_d.run_increment(e, max_cycles=500_000)
        rt = eng_t.run_increment(e, max_cycles=500_000,
                                 collect_traces=True)
        assert rd.frames.totals() == rt.frames.totals()
        np.testing.assert_array_equal(rd.frames.last()["cell"],
                                      rt.frames.last()["cell"])
        np.testing.assert_array_equal(rd.frames.last()["lane"],
                                      rt.frames.last()["lane"])


def test_frame_ring_wraps_and_keeps_newest():
    """A tiny ring on a long increment drops the oldest frames but keeps
    the final (reconciling) frame; deltas() switches to window-only."""
    eng, incs = _ref_engine("jnp", telemetry=True, frame_ring=2)
    r = eng.run_increment(incs[1], max_cycles=500_000)
    assert len(r.frames) == 2 and r.frames.dropped > 0
    assert r.frames.totals()["hops"] == r.hops
    d = r.frames.deltas()
    assert d["cell"].shape[0] == len(r.frames) - 1
    # cumulative planes are monotone, so the in-window delta is >= 0
    assert (d["cell"] >= 0).all() and (d["scal"][:, FS_CYCLE] > 0).all()


# --------------------- livelock flight recorder --------------------------

def _hub_cfg(**kw):
    base = dict(height=8, width=8, n_vertices=128, edge_cap=4,
                ghost_slots=48, queue_cap=20, chan_cap=16, futq_cap=4,
                io_stream_cap=2048, chunk=64, lanes=1)
    base.update(kw)
    return EngineConfig(**base)


def _hub_stream(n=128, degree=200, seed=3):
    e = hub_edges(n, 0, degree, seed=seed)
    return np.concatenate([e, np.full((len(e), 1), ONE, np.int64)],
                          1).astype(np.int32)


def test_flight_recorder_names_wedged_cells():
    """The known §4.2 hub livelock raises LivelockError with frames, and
    the wedge analysis names the hub cell (0,0) — whose action queue is
    full — plus the row-0 lanes feeding it."""
    eng = StreamingEngine(_hub_cfg(telemetry=True, frame_ring=16), "bfs")
    eng.seed(0, 0.0)
    with pytest.raises(LivelockError) as ei:
        eng.run_increment(_hub_stream(), max_cycles=500_000)
    err = ei.value
    assert err.cycle > 0 and err.chunk > 0
    assert isinstance(err.frames, FrameLog) and len(err.frames) >= 2
    cells = wedged_cells(eng.cfg, err.frames)
    lanes = wedged_lanes(eng.cfg, err.frames)
    assert cells, "no wedged cells found at livelock"
    assert (0, 0) in [d["cell"] for d in cells]   # the hub vertex's cell
    hub = next(d for d in cells if d["cell"] == (0, 0))
    assert hub["aq"] > 0 and hub["aq_hiwater"] >= hub["aq"]
    assert lanes, "no wedged lanes found at livelock"
    assert all(e["occ"] > 0 for e in lanes)
    # the rendered report names the machinery for humans too
    assert "flight recorder" in str(err) and "cell (0,0)" in str(err)


def test_livelock_without_telemetry_is_structured_but_frameless():
    """Telemetry off: the detector still raises the structured error
    (catchable without regex), just with no frame log attached."""
    eng = StreamingEngine(_hub_cfg(), "bfs")
    eng.seed(0, 0.0)
    with pytest.raises(LivelockError) as ei:
        eng.run_increment(_hub_stream(), max_cycles=500_000)
    assert ei.value.frames is None
    assert "livelock" in str(ei.value)     # back-compat substring


# ----------------------------- exporter ----------------------------------

def _frames(backend="jnp"):
    eng, incs = _ref_engine(backend, telemetry=True, frame_ring=16)
    r = eng.run_increment(incs[0], max_cycles=500_000)
    return eng.cfg, r


def test_congestion_heatmap_totals_and_report_render():
    cfg, r = _frames()
    heat = congestion_heatmap(cfg, r.frames)
    assert heat["grid"] == [cfg.height, cfg.width]
    assert sum(map(sum, heat["stages"]["hop"])) == r.hops
    assert sum(map(sum, heat["stages"]["exec"])) == r.execs
    assert max(map(max, heat["aq_hiwater"])) > 0
    # the report renderer consumes the dump (satellite: report.py)
    from benchmarks.report import congestion_section
    md = congestion_section(heat)
    assert "message arrivals" in md and "```" in md


def test_engine_rates_and_summarize():
    cfg, r = _frames()
    rates = engine_rates(r.frames)
    assert rates["cycles"] == r.cycles
    assert rates["execs_per_cycle"] == pytest.approx(r.execs / r.cycles)
    assert rates["peak_backlog"] >= 0
    s = summarize([1.0, 2.0, 3.0, 4.0], "ms")
    assert s["n"] == 4 and s["p50"] == pytest.approx(2.5)
    assert s["max"] == 4.0 and s["p99"] <= 4.0
