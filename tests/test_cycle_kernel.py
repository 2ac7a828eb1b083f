"""Fused Pallas cycle-megakernel parity (DESIGN §6).

Mirrors test_dist_cca_parity: the ``backend="pallas"`` engine
(interpret mode on CPU) must be BIT-EXACT per state leaf against the
``backend="jnp"`` engine over a full BFS-to-quiescence stream — plus
the sync-free driver equivalences (``collect_traces=False`` totals ==
traced totals) and identical livelock-detector behaviour on both
backends.
"""
import jax
import numpy as np
import pytest

from repro.core import EngineConfig, StreamingEngine
from repro.core.apps import BFS
from repro.core.ingest import load_stream
from repro.core.reference import bfs_levels
from repro.graph.streams import StreamSpec, make_stream
from repro.kernels.cca_cycle.ops import cca_cycle_chunk
from repro.kernels.cca_cycle.ref import cca_cycle_chunk_ref

ONE = np.float32(1.0).view(np.int32)


def small_cfg(**kw):
    base = dict(height=8, width=8, n_vertices=128, edge_cap=4,
                ghost_slots=32, queue_cap=32, chan_cap=8, futq_cap=8,
                io_stream_cap=2048, chunk=64)
    base.update(kw)
    return EngineConfig(**base)


def assert_states_equal(sa, sb, ctx=""):
    for name, a, b in zip(sa._fields, sa, sb):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"state leaf '{name}' diverged {ctx}")


def run_bfs(cfg, incs, **kw):
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    rs = [eng.run_increment(e, max_cycles=500_000, **kw) for e in incs]
    return eng, rs


def test_megakernel_chunk_bit_exact_vs_ref():
    """One pallas_call (interpret) == the pure-jnp reference chunk, per
    state leaf and per SMEM counter, chunk by chunk to quiescence."""
    rng = np.random.default_rng(0)
    E = 160
    edges = np.stack([rng.integers(0, 64, E), rng.integers(0, 64, E),
                      np.full(E, ONE)], 1).astype(np.int32)
    cfg = small_cfg(n_vertices=64, ghost_slots=16, io_stream_cap=256,
                    chunk=32)
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    cfg = eng.cfg
    st, spill = load_stream(cfg, eng.state, edges)
    assert len(spill) == 0
    fk = jax.jit(lambda s: cca_cycle_chunk(cfg, BFS, s, interpret=True))
    fr = jax.jit(lambda s: cca_cycle_chunk_ref(cfg, BFS, s))
    sk, sr = st, st
    for i in range(70):
        sk, ck = fk(sk)
        sr, cr = fr(sr)
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr),
                                      err_msg=f"counters, chunk {i}")
        assert_states_equal(sk, sr, f"(kernel vs ref, chunk {i})")
        if bool(np.asarray(ck)[0]):
            break
    assert bool(np.asarray(ck)[0]), "stream did not quiesce in 70 chunks"
    eng.state = sk
    np.testing.assert_array_equal(eng.values(64), bfs_levels(64, edges, 0))


def test_backend_bit_exact_full_stream():
    """backend="pallas" engine == backend="jnp" engine, bit-exact per
    state leaf over a multi-increment BFS stream, identical cycle counts
    and totals, and both exactly NetworkX."""
    spec = StreamSpec(n_vertices=128, n_edges=768, increments=3, seed=7)
    incs = make_stream(spec)
    want = bfs_levels(128, np.concatenate(incs), 0)
    engines, cycles = {}, {}
    for backend in ("jnp", "pallas"):
        eng, rs = run_bfs(small_cfg(backend=backend, chunk=128), incs)
        np.testing.assert_array_equal(eng.values(128), want)
        engines[backend] = eng
        cycles[backend] = [r.cycles for r in rs]
    assert cycles["jnp"] == cycles["pallas"]
    assert engines["jnp"].totals == engines["pallas"].totals
    assert_states_equal(engines["jnp"].state, engines["pallas"].state,
                        "(jnp vs pallas backend)")


def test_backend_parity_rhizome_cap():
    """rhizome_cap > 1 (multi-root protocol incl. OP_LINK_RHIZOME /
    OP_RHIZOME_FWD) behaves identically on both backends."""
    hub = np.array([(0, i, ONE) for i in range(1, 41)], np.int32)
    engines = {}
    for backend in ("jnp", "pallas"):
        cfg = small_cfg(n_vertices=64, ghost_slots=16, futq_cap=4,
                        rhizome_cap=4, backend=backend)
        eng, _ = run_bfs(cfg, [hub])
        np.testing.assert_array_equal(eng.values(64),
                                      bfs_levels(64, hub, 0))
        engines[backend] = eng
    assert (engines["jnp"].vertex_object_stats()
            == engines["pallas"].vertex_object_stats())
    assert_states_equal(engines["jnp"].state, engines["pallas"].state,
                        "(rhizome_cap=4)")


def test_collect_traces_equivalence():
    """The sync-free fast path returns the same IncrementResult totals
    and final state as the traced host loop; only the per-cycle traces
    differ (empty vs length == cycles)."""
    spec = StreamSpec(n_vertices=128, n_edges=768, increments=3, seed=11)
    incs = make_stream(spec)
    fast, rf = run_bfs(small_cfg(), incs)                  # default: fast
    traced, rt = run_bfs(small_cfg(), incs, collect_traces=True)
    for a, b in zip(rf, rt):
        assert (a.cycles, a.hops, a.execs, a.stalls, a.allocs) \
            == (b.cycles, b.hops, b.execs, b.stalls, b.allocs)
        assert len(a.active_per_cycle) == 0
        assert len(a.in_flight_per_cycle) == 0
        assert len(b.active_per_cycle) == b.cycles
    assert fast.totals == traced.totals
    assert fast.total_cycles == traced.total_cycles
    assert_states_equal(fast.state, traced.state, "(fast vs traced)")


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_livelock_detector_both_backends(backend):
    """DESIGN §4.2: undersized buffers must raise identically whether the
    detector runs host-side (traced) or folded into the device loop."""
    spec = StreamSpec(n_vertices=64, n_edges=400, increments=2, seed=21)
    incs = make_stream(spec)
    cfg = small_cfg(n_vertices=64, edge_cap=2, ghost_slots=48,
                    queue_cap=8, chan_cap=2, futq_cap=2, backend=backend)
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    with pytest.raises(RuntimeError, match="livelock"):
        for e in incs:
            eng.run_increment(e, max_cycles=500_000)


def test_fast_path_single_jit_per_pass(monkeypatch):
    """O(1) host<->device syncs: exactly one device-loop invocation per
    spill pass of run_increment (here: one pass -> one call)."""
    import repro.core.engine as engine_mod
    calls = []
    orig = engine_mod._increment_device_loop

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(engine_mod, "_increment_device_loop", counting)
    spec = StreamSpec(n_vertices=128, n_edges=512, increments=2, seed=5)
    incs = make_stream(spec)
    eng, _ = run_bfs(small_cfg(), incs)
    assert len(calls) == len(incs)  # no spill -> one jit call each


def test_pallas_backend_refused_on_tpu(monkeypatch):
    """The megakernel cannot lower on Mosaic, so a TPU backend rejects
    ``backend="pallas"`` at construction instead of deep in a compile."""
    from repro.core.engine import PALLAS_ON_TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="scatter") as ei:
        StreamingEngine(small_cfg(backend="pallas"), "bfs")
    assert str(ei.value) == PALLAS_ON_TPU
    StreamingEngine(small_cfg(backend="jnp"), "bfs")   # jnp still builds
