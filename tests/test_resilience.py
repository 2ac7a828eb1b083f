"""Resilience layer (DESIGN §9): deterministic fault injection with
detection + repair, durable checkpoint/restore (kill-and-resume), and
self-healing livelock recovery.

The exactness bar is the same as everywhere else in this repo: a faulty
run must converge to the NetworkX-exact values (via the repair pass),
kill-and-resume must be BIT-exact with the uninterrupted run on both
backends, and ``faults=None`` / ``recover=None`` must leave the engine
bit-identical to the pre-resilience driver (pinned by the fingerprint
tests in test_lanes / test_engine, which run this same code with the
resilience knobs off).
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import EngineConfig, StreamingEngine
from repro.core.engine import LivelockError
from repro.core.msg import OP_APP, OP_REPAIR, N_OPS, make_msg, msg_seal, seal_msg
from repro.core.reference import bfs_levels
from repro.graph.streams import hub_edges
from repro.resilience import (FLT_BLACKOUT, FLT_CORRUPT, FLT_DROP, FLT_DUP,
                              FaultPlan, RecoveryPolicy, config_fingerprint,
                              fault_hash16, migrate_state)
from repro.train.checkpoint import Checkpointer

ONE = np.float32(1.0).view(np.int32)
BACKENDS = ("jnp", "pallas")


def _hub_stream(n=256, degree=120, seed=3):
    e = hub_edges(n, 0, degree, seed=seed)
    return np.concatenate([e, np.full((len(e), 1), ONE, np.int64)],
                          1).astype(np.int32)


def _cfg(**kw):
    base = dict(height=8, width=8, n_vertices=256, edge_cap=8,
                ghost_slots=24, queue_cap=32, chan_cap=16, chunk=64,
                lanes=2, max_cycles=200_000, backend="jnp", telemetry=True)
    base.update(kw)
    return EngineConfig(**base)


def _run(cfg, edges, **kw):
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    res = eng.run_increment(edges, **kw)
    return eng, res


# ------------------------- fault plan mechanics -------------------------

def test_fault_hash_deterministic_and_uniform():
    import jax.numpy as jnp
    cyc = jnp.arange(512)
    a = np.asarray(fault_hash16(7, cyc, 13, 1))
    b = np.asarray(fault_hash16(7, cyc, 13, 1))
    np.testing.assert_array_equal(a, b)          # same inputs, same bits
    assert a.min() >= 0 and a.max() < 65536
    # decisions decorrelate across salt, link and seed
    assert not np.array_equal(a, np.asarray(fault_hash16(7, cyc, 13, 2)))
    assert not np.array_equal(a, np.asarray(fault_hash16(7, cyc, 14, 1)))
    assert not np.array_equal(a, np.asarray(fault_hash16(8, cyc, 13, 1)))
    # a 5% threshold admits roughly 5% of a long window (static rate)
    frac = (a < int(0.05 * 65536)).mean()
    assert 0.01 < frac < 0.12


def test_fault_plan_validation():
    plan = FaultPlan(seed=1, drop_rate=0.5)
    assert plan.drop_thr == int(0.5 * 65536)
    s = plan.safe()
    assert s.drop_thr == 0 and s.blackouts == ()
    with pytest.raises(AssertionError):
        _cfg(faults=FaultPlan(drop_rate=1.5)).validate()
    with pytest.raises(AssertionError):   # blackout cell off the grid
        _cfg(faults=FaultPlan(blackouts=((9, 0, 2, 0, 4),))).validate()


def test_repair_op_and_seal():
    assert OP_REPAIR < N_OPS
    m = make_msg(OP_APP, np.int32(37), np.int32(-123456789))
    sealed = np.asarray(seal_msg(m))
    assert sealed[4] == np.asarray(msg_seal(m))
    # any single bit flip in the payload words breaks the seal
    bad = sealed.copy()
    bad[2] ^= 1 << 11
    assert np.asarray(msg_seal(bad)) != bad[4]


# ------------------- injected faults, exact convergence -------------------

def test_zero_rate_plan_bit_exact():
    edges = _hub_stream()
    e0, r0 = _run(_cfg(), edges)
    e1, r1 = _run(_cfg(faults=FaultPlan(seed=7)), edges)
    assert r1.cycles == r0.cycles
    np.testing.assert_array_equal(e1.values(), e0.values())
    np.testing.assert_array_equal(np.asarray(e1.state.vals),
                                  np.asarray(e0.state.vals))
    assert np.asarray(e1.state.flt).sum() == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_faulty_stream_converges_exact(backend):
    """Seeded drop+dup+corrupt on the hub stream: messages demonstrably
    lost, end state still NetworkX-exact via the §9 repair pass."""
    edges = _hub_stream()
    ref = bfs_levels(256, edges[:, :2], source=0)
    plan = FaultPlan(seed=7, drop_rate=0.05, dup_rate=0.03,
                     corrupt_rate=0.02)
    eng, _ = _run(_cfg(backend=backend, faults=plan), edges)
    flt = np.asarray(eng.state.flt)
    assert flt[FLT_DROP] > 0 and flt[FLT_DUP] > 0 and flt[FLT_CORRUPT] > 0
    np.testing.assert_array_equal(eng.values(), ref)


def test_backends_bit_exact_under_faults():
    """The injected hazards are part of the cycle semantics: both
    backends must take the SAME faults and land on the same state."""
    edges = _hub_stream()
    plan = FaultPlan(seed=7, drop_rate=0.05, dup_rate=0.03,
                     corrupt_rate=0.02)
    ej, _ = _run(_cfg(backend="jnp", faults=plan), edges)
    ep, _ = _run(_cfg(backend="pallas", faults=plan), edges)
    np.testing.assert_array_equal(np.asarray(ej.state.flt),
                                  np.asarray(ep.state.flt))
    np.testing.assert_array_equal(np.asarray(ej.state.vals),
                                  np.asarray(ep.state.vals))


def test_blackout_is_lossless_delay():
    """A link blackout only delays traffic (senders retry): messages hit
    the dead window but nothing is lost, so no repair is needed and the
    values are exact without any OP_REPAIR traffic."""
    edges = _hub_stream()
    ref = bfs_levels(256, edges[:, :2], source=0)
    # hub vid 0 lives at cell (0,0): its inbound row-0 W links carry the
    # flood, so blacking them out early is guaranteed to be exercised
    plan = FaultPlan(seed=7, blackouts=((0, 1, 2, 0, 64), (0, 2, 2, 0, 64)))
    eng, res = _run(_cfg(faults=plan), edges)
    flt = np.asarray(eng.state.flt)
    assert flt[FLT_BLACKOUT] > 0
    assert flt[FLT_DROP] == 0 and flt[FLT_CORRUPT] == 0
    assert res.execs == int(np.asarray(eng.state.stat_exec))
    np.testing.assert_array_equal(eng.values(), ref)


def test_duplicates_are_idempotent():
    """Duplicate delivery alone (no loss) must not perturb the fixpoint:
    monotone relaxation absorbs replays."""
    edges = _hub_stream()
    ref = bfs_levels(256, edges[:, :2], source=0)
    eng, _ = _run(_cfg(faults=FaultPlan(seed=11, dup_rate=0.08)), edges)
    flt = np.asarray(eng.state.flt)
    assert flt[FLT_DUP] > 0 and flt[FLT_DROP] == 0
    np.testing.assert_array_equal(eng.values(), ref)


def test_faulty_multi_increment_stream():
    """Faults + repair across several increments of one growing graph."""
    edges = _hub_stream()
    ref = bfs_levels(256, edges[:, :2], source=0)
    plan = FaultPlan(seed=3, drop_rate=0.04, corrupt_rate=0.02)
    eng = StreamingEngine(_cfg(faults=plan), "bfs")
    eng.seed(0, 0.0)
    for lo, hi in ((0, 150), (150, 300), (300, len(edges))):
        eng.run_increment(edges[lo:hi])
    assert eng.stream_pos == 3
    np.testing.assert_array_equal(eng.values(), ref)


# ------------------- durable state: kill-and-resume -------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_kill_and_resume_bit_exact(backend, tmp_path):
    """Checkpoint at an increment boundary, throw the engine away,
    restore, replay the tail: every state leaf bit-equal to the
    uninterrupted run."""
    edges = _hub_stream()
    incs = [edges[:200], edges[200:350], edges[350:]]
    cfg = _cfg(backend=backend)

    ref = StreamingEngine(cfg, "bfs")
    ref.seed(0, 0.0)
    for inc in incs:
        ref.run_increment(inc)

    ck = Checkpointer(tmp_path)
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    for inc in incs[:2]:
        eng.run_increment(inc, ckpt=ck)   # async boundary saves
    eng.checkpoint(ck)                    # boundary after increment 2
    del eng                               # "kill -9"

    res = StreamingEngine.restore(cfg, "bfs", Checkpointer(tmp_path))
    assert res.stream_pos == 2
    res.run_increment(incs[2])
    assert res.totals == ref.totals
    assert res.total_cycles == ref.total_cycles
    for name, a, b in zip(res.state._fields, res.state, ref.state):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"state leaf '{name}' diverged across kill-and-resume")


def test_checkpoint_roundtrip_all_leaves(tmp_path):
    """Property check over the full pytree: every leaf (including the
    bool masks and int32 scalars) survives the npz round trip with
    dtype, shape and bits intact; checksum tampering is caught."""
    edges = _hub_stream()
    eng, _ = _run(_cfg(faults=FaultPlan(seed=1, drop_rate=0.02)), edges)
    ck = Checkpointer(tmp_path)
    eng.checkpoint(ck)
    res = StreamingEngine.restore(_cfg(faults=FaultPlan(seed=1,
                                                        drop_rate=0.02)),
                                  "bfs", ck)
    for name, a, b in zip(eng.state._fields, eng.state, res.state):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, name
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    # config fingerprint gates the restore
    with pytest.raises(ValueError, match="config"):
        StreamingEngine.restore(_cfg(), "bfs", ck)
    # flip one byte in a shard: tampering is caught — by the manifest
    # checksum, or earlier by the zip container's own CRC
    import zipfile
    shard = next((tmp_path / "step_1").glob("shard_*.npz"))
    blob = bytearray(shard.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    shard.write_bytes(bytes(blob))
    with pytest.raises((IOError, ValueError, zipfile.BadZipFile)):
        StreamingEngine.restore(_cfg(faults=FaultPlan(seed=1,
                                                      drop_rate=0.02)),
                                "bfs", Checkpointer(tmp_path))


def test_restore_sharded_on_fake_mesh():
    """Restore a checkpoint under ``cca_state_shardings`` on 8 fake host
    devices (4x2 mesh) and finish the stream there: values exact
    (subprocess — XLA device count locks at first jax init)."""
    script = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, numpy as np
        from jax.sharding import AxisType
        from repro.core import EngineConfig, StreamingEngine
        from repro.core.reference import bfs_levels
        from repro.dist.sharding import cca_state_shardings
        from repro.graph.streams import hub_edges
        from repro.train.checkpoint import Checkpointer

        ONE = np.float32(1.0).view(np.int32)
        e = hub_edges(256, 0, 120, seed=3)
        edges = np.concatenate(
            [e, np.full((len(e), 1), ONE, np.int64)], 1).astype(np.int32)
        cfg = EngineConfig(height=8, width=8, n_vertices=256, edge_cap=8,
                           ghost_slots=24, queue_cap=32, chan_cap=16,
                           chunk=64, lanes=2, max_cycles=200000)
        with tempfile.TemporaryDirectory() as d:
            eng = StreamingEngine(cfg, "bfs")
            eng.seed(0, 0.0)
            eng.run_increment(edges[:250])
            eng.checkpoint(Checkpointer(d))

            mesh = jax.make_mesh((4, 2), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            sh = cca_state_shardings(mesh, jax.eval_shape(lambda: eng.state))
            res = StreamingEngine.restore(cfg, "bfs", Checkpointer(d),
                                          shardings=sh)
            assert res.state.vals.sharding == sh.vals
            res.run_increment(edges[250:])
            eng.run_increment(edges[250:])
            np.testing.assert_array_equal(res.values(), eng.values())
            np.testing.assert_array_equal(
                res.values(), bfs_levels(256, edges[:, :2], source=0))
        print("SHARDED_RESTORE_OK")
    """)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "SHARDED_RESTORE_OK" in r.stdout, r.stdout + r.stderr


# ---------------------- Checkpointer satellite fixes ----------------------

def test_checkpointer_async_error_surfaces(tmp_path):
    """An exception on the writer thread must re-raise from wait(), not
    vanish (a silently-missing checkpoint defeats the whole layer)."""
    ck = Checkpointer(tmp_path / "ck")
    ck.dir = tmp_path / "ck" / "not_a_dir" / "sub"
    (tmp_path / "ck" / "not_a_dir").write_text("file, not dir")
    ck.save_async(0, dict(x=np.arange(4)))
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        ck.wait()
    ck.wait()                  # exception is consumed, not re-raised twice


def test_checkpointer_stale_tmp_cleanup(tmp_path):
    stale = tmp_path / "step_5.tmp"
    stale.mkdir(parents=True)
    (stale / "shard_0.npz").write_bytes(b"garbage")
    ck = Checkpointer(tmp_path)
    assert not stale.exists()
    assert ck.all_steps() == []


# ------------------- livelock recovery (self-healing) -------------------

def _wedge_cfg(**kw):
    # the pinned §4.2 hub wedge from test_lanes: lanes=1 + degree-200 hub
    base = dict(height=8, width=8, n_vertices=128, edge_cap=4,
                ghost_slots=48, queue_cap=20, chan_cap=16, futq_cap=4,
                chunk=64, lanes=1, max_cycles=200_000, telemetry=True)
    base.update(kw)
    return EngineConfig(**base)


def test_livelock_recovery_escalates_lanes():
    """The known lanes=1 hub wedge completes via escalation: restore the
    boundary snapshot, retry with lanes+1, keep the relieved config."""
    edges = _hub_stream(n=128, degree=200, seed=3)
    eng = StreamingEngine(_wedge_cfg(), "bfs")
    eng.seed(0, 0.0)
    eng.run_increment(edges, recover=RecoveryPolicy(max_attempts=2))
    assert eng.cfg.lanes == 2                      # degraded gracefully
    np.testing.assert_array_equal(
        eng.values(), bfs_levels(128, edges[:, :2], source=0))
    assert len(eng.recovery_log) == 1
    entry = eng.recovery_log[0]
    assert entry["lanes"] == 1 and entry["escalated_to"]["lanes"] == 2
    assert entry["backoff_s"] == 0.0
    assert "livelock" in entry["wedge"]
    assert "wedged cell" in entry["wedge"]         # flight-recorder report


def test_recovery_budget_exhausted_reraises():
    """A policy that never relieves anything must exhaust its budget and
    re-raise with the attempt log attached."""
    edges = _hub_stream(n=128, degree=200, seed=3)
    eng = StreamingEngine(_wedge_cfg(), "bfs")
    eng.seed(0, 0.0)
    policy = RecoveryPolicy(max_attempts=1, lanes_step=0, queue_cap_step=0)
    with pytest.raises(LivelockError, match="recovery budget exhausted"):
        eng.run_increment(edges, recover=policy)
    assert len(eng.recovery_log) == 2              # initial try + 1 retry
    assert [e["attempt"] for e in eng.recovery_log] == [0, 1]


def test_migrate_state_rejects_mid_increment_snapshot():
    from repro.core.apps import APPS
    from repro.core.ingest import load_stream
    cfg = _cfg()
    eng = StreamingEngine(cfg, "bfs")
    st, _ = load_stream(eng.cfg, eng.state, _hub_stream()[:8])
    with pytest.raises(ValueError, match="not an increment boundary"):
        migrate_state(eng.cfg, APPS["bfs"], st)


def test_recovery_policy_escalation_is_validated():
    cfg = _wedge_cfg()
    pol = RecoveryPolicy(lanes_step=1, queue_cap_step=4)
    c2 = pol.escalate(cfg, 2)
    assert c2.lanes == 3 and c2.queue_cap == 28
    assert config_fingerprint(c2) != config_fingerprint(cfg)


# ----------------------- ingest guard (backpressure) -----------------------

def test_ingest_guard_throttles_under_pressure():
    """tm_hiw within the reserve band of queue_cap halves the admission
    budget; a calm fabric doubles it back (AIMD)."""
    import jax.numpy as jnp
    cfg = _cfg(ingest_guard=True)
    eng = StreamingEngine(cfg, "bfs")
    cap = eng.cfg.io_cells * eng.cfg.io_stream_cap
    ceiling = eng.cfg.queue_cap - eng.cfg.aq_reserve - eng.cfg.sys_reserve
    eng.state = eng.state._replace(
        tm_hiw=eng.state.tm_hiw.at[0, 0, 0].set(jnp.int32(ceiling)))
    eng._update_ingest_budget()
    assert eng._ingest_budget == cap // 2
    eng._update_ingest_budget()
    assert eng._ingest_budget == cap // 4
    eng.state = eng.state._replace(tm_hiw=jnp.zeros_like(eng.state.tm_hiw))
    eng._update_ingest_budget()
    assert eng._ingest_budget == cap // 2           # additive... doubling back
    assert eng._ingest_limit() == cap // 2


def test_ingest_guard_stream_still_exact():
    """With the guard throttling admission the stream takes more spill
    passes but the fixpoint is unchanged."""
    edges = _hub_stream()
    ref = bfs_levels(256, edges[:, :2], source=0)
    eng = StreamingEngine(_cfg(ingest_guard=True, io_stream_cap=32), "bfs")
    eng.seed(0, 0.0)
    eng.run_increment(edges)
    np.testing.assert_array_equal(eng.values(), ref)
    assert eng._ingest_budget is not None


def test_ingest_guard_requires_telemetry():
    with pytest.raises(AssertionError, match="ingest_guard"):
        _cfg(ingest_guard=True, telemetry=False).validate()
