"""Storage shape of the vertex values (DESIGN §10).

``vals`` is ``[H,W,S]`` at ``qbatch == 1`` and ``[H,W,S,Q]`` above, the
rule ``fwd_val`` and ``cemit`` follow; every host reader and writer
addresses it through ``state.vals_index``.  These tests pin the shape and
check that seeding, readback, the repair sweep, single-tenant admission,
recovery migration and checkpoints touch exactly the intended cells.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, StreamingEngine
from repro.core.alloc import rhizome_rcs
from repro.core.reference import bfs_levels, cc_labels
from repro.core.state import init_state, vals_index
from repro.graph.streams import StreamSpec, hub_edges, make_stream
from repro.mq.app import batch_app
from repro.mq.session import MQSession
from repro.resilience import RecoveryPolicy, migrate_state
from repro.train.checkpoint import Checkpointer

N = 64
ONE = np.float32(1.0).view(np.int32)
Q4 = ("bfs", "widest", "sssp", "bfs")


def _cfg(**kw):
    base = dict(height=8, width=8, n_vertices=N, edge_cap=4,
                ghost_slots=32, queue_cap=96, chan_cap=16, futq_cap=8,
                io_stream_cap=512, chunk=128, rhizome_cap=4, lanes=2)
    base.update(kw)
    return EngineConfig(**base)


def _roots(cfg, vid):
    return rhizome_rcs(cfg, vid, np.arange(cfg.rhizome_cap))


@pytest.mark.parametrize("qbatch", [1, 4])
def test_init_state_vals_shape(qbatch):
    cfg = _cfg(qbatch=qbatch, n_vals=qbatch)
    init = 1e9 if qbatch == 1 else (1e9, 0.0, 1e9, 5.0)
    st = init_state(cfg, init_vals=init)
    H, W, S = cfg.height, cfg.width, cfg.slots
    assert st.vals.dtype == jnp.float32
    assert st.vals.shape == ((H, W, S) if qbatch == 1 else (H, W, S, 4))
    assert st.vals.shape == st.fwd_val.shape
    for q in range(qbatch):
        plane = np.asarray(st.vals[vals_index(cfg, ..., q=q)])
        want = init if qbatch == 1 else init[q]
        assert plane.shape == (H, W, S)
        assert (plane == np.float32(want)).all()


def test_scalar_engine_holds_one_value_per_slot():
    with pytest.raises(AssertionError, match="one value per slot"):
        _cfg(n_vals=2).validate()


@pytest.mark.parametrize("q", [None, 2])
def test_seed_and_values_address_vals_cells(q):
    """``seed`` writes every rhizome root of the vertex and nothing else;
    ``values`` reduces over the same cells.  ``q`` None is the scalar
    engine, else the query slot of a Q=4 composite."""
    app = "bfs" if q is None else batch_app(Q4)
    eng = StreamingEngine(_cfg(), app)
    cfg, qi = eng.cfg, q or 0
    before = np.asarray(eng.state.vals)
    eng.seed(5, 3.0, val_idx=qi)
    vals = np.asarray(eng.state.vals)
    r, c, s = _roots(cfg, 5)
    at = vals_index(cfg, r, c, s, q=qi)
    assert (vals[at] == 3.0).all()
    changed = np.argwhere(vals != before)
    assert len(changed) == cfg.rhizome_cap
    # a sibling root holding a tighter value wins the min reduce
    vals = vals.copy()
    vals[vals_index(cfg, r[2], c[2], s[2], q=qi)] = 1.0
    eng.state = eng.state._replace(vals=jnp.asarray(vals))
    out = eng.values(val_idx=qi, combine=np.minimum)
    assert out[5] == 1.0
    assert (np.delete(out, 5) == np.float32(1e9)).all()


def test_repair_entries_read_vals():
    """The repair sweep re-injects each finite value at every active
    root: the canonical root always, a sibling once linked."""
    eng = StreamingEngine(_cfg(), "bfs")
    cfg = eng.cfg
    vals = np.asarray(eng.state.vals).copy()
    on = np.asarray(eng.state.rhz_on).copy()
    r, c, s = _roots(cfg, 3)
    vals[r[0], c[0], s[0]] = 2.0
    r, c, s = _roots(cfg, 9)
    vals[r, c, s] = 4.0
    vals[r[0], c[0], s[0]] = 1.0
    on[r[1], c[1], s[1]] = True
    eng.state = eng.state._replace(vals=jnp.asarray(vals),
                                   rhz_on=jnp.asarray(on))
    bits = lambda x: int(np.float32(x).view(np.int32))
    rows = {tuple(int(x) for x in row) for row in eng._repair_entries()}
    assert rows == {(3, -1, bits(2.0)), (9, -1, bits(1.0)),
                    (9, -2, bits(1.0))}


def test_single_tenant_session_admits():
    """A qbatch=1 session writes CC labels, resets the plane on
    re-admission, and its tenants reach the reference fixpoints."""
    edges = np.concatenate(make_stream(StreamSpec(
        n_vertices=N, n_edges=256, increments=2, symmetric=True, seed=4)))
    ses = MQSession(_cfg(), qbatch=1)
    cfg = ses.eng.cfg
    ses.admit("cc", source=0)
    vals = np.asarray(ses.eng.state.vals)
    assert vals.shape == (cfg.height, cfg.width, cfg.slots)
    vids = np.arange(N)
    r, c, s = rhizome_rcs(cfg, vids[None, :],
                          np.arange(cfg.rhizome_cap)[:, None])
    np.testing.assert_array_equal(
        vals[r, c, s], np.broadcast_to(vids.astype(np.float32), r.shape))
    rest = np.ones(vals.shape, bool)
    rest[r, c, s] = False
    assert (vals[rest] == np.float32(1e9)).all()
    ses.run_increment(edges)
    np.testing.assert_array_equal(ses.values(0), cc_labels(N, edges))

    # a single-source tenant admitted into the grown graph: the plane
    # resets, and the seed diffuses over the stored edges
    ses.retire(0)
    ses.admit("bfs", source=0)
    assert (np.asarray(ses.eng.state.vals) == np.float32(1e9)).all()
    ses.run_increment(np.zeros((0, 3), np.int32))
    np.testing.assert_array_equal(ses.values(0), bfs_levels(N, edges, 0))


@pytest.fixture(scope="module")
def hub_engine():
    e2 = hub_edges(N, hub=0, degree=40, seed=3)
    edges = np.concatenate([e2, np.full((len(e2), 1), ONE, np.int64)],
                           axis=1).astype(np.int32)
    eng = StreamingEngine(_cfg(), "bfs")
    eng.seed(0, 0.0)
    eng.run_increment(edges)
    np.testing.assert_array_equal(eng.values(), bfs_levels(N, edges, 0))
    return eng


def test_migrate_state_keeps_vals(hub_engine):
    eng = hub_engine
    relief = RecoveryPolicy().escalate(eng.cfg, 1)
    st = migrate_state(relief, eng.app, eng.state)
    assert st.vals.shape == (relief.height, relief.width, relief.slots)
    np.testing.assert_array_equal(np.asarray(st.vals),
                                  np.asarray(eng.state.vals))


def test_checkpoint_roundtrip_keeps_vals(hub_engine, tmp_path):
    eng = hub_engine
    ck = Checkpointer(tmp_path)
    eng.checkpoint(ck)
    res = StreamingEngine.restore(_cfg(), "bfs", ck)
    a, b = np.asarray(eng.state.vals), np.asarray(res.state.vals)
    assert b.dtype == a.dtype and b.shape == a.shape == (8, 8, a.shape[2])
    np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))
    np.testing.assert_array_equal(res.values(), eng.values())
