"""Multi-tenant query serving (repro.mq, DESIGN §10).

Pins the four contracts of the Q-batched engine:

* **Q=1 is the old engine**: an MQSession at qbatch=1 replays the
  recorded pre-lanes fingerprint bit-exactly on both backends — the
  widened message format and per-slot counters specialize away;
* **Q-batched is Q engines**: a mixed Q=8 batch (bfs / sssp / cc /
  widest / reliable) over one weighted symmetric stream matches the 8
  single-query runs bit-exactly per slot, and the min-trio slots match
  the NetworkX oracles — over-propagated neutral payloads no-op under
  monotone relaxation;
* **mid-stream admission / retirement**: a tenant admitted at an
  increment boundary re-seeds only its own slot against the live graph
  and converges to the full-graph oracle; a retired slot recycles into
  a different app (composite rebuild) and stays exact;
* **backend parity at Q>1**: jnp and the Pallas megakernel agree on
  cycle counts and every state leaf for a Q=3 mixed batch.
"""
import json
import pathlib

import numpy as np
import pytest

from repro.core.alloc import rhizome_rcs
from repro.core.apps import APPS
from repro.core.config import EngineConfig
from repro.core.engine import StreamingEngine
from repro.core.reference import (bfs_levels, cc_labels, sssp_dists,
                                  widest_caps)
from repro.core.state import vals_index
from repro.graph.streams import StreamSpec, hashed_pair_weights, make_stream
from repro.mq.session import DEFAULT_SEEDS, MQSession, QuerySlot

REF = json.loads((pathlib.Path(__file__).parent
                  / "data" / "pre_lanes_reference.json").read_text())


def _mq_cfg(**kw):
    base = dict(height=8, width=8, n_vertices=128, edge_cap=8,
                ghost_slots=64, queue_cap=64, chan_cap=32, futq_cap=8,
                io_stream_cap=2048, lanes=4, chunk=128)
    base.update(kw)
    return EngineConfig(**base)


def _weighted_stream(n=128, n_edges=360, increments=2, seed=11):
    """Symmetric SBM increments with hashed per-pair weights in
    (0.1, 1.0] so sssp / widest / reliable diverge from bfs."""
    return hashed_pair_weights(make_stream(StreamSpec(
        n_vertices=n, n_edges=n_edges, increments=increments,
        symmetric=True, seed=seed)))


def _edge_floats(edges):
    return edges[:, 2].astype(np.int32).view(np.float32)


def _seed_single(eng, app_name, source):
    if app_name == "cc":
        cfg = eng.cfg
        vids = np.arange(cfg.n_vertices, dtype=np.int64)[None, :]
        ks = np.arange(cfg.rhizome_cap, dtype=np.int64)[:, None]
        r, c, s = rhizome_rcs(cfg, vids, ks)
        labels = np.broadcast_to(vids.astype(np.float32), r.shape)
        eng.state = eng.state._replace(
            vals=eng.state.vals.at[vals_index(cfg, r, c, s)].set(labels))
    else:
        eng.seed(source, DEFAULT_SEEDS[app_name])


# ------------------ Q=1 replays the recorded fingerprint -----------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_q1_bit_exact_vs_recorded_engine(backend):
    """The mq layer at qbatch=1 IS the pre-mq engine: per-increment
    counters and final values replay the pre-lanes recording exactly."""
    incs = make_stream(StreamSpec(**REF["spec"]))
    ses = MQSession(EngineConfig(backend=backend, **REF["cfg"]), qbatch=1)
    ses.eng.seed(0, 0.0)
    ses.slots[0] = QuerySlot(app=APPS["bfs"], source=0, state="active")
    rows = []
    for e in incs:
        r = ses.run_increment(e, max_cycles=500_000)
        rows.append(dict(cycles=r.cycles, hops=r.hops, execs=r.execs,
                         stalls=r.stalls, allocs=r.allocs))
    want = REF["backends"][backend]
    assert rows == want["increments"]
    np.testing.assert_array_equal(
        ses.values(0, 128), np.array(want["values"]))
    # qbatch=1 lifecycle: settles at the first quiet boundary
    assert ses.slots[0].state == "active"
    ses.run_increment(np.zeros((0, 3), np.int32))
    assert ses.settled_slots() == [0]


# ---------------- Q=8 mixed batch == 8 single-query runs -----------------

MIX8 = (("bfs", 0), ("bfs", 7), ("sssp", 3), ("sssp", 11), ("cc", 0),
        ("widest", 5), ("reliable", 9), ("bfs", 23))


def test_q8_mixed_batch_matches_single_runs():
    cfg = _mq_cfg()
    incs = _weighted_stream()
    edges = np.concatenate(incs)
    Q = len(MIX8)
    ses = MQSession(cfg, qbatch=Q, apps=[a for a, _ in MIX8])
    for q, (app, src) in enumerate(MIX8):
        ses.admit(app, src, slot=q)
    for e in incs:
        ses.run_increment(e)
    ses.run_increment(np.zeros((0, 3), np.int32))   # settle boundary
    assert ses.settled_slots() == list(range(Q))

    n = cfg.n_vertices
    for q, (app, src) in enumerate(MIX8):
        eng = StreamingEngine(cfg, app)
        _seed_single(eng, app, src)
        for e in incs:
            eng.run_increment(e)
        np.testing.assert_array_equal(
            ses.values(q), eng.values(),
            err_msg=f"slot {q} ({app}@{src}) != single-query run")

    # and the min-trio slots against the NetworkX oracles
    np.testing.assert_array_equal(ses.values(0), bfs_levels(n, edges, 0))
    np.testing.assert_allclose(
        ses.values(2), sssp_dists(n, edges, _edge_floats(edges), 3),
        rtol=1e-5)
    np.testing.assert_array_equal(ses.values(4), cc_labels(n, edges))
    np.testing.assert_allclose(
        ses.values(5), widest_caps(n, edges, 5), rtol=1e-6)

    # per-tenant latency accounting: every settled tenant has a receipt
    for q in range(Q):
        r = ses.retire(q)
        assert r["latency_cycles"] is not None and r["latency_cycles"] > 0
    assert ses.free_slots() == list(range(Q))


# ------------------- mid-stream admission / recycling --------------------

def test_mid_stream_admit_and_recycle():
    cfg = _mq_cfg()
    incs = _weighted_stream(n_edges=240, increments=3, seed=5)
    ses = MQSession(cfg, qbatch=2, apps=["bfs", "sssp"])
    ses.admit("bfs", 0, slot=0)
    ses.run_increment(incs[0])
    # tenant 1 arrives mid-stream: re-seed only slot 1 on the live graph
    ses.admit("sssp", 3, slot=1)
    ses.run_increment(incs[1])
    ses.run_increment(incs[2])
    ses.run_increment(np.zeros((0, 3), np.int32))
    edges = np.concatenate(incs)
    n = cfg.n_vertices
    np.testing.assert_array_equal(ses.values(0), bfs_levels(n, edges, 0))
    np.testing.assert_allclose(
        ses.values(1), sssp_dists(n, edges, _edge_floats(edges), 3),
        rtol=1e-5)
    assert set(ses.settled_slots()) == {0, 1}

    # retire the sssp tenant and recycle its slot into a DIFFERENT app —
    # the composite rebuilds (jit recompile), the bfs tenant rides along
    receipt = ses.retire(1)
    assert receipt["app"] == "sssp" and receipt["latency_cycles"] > 0
    assert ses.free_slots() == [1]
    ses.admit("widest", 5, slot=1)
    assert ses.slots[1].generation == 2
    ses.run_increment(np.zeros((0, 3), np.int32))
    np.testing.assert_allclose(
        ses.values(1), widest_caps(n, edges, 5), rtol=1e-6)
    np.testing.assert_array_equal(ses.values(0), bfs_levels(n, edges, 0))

    # label-flood apps cannot join once edges have streamed
    ses.retire(1)
    with pytest.raises(ValueError, match="label-flood"):
        ses.admit("cc", 0, slot=1)


# ---------------------- backend parity at Q > 1 --------------------------

def test_megakernel_parity_q3():
    cfg_kw = dict(height=4, width=4, n_vertices=64, edge_cap=8,
                  ghost_slots=32, queue_cap=64, chan_cap=32, futq_cap=8,
                  io_stream_cap=1024, lanes=4, chunk=64)
    incs = _weighted_stream(n=64, n_edges=120, increments=2, seed=9)
    mix = (("bfs", 0), ("sssp", 3), ("widest", 5))
    finals = {}
    for backend in ("jnp", "pallas"):
        ses = MQSession(_mq_cfg(backend=backend, **cfg_kw), qbatch=3,
                        apps=[a for a, _ in mix])
        for q, (app, src) in enumerate(mix):
            ses.admit(app, src, slot=q)
        cycles = 0
        for e in incs:
            cycles += ses.run_increment(e).cycles
        finals[backend] = (ses.eng.state, cycles,
                          [np.asarray(ses.values(q)) for q in range(3)])
    assert finals["jnp"][1] == finals["pallas"][1]
    for q in range(3):
        np.testing.assert_array_equal(finals["jnp"][2][q],
                                      finals["pallas"][2][q])
    for name, a, b in zip(finals["jnp"][0]._fields, finals["jnp"][0],
                          finals["pallas"][0]):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"state leaf '{name}' diverged between backends")
