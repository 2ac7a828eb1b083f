"""The Graph500 R-MAT deployment (SCALE 16 on the 32x32 chip at
``rhizome_cap`` 4) at a small size: a SCALE-9 stream drawn with the
Graph500 recipe on an 8x8 machine under the deployment's preset settings
(lanes 8, chan_cap 64, queue_cap 256, inserts sent to each root's chain
tail), BFS from the hub compared with the host reference after every
increment, the rhizome protocol's two counters (DESIGN §4.5) checked
against the roots the engine grew, and the chain tails (DESIGN §4.6)
against the chains they stand for.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import shape
from repro.configs.cca_paper import stream_config_for
from repro.core import StreamingEngine
from repro.core.reference import bfs_levels
from repro.graph.streams import StreamSpec, rmat_edges

SCALE, EDGEFACTOR, INCREMENTS = 9, 16, 10
N = 2 ** SCALE
ONE = np.float32(1.0).view(np.int32)


def graph500_stream(seed: int = 9) -> list[np.ndarray]:
    """``EDGEFACTOR * 2^SCALE`` unique directed non-loop edges of the
    program's R-MAT generator (Graph500's A/B/C .57/.19/.19), the first
    draw of each repeated pair kept, labels permuted, the order shuffled
    and cut into ``INCREMENTS`` increments of (src, dst, unit weight bits)
    rows."""
    m = EDGEFACTOR * N
    e = rmat_edges(StreamSpec(kind="rmat", n_vertices=N, n_edges=4 * m,
                              seed=seed)).astype(np.int64)
    _, first = np.unique((e[:, 0] << 32) | e[:, 1], return_index=True)
    e = e[np.sort(first)][:m]
    assert len(e) == m
    rng = np.random.default_rng(seed)
    label = rng.permutation(N)
    e = np.stack([label[e[:, 0]], label[e[:, 1]], np.full(m, ONE)],
                 axis=1).astype(np.int32)
    return [e[p] for p in np.array_split(rng.permutation(m), INCREMENTS)]


@pytest.fixture(scope="module")
def stream():
    return graph500_stream()


def deployment_config(rhizome_cap: int, tail_insert: bool = True):
    """The deployment's preset (``stream_config_for``) on an 8x8 grid."""
    spec = shape("tiny_8x8_rmat9", "cca_stream", height=8, width=8,
                 n_vertices=N, stream_edges=EDGEFACTOR * N // INCREMENTS)
    return dataclasses.replace(stream_config_for(spec), edge_cap=8,
                               rhizome_cap=rhizome_cap, chunk=128,
                               tail_insert=tail_insert)


def test_stream_is_skewed_unique_and_permuted(stream):
    e = np.concatenate(stream)
    assert [len(x) for x in stream] == [820] * 2 + [819] * 8
    keys = (e[:, 0].astype(np.int64) << 32) | e[:, 1]
    assert len(np.unique(keys)) == len(e) and (e[:, 0] != e[:, 1]).all()
    deg = np.bincount(e[:, 0], minlength=N)
    assert deg.max() > 16 * np.median(deg)
    # the label permutation moves the busiest vertex off id 0, where
    # R-MAT's A quadrant puts it
    assert np.argmax(deg) != 0


@pytest.mark.parametrize("rhizome_cap", [4, 1])
def test_bfs_from_the_hub_exact_after_every_increment(stream, rhizome_cap):
    deg = np.bincount(np.concatenate(stream)[:, 0], minlength=N)
    hub = int(np.argmax(deg))
    cfg = deployment_config(rhizome_cap)
    assert (cfg.lanes, cfg.chan_cap, cfg.queue_cap) == (8, 64, 256)
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(hub, 0.0)
    done, rlinks, rfwds = [], 0, 0
    for k, inc in enumerate(stream):
        r = eng.run_increment(inc)
        done.append(inc)
        rlinks += r.rlinks
        rfwds += r.rfwds
        want = bfs_levels(N, np.concatenate(done), hub)
        np.testing.assert_array_equal(eng.values(N), want,
                                      err_msg=f"increment {k}")
    grown = eng.vertex_object_stats()["rhizomes"]
    assert (eng.totals["rlinks"], eng.totals["rfwds"]) == (rlinks, rfwds)
    if rhizome_cap == 1:
        assert rlinks == rfwds == grown == 0
        return
    # a root is linked once, by its first insert; a sibling broadcast of a
    # changed relax at the canonical root activates the siblings that no
    # insert has reached yet without a link, so links <= roots grown
    assert 0 < rlinks <= grown
    # every link is acked by one OP_RHIZOME_FWD, and the broadcasts add more
    assert rfwds > rlinks


def test_links_equal_the_roots_grown_where_nothing_broadcasts(stream):
    """With propagation off no relax changes a value, so no sibling
    broadcast runs: every secondary root is activated by its own link's
    ack, once, and the ack is the only OP_RHIZOME_FWD."""
    eng = StreamingEngine(deployment_config(4), "ingest_only")
    results = [eng.run_increment(inc) for inc in stream[:4]]
    rlinks = sum(r.rlinks for r in results)
    assert rlinks == eng.vertex_object_stats()["rhizomes"] > 0
    assert sum(r.rfwds for r in results) == rlinks
    assert int(np.asarray(eng.state.nedges).sum()) == sum(
        len(inc) for inc in stream[:4])


def chain_tails(eng):
    """``{root address: (last node address, its edges)}`` of every root
    whose chain has a ghost, walked along ``gaddr``."""
    cfg = eng.cfg
    gaddr = np.asarray(eng.state.gaddr).reshape(-1)
    ne = np.asarray(eng.state.nedges).reshape(-1)
    S = cfg.slots
    out = {}
    for cell in range(cfg.n_cells):
        for slot in range(cfg.primary_slots):
            a = cell * S + slot
            if gaddr[a] < 0:
                continue
            while gaddr[a] >= 0:
                a = gaddr[a]
            out[cell * S + slot] = (a, ne[a])
    return out


def test_tails_walk_no_chain_and_grow_the_same_ghosts(stream):
    """Inserts sent to each root's tail fill the same chains as the
    classic walk down them, with far fewer actions; every root's
    ``gtail``/``tcnt`` names its chain's last node and that node's
    edges."""
    engs = {}
    for tail in (False, True):
        eng = StreamingEngine(deployment_config(4, tail), "ingest_only")
        for inc in stream:
            eng.run_increment(inc)
        engs[tail] = eng
    classic, tails = engs[False], engs[True]
    assert classic.totals["allocs"] == tails.totals["allocs"] > 0
    assert tails.totals["execs"] < 0.9 * classic.totals["execs"]
    for eng in (classic, tails):
        assert int(np.asarray(eng.state.nedges).sum()) == len(
            np.concatenate(stream))
    want = chain_tails(tails)
    gtail = np.asarray(tails.state.gtail).reshape(-1)
    tcnt = np.asarray(tails.state.tcnt).reshape(-1)
    assert len(want) > 0
    for root, (last, n) in want.items():
        assert (gtail[root], tcnt[root]) == (last, n)
    assert sorted(n for _, n in want.values()) == sorted(
        n for _, n in chain_tails(classic).values())
