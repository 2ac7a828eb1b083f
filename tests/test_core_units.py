"""Unit tests for the message-driven engine's building blocks."""
import ast
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rings
from repro.core.alloc import choose_alloc_cell, vicinity_offsets
from repro.core.config import EngineConfig
from repro.core.exec_stage import take_cell
from repro.core.msg import (TB_AQ_SELF, TB_CHAN_E, TB_CHAN_N, TB_CHAN_S,
                            TB_CHAN_W, f2i, i2f, make_msg)
from repro.core.routing import yx_target_buffer


def test_msg_roundtrip():
    for v in (0.0, 1.0, -3.5, 1e9, 2.5e-4):
        assert float(i2f(f2i(v))) == np.float32(v)


def test_make_msg_shape():
    m = make_msg(1, jnp.arange(4), 7)
    assert m.shape == (4, 5)
    assert int(m[2, 1]) == 2 and int(m[0, 2]) == 7


def test_ring_push_pop_fifo():
    buf = jnp.zeros((2, 4, 5), jnp.int32)
    cnt = jnp.zeros((2,), jnp.int32)
    head = jnp.zeros((2,), jnp.int32)
    msgs = [make_msg(1, i, i * 10) for i in range(3)]
    for m in msgs:
        buf, cnt = rings.ring_push(buf, cnt, head,
                                   jnp.broadcast_to(m, (2, 5)),
                                   jnp.array([True, False]))
    assert int(cnt[0]) == 3 and int(cnt[1]) == 0
    outs = []
    for _ in range(3):
        outs.append(np.asarray(rings.ring_peek(buf, head))[0])
        cnt, head = rings.ring_pop(cnt, head, 4, jnp.array([True, False]))
    assert [o[1] for o in outs] == [0, 1, 2]  # FIFO order
    assert int(cnt[0]) == 0


def test_ring_wraparound():
    buf = jnp.zeros((1, 2, 5), jnp.int32)
    cnt = jnp.zeros((1,), jnp.int32)
    head = jnp.zeros((1,), jnp.int32)
    t = jnp.array([True])
    for i in range(5):  # push/pop interleaved past capacity
        buf, cnt = rings.ring_push(buf, cnt, head, make_msg(1, i)[None], t)
        got = int(rings.ring_peek(buf, head)[0, 1])
        assert got == i
        cnt, head = rings.ring_pop(cnt, head, 2, t)


def test_yx_routing_vertical_first():
    cfg = EngineConfig(height=4, width=4, n_vertices=16)
    r = jnp.array(1)
    c = jnp.array(1)
    # dst below and right -> go S first (vertical first)
    assert int(yx_target_buffer(cfg, jnp.array(3 * 4 + 3), r, c)) == TB_CHAN_S
    assert int(yx_target_buffer(cfg, jnp.array(0 * 4 + 3), r, c)) == TB_CHAN_N
    # same row -> horizontal
    assert int(yx_target_buffer(cfg, jnp.array(1 * 4 + 3), r, c)) == TB_CHAN_E
    assert int(yx_target_buffer(cfg, jnp.array(1 * 4 + 0), r, c)) == TB_CHAN_W
    # arrived
    assert int(yx_target_buffer(cfg, jnp.array(1 * 4 + 1), r, c)) == TB_AQ_SELF


def test_vicinity_offsets_bound():
    offs = vicinity_offsets(2)
    assert len(offs) == 24
    assert (np.abs(offs).max(axis=1) <= 2).all()
    assert (np.abs(offs).max(axis=1) >= 1).all()


@pytest.mark.parametrize("module_name", ["repro.core.rings",
                                         "repro.core.routing"])
def test_public_docstrings(module_name):
    """pydocstyle-level gate (the tool isn't pinned in this image): every
    public function of the ring/routing modules documents itself — a
    docstring exists, starts on the first line with a capital letter or
    backtick, and the summary sentence ends with a period.  deliver's
    reserve-predicate contract riding on this is load-bearing: each
    caller supplies a different §4.2 admission rule."""
    import importlib
    mod = importlib.import_module(module_name)
    tree = ast.parse(inspect.getsource(mod))
    funcs = [n for n in tree.body if isinstance(n, ast.FunctionDef)
             and not n.name.startswith("_")]
    assert funcs, f"no public functions found in {module_name}"
    for fn in funcs:
        doc = ast.get_docstring(fn)
        assert doc, f"{module_name}.{fn.name} is missing a docstring"
        first = doc.strip().splitlines()[0].strip()
        assert first and (first[0].isupper() or first[0] in "`\"'["), \
            f"{module_name}.{fn.name}: summary should start capitalized"
        summary = doc.strip().split("\n\n")[0].rstrip()
        assert summary.endswith((".", ":", "::")), \
            f"{module_name}.{fn.name}: summary should end with a period"
    if module_name.endswith("routing"):
        doc = next(ast.get_docstring(f) for f in funcs
                   if f.name == "deliver")
        assert "reserve" in doc.lower() and "aq_room" in doc, \
            "deliver must document the reserve-predicate contract"


def _index_grid(H, W, n, seed, pin):
    """[H,W] indices in [0, n), with the cells of ``pin`` set to its
    values (the ends of the range, a wrapped head)."""
    idx = np.random.default_rng(seed).integers(0, n, (H, W))
    for (i, j), v in pin.items():
        idx[i, j] = v
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("S", [5, 244, 512])
def test_take_cell_reads_the_future_queue_head_as_the_one_hot_reduce(S):
    """Staging's gather of one future-queue entry per cell equals the
    one-hot read it replaced (the slot's queue by a masked sum over the
    slot axis, then ``ring_peek``) for every word, at the slot counts of
    the 50K and SCALE-16 shapes: heads past the capacity wrap, and a
    never-written queue reads its zeros."""
    H, W, FQ = 3, 4, 8
    rng = np.random.default_rng(S)
    fq = rng.integers(-2**31, 2**31, (H, W, S, FQ, 3), dtype=np.int64)
    fq[:, :, 0] = 0                                   # empty, never written
    fq = jnp.asarray(fq.astype(np.int32))
    slot = _index_grid(H, W, S, S + 1, {(0, 0): 0, (2, 3): S - 1})
    head = _index_grid(H, W, 3 * FQ, S + 2,
                       {(0, 1): FQ - 1, (1, 0): FQ, (1, 1): 3 * FQ - 1})
    oh = (jnp.arange(S) == slot[..., None])[..., None, None]
    old = rings.ring_peek(jnp.sum(jnp.where(oh, fq, 0), axis=2), head)
    new = take_cell(fq, slot, head % FQ)
    assert new.shape == (H, W, 3)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("S", [5, 244, 512])
def test_take_cell_reads_the_edge_as_the_one_hot_reduce(S):
    """Staging's gather of the emitted edge's destination and weight
    equals the one-hot reduce over (slot, edge) it replaced.  Weights are
    normal floats: the masked sum returns those exactly (it would turn a
    -0.0 into 0.0, which the gather keeps)."""
    H, W, E = 3, 4, 8
    rng = np.random.default_rng(S)
    edst = jnp.asarray(rng.integers(-2**31, 2**31, (H, W, S, E),
                                    dtype=np.int64).astype(np.int32))
    ew = jnp.asarray(rng.uniform(0.5, 100.0, (H, W, S, E)).astype(np.float32))
    slot = _index_grid(H, W, S, S + 3, {(0, 0): 0, (2, 3): S - 1})
    ek = _index_grid(H, W, E, S + 4, {(0, 1): 0, (1, 0): E - 1})
    oh = ((jnp.arange(S) == slot[..., None])[..., None]
          & (jnp.arange(E) == ek[..., None])[..., None, :])
    for leaf in (edst, ew):
        old = jnp.sum(jnp.where(oh, leaf, 0), axis=(2, 3)).astype(leaf.dtype)
        new = take_cell(leaf, slot, ek)
        assert new.shape == (H, W) and new.dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("policy", ["vicinity", "random"])
def test_choose_alloc_cell_in_range(policy):
    cfg = EngineConfig(height=8, width=8, n_vertices=64, allocator=policy)
    rows = jnp.tile(jnp.arange(8, dtype=jnp.int32)[:, None], (1, 8))
    cols = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None, :], (8, 1))
    for rot in range(5):
        cells = np.asarray(choose_alloc_cell(cfg, rows, cols,
                                             jnp.full((8, 8), rot, jnp.int32)))
        assert ((cells >= 0) & (cells < 64)).all()
        if policy == "vicinity":
            tr, tc = cells // 8, cells % 8
            cheb = np.maximum(np.abs(tr - np.asarray(rows)),
                              np.abs(tc - np.asarray(cols)))
            assert (cheb <= cfg.vicinity_hops).all()
            # ring excludes self unless clipped at the border
            interior = ((np.asarray(rows) >= 2) & (np.asarray(rows) < 6)
                        & (np.asarray(cols) >= 2) & (np.asarray(cols) < 6))
            assert (cheb[interior] >= 1).all()
