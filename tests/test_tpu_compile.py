"""The engine's main-path programs compile for a described TPU v5e.

No chip is needed: the TPU compiler is given the ``v5e:2x2`` topology
description and compiles for its devices, so a program the chip's
compiler would refuse (an op Mosaic/XLA cannot lower, a state that does
not fit HBM, a sharding that cannot be partitioned) fails here.  The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  Nothing here runs, so nothing here is a timing.
"""
import dataclasses
import functools
import re

import pytest

HBM_BYTES = 16 * 2**30      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler / library lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _paper_cfg(serve=False):
    from repro.configs import cca_paper
    spec = next(s for s in cca_paper.cca_shapes()
                if s.name == "chip_32x32_50k")
    return (cca_paper.serve_config_for(spec) if serve
            else cca_paper.stream_config_for(spec))


def _engine_args(cfg, app, sharding=None):
    """(cfg, state shapes) exactly as ``StreamingEngine(cfg, app)`` holds
    them, each leaf placed by ``sharding`` (one sharding or a pytree)."""
    import jax
    from repro.core.state import MachineState, init_state
    cfg = dataclasses.replace(cfg, n_vals=app.n_vals, qbatch=app.qbatch)
    shapes = jax.eval_shape(functools.partial(
        init_state, cfg, init_vals=app.init_val, fwd_init=app.fwd_neutral))
    if sharding is None:
        return cfg, shapes
    if not isinstance(sharding, MachineState):
        sharding = jax.tree.map(lambda _: sharding, shapes)
    return cfg, jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, sharding)


def _compile_device_loop(cfg, app, one_chip):
    import jax
    import jax.numpy as jnp
    from repro.core.engine import _increment_device_loop
    cfg, st = _engine_args(cfg, app, one_chip)
    limit = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return _increment_device_loop.lower(cfg, app, st, limit).compile()


def test_device_loop_compiles_8x8(one_chip):
    from repro.core.apps import BFS
    from repro.core.config import EngineConfig
    cfg = EngineConfig(height=8, width=8, n_vertices=64, ghost_slots=16,
                       io_stream_cap=256, chunk=32)
    compiled = _compile_device_loop(cfg, BFS, one_chip)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.fixture(scope="module")
def bfs_loop_paper(one_chip):
    """The BFS device loop at ``chip_32x32_50k``, compiled once (~1 min)."""
    from repro.core.apps import BFS
    return _compile_device_loop(_paper_cfg(), BFS, one_chip)


def test_device_loop_fits_one_chip_at_paper_size(bfs_loop_paper):
    mem = bfs_loop_paper.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


def test_bfs_loop_keeps_vals_unpadded(bfs_loop_paper):
    """Single-query ``vals`` is ``[H,W,S]``: a size-1 value axis would sit
    on the 128 lanes, and the loop would carry and rewrite a 128-fold
    padded copy (~130 MB at this size) every cycle.  Besides the io
    stream's relayout before the loop (``io_edges [IO, L, 3]`` with its
    3-word record on the lanes, once per pass) the temporaries hold
    under 32 MB."""
    cfg = _paper_cfg()
    io_relayout = cfg.io_cells * cfg.io_stream_cap * 128 * 4
    temp = bfs_loop_paper.memory_analysis().temp_size_in_bytes
    assert temp - io_relayout < 32 * 2**20, temp
    carries = re.findall(r"= \((\w+\[[\d,]*\])\S* .*? while\(",
                         bfs_loop_paper.as_text())
    assert carries and set(carries) == {"f32[32,32,244]"}, carries


def _shapes(hlo):
    """Every instruction's result shape by name (``s32[32,32,244]``)."""
    return dict(re.findall(r"%([\w.-]+) = (\w+\[[\d,]*\])", hlo))


def _carry_layouts(hlo, shape):
    """The layouts ``shape`` takes in the program's parameters and in the
    carry of every ``while`` (minor-to-major, without tiling)."""
    out = set()
    pat = re.escape(shape) + r"\{([\d,]+)"
    for line in hlo.splitlines():
        if " parameter(" in line or re.search(r"\) while\(", line):
            out.update(re.findall(pat, line.split(" while(")[0]))
    return out


def test_bfs_loop_stages_one_entry_per_cell(bfs_loop_paper):
    """Staging reads the future queue's head and the emitted edge with
    per-cell gathers: no reduce under ``cca.staging`` takes the whole
    ``fq`` or ``edst`` leaf (a one-hot reduce of ``fq`` over its
    lane-mapped slot axis cost ~95 us a machine cycle on a v5e)."""
    hlo = bfs_loop_paper.as_text()
    shapes = _shapes(hlo)
    staged = re.findall(r" (reduce|gather)\(%([\w.-]+).*?op_name=\"[^\"]*"
                        r"cca\.staging/", hlo)
    whole = {"s32[32,32,244,8,3]", "s32[32,32,244,8]"}
    assert not [a for op, a in staged
                if op == "reduce" and shapes.get(a) in whole]
    assert {shapes.get(a) for op, a in staged if op == "gather"} >= whole


def test_bfs_loop_keeps_slot_leaf_layouts(bfs_loop_paper):
    """``fq``, ``edst`` and ``ew`` keep one layout each, the one they are
    passed in, through both loops: a gather that slices along the slot
    axis (or a one-hot read it displaced) has XLA relayout such a leaf
    with its small axis on the 128 lanes, and the loop then rewrites a
    padded copy of it every cycle."""
    hlo = bfs_loop_paper.as_text()
    for shape in ("s32[32,32,244,8,3]", "s32[32,32,244,8]",
                  "f32[32,32,244,8]"):
        assert len(_carry_layouts(hlo, shape)) == 1, \
            (shape, _carry_layouts(hlo, shape))


def test_mq_q4_device_loop_compiles_32x32(one_chip):
    from repro.mq.app import batch_app
    app = batch_app(["bfs", "sssp", "widest", "bfs"])
    mem = _compile_device_loop(_paper_cfg(serve=True), app,
                               one_chip).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_rmat16_rhizome_loop_fits_one_chip(one_chip):
    """The Graph500 SCALE-16 deployment's BFS loop: ``chip_32x32_rmat16``
    at ``rhizome_cap`` 4 holds 4 x 64 root slots and 256 ghosts, 512
    slots a cell against the 50K shape's 244, with each root's chain tail
    (``tail_insert``), and still fits one chip."""
    from repro.configs import cca_paper
    from repro.core.apps import BFS
    spec = next(s for s in cca_paper.cca_shapes()
                if s.name == "chip_32x32_rmat16")
    cfg = dataclasses.replace(cca_paper.stream_config_for(spec),
                              rhizome_cap=4, tail_insert=True)
    assert (cfg.slots, cfg.ghost_slots, cfg.io_stream_cap) == (512, 256,
                                                               6553)
    mem = _compile_device_loop(cfg, BFS, one_chip).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_sharded_chunk_compiles_on_2x2_mesh(topo):
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.core.apps import BFS
    from repro.core.engine import run_chunk_body
    from repro.dist.sharding import cca_state_shardings

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg, whole = _engine_args(_paper_cfg(), BFS)
    shards = cca_state_shardings(mesh, whole)
    _, st = _engine_args(_paper_cfg(), BFS, shards)
    compiled = jax.jit(lambda s: run_chunk_body(cfg, BFS, s),
                       in_shardings=(shards,), out_shardings=shards
                       ).lower(st).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo
    # the per-cell gathers partition over the cell grid: no gather of the
    # state across chips, and each chip's slot leaves keep their layout
    assert " all-gather" not in hlo and " all-to-all" not in hlo
    for shape in ("s32[16,16,244,8,3]", "s32[16,16,244,8]",
                  "f32[16,16,244,8]"):
        assert len(_carry_layouts(hlo, shape)) == 1, \
            (shape, _carry_layouts(hlo, shape))
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(whole))
    assert per_chip < total / 2     # the state is tiled, not replicated
