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


def test_device_loop_fits_one_chip_at_paper_size(one_chip):
    from repro.core.apps import BFS
    mem = _compile_device_loop(_paper_cfg(), BFS, one_chip).memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


def test_mq_q4_device_loop_compiles_32x32(one_chip):
    from repro.mq.app import batch_app
    app = batch_app(["bfs", "sssp", "widest", "bfs"])
    mem = _compile_device_loop(_paper_cfg(serve=True), app,
                               one_chip).memory_analysis()
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
    assert "collective-permute" in compiled.as_text()
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(whole))
    assert per_chip < total / 2     # the state is tiled, not replicated
