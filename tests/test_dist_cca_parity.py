"""Multi-device CCA parity: ``run_chunk_body`` under ``cca_state_shardings``
on 8 fake host devices is BIT-EXACT with the single-device run — the
paper's single-programming-abstraction claim, end to end (subprocess like
test_partitioned_spmm: XLA device count is locked at first jax init).

Plus in-process unit tests for the repro.dist helpers.
"""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, numpy as np
    from jax.sharding import AxisType
    from repro.core.apps import BFS
    from repro.core.config import EngineConfig
    from repro.core.engine import StreamingEngine, run_chunk_body, quiescent
    from repro.core.ingest import load_stream
    from repro.core.reference import bfs_levels
    from repro.dist.sharding import cca_state_shardings

    cfg = EngineConfig(height=8, width=8, n_vertices=64, ghost_slots=16,
                       io_stream_cap=256, chunk=32)
    rng = np.random.default_rng(0)
    one = np.float32(1.0).view(np.int32)
    E = 160
    edges = np.stack([rng.integers(0, 64, E), rng.integers(0, 64, E),
                      np.full(E, one)], 1).astype(np.int32)

    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    cfg = eng.cfg
    st0, spill = load_stream(cfg, eng.state, edges)
    assert len(spill) == 0
    K = 70  # 70 chunks x 32 cycles covers quiescence with slack

    f1 = jax.jit(lambda s: run_chunk_body(cfg, BFS, s))
    sA, k_run = st0, 0
    for _ in range(K):
        sA, k_run = f1(sA), k_run + 1
        if bool(quiescent(sA)):
            break
    assert bool(quiescent(sA)), "single-device run did not quiesce"

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    shards = cca_state_shardings(mesh, jax.eval_shape(lambda: st0))
    # the mapping: cell rows over 'data', cell columns over 'model'
    from jax.sharding import PartitionSpec as P
    assert shards.vals.spec == P("data", "model", None, None)
    assert shards.aq_n.spec == P("data", "model")
    assert shards.cycle.spec == P()
    sB = jax.device_put(st0, shards)
    f8 = jax.jit(lambda s: run_chunk_body(cfg, BFS, s),
                 in_shardings=(shards,), out_shardings=shards)
    for _ in range(k_run):  # exactly as many chunks as the reference run
        sB = f8(sB)
    assert bool(quiescent(sB)), "sharded run did not quiesce"

    for name, a, b in zip(sA._fields, sA, sB):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"state leaf '{name}' diverged under sharding")

    eng.state = sA
    np.testing.assert_array_equal(eng.values(),
                                  bfs_levels(cfg.n_vertices, edges, 0))
    print("CCA_PARITY_OK")
""")


def test_sharded_cca_bit_exact():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "CCA_PARITY_OK" in r.stdout, r.stdout + r.stderr


# --------------------------- in-process units ---------------------------

def test_pad_to():
    from repro.dist.sharding import pad_to
    assert pad_to(5, 4) == 8
    assert pad_to(8, 4) == 8
    assert pad_to(3, 1) == 3
    assert pad_to(0, 4) == 0


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    from repro.dist import ctx
    ctx.set_dist_mesh(None)
    x = jnp.ones((4, 6))
    assert ctx.constrain(x, "dp", "model") is x
    assert ctx.model_size() == 1
    assert ctx.dp_axes_active() == ("data",)


def test_constrain_degrades_per_dim():
    """Absent axes and indivisible dims replicate instead of erroring."""
    from repro.dist import ctx
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    # 5 not divisible by anything > 1, "pipe" absent from the mesh
    spec = ctx.resolve_spec(mesh, (5, 8), ("pipe", "model"))
    assert spec[0] is None
    ctx.set_dist_mesh(mesh)
    try:
        import jax.numpy as jnp
        y = ctx.constrain(jnp.ones((4, 4)), "dp", "model")
        assert y.shape == (4, 4)
    finally:
        ctx.set_dist_mesh(None)


def test_split_stages_shapes():
    import jax.numpy as jnp
    import pytest
    from repro.dist.pipeline import split_stages
    p = dict(w=jnp.arange(8 * 3 * 3, dtype=jnp.float32).reshape(8, 3, 3),
             b=jnp.arange(8.0).reshape(8))
    s = split_stages(p, 4)
    assert s["w"].shape == (4, 2, 3, 3) and s["b"].shape == (4, 2)
    with pytest.raises(ValueError):
        split_stages(p, 3)


def test_pipelined_apply_sequential_fallback():
    """Without a pipe axis, pipelined_apply == the plain sequential net."""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.pipeline import pipelined_apply, split_stages
    L, D = 4, 8
    k = jax.random.PRNGKey(0)
    w = jax.random.normal(k, (L, D, D)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1

    def stage_fn(p, x):
        def body(x, lp):
            return jnp.tanh(x @ lp["w"] + lp["b"]), None
        x, _ = jax.lax.scan(body, x, p)
        return x

    xs = jax.random.normal(jax.random.PRNGKey(2), (3, 5, D))
    got = pipelined_apply(stage_fn, split_stages(dict(w=w, b=b), 2),
                          xs, mesh=None)

    def ref_one(x):
        for l in range(L):
            x = jnp.tanh(x @ w[l] + b[l])
        return x
    want = jax.vmap(ref_one)(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_cca_state_sharding_rules():
    """Every leaf gets a sharding; on a 1-device mesh all replicate
    (size-1 axes degrade to None — exact tiling is asserted on the real
    8-device mesh inside the subprocess above)."""
    import functools
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.config import EngineConfig
    from repro.core.state import init_state
    from repro.dist.sharding import cca_state_shardings
    from repro.launch.mesh import make_host_mesh
    cfg = EngineConfig(height=8, width=8, n_vertices=64, ghost_slots=16,
                       io_stream_cap=256, chunk=8)
    shape = jax.eval_shape(functools.partial(init_state, cfg))
    sh = cca_state_shardings(make_host_mesh(1, 1), shape)
    assert all(isinstance(s, NamedSharding) for s in jax.tree.leaves(sh))
    assert sh.cycle.spec == P()
    assert all(e is None for e in sh.vals.spec)  # size-1 axes -> replicated
