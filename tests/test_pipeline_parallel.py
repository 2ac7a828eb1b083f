"""Pipeline parallelism correctness: run in a subprocess with 8 fake host
devices (XLA device count is locked at first jax init, so the multi-device
test must own its process)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.pipeline import pipelined_apply, split_stages

    mesh = jax.make_mesh((4,), ("pipe",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    L, D, n_micro, micro = 8, 16, 6, 4
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, D, D)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1

    def layer(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def stage_fn(p, x):   # apply this stage's L/S layers sequentially
        def body(x, lp):
            return layer(lp, x), None
        x, _ = jax.lax.scan(body, x, p)
        return x

    xs = jax.random.normal(jax.random.PRNGKey(2), (n_micro, micro, D))
    stages = split_stages(dict(w=w, b=b), 4)
    got = pipelined_apply(stage_fn, stages, xs, mesh, axis="pipe")

    # sequential reference
    def ref_one(x):
        for l in range(L):
            x = layer(dict(w=w[l], b=b[l]), x)
        return x
    want = jax.vmap(ref_one)(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # bubble math: 6 micro + 4 stages - 1 = 9 ticks
    print("PIPELINE_OK")
""")


def test_pipeline_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
