"""Owner-partitioned SpMM == plain segment_sum (multi-device subprocess)."""
import subprocess
import sys


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from repro.graph.partition import partition_edges, spmm_partitioned
from repro.graph.segment_ops import spmm

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N, E, D = 64, 500, 16
src = rng.integers(0, N, E).astype(np.int32)
dst = rng.integers(0, N, E).astype(np.int32)
x = rng.standard_normal((N, D), dtype=np.float32)
coeff = rng.standard_normal(E).astype(np.float32)

part = partition_edges(np.stack([src, dst]), N, 8)
# pad coeff to the partitioned layout (recompute per-edge coeff by lookup)
key = {(int(s), int(d)): float(c) for s, d, c in zip(src, dst, coeff)}
# duplicate edges share coeff; rebuild by matching original positions
cpart = np.zeros(part.shape[1], np.float32)
used = {}
orig = {}
for i, (s, d) in enumerate(zip(src, dst)):
    orig.setdefault((int(s), int(d)), []).append(coeff[i])
for j in range(part.shape[1]):
    s, d = int(part[0, j]), int(part[1, j])
    if d >= N:
        continue
    lst = orig[(s, d)]
    cpart[j] = lst[used.get((s, d), 0) % len(lst)]
    used[(s, d)] = used.get((s, d), 0) + 1

with jax.set_mesh(mesh):
    got = spmm_partitioned(jnp.asarray(x), jnp.asarray(part), N,
                           jnp.asarray(cpart), mesh)
want = spmm(jnp.asarray(x.astype(np.float32)), jnp.stack([src, dst]), N,
            jnp.asarray(coeff))
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-2, atol=2e-2)  # bf16 gather
print("PART_SPMM_OK")
"""


def test_partitioned_spmm_matches():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "PART_SPMM_OK" in r.stdout, r.stdout + r.stderr


def test_partition_edges_layout():
    import numpy as np
    from repro.graph.partition import partition_edges, PAD_DST
    rng = np.random.default_rng(1)
    src = rng.integers(0, 40, 200).astype(np.int32)
    dst = rng.integers(0, 40, 200).astype(np.int32)
    part = partition_edges(np.stack([src, dst]), 40, 4)
    assert part.shape[1] % 4 == 0
    emax = part.shape[1] // 4
    n_loc = 10
    for s in range(4):
        blk = part[1, s * emax:(s + 1) * emax]
        real = blk[blk != PAD_DST]
        assert ((real // n_loc) == s).all()
    # every edge present exactly once
    real_cols = part[:, part[1] != PAD_DST]
    assert real_cols.shape[1] == 200
