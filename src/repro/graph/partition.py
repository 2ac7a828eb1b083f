"""Owner-partitioned COO edges: the GNN collective optimization
(EXPERIMENTS.md §Perf, gcn-cora cell).

Edges are bucketed by the shard that OWNS their destination node (node
blocks are contiguous ranges), each bucket padded to the common max so
the flat edge array shards evenly.  Message passing then needs exactly
ONE collective per layer — the bf16 all-gather of node features — and the
scatter-add is purely local (no all-reduce): the paper's "work to data"
principle applied to bulk message passing.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


PAD_DST = np.int32(2 ** 30)


def partition_edges(edge_index: np.ndarray, n_nodes: int,
                    n_shards: int) -> np.ndarray:
    """[2, E] COO -> [2, E_pad] bucketed by dst owner, equal buckets."""
    src, dst = np.asarray(edge_index)
    n_loc = -(-n_nodes // n_shards)
    owner = dst // n_loc
    order = np.argsort(owner, kind="stable")
    src, dst, owner = src[order], dst[order], owner[order]
    counts = np.bincount(owner, minlength=n_shards)
    emax = int(counts.max())
    out = np.full((2, n_shards * emax), PAD_DST, np.int32)
    pos = 0
    for s in range(n_shards):
        c = counts[s]
        out[0, s * emax:s * emax + c] = src[pos:pos + c]
        out[1, s * emax:s * emax + c] = dst[pos:pos + c]
        pos += c
    return out


def spmm_partitioned(x, edge_index, n_nodes, coeff=None, mesh=None,
                     axes=("data", "model")):
    """A @ X with owner-partitioned edges under shard_map.

    x: [N, D] sharded over axes; edge_index: [2, E_pad] bucketed so the
    e-th shard's edges all target the e-th node block.  One bf16
    all-gather of x per call; scatter-add entirely local.
    """
    nsh = int(np.prod([mesh.shape[a] for a in axes]))
    N, D = x.shape
    n_loc = N // nsh

    def local(x_l, ei_l, coeff_l):
        xf = jax.lax.all_gather(x_l.astype(jnp.bfloat16), axes, axis=0,
                                tiled=True)
        src, dst = ei_l[0], ei_l[1]
        m = xf[jnp.clip(src, 0, N - 1)].astype(jnp.float32)
        if coeff_l is not None:
            m = m * coeff_l[:, None]
        idx = (jax.lax.axis_index(axes[0]) * mesh.shape[axes[1]]
               + jax.lax.axis_index(axes[1]))
        local_dst = dst - idx * n_loc   # out-of-range (incl. pad) dropped
        out = jnp.zeros((n_loc, D), jnp.float32)
        return out.at[local_dst].add(m, mode="drop")

    specs = (P(axes, None), P(None, axes),
             P(axes) if coeff is not None else None)
    args = (x, edge_index) + ((coeff,) if coeff is not None else ())
    if coeff is None:
        def local2(x_l, ei_l):
            return local(x_l, ei_l, None)
        return jax.shard_map(local2, mesh=mesh, in_specs=specs[:2],
                             out_specs=P(axes, None))(*args)
    return jax.shard_map(local, mesh=mesh, in_specs=specs,
                         out_specs=P(axes, None))(*args)
