"""Pallas TPU scatter-SpMM: segment-sum of edge messages via one-hot MXU
matmuls (DESIGN §2 "MXU exploitation").

GPU GNN kernels scatter with atomics; TPU has no atomics but has a 128x128
systolic array.  With edges sorted by destination, a [bn x be] one-hot
ownership matrix turns the scatter into a dense matmul:

    out[r*bn:(r+1)*bn] += onehot(dst_block - r*bn) @ msgs_block

Scalar-prefetched per-edge-block (min, max) destination ranges let the
kernel skip disjoint (row-block, edge-block) pairs — the sparsity
structure — while everything that does run is MXU work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmm_kernel(ranges_ref, dst_ref, msgs_ref, o_ref, *, bn, be):
    r = pl.program_id(0)
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lo = ranges_ref[e, 0]
    hi = ranges_ref[e, 1]
    overlap = (hi >= r * bn) & (lo < (r + 1) * bn)

    @pl.when(overlap)
    def _accum():
        dst = dst_ref[...]                                   # [be]
        local = dst - r * bn
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, be), 0)
        onehot = (rows == local[None, :]).astype(msgs_ref.dtype)
        o_ref[...] += jax.lax.dot_general(
            onehot, msgs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def scatter_spmm(msgs, dst, n_nodes, *, bn=128, be=256, interpret=False):
    """msgs: [E, D] edge messages; dst: [E] int32 SORTED ascending.

    Returns [n_nodes, D] segment sums.
    """
    E, D = msgs.shape
    bn = min(bn, max(8, n_nodes))
    n_pad = -(-n_nodes // bn) * bn
    e_pad = -(-E // be) * be
    if e_pad > E:
        msgs = jnp.pad(msgs, ((0, e_pad - E), (0, 0)))
        dst = jnp.pad(dst, (0, e_pad - E), constant_values=jnp.int32(2**30))
    nE = e_pad // be
    nR = n_pad // bn
    # per-edge-block dst ranges (scalar prefetch -> SMEM)
    db = dst.reshape(nE, be)
    ranges = jnp.stack([db.min(axis=1), db.max(axis=1)], axis=1)
    ranges = ranges.astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_spmm_kernel, bn=bn, be=be),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nR, nE),
            in_specs=[
                pl.BlockSpec((be,), lambda r, e, rng: (e,)),
                pl.BlockSpec((be, D), lambda r, e, rng: (e, 0)),
            ],
            out_specs=pl.BlockSpec((bn, D), lambda r, e, rng: (r, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ranges, dst, msgs)
    return out[:n_nodes]
