"""Pallas TPU EmbeddingBag: scalar-prefetched row gather + bag reduce.

The bag indices are scalar-prefetched (SMEM) so the BlockSpec index_map
can stream exactly the needed table rows HBM->VMEM — the TPU version of
FBGEMM's TBE gather.  Grid (B, L): the L axis accumulates the bag sum in
the output block.  The huge table never leaves HBM except for the touched
rows (this is what makes the lookup the "work to data" hot path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, row_ref, o_ref, *, L, combiner):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += row_ref[...].astype(o_ref.dtype)

    if combiner == "mean":
        @pl.when(l == L - 1)
        def _final():
            o_ref[...] = o_ref[...] / L


def embedding_bag_fwd(table, indices, *, combiner="sum", interpret=False):
    """table: [V, D]; indices: [B, L] int32 -> [B, D] (f32)."""
    V, D = table.shape
    B, L = indices.shape
    flat = indices.reshape(-1).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_bag_kernel, L=L, combiner=combiner),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, L),
            in_specs=[
                pl.BlockSpec((1, D), lambda b, l, idx: (idx[b * L + l], 0)),
            ],
            out_specs=pl.BlockSpec((1, D), lambda b, l, idx: (b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat, table)
    return out
