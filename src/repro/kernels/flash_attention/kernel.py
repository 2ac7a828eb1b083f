"""Pallas TPU flash-attention forward (causal, GQA).

Grid: (B * H, nQ, nK) — the K dimension is innermost ("arbitrary"), so the
output block plus the running (m, l) scratch accumulate across K steps in
VMEM (the canonical TPU flash schedule: HBM->VMEM stream of KV tiles
through the MXU).  GQA is handled in the BlockSpec index maps: the KV tile
for flat head h comes from kv head h // G — no materialized repeat.

Causal skipping: K tiles strictly above the diagonal still run (grid is
static) but their contribution is masked; the @pl.when(init) guard keeps
the accumulator exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, bq, bk, causal):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, dh]
    k = k_ref[0].astype(jnp.float32)                  # [bk, dh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq,bk]
    if causal:
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])                   # [bq, bk]
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    v = v_ref[0].astype(jnp.float32)                  # [bk, dh]
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, bq=128, bk=128,
                        interpret=False):
    """q: [B, Tq, H, dh]; k/v: [B, Tk, Kh, dh] -> [B, Tq, H, dh]."""
    B, Tq, H, dh = q.shape
    Tk, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0
    scale = 1.0 / np.sqrt(dh)
    # flatten (B, H) -> rows of a [B*H, T, dh] layout
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kh, Tk, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kh, Tk, dh)

    def kv_map(b, iq, ik):
        return (b // G, ik, 0)

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bk, dh), kv_map),
            pl.BlockSpec((1, bk, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Tq, dh).transpose(0, 2, 1, 3)
