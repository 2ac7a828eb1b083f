"""Decoder-only LM: GQA + RoPE (+ optional qk-norm), dense or MoE FFN.

Design notes (MaxText-style, sized for 1000+-chip runs):

* parameters are **stacked over layers** and the forward is a
  ``lax.scan`` over the stack -> HLO size is O(1) in depth, which keeps
  512-device dry-run compiles fast and enables uniform remat;
* attention is **chunked online-softmax** (flash) even in the pure-XLA
  path, so peak memory never materializes the [T, T] score matrix; on
  real TPUs the Pallas kernel (repro.kernels.flash_attention.ops) is the
  drop-in replacement for flash_attention_xla (validated against the
  same oracle in tests/test_kernels.py);
* all matmuls run in bf16 with f32 accumulation; params live in f32
  (master copy) unless cfg.param_dtype says otherwise.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import dense_init, layer_norm, rms_norm


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str = "lm"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 1024
    vocab: int = 1024
    head_dim: int = 0            # 0 -> d_model // n_heads
    norm: str = "rms"            # "rms" | "ln"
    qk_norm: bool = False
    gated_ffn: bool = True       # SwiGLU (llama-family); False -> GELU MLP
    rope_theta: float = 10_000.0
    # --- MoE ---
    n_experts: int = 0           # 0 -> dense FFN
    top_k: int = 2
    dense_residual: bool = False  # arctic: dense FFN in parallel with MoE
    capacity_factor: float = 1.25
    # --- numerics ---
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_chunk: int = 512
    # --- distribution ---
    moe_impl: str = "dense"      # "dense" (GShard einsum, small S) |
                                 # "ep" (shard_map expert parallelism)

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def n_params(self) -> int:
        """Analytic parameter count (embedding + layers + head)."""
        D, F, H, K, dh = (self.d_model, self.d_ff, self.n_heads,
                          self.n_kv_heads, self.dh)
        attn = D * H * dh + 2 * D * K * dh + H * dh * D
        ffn = D * F * (3 if self.gated_ffn else 2)
        if self.n_experts:
            moe = self.n_experts * ffn + D * self.n_experts
            ffn = moe + (ffn if self.dense_residual else 0)
        per_layer = attn + ffn + 2 * D
        return self.vocab * D * 2 + self.n_layers * per_layer + D

    def n_active_params(self) -> int:
        """Active (per-token) params — MoE uses top_k experts only."""
        if not self.n_experts:
            return self.n_params()
        D, F = self.d_model, self.d_ff
        ffn1 = D * F * (3 if self.gated_ffn else 2)
        inactive = self.n_layers * (self.n_experts - self.top_k) * ffn1
        return self.n_params() - max(inactive, 0)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def padded_heads(cfg: LMConfig) -> int:
    """Physical head count (§Perf iter 3 — REFUTED and disabled).

    Zero-padding heads to a TP multiple (arctic 56->64) removed the
    attention dK/dQ all-reduces (-1.9 GB/dev) but the replacement
    row-parallel psums + 14% bigger FSDP gathers cost more than it saved
    (+4.2 GB/dev all-gather).  Sequence-sharded attention (the fallback
    when H %% TP != 0) is the better regime for these archs; kept here
    (returning the unpadded count) with the measurement recorded in
    EXPERIMENTS.md so the refutation is reproducible."""
    return cfg.n_heads


def init_lm_params(cfg: LMConfig, key) -> dict:
    D, F, H, K, dh, L = (cfg.d_model, cfg.d_ff, padded_heads(cfg),
                         cfg.n_kv_heads, cfg.dh, cfg.n_layers)
    keys = jax.random.split(key, 12)
    pd = cfg.param_dtype

    def stack(k, shape, fan_in):
        if L == 0:  # cost-extraction lowers use 0-layer variants
            return jnp.zeros((0, *shape), pd)
        ks = jax.random.split(k, L)
        return jnp.stack([dense_init(ks[i], shape, fan_in, pd)
                          for i in range(L)])

    layers = dict(
        wq=stack(keys[0], (D, H * dh), D),
        wk=stack(keys[1], (D, K * dh), D),
        wv=stack(keys[2], (D, K * dh), D),
        wo=stack(keys[3], (H * dh, D), H * dh),
        ln1=jnp.ones((L, D), pd),
        ln2=jnp.ones((L, D), pd),
    )
    if cfg.norm == "ln":
        layers["ln1b"] = jnp.zeros((L, D), pd)
        layers["ln2b"] = jnp.zeros((L, D), pd)
    if cfg.qk_norm:
        layers["qnorm"] = jnp.ones((L, dh), pd)
        layers["knorm"] = jnp.ones((L, dh), pd)

    def ffn_params(k, prefix, e=None):
        ks = jax.random.split(k, 3)
        shp = (L, D, F) if e is None else (L, e, D, F)
        shp_out = (L, F, D) if e is None else (L, e, F, D)

        def stk(kk, shape, fan_in):
            if L == 0:
                return jnp.zeros((0, *shape[1:]), pd)
            return jnp.stack([dense_init(k2, shape[1:], fan_in, pd)
                              for k2 in jax.random.split(kk, L)])

        p = {prefix + "wi": stk(ks[0], shp, D)}
        if cfg.gated_ffn:
            p[prefix + "wg"] = stk(ks[1], shp, D)
        p[prefix + "wo"] = stk(ks[2], shp_out, F)
        return p

    if cfg.n_experts:
        layers.update(ffn_params(keys[4], "moe_", cfg.n_experts))
        layers["router"] = stack(keys[5], (D, cfg.n_experts), D)
        if cfg.dense_residual:
            layers.update(ffn_params(keys[6], "ffn_"))
    else:
        layers.update(ffn_params(keys[4], "ffn_"))

    return dict(
        embed=dense_init(keys[7], (cfg.vocab, D), D, pd),
        unembed=dense_init(keys[8], (D, cfg.vocab), D, pd),
        final_norm=jnp.ones((D,), pd),
        layers=layers,
    )


# --------------------------------------------------------------------------
# rope / norm helpers
# --------------------------------------------------------------------------

def rope(x, positions, theta):
    """x: [B, T, H, dh]; positions: [B, T].

    cos/sin are computed in f32 but CAST to x.dtype before touching x:
    otherwise every rope output (and its bwd cotangent) is silently f32,
    doubling the attention-path collective/memory bytes (found via the
    dry-run HLO collective audit — EXPERIMENTS.md §Perf iter 1).
    """
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B,T,half]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _norm(cfg, x, scale, bias=None):
    if cfg.norm == "ln":
        return layer_norm(x, scale, bias)
    return rms_norm(x, scale)


@jax.custom_vjp
def ct_cast(x):
    """Identity that forces the COTANGENT back to x's dtype.

    f32-accumulating einsums (preferred_element_type=f32) emit f32
    cotangents which then flow through the whole backward residual/QKV
    stream — doubling every backward all-gather/all-reduce (arctic HLO
    audit, EXPERIMENTS.md §Perf iter 1).  Inserting ct_cast at the layer
    and attention inputs pins the backward stream to bf16.
    """
    return x


def _ct_fwd(x):
    return x, jnp.zeros((0,), x.dtype)  # dtype token (valid JAX residual)


def _ct_bwd(token, g):
    return (g.astype(token.dtype),)


ct_cast.defvjp(_ct_fwd, _ct_bwd)


def wcast(w, cfg, *spec):
    """Cast a weight to compute dtype AND pin the cast output to the
    weight's own sharding.  Without the pin, GSPMD may all-gather the f32
    master weight and convert after — 2x the FSDP gather bytes (found in
    the arctic HLO audit, EXPERIMENTS.md §Perf iter 1)."""
    from repro.dist.ctx import constrain
    return constrain(w.astype(cfg.compute_dtype), *spec)


# --------------------------------------------------------------------------
# attention (chunked online softmax — flash, in plain XLA)
# --------------------------------------------------------------------------

def flash_attention_xla(q, k, v, *, causal=True, chunk=512, q_offset=0):
    """q: [B,Tq,H,dh], k/v: [B,Tk,Kh,dh] (GQA: H % Kh == 0).

    Scans KV chunks with a running (max, sum, acc) — peak memory is
    O(Tq * chunk), never [Tq, Tk].  Computes in flat-H layout (KV heads
    broadcast per chunk): one head axis shards cleanly over `model` for
    every assigned head count (DESIGN §6); when H doesn't divide the TP
    degree the q-time axis is sharded instead (sequence parallelism).
    """
    from repro.dist.ctx import constrain, model_size
    B, Tq, H, dh = q.shape
    Tk, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    scale = 1.0 / np.sqrt(dh)
    qf = (q * scale).astype(jnp.bfloat16)
    tp = model_size()
    if H % tp == 0:
        qf = constrain(qf, "dp", None, "model", None)
    elif Tq % tp == 0:
        qf = constrain(qf, "dp", "model", None, None)
    nchunks = -(-Tk // chunk)
    pad = nchunks * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ks = k.reshape(B, nchunks, chunk, Kh, dh)
    vs = v.reshape(B, nchunks, chunk, Kh, dh)
    rows = q_offset + jnp.arange(Tq)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, ci = inp
        # Resharding (if any) must happen on the COMPACT [*, Kh, dh] KV
        # chunk, not on the H-broadcast copy — for 56:8 GQA that is 7x
        # fewer gathered bytes (EXPERIMENTS.md §Perf iter 2).
        kc = constrain(kc.astype(jnp.bfloat16), "dp", None, None, None)
        vc = constrain(vc.astype(jnp.bfloat16), "dp", None, None, None)
        # broadcast KV heads to flat H (virtual repeat; fused by XLA)
        kcf = jnp.broadcast_to(kc[:, :, :, None],
                               (B, chunk, Kh, G, dh)).reshape(B, chunk, H, dh)
        vcf = jnp.broadcast_to(vc[:, :, :, None],
                               (B, chunk, Kh, G, dh)).reshape(B, chunk, H, dh)
        s = jnp.einsum("bthd,bchd->bthc", qf, kcf,
                       preferred_element_type=jnp.float32)
        cols = ci * chunk + jnp.arange(chunk)
        mask = cols[None, :] <= rows[:, None] if causal else \
            jnp.broadcast_to(cols[None, :] >= 0, (Tq, chunk))
        mask = mask & (cols[None, :] < Tk)
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        m2 = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m2)
        p = jnp.exp(s - m2[..., None])
        l2 = l * corr + p.sum(axis=-1)
        acc2 = acc * corr[..., None] + jnp.einsum(
            "bthc,bchd->bthd", p.astype(jnp.bfloat16), vcf,
            preferred_element_type=jnp.float32)
        return (m2, l2, acc2), None

    # carries start from q, not fresh constants, so inside a shard_map
    # (dist/pipeline.py) they vary over the same mesh axes as the body's
    # outputs, which the scan's carry type check requires
    m0 = jnp.full_like(q[..., 0], -jnp.inf, jnp.float32)
    l0 = jnp.zeros_like(q[..., 0], jnp.float32)
    a0 = jnp.zeros_like(q, jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0),
         jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths):
    """Single-token decode: q [B,1,H,dh]; caches [B,T,Kh,dh]; lengths [B].

    Plain (non-chunked) — decode is linear in T; with the cache's T axis
    sharded this is flash-decoding: partial softmax merged by the psum XLA
    inserts for the reductions over the sharded axis.
    """
    B, _, H, dh = q.shape
    Kh = k_cache.shape[2]
    G = H // Kh
    scale = 1.0 / np.sqrt(dh)
    qf = (q[:, 0] * scale).reshape(B, Kh, G, dh).astype(jnp.bfloat16)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    T = k_cache.shape[1]
    mask = jnp.arange(T)[None, :] < lengths[:, None]          # [B,T]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(jnp.bfloat16),
                     v_cache.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, dh)


# --------------------------------------------------------------------------
# layer / model forward
# --------------------------------------------------------------------------

def _ffn_dense(cfg: LMConfig, lp, x, prefix="ffn_"):
    wi = wcast(lp[prefix + "wi"], cfg, "dp", "model")
    wo = wcast(lp[prefix + "wo"], cfg, "model", "dp")
    if cfg.gated_ffn:
        wg = wcast(lp[prefix + "wg"], cfg, "dp", "model")
        h = (x @ wi) * jax.nn.silu(x @ wg)
    else:
        h = jax.nn.gelu(x @ wi)
    return h @ wo


def _ffn_moe(cfg: LMConfig, lp, x):
    """Top-k routed experts, GShard-style dense dispatch einsums.

    x: [B,T,D] -> combine over top_k expert outputs.  Experts dim is
    sharded over the 'model'/'expert' mesh axis; the dispatch einsum
    becomes an all-to-all under GSPMD.
    """
    cd = cfg.compute_dtype
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    S = B * T
    xs = x.reshape(S, D)
    logits = (xs @ lp["router"].astype(cd)).astype(jnp.float32)  # [S,E]
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, K)                          # [S,K]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    cap = int(np.ceil(S * K * cfg.capacity_factor / E))
    cap = max(cap, 4)
    # position of each (token, k) within its expert
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)             # [S,K,E]
    flat = onehot.reshape(S * K, E)
    pos = jnp.cumsum(flat, axis=0) - flat                          # [S*K,E]
    pos = (pos * flat).sum(-1).reshape(S, K)                       # [S,K]
    keep = pos < cap
    # dispatch tensor [S, K, E, cap] is huge; build [S,E,cap] combining K
    disp = jnp.zeros((S, E, cap), cd)
    sidx = jnp.arange(S)[:, None].repeat(K, 1)
    disp = disp.at[sidx, topi, jnp.minimum(pos, cap - 1)].add(
        keep.astype(cd))
    # expert inputs [E, cap, D]
    ein = jnp.einsum("sec,sd->ecd", disp, xs.astype(cd))
    if cfg.gated_ffn:
        h = jnp.einsum("ecd,edf->ecf", ein, lp["moe_wi"].astype(cd)) \
            * jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein,
                                     lp["moe_wg"].astype(cd)))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ein,
                                   lp["moe_wi"].astype(cd)))
    eout = jnp.einsum("ecf,efd->ecd", h, lp["moe_wo"].astype(cd))
    # combine weights: scatter the (normalized) gate values into [S,E,cap]
    comb = jnp.zeros((S, E, cap), cd)
    comb = comb.at[sidx, topi, jnp.minimum(pos, cap - 1)].add(
        (keep * topv).astype(cd))
    out = jnp.einsum("sec,ecd->sd", comb, eout)
    # aux load-balancing loss (Switch): E * sum_e (frac_tokens_e * frac_prob_e)
    frac_t = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), 0)
    frac_p = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(frac_t * frac_p)
    return out.reshape(B, T, D), aux


def _ffn_moe_ep(cfg: LMConfig, lp, x):
    """Expert-parallel MoE via shard_map (DESIGN §6).

    Experts are sharded over the `model` axis.  Activations are
    TP-replicated over `model`, so *no all-to-all is needed*: each model
    shard locally selects the tokens routed to its own experts
    (capacity-bounded sort-gather), runs an MXU-shaped FFN per local
    expert, scatter-combines with the gate weights, and the standard
    row-parallel psum over `model` completes the combine.  Expert weights
    are FSDP-sharded on d_model over the dp group and all-gathered in bf16
    per layer (ZeRO-3).
    """
    from jax.sharding import PartitionSpec as P
    from repro.dist.ctx import dp_axes_active, get_dist_mesh

    mesh = get_dist_mesh()
    dp = dp_axes_active()
    cd = cfg.compute_dtype
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    S = B * T
    n_dp_ = 1 if mesh is None else int(
        np.prod([mesh.shape[a] for a in dp]))
    if mesh is None or S % n_dp_ or (S // n_dp_) * K < E // 4:
        # tiny token counts (e.g. batch-1 decode): dense dispatch is cheap
        return _ffn_moe(cfg, lp, x)
    xs = x.reshape(S, D)

    # routing (computed in the replicated TP region; tiny)
    logits = (xs @ lp["router"].astype(cd)).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    frac_t = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), 0)
    aux = E * jnp.sum(frac_t * jnp.mean(gates, axis=0))

    n_model = mesh.shape["model"]
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    E_loc = E // n_model
    S_loc = S // n_dp
    cap = max(int(np.ceil(S_loc * K * cfg.capacity_factor / E)), 8)

    def local_moe(xs_l, topi_l, topv_l, wi, wg, wo):
        # xs_l [S_loc, D]; wi/wg/wo already bf16 (cast OUTSIDE shard_map
        # so the cast can't be hoisted past the gather) -> gather full D
        wi = jax.lax.all_gather(wi, dp, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, dp, axis=2, tiled=True)
        first = jax.lax.axis_index("model") * E_loc
        assign = topi_l.reshape(-1)              # [S_loc*K]
        gate = topv_l.reshape(-1)
        out = jnp.zeros((S_loc, D), jnp.float32)
        for el in range(E_loc):
            hit = assign == (first + el)
            order = jnp.argsort(~hit, stable=True)[:cap]
            valid = hit[order]
            tok = order // K
            g = jnp.where(valid, gate[order], 0.0)
            xe = xs_l[tok].astype(cd)
            h = (xe @ wi[el]) * jax.nn.silu(xe @ wg[el]) if cfg.gated_ffn \
                else jax.nn.gelu(xe @ wi[el])
            ye = (h @ wo[el]).astype(jnp.float32)
            out = out.at[tok].add(ye * g[:, None])
        # <=top_k nonzero contributions per token across shards: bf16
        # psum is numerically safe and halves the combine bytes
        return jax.lax.psum(out.astype(cd), "model")

    wi_spec = P("model", dp, None)
    wo_spec = P("model", None, dp)
    wi_b = wcast(lp["moe_wi"], cfg, *wi_spec)
    wg_b = wcast(lp["moe_wg"], cfg, *wi_spec) if cfg.gated_ffn else wi_b
    wo_b = wcast(lp["moe_wo"], cfg, *wo_spec)
    out = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None),
                  wi_spec, wi_spec, wo_spec),
        out_specs=P(dp, None),
    )(xs, topi, topv, wi_b, wg_b, wo_b)
    return out.astype(cd).reshape(B, T, D), aux


def _attn(cfg: LMConfig, lp, x, positions, kv_cache=None, lengths=None):
    cd = cfg.compute_dtype
    B, T, D = x.shape
    H, K, dh = padded_heads(cfg), cfg.n_kv_heads, cfg.dh
    q = (x @ wcast(lp["wq"], cfg, "dp", "model")).reshape(B, T, H, dh)
    k = (x @ wcast(lp["wk"], cfg, "dp", "model")).reshape(B, T, K, dh)
    v = (x @ wcast(lp["wv"], cfg, "dp", "model")).reshape(B, T, K, dh)
    if cfg.qk_norm:
        q = rms_norm(q, lp["qnorm"])
        k = rms_norm(k, lp["knorm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if kv_cache is None:
        o = flash_attention_xla(ct_cast(q), ct_cast(k), ct_cast(v),
                                causal=True, chunk=cfg.attn_chunk)
        new_cache = None
    else:
        ck, cv = kv_cache                     # [B,Tmax,K,dh]
        idx = lengths[:, None] + jnp.arange(T)[None, :]       # [B,T]
        bidx = jnp.arange(B)[:, None]
        ck = ck.at[bidx, idx].set(k.astype(ck.dtype))
        cv = cv.at[bidx, idx].set(v.astype(cv.dtype))
        o = decode_attention(q, ck, cv, lengths + T)
        new_cache = (ck, cv)
    if H != cfg.n_heads:
        # zero the padded heads: exact published math, zero pad-gradients
        hmask = (jnp.arange(H) < cfg.n_heads).astype(o.dtype)
        o = o * hmask[None, None, :, None]
    o = o.reshape(B, T, H * dh).astype(cd)
    return o @ wcast(lp["wo"], cfg, "model", "dp"), new_cache


def _layer(cfg: LMConfig, lp, x, positions, kv_cache=None, lengths=None):
    x = ct_cast(x)  # pin the backward residual stream to compute dtype
    b1 = lp.get("ln1b")
    b2 = lp.get("ln2b")
    a, new_cache = _attn(cfg, lp, _norm(cfg, x, lp["ln1"], b1), positions,
                         kv_cache, lengths)
    x = x + a
    h = _norm(cfg, x, lp["ln2"], b2)
    aux = jnp.float32(0)
    if cfg.n_experts:
        moe = _ffn_moe_ep if cfg.moe_impl == "ep" else _ffn_moe
        f, aux = moe(cfg, lp, h)
        if cfg.dense_residual:
            f = f + _ffn_dense(cfg, lp, h)
    else:
        f = _ffn_dense(cfg, lp, h)
    return x + f, aux, new_cache


def lm_forward(cfg: LMConfig, params, tokens, positions=None):
    """tokens: [B, T] -> logits [B, T, vocab] (training/prefill, causal)."""
    cd = cfg.compute_dtype
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    x = wcast(params["embed"], cfg, "model", None)[tokens]

    def body(carry, lp):
        x, aux = carry
        x2, a, _ = _layer(cfg, lp, x, positions)
        return (x2, aux + a), None

    step = body
    if cfg.remat:
        step = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(step, (x, jnp.float32(0)), params["layers"])
    x = rms_norm(x, params["final_norm"])
    # logits [B, T, V] is the biggest tensor in the program: keep the time
    # axis sharded over `model` so no device ever holds [T, V] (the vocab
    # axis stays local -> softmax/CE need no collectives).
    from repro.dist.ctx import constrain, model_size
    if T % model_size() == 0:
        x = constrain(x, "dp", "model", None)
    # logits stay bf16: the [B, T/tp, V] tensor is the program's largest
    # temp; the loss does its reductions in f32 without materializing an
    # f32 copy (§Perf iter A5)
    logits = x @ wcast(params["unembed"], cfg, "dp", None)
    return logits, aux


def lm_loss(cfg: LMConfig, params, batch):
    """batch: dict(tokens [B,T], targets [B,T]).

    Cross-entropy from bf16 logits with f32 reductions: logsumexp and
    the target gather upcast per-element inside fused reductions, so no
    [B, T, V] f32 temp is ever materialized (§Perf iter A5).
    """
    logits, aux = lm_forward(cfg, params, batch["tokens"])
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None],
                              -1)[..., 0].astype(jnp.float32)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    loss = (lse - tgt).mean()
    if cfg.n_experts:
        loss = loss + 0.01 * aux / cfg.n_layers
    return loss


def init_kv_cache(cfg: LMConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def lm_decode_step(cfg: LMConfig, params, tokens, kv_cache, lengths):
    """One serving step: tokens [B,1] + caches -> next-token logits.

    kv_cache: tuple of [L,B,Tmax,K,dh]; lengths: [B] current cache fill.
    """
    cd = cfg.compute_dtype
    B, T = tokens.shape
    positions = lengths[:, None] + jnp.arange(T)[None, :]
    x = wcast(params["embed"], cfg, "model", None)[tokens]

    def body(x, inp):
        lp, ck, cv = inp
        x2, _, (nk, nv) = _layer(cfg, lp, x, positions, (ck, cv), lengths)
        return x2, (nk, nv)

    x, (nk, nv) = jax.lax.scan(body, x,
                               (params["layers"], kv_cache[0], kv_cache[1]))
    x = rms_norm(x, params["final_norm"])
    logits = (x @ wcast(params["unembed"], cfg, "dp", None)
              ).astype(jnp.float32)
    return logits, (nk, nv)
