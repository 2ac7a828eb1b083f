"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

``split_stages`` carves a layer-stacked parameter pytree into S
contiguous stages; ``pipelined_apply`` runs the classic tick schedule
under shard_map: every tick each device applies its own stage to the
activation it holds, then a ``ppermute`` shifts activations one stage
forward while stage 0 feeds the next microbatch.  With M microbatches and
S stages the schedule drains in ``M + S - 1`` ticks (the pipeline
bubble), implemented as a single ``lax.scan`` over ticks so the HLO is
O(1) in both M and S.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def split_stages(params, n_stages: int):
    """Split a layer-stacked pytree (leaves ``[L, ...]``) into
    ``n_stages`` equal contiguous stages (leaves ``[S, L/S, ...]``)."""
    def split(l):
        L = l.shape[0]
        if L % n_stages:
            raise ValueError(
                f"cannot split {L} stacked layers into {n_stages} stages")
        return l.reshape(n_stages, L // n_stages, *l.shape[1:])
    return jax.tree.map(split, params)


def pipelined_apply(stage_fn, stages, xs, mesh, axis: str = "pipe"):
    """Run ``xs`` ([n_micro, ...microbatch]) through the staged network.

    ``stage_fn(stage_params, x)`` applies ONE stage (its leaves are the
    ``[L/S, ...]`` slice of the layer stack) to one microbatch.  The
    stage dim of ``stages`` is sharded over ``mesh[axis]``; activations
    hop stage-to-stage via ppermute.  Returns ``[n_micro, ...]`` outputs,
    replicated.  Falls back to a sequential loop when ``mesh`` is None or
    lacks ``axis`` (so the same driver code runs unmeshed).
    """
    n_stages = int(jax.tree.leaves(stages)[0].shape[0])
    if mesh is None or axis not in mesh.axis_names \
            or int(mesh.shape[axis]) == 1:
        def seq(x):
            for s in range(n_stages):
                x = stage_fn(jax.tree.map(lambda l: l[s], stages), x)
            return x
        return jax.vmap(seq)(xs)

    if int(mesh.shape[axis]) != n_stages:
        raise ValueError(
            f"{n_stages} stages need mesh axis '{axis}' of that size, "
            f"got {int(mesh.shape[axis])}")
    n_micro = xs.shape[0]
    n_ticks = n_micro + n_stages - 1          # the pipeline bubble
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(stages_l, xs_l):
        p = jax.tree.map(lambda l: l[0], stages_l)   # this device's stage
        sid = jax.lax.axis_index(axis)
        # the carry varies along the axis (each stage holds its own
        # activation after the first ppermute), so it must start varying
        buf0, outs0 = jax.lax.pcast(
            (jnp.zeros(xs_l.shape[1:], xs_l.dtype), jnp.zeros_like(xs_l)),
            axis, to="varying")

        def tick(carry, t):
            buf, outs = carry
            feed = xs_l[jnp.minimum(t, n_micro - 1)]
            out = stage_fn(p, jnp.where(sid == 0, feed, buf))
            done = t - (n_stages - 1)         # microbatch finishing now
            keep = (sid == n_stages - 1) & (done >= 0)
            outs = jnp.where(
                keep, outs.at[jnp.clip(done, 0, n_micro - 1)].set(out),
                outs)
            # shift activations one stage forward (stage 0 gets zeros,
            # which it never reads — it always consumes the feed)
            return (jax.lax.ppermute(out, axis, fwd), outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        # only the last stage holds real outputs; psum replicates them
        return jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)

    stage_specs = jax.tree.map(
        lambda l: P(axis, *((None,) * (l.ndim - 1))), stages)
    rep = P(*((None,) * xs.ndim))
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(stage_specs, rep), out_specs=rep)
    return fn(stages, xs)
