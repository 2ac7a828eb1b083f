"""Host wrapper for the fused cycle megakernel.

``cca_cycle_chunk`` flattens the ``MachineState`` into Pallas operands
(bool leaves ride as int32, the five scalar counters pack into one
``(1, 8)`` SMEM record), launches ``kernel.cycle_megakernel`` with every
input aliased onto its output (the state is updated in place — no
second copy of the machine in HBM), and rebuilds the pytree.

Off-TPU the kernel runs with ``interpret=True``, so CPU CI runs the
identical kernel semantics (Pallas interpret mode discharges the kernel
into the surrounding XLA computation, so it is still jit-compiled —
only the VMEM residency is simulated).  On TPU, Mosaic refuses it (the
stages' scatters) and its state exceeds VMEM, so ``StreamingEngine``
rejects ``backend="pallas"`` there (``engine.PALLAS_ON_TPU``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.apps import DiffusionApp
from repro.core.config import EngineConfig
from repro.core.state import MachineState
from repro.kernels.cca_cycle.kernel import (BOOL_LEAVES, IDX_QUIESCENT,
                                            IDX_RAN, N_SCALARS,
                                            SCALAR_LEAVES, cycle_megakernel)

ARRAY_LEAVES = tuple(f for f in MachineState._fields
                     if f not in SCALAR_LEAVES)


def cca_cycle_chunk(cfg: EngineConfig, app: DiffusionApp, st: MachineState,
                    n_cycles: int | None = None, interpret: bool | None = None):
    """Run up to ``n_cycles`` (default ``cfg.chunk``) engine cycles in one
    fused Pallas launch with freeze-at-quiescence.

    Returns ``(state, counters)`` — ``counters`` is int32
    ``[quiescent_at_end, cycles_run]`` read from the kernel's SMEM
    record.  Traceable: safe to call inside jit / ``lax.while_loop``
    (the engine's sync-free driver does exactly that).
    """
    n_cycles = cfg.chunk if n_cycles is None else n_cycles
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    arrs = [getattr(st, name).astype(jnp.int32)
            if name in BOOL_LEAVES else getattr(st, name)
            for name in ARRAY_LEAVES]
    scal = jnp.stack(
        [getattr(st, name) for name in SCALAR_LEAVES]
        + [jnp.int32(0)] * (N_SCALARS - len(SCALAR_LEAVES))).reshape(1, -1)

    kernel = functools.partial(cycle_megakernel, cfg, app, n_cycles,
                               ARRAY_LEAVES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.ANY if interpret else pltpu.VMEM)
    outs = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(scal.shape, jnp.int32)]
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrs],
        in_specs=[smem] + [vmem] * len(arrs),
        out_specs=[smem] + [vmem] * len(arrs),
        input_output_aliases={i: i for i in range(1 + len(arrs))},
        interpret=interpret,
    )(scal, *arrs)

    scal_o, arr_o = outs[0], outs[1:]
    leaves = dict(zip(ARRAY_LEAVES, arr_o))
    for name in BOOL_LEAVES:
        leaves[name] = leaves[name].astype(bool)
    for i, name in enumerate(SCALAR_LEAVES):
        leaves[name] = scal_o[0, i]
    return MachineState(**leaves), scal_o[0, IDX_QUIESCENT:IDX_RAN + 1]
