"""Production mesh construction.

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod:  2x16x16 = 512 chips ("pod", "data", "model") — the pod axis is
pure data parallelism over DCN; gradient reduction is hierarchical
(reduce-scatter intra-pod over ICI, all-reduce inter-pod).

Defined as a function (NOT a module-level constant) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (1 device unless XLA_FLAGS overrides)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    """The data-parallel axis group: ('pod','data') when a pod axis exists."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
