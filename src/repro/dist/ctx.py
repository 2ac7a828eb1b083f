"""Process-global distribution context: one mesh, one constraint helper.

The paper's vertex object is "parallelized across many scratchpad
memory-coupled cores and yet provides a single programming abstraction to
the data object" — here the single abstraction is the model/engine code
written against plain arrays, and this module is the thin seam through
which GSPMD distributes them.  Model code never talks to a mesh directly:
it calls ``constrain(x, *axes)`` with logical axis names and the call
degrades to identity when no mesh is registered (single-process tests) or
when the named axes do not exist / do not divide the dimension.

Logical axis vocabulary (DESIGN §5):

* ``"model"``          — tensor-parallel axis,
* ``"data"`` / ``"pod"`` — data-parallel axes (``"pod"`` only on
  multi-pod meshes; gradient reduction is hierarchical),
* ``"dp"``             — alias expanding to the active data-parallel axis
  group (``("pod", "data")`` or ``("data",)``),
* ``None``             — replicated dimension,
* a tuple of names     — dimension sharded over several mesh axes.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_DIST_MESH = None


def set_dist_mesh(mesh):
    """Register the process mesh used by ``constrain`` (None to clear)."""
    global _DIST_MESH
    _DIST_MESH = mesh
    return mesh


def get_dist_mesh():
    return _DIST_MESH


def model_size(mesh=None) -> int:
    """Size of the tensor-parallel ('model') axis; 1 when unmeshed."""
    mesh = mesh if mesh is not None else _DIST_MESH
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def dp_axes_active(mesh=None) -> tuple:
    """The data-parallel axis group present on the mesh.

    ``("pod", "data")`` on multi-pod meshes, ``("data",)`` otherwise;
    defaults to ``("data",)`` when no mesh is registered so callers can
    build PartitionSpecs unconditionally.
    """
    mesh = mesh if mesh is not None else _DIST_MESH
    if mesh is None:
        return ("data",)
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names) \
        or ("data",)


def _resolve_entry(mesh, entry):
    """One PartitionSpec entry -> tuple of valid mesh axis names (or ())."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        names = dp_axes_active(mesh) if entry == "dp" else (entry,)
    else:  # tuple/list of axis names (possibly containing "dp")
        names = []
        for e in entry:
            names.extend(dp_axes_active(mesh) if e == "dp" else (e,))
        names = tuple(names)
    return tuple(n for n in names if n in mesh.axis_names)


def resolve_spec(mesh, shape, axes) -> P:
    """Logical axes -> a PartitionSpec valid for ``shape`` on ``mesh``.

    Per-dimension no-op (-> replicated) when the named axes are absent
    from the mesh or their combined size does not divide the dimension.
    """
    spec = []
    for dim, entry in zip(shape, axes):
        names = _resolve_entry(mesh, entry)
        size = int(np.prod([mesh.shape[n] for n in names])) if names else 1
        if not names or size <= 1 or dim % size != 0:
            spec.append(None)
        elif len(names) == 1:
            spec.append(names[0])
        else:
            spec.append(tuple(names))
    return P(*spec)


def constrain(x, *axes):
    """Sharding-constrain ``x`` onto the registered mesh (identity when
    unmeshed, axes absent, or sizes indivisible).  ``len(axes)`` must
    equal ``x.ndim``."""
    mesh = _DIST_MESH
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(
            f"constrain: got {len(axes)} axes for rank-{x.ndim} array")
    spec = resolve_spec(mesh, x.shape, axes)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
