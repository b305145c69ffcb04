"""Production mesh construction (multi-pod dry-run spec).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axis semantics: "pod" = pure data parallelism over the cross-pod DCN
    (gradient all-reduce only, int8-compressible); "data" = within-pod
    data/FSDP axis; "model" = tensor/sequence parallel axis on ICI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CI: same axis names, tiny shapes."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (the installed JAX
    defaults to ``Explicit``, which the sharding rules here do not use)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Physical axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
