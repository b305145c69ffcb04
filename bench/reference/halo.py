"""Plain reference of the ``halo3d`` deployment.

The whole domain as one array, every stencil application a periodic
wrap-and-sum over it: no halos, no exchange, no ranks.  The layout
helpers only undo the program's storage (ranks stacked along the
leading axis, each block an interior inside a zero-padded shell) and
read nothing but array shapes and the configuration.
"""

from __future__ import annotations

import functools
import itertools
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "box_offsets", "interiors", "assemble", "apply_op", "cycles",
    "max_rel_err",
]


def box_offsets(radii: Sequence[int]) -> Tuple[Tuple[int, int, int], ...]:
    """Every nonzero offset of the ``[-r..r]`` box, z slowest."""
    rz, ry, rx = radii
    return tuple(
        d for d in itertools.product(
            range(-rz, rz + 1), range(-ry, ry + 1), range(-rx, rx + 1)
        ) if d != (0, 0, 0)
    )


def interiors(field: np.ndarray, nranks: int,
              interior: Sequence[int]) -> np.ndarray:
    """(nranks, nz, ny, nx) interiors of a (nranks*az, ay, ax) field whose
    blocks hold each interior centred in a shell of equal depth per
    side."""
    field = np.asarray(field)
    az = field.shape[0] // nranks
    blocks = field.reshape(nranks, az, *field.shape[1:])
    lo = [(a - n) // 2 for a, n in zip(blocks.shape[1:], interior)]
    return blocks[:, lo[0]:lo[0] + interior[0], lo[1]:lo[1] + interior[1],
                  lo[2]:lo[2] + interior[2]]


def assemble(parts: np.ndarray, grid: Sequence[int]) -> np.ndarray:
    """The global field from per-rank interiors; rank ``r`` sits at grid
    coordinates ``unravel_index(r, grid)`` (row-major)."""
    pz, py, px = grid
    _, nz, ny, nx = parts.shape
    return (
        parts.reshape(pz, py, px, nz, ny, nx)
        .transpose(0, 3, 1, 4, 2, 5)
        .reshape(pz * nz, py * ny, px * nx)
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def apply_op(u: jax.Array, radii: Tuple[int, int, int], weight: float
             ) -> jax.Array:
    """``(1-w)*u + w/N * sum of the N shifted copies``, periodic, in
    ``u``'s dtype: the field wrapped once, then one shifted window of
    it added at a time, so that no more than three copies of the field
    are held."""
    offs = box_offsets(radii)
    starts = jnp.asarray([[r + d for r, d in zip(radii, o)] for o in offs],
                         jnp.int32)
    wrapped = jnp.pad(u, [(r, r) for r in radii], mode="wrap")

    def add(i, acc):
        return acc + jax.lax.dynamic_slice(
            wrapped, (starts[i, 0], starts[i, 1], starts[i, 2]), u.shape)

    acc = jax.lax.fori_loop(0, len(offs), add, jnp.zeros_like(u))
    w = jnp.asarray(weight, u.dtype)
    return (1 - w) * u + (w / len(offs)) * acc


def cycles(u: jax.Array, cycle: Sequence[dict], repeats: int,
           dtype=jnp.float32) -> jax.Array:
    """``repeats`` passes of the op ``cycle`` (the configuration's
    ``[{"radii": .., "weight": ..}, ...]``) over the global field,
    computed in ``dtype``, one application at a time; the result is
    float32."""
    v = jnp.asarray(u).astype(dtype)
    for _ in range(repeats):
        for op in cycle:
            v = apply_op(v, tuple(op["radii"]), float(op["weight"]))
    return v.astype(jnp.float32)


def max_rel_err(got: jax.Array, want: jax.Array) -> float:
    """``max |got - want| / max |want|``."""
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
    return float(err / jnp.max(jnp.abs(want)))
