"""Pallas TPU unpack kernels — inverses of ``repro.kernels.pack``.

Unpack writes *into* an existing buffer, so both kernels are in-place
(``input_output_aliases``):

* ``unpack_rows`` — read-modify-write of full-pitch row-groups.  Each
  grid step fetches the destination rows, splices the packed lanes in
  registers/VMEM and stores the rows back.  Requires the plane row
  ranges to be disjoint (guaranteed for well-formed strided types where
  ``strides[2] >= counts[1]*strides[1]``; checked by the planner).

* ``unpack_dma``  — the destination stays in HBM (ANY); each step DMAs
  the whole pitch rows of a row-chunk into VMEM scratch, splices the
  packed blocks in, and DMAs the rows back (TPU DMAs move whole (8, 128)
  tiles, so a narrower window cannot be written).

The paper notes unpack is slower than pack ("non-contiguous writes
instead of non-contiguous reads"); the same asymmetry exists here —
``unpack_rows`` moves 2x the pitch bytes (read + write-back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.geometry import PackGeometry
from repro.kernels.pack import dma_steps, plane_view
from repro.obs.trace import scope

__all__ = ["unpack_rows", "unpack_dma", "unpack_ragged", "decode_unpack_ragged"]


@scope("unpack")
def unpack_ragged(dst: jax.Array, wire: jax.Array, leaves) -> jax.Array:
    """Inverse of :func:`repro.kernels.pack.pack_ragged`: slice each
    leaf's exact wire segment out of the flat received buffer and
    scatter it into ``dst``.

    ``leaves`` is a sequence of ``(offset, nbytes, unpack_fn)``:
    ``unpack_fn(dst, payload)`` consumes one leaf's ``uint8[nbytes]``
    wire payload (a strategy's ``unpack_wire`` path, already bound to
    its committed type) and returns the updated destination.  Offsets
    are the wire plan's exact segment offsets — no padding is skipped
    because none was sent.
    """
    for offset, nbytes, unpack_fn in leaves:
        part = jax.lax.dynamic_slice(wire, (offset,), (nbytes,))
        dst = unpack_fn(dst, part)
    return dst


@scope("unpack")
def decode_unpack_ragged(dst: jax.Array, wire: jax.Array, leaves) -> jax.Array:
    """Fused decompress+unpack: inverse of
    :func:`repro.kernels.pack.pack_compress_ragged`.

    ``leaves`` is a sequence of ``(offset, nbytes, decode_fn,
    unpack_fn)``: each leaf's ``nbytes`` wire bytes (for a length-aware
    transport this is the *stream* length, not the capacity) are sliced
    out, decoded to member bytes by ``decode_fn`` (e.g.
    :meth:`repro.comm.compress.RleWire.decode_wire` bound to the member
    size) and scattered by ``unpack_fn(dst, member)`` — decode and
    scatter stay in one traced expression, no extra materialized pass.
    ``decode_fn=None`` means the wire bytes *are* the payload
    ``unpack_fn`` consumes (the uncompressed strategies' ``unpack_wire``
    path), degenerating to :func:`unpack_ragged` exactly.
    """
    for offset, nbytes, decode_fn, unpack_fn in leaves:
        part = jax.lax.dynamic_slice(wire, (offset,), (nbytes,))
        if decode_fn is not None:
            part = decode_fn(part)
        dst = unpack_fn(dst, part)
    return dst


def _unpack_rows_kernel(dst_ref, pk_ref, out_ref, *, r: int, lanes: int):
    # dst_ref/out_ref: (G, pitch); pk_ref: (1, G, lanes).  Two ref
    # stores, not a value-level .at[].set (Mosaic has no scatter).
    out_ref[...] = dst_ref[...]
    out_ref[:, r : r + lanes] = pk_ref[0]


def _unpack_plane_kernel(dst_ref, pk_ref, out_ref, *, y0: int, rows: int,
                         r: int, lanes: int):
    # dst_ref/out_ref: (1, view_rows, pitch); pk_ref: (1, rows, lanes)
    out_ref[...] = dst_ref[...]
    out_ref[0, y0 : y0 + rows, r : r + lanes] = pk_ref[0]


def unpack_rows(
    dst2d: jax.Array,
    packed3d: jax.Array,
    geom: PackGeometry,
    interpret: bool = False,
):
    """In-place splice of packed blocks into full-pitch row-groups (or
    whole view planes, see :class:`PackGeometry`).

    ``dst2d``: (rows_padded, pitch) word view of the destination buffer.
    ``packed3d``: (planes, rows, lanes).  Returns the updated 2D view.
    """
    if geom.plane_block:
        z0, y0 = divmod(geom.q, geom.view_rows)
        plane = pl.BlockSpec((1, geom.view_rows, geom.pitch),
                             lambda p: (z0 + p, 0, 0))
        dst3d = plane_view(dst2d, geom)
        out = pl.pallas_call(
            functools.partial(
                _unpack_plane_kernel, y0=y0, rows=geom.rows, r=geom.r,
                lanes=geom.lanes,
            ),
            grid=geom.grid,
            in_specs=[
                plane,
                pl.BlockSpec((1, geom.rows, geom.lanes), lambda p: (p, 0, 0)),
            ],
            out_specs=plane,
            out_shape=jax.ShapeDtypeStruct(dst3d.shape, dst3d.dtype),
            input_output_aliases={0: 0},
            interpret=interpret,
            name="tempi_unpack_planes",
        )(dst3d, packed3d)
        return out.reshape(dst2d.shape)

    g = geom.group
    qb = geom.q // g
    prb = geom.plane_rows // g if geom.plane_rows else 0
    row_idx = lambda p, i: (qb + p * prb + i, 0)

    return pl.pallas_call(
        functools.partial(_unpack_rows_kernel, r=geom.r, lanes=geom.lanes),
        grid=geom.grid,
        in_specs=[
            pl.BlockSpec((g, geom.pitch), row_idx),
            pl.BlockSpec((1, g, geom.lanes), lambda p, i: (p, i, 0)),
        ],
        out_specs=pl.BlockSpec((g, geom.pitch), row_idx),
        out_shape=jax.ShapeDtypeStruct(dst2d.shape, dst2d.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="tempi_unpack_rows",
    )(dst2d, packed3d)


def _unpack_dma_kernel(pk_ref, dst_ref, out_ref, scratch, sem, *, window,
                       r, lanes):
    del dst_ref  # aliased with out_ref; present only for donation
    rows = window(out_ref)
    cp = pltpu.make_async_copy(rows, scratch, sem)
    cp.start()
    cp.wait()
    scratch[:, r : r + lanes] = pk_ref[0]
    cp = pltpu.make_async_copy(scratch, rows, sem)
    cp.start()
    cp.wait()


def unpack_dma(
    dst2d: jax.Array,
    packed3d: jax.Array,
    geom: PackGeometry,
    vmem_budget: int,
    interpret: bool = False,
):
    """In-place scatter of packed blocks by read-modify-write DMAs of
    whole pitch rows, one step at a time — so steps whose rows overlap
    (interleaved planes) never lose an update."""
    chunk, window = dma_steps(geom, vmem_budget)
    return pl.pallas_call(
        functools.partial(
            _unpack_dma_kernel, window=window, r=geom.r, lanes=geom.lanes
        ),
        grid=(geom.planes, geom.rows // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, geom.lanes), lambda p, i: (p, i, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        out_shape=jax.ShapeDtypeStruct(dst2d.shape, dst2d.dtype),
        input_output_aliases={1: 0},
        scratch_shapes=[
            pltpu.VMEM((chunk, geom.pitch), dst2d.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        name="tempi_unpack_dma",
    )(packed3d, dst2d)
