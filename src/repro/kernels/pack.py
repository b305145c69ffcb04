"""Pallas TPU pack kernels (paper §3.3, TPU-adapted).

Two generic kernels cover every canonical 2D/3D StridedBlock — mirroring
the paper's claim that "each MPI datatype is mapped to one of two kernel
implementations parameterized by W":

* ``pack_rows``  — *pitched row kernel.*  The flat buffer is viewed as a
  ``(rows, pitch)`` 2D array (pitch = strides[1]/W); the BlockSpec index
  map jumps straight to each block's row-group, so Pallas's automatic
  double-buffered pipeline streams HBM->VMEM.  Reads the full pitch
  (over-fetch factor pitch/lanes) — cheap when blocks are a large
  fraction of the pitch.

* ``pack_dma``   — *DMA kernel.*  The source stays in HBM
  (memory_space=ANY) and each grid step issues one DMA of the whole
  pitch rows of a row-chunk of blocks, cutting the blocks out in VMEM.
  TPU HBM is tiled (8, 128), so a DMA cannot fetch a narrower lane
  window: it over-fetches like the row kernel, and differs from it in
  being manually synchronized (single-buffered v1).  Needs an 8-aligned
  row geometry (not ``PackGeometry.plane_block``).

The runtime performance model (``repro.comm.perfmodel``) chooses between
them, as the paper chooses between one-shot/device/staged.

Both kernels are parameterized by host scalars only — **no per-type
metadata is stored in device memory** (the paper's key property of the
canonical representation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.geometry import PackGeometry
from repro.obs.trace import scope

__all__ = [
    "pack_rows",
    "pack_dma",
    "pack_ragged",
    "pack_compress_ragged",
    "choose_chunk",
    "dma_steps",
    "plane_view",
]

# ---------------------------------------------------------------------------
# ragged wire assembly
# ---------------------------------------------------------------------------

@scope("wire")
def pack_ragged(buf: jax.Array, leaves, total: int) -> jax.Array:
    """Scatter packed leaves directly into a flat wire buffer.

    ``leaves`` is a sequence of ``(offset, pack_fn)`` pairs: ``pack_fn``
    produces one leaf's packed ``uint8`` payload from ``buf`` (any of
    the strategy pack kernels above, already specialized), and the
    payload lands at its exact byte ``offset`` in a ``uint8[total]``
    buffer.  Offsets come from a wire plan's
    :class:`~repro.core.commit.WireSegment` descriptors — the buffer is
    exactly ``sum(segment extents)`` bytes, with no per-class padding
    rows and no intermediate per-destination concatenation.
    """
    wire = jnp.zeros((total,), jnp.uint8)
    for offset, pack_fn in leaves:
        wire = jax.lax.dynamic_update_slice(wire, pack_fn(buf), (offset,))
    return wire


@scope("wire")
def pack_compress_ragged(buf: jax.Array, leaves, total: int) -> jax.Array:
    """Fused pack+compress wire assembly.

    Like :func:`pack_ragged`, but each leaf is ``(offset, pack_fn,
    encode_fn)``: the gathered member bytes flow straight through the
    leaf's wire encoder (``encode_fn``, e.g.
    :meth:`repro.comm.compress.RleWire.encode_wire`) inside the same
    traced expression — compression adds no extra materialized pass
    over the buffer.  ``encode_fn=None`` means the wire format *is* the
    packed bytes (the uncompressed strategies), degenerating to
    :func:`pack_ragged` exactly.
    """
    wire = jnp.zeros((total,), jnp.uint8)
    for offset, pack_fn, encode_fn in leaves:
        part = pack_fn(buf)
        if encode_fn is not None:
            with scope("pack"):
                part = encode_fn(part)
        wire = jax.lax.dynamic_update_slice(wire, part, (offset,))
    return wire


# ---------------------------------------------------------------------------
# pitched row kernel
# ---------------------------------------------------------------------------

def _pack_rows_kernel(src_ref, out_ref, *, r: int, lanes: int):
    # src_ref: (G, pitch) VMEM tile of full-pitch rows
    # out_ref: (1, G, lanes) packed tile
    out_ref[0] = src_ref[:, r : r + lanes]


def _pack_plane_kernel(src_ref, out_ref, *, y0: int, rows: int, r: int,
                       lanes: int):
    # src_ref: (1, view_rows, pitch) one whole view plane
    # out_ref: (1, rows, lanes) that plane's packed blocks
    out_ref[0] = src_ref[0, y0 : y0 + rows, r : r + lanes]


def plane_view(src2d: jax.Array, geom: PackGeometry) -> jax.Array:
    """The (rows_padded, pitch) word view as (planes, view_rows, pitch)."""
    return src2d.reshape(-1, geom.view_rows, geom.pitch)


def pack_rows(src2d: jax.Array, geom: PackGeometry, interpret: bool = False):
    """Pack via pitched BlockSpec row-groups (or whole view planes, see
    :class:`PackGeometry`).

    ``src2d`` is the W-word view reshaped to (rows_padded, pitch).
    Returns the packed array of shape (planes, rows, lanes).
    """
    out_shape = jax.ShapeDtypeStruct(
        (geom.planes, geom.rows, geom.lanes), src2d.dtype
    )
    if geom.plane_block:
        z0, y0 = divmod(geom.q, geom.view_rows)
        return pl.pallas_call(
            functools.partial(
                _pack_plane_kernel, y0=y0, rows=geom.rows, r=geom.r,
                lanes=geom.lanes,
            ),
            grid=geom.grid,
            in_specs=[
                pl.BlockSpec((1, geom.view_rows, geom.pitch),
                             lambda p: (z0 + p, 0, 0))
            ],
            out_specs=pl.BlockSpec((1, geom.rows, geom.lanes),
                                   lambda p: (p, 0, 0)),
            out_shape=out_shape,
            interpret=interpret,
            name="tempi_pack_planes",
        )(plane_view(src2d, geom))

    g = geom.group
    qb = geom.q // g
    prb = geom.plane_rows // g if geom.plane_rows else 0
    return pl.pallas_call(
        functools.partial(_pack_rows_kernel, r=geom.r, lanes=geom.lanes),
        grid=geom.grid,
        in_specs=[
            pl.BlockSpec((g, geom.pitch), lambda p, i: (qb + p * prb + i, 0))
        ],
        out_specs=pl.BlockSpec((1, g, geom.lanes), lambda p, i: (p, i, 0)),
        out_shape=out_shape,
        interpret=interpret,
        name="tempi_pack_rows",
    )(src2d)


# ---------------------------------------------------------------------------
# DMA kernel
# ---------------------------------------------------------------------------
#
# TPU HBM is laid out in (8, 128) tiles and a DMA moves whole tiles, so a
# copy can address neither a narrower lane window of a row nor a row
# window that does not start on an 8-row boundary.  Each step DMAs whole
# pitch rows of an 8-aligned row-chunk into fast memory and cuts the
# blocks out there; plane-block geometries have no such chunks.

def choose_chunk(rows: int, width: int, word: int, budget: int) -> int:
    """Rows per DMA step: largest divisor of ``rows`` from an 8-aligned
    pow2 ladder whose (chunk, width) scratch fits the VMEM budget
    (0 when none does)."""
    for c in (512, 256, 128, 64, 32, 16, 8):
        if rows % c == 0 and c * width * word <= budget:
            return c
    return 0


def dma_steps(geom: PackGeometry, vmem_budget: int):
    """``(chunk, window)`` shared by both DMA kernels: each grid step
    ``(p, i)`` moves the ``chunk`` full-pitch rows ``window(ref)`` of
    plane ``p``.  Needs a row-group geometry (not ``plane_block``)."""
    if geom.plane_block:
        raise ValueError("the DMA kernels need an 8-aligned row geometry")
    chunk = choose_chunk(geom.rows, geom.pitch, geom.word_bytes, vmem_budget)

    def window(ref):
        row0 = (
            geom.q + pl.program_id(0) * geom.plane_rows
            + pl.program_id(1) * chunk
        )
        return ref.at[pl.ds(pl.multiple_of(row0, 8), chunk)]

    return chunk, window


def _pack_dma_kernel(src_ref, out_ref, scratch, sem, *, window, r, lanes):
    cp = pltpu.make_async_copy(window(src_ref), scratch, sem)
    cp.start()
    cp.wait()
    out_ref[0] = scratch[:, r : r + lanes]


def pack_dma(
    src2d: jax.Array,
    geom: PackGeometry,
    vmem_budget: int,
    interpret: bool = False,
):
    """Pack via one DMA of whole pitch rows per row-chunk (manually
    synchronized, single-buffered).  ``src2d`` as in :func:`pack_rows`."""
    chunk, window = dma_steps(geom, vmem_budget)
    return pl.pallas_call(
        functools.partial(
            _pack_dma_kernel, window=window, r=geom.r, lanes=geom.lanes
        ),
        grid=(geom.planes, geom.rows // chunk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec((1, chunk, geom.lanes), lambda p, i: (p, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (geom.planes, geom.rows, geom.lanes), src2d.dtype
        ),
        scratch_shapes=[
            pltpu.VMEM((chunk, geom.pitch), src2d.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        name="tempi_pack_dma",
    )(src2d)
