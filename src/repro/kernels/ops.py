"""Public pack/unpack operations: jit'd wrappers + plan caching.

This is TEMPI's ``MPI_Pack``/``MPI_Unpack`` (paper §6.2) for JAX arrays.
The committed type's canonical StridedBlock drives everything:

    kind CONTIG     -> one contiguous copy (cudaMemcpyAsync analogue)
    kind KERNEL_2D/3D -> Pallas kernel, chosen by the strategy plugin
    kind KERNEL_ND  -> python loop of 3D kernels over the outer dims
    kind GENERIC or unplannable geometry -> gather fallback (ref path)

``incount`` repeats the datatype at ``extent`` strides, handled as an
extra outer dimension exactly as the paper describes (§3.3 last ¶).

Strategy *dispatch* lives in ``repro.comm.api`` (the strategy registry);
this module owns the strategy-independent machinery: plan caching, the
1D fast paths, repetition loops, and the word-view plumbing the strategy
kernels share.  ``strategy`` arguments accept a Strategy object, a
registered name, or None (the static-auto heuristic).

Buffers can be any dtype/shape.  The strategy kernels run on a K-byte
kernel word, the narrower of the geometry's word W (the paper's
word-size specialization) and the buffer's own itemsize: a buffer of
elements narrower than W is handed over flat at its own width (a
same-width bitcast, free), never re-viewed as wider words.  On the TPU a
flat narrow array keeps each element in its own 32-bit container
(``uint8[n]`` tiles as ``T(1024)(128)(4,1)``), so any wider or narrower
view of it, bytes to words or words to bytes, is a relayout copy through
a lane-padded ``[n, 4]`` shape that moves ~17 GB for a 64 MiB buffer.
Buffers whose itemsize is not narrower than W are re-viewed as bytes and
then as W-byte words, a relayout where the tiled layouts differ.  Each
phase traces under its
``repro.obs.scope``: ``tempi.pack``, ``tempi.unpack``, ``tempi.stencil``,
and ``tempi.bytes`` for every byte/word re-view, so a profiler trace
splits their device time.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.commit import CommittedType, KernelKind
from repro.core.strided_block import StridedBlock
from repro.kernels import ref as refk
from repro.kernels.geometry import PackGeometry, plan_geometry
from repro.obs.trace import scope

__all__ = [
    "byte_view",
    "unbyte_view",
    "as_words",
    "words_to_bytes",
    "pack",
    "unpack",
    "pack_block",
    "run_pack_kernel",
    "run_unpack_kernel",
    "default_strategy",
    "shifted_window_sum",
    "stencil_window_update",
    "stencil_window_chain",
    "STRATEGIES",
]

_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

#: geometry plan cache — the paper's §4 "caching layer": keyed by the
#: committed type's content fingerprint + incount, so repeated
#: Pack/Unpack of the same structure re-dispatch in a dict lookup.
_PLAN_CACHE: Dict[Tuple[str, int], Optional["_Plan"]] = {}


def _resolve(strategy):
    from repro.comm.api import resolve_strategy

    return resolve_strategy(strategy)


def __getattr__(name):
    if name == "STRATEGIES":
        # legacy constant: the registered strategy names (now sourced
        # from the registry so plugins appear automatically)
        from repro.comm.api import default_registry

        return default_registry().names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def default_strategy(geom: Optional[PackGeometry]) -> str:
    """Name of the kernel the static geometry heuristic picks (the
    calibrated model refines this crossover)."""
    from repro.comm.api import static_choice

    return static_choice(geom).name


def _interpret_default() -> bool:
    # Pallas TPU kernels run in interpret mode anywhere but real TPUs.
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# shifted-window stencil primitives (per-dimension radii)
# ---------------------------------------------------------------------------
#
# The halo layer's stencil kernels are all instances of one operation:
# accumulate dynamic slices of an N-D array shifted by a set of offsets,
# over a window whose origin/shape the caller picks.  Keeping the
# primitive here (rather than inside repro.halo) lets every consumer —
# full-allocation applications, shrinking-region deep-halo steps, and
# the dense interior chain of the overlap pipeline — share one
# accumulation order, which is what makes their results bit-identical
# on the overlapping regions.

def shifted_window_sum(arr, offsets, origin, shape):
    """Sum of ``arr`` windows at ``origin + d`` for each offset ``d``.

    Offsets may be negative; the caller guarantees every shifted window
    stays in bounds.  Accumulation is in ``offsets`` order, so two calls
    with the same offsets and values produce bit-identical results.
    """
    acc = jnp.zeros(shape, arr.dtype)
    for d in offsets:
        acc = acc + jax.lax.dynamic_slice(
            arr, tuple(o + di for o, di in zip(origin, d)), shape
        )
    return acc


@scope("stencil")
def stencil_window_update(arr, offsets, weight, origin, shape):
    """One weighted-neighborhood stencil update of the window
    ``arr[origin : origin + shape]``:

        new = (1 - w) * center + (w / len(offsets)) * sum(shifted views)

    Returns the updated window only (the caller splices it back, or uses
    it directly as a deep-interior block).  ``offsets`` carries the
    per-dimension stencil radii implicitly — any box neighborhood,
    symmetric or not, is just a different offset list.
    """
    w = jnp.asarray(weight, arr.dtype)
    acc = shifted_window_sum(arr, offsets, origin, shape)
    center = jax.lax.dynamic_slice(arr, tuple(origin), shape)
    return (1 - w) * center + (w / len(offsets)) * acc


@scope("stencil")
def stencil_window_chain(arr, stages):
    """Apply a *sequence* of stencil window updates, each stage consuming
    the previous stage's window: stage ``(offsets, weight, radii)``
    shrinks the current window by ``radii`` per side and applies
    :func:`stencil_window_update` to it.  Returns every intermediate
    block, so the caller can splice each one over its region of a wider
    computation (the deep-interior overlap chain does exactly that).

    The stages need not share radii — a heterogeneous op cycle (e.g. a
    predictor/corrector pair) is just a different stage list.  Because
    every stage goes through the same primitive, the chain's blocks are
    bit-identical to the matching regions of the full-allocation path.
    """
    blocks = []
    x = arr
    for k, (offsets, weight, radii) in enumerate(stages):
        shape = tuple(s - 2 * r for s, r in zip(x.shape, radii))
        if any(s < 1 for s in shape):
            raise ValueError(
                f"window {arr.shape} too small for stage {k + 1} of the "
                f"chain (radii {tuple(radii)})"
            )
        x = stencil_window_update(x, offsets, weight, tuple(radii), shape)
        blocks.append(x)
    return blocks


# ---------------------------------------------------------------------------
# byte / word re-viewing (a relayout copy on the TPU where the tiled
# layouts differ)
# ---------------------------------------------------------------------------

@scope("bytes")
def byte_view(arr: jax.Array) -> jax.Array:
    """Flat uint8 view of any (non-bool) array's underlying bytes."""
    if arr.dtype == jnp.bool_:
        raise TypeError("bool buffers are not byte-addressable; cast first")
    flat = arr.reshape(-1)
    if arr.dtype == jnp.uint8:
        return flat
    return jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1)


@scope("bytes")
def unbyte_view(b: jax.Array, dtype, shape) -> jax.Array:
    """Inverse of :func:`byte_view`, and of :func:`_element_view`."""
    if dtype == b.dtype:
        return b.reshape(shape)
    w = jnp.dtype(dtype).itemsize // b.dtype.itemsize
    if w > 1:
        b = b.reshape(-1, w)
    return jax.lax.bitcast_convert_type(b, dtype).reshape(shape)


@scope("bytes")
def _element_view(arr: jax.Array) -> jax.Array:
    """Flat ``uint{8k}`` view of an array of ``k``-byte elements: a
    same-width bitcast, which keeps the TPU layout (no copy)."""
    if arr.dtype == jnp.bool_:
        raise TypeError("bool buffers are not byte-addressable; cast first")
    flat = arr.reshape(-1)
    u = _UINT[arr.dtype.itemsize]
    return flat if flat.dtype == u else jax.lax.bitcast_convert_type(flat, u)


@scope("bytes")
def as_words(b: jax.Array, w: int) -> jax.Array:
    """uint8[n] -> uintW[n/w] (n already padded to a multiple of w)."""
    if w == 1:
        return b
    return jax.lax.bitcast_convert_type(b.reshape(-1, w), _UINT[w])


@scope("bytes")
def words_to_bytes(x: jax.Array) -> jax.Array:
    w = x.dtype.itemsize
    if w == 1:
        return x.reshape(-1)
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

class _Plan:
    """Host-side execution plan for one (committed type, incount)."""

    __slots__ = ("sb", "reps", "rep_extent", "geom", "kind", "narrow")

    def __init__(self, ct: CommittedType, incount: int):
        sb = ct.block
        self.kind = ct.kernel
        self.reps = 1
        self.rep_extent = ct.extent
        if sb is not None and incount > 1:
            if sb.ndims == 1:
                if ct.extent == sb.counts[0] and sb.start == 0:
                    # contiguous repetitions stay contiguous
                    sb = StridedBlock(0, (sb.counts[0] * incount,), (1,))
                else:
                    sb = StridedBlock(
                        sb.start,
                        (sb.counts[0], incount),
                        (1, ct.extent),
                    )
            elif sb.ndims == 2:
                sb = StridedBlock(
                    sb.start,
                    sb.counts + (incount,),
                    sb.strides + (ct.extent,),
                )
            else:
                # 3D+ repeated: loop reps on host (paper: "handled
                # dynamically" — known only at the call site)
                self.reps = incount
        self.sb = sb
        self.geom = (
            plan_geometry(sb) if sb is not None and sb.ndims in (2, 3) else None
        )
        self.narrow: Dict[int, Optional[PackGeometry]] = {}

    def narrowed(self, k: int) -> Optional[PackGeometry]:
        """The leaf's geometry at a ``k``-byte kernel word, cached per
        ``k`` (see :func:`_narrow_geometry`); None for the word path,
        and for 3D+ types repeated on the host."""
        if k not in self.narrow:
            self.narrow[k] = (
                _narrow_geometry(self.sb, self.geom, k) if self.reps == 1
                else None
            )
        return self.narrow[k]


def _narrow_geometry(
    sb: StridedBlock, geom: Optional[PackGeometry], k: int
) -> Optional[PackGeometry]:
    """``geom`` re-planned at a ``k``-byte kernel word, for a buffer of
    ``k``-byte elements narrower than its word W.  None where the W-word
    path stays: ``k`` is not narrower, or the narrowed row group does
    not sit on the sublane tile of ``k``-byte elements (Mosaic tiles
    8-bit blocks as (32, 128) and 16-bit ones as (16, 128)), or there is
    none (whole view planes)."""
    if geom is None or k >= geom.word_bytes:
        return None
    g = plan_geometry(sb, word_bytes=k)
    if g is None or g.plane_block or g.group % (32 // k):
        return None
    return g


def _plan(ct: CommittedType, incount: int) -> _Plan:
    # content-fingerprint key: id(ct) can be recycled after a committed
    # type is garbage-collected, silently serving a stale plan for a
    # structurally different type; equal structures share a plan instead
    key = (ct.fingerprint, incount)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        plan = _Plan(ct, incount)
        _PLAN_CACHE[key] = plan
    return plan


# ---------------------------------------------------------------------------
# shared word-view plumbing for the Pallas strategy kernels
# ---------------------------------------------------------------------------

@scope("bytes")
def _prep_words(b: jax.Array, geom: PackGeometry) -> jax.Array:
    """Flat ``b`` -> padded (rows_padded, pitch) kernel-word view.

    ``b`` holds either bytes, re-viewed here as the geometry's W-byte
    words, or elements already as wide as the kernel word (a buffer
    narrower than W, see :func:`_narrow_geometry`), which then only
    reshapes: one ordinary relayout copy on the TPU, where a wider view
    of a narrow buffer would expand it through a lane-padded ``[n, W]``
    shape."""
    per = geom.word_bytes // b.dtype.itemsize  # elements per kernel word
    n = b.shape[0]
    need = geom.rows_padded * geom.pitch * per
    if n % per or n < need:
        pad = max(need, ((n + per - 1) // per) * per) - n
        b = jnp.pad(b, (0, pad))
    words = as_words(b, per)
    words = words[: geom.rows_padded * geom.pitch]
    return words.reshape(geom.rows_padded, geom.pitch)


#: bytes per step of :func:`packed_bytes` / :func:`packed_words`, in
#: 4-byte words
_BYTES_CHUNK_WORDS = 4096


def _chunk_rows(lanes: int, itemsize: int) -> int:
    """Rows of ``lanes`` elements per loop step: the same bytes a step
    whatever the kernel word."""
    return max(1, _BYTES_CHUNK_WORDS * 4 // (lanes * itemsize))


@scope("bytes")
def packed_bytes(packed: jax.Array) -> jax.Array:
    """Flat bytes of a (planes, rows, lanes) packed kernel-word array,
    converted ``4 * _BYTES_CHUNK_WORDS`` bytes per loop step (word-1
    kernels too: a step of a narrow word would carry fewer bytes).
    Turning narrow word rows into flat bytes is a relayout on the TPU,
    and the TPU compiler's time for it grows with the array: about 95 s
    for one 1 MiB object in one piece (v5e, installed libtpu), about a
    second in chunks."""
    rows = packed.reshape(-1, packed.shape[-1])
    m, k = rows.shape
    per = _chunk_rows(k, rows.dtype.itemsize)
    if m <= per:
        return words_to_bytes(rows.reshape(-1))
    nfull, tail = divmod(m, per)
    step = per * k * rows.dtype.itemsize

    def body(i, acc):
        chunk = jax.lax.dynamic_slice_in_dim(rows, i * per, per)
        return jax.lax.dynamic_update_slice(
            acc, words_to_bytes(chunk.reshape(-1)), (i * step,)
        )

    out = jax.lax.fori_loop(
        0, nfull, body, jnp.zeros((m * k * rows.dtype.itemsize,), jnp.uint8)
    )
    if tail:
        out = jax.lax.dynamic_update_slice(
            out, words_to_bytes(rows[nfull * per:].reshape(-1)),
            (nfull * step,),
        )
    return out


@scope("bytes")
def packed_words(packed: jax.Array, w: int, shape) -> jax.Array:
    """Inverse of :func:`packed_bytes`: flat packed bytes as a word array
    of ``shape`` (..., lanes), in the same bounded loop steps.  The
    barrier keeps the compiler from hoisting the byte-to-word relayout
    above the slice that cut ``packed`` out of a wire buffer, which would
    relayout the whole wire once per leaf."""
    packed = jax.lax.optimization_barrier(packed)
    k = shape[-1]
    m = packed.shape[0] // (w * k)
    per = _chunk_rows(k, w)
    if m <= per:
        return as_words(packed, w).reshape(shape)
    nfull, tail = divmod(m, per)
    step = per * k * w

    def body(i, acc):
        chunk = jax.lax.dynamic_slice(packed, (i * step,), (step,))
        return jax.lax.dynamic_update_slice_in_dim(
            acc, as_words(chunk, w).reshape(per, k), i * per, axis=0
        )

    out = jax.lax.fori_loop(0, nfull, body, jnp.zeros((m, k), _UINT[w]))
    if tail:
        out = jax.lax.dynamic_update_slice_in_dim(
            out, as_words(packed[nfull * step:], w).reshape(tail, k),
            nfull * per, axis=0,
        )
    return out.reshape(shape)


def run_pack_kernel(b: jax.Array, geom: PackGeometry, kernel, interpret: bool):
    """Drive a (src2d, geom, interpret) -> (planes, rows, lanes) pack
    kernel through the shared word-view prep, returning packed bytes."""
    src2d = _prep_words(b, geom)
    return packed_bytes(kernel(src2d, geom, interpret=interpret))


def run_unpack_kernel(
    b: jax.Array, packed: jax.Array, geom: PackGeometry, kernel, interpret: bool
):
    """Drive a (dst2d, pk3, geom, interpret) -> dst2d unpack kernel:
    word-view prep, kernel, and tail reassembly for bytes the 2D view
    does not cover.  Returns ``b`` updated, flat and of ``b``'s dtype:
    elements at the kernel word's width come back by the inverse
    reshape alone, never as a whole-buffer byte view."""
    n = b.shape[0]
    per = geom.word_bytes // b.dtype.itemsize  # elements per kernel word
    covered = geom.rows_padded * geom.pitch * per
    dst2d = _prep_words(b, geom)
    pk3 = packed_words(
        packed, geom.word_bytes, (geom.planes, geom.rows, geom.lanes)
    )
    out = _unprep_words(kernel(dst2d, pk3, geom, interpret=interpret), b.dtype)
    if covered >= n:
        return out[:n]
    # the 2D word view only covers the strided region; keep the tail
    return jnp.concatenate([out, b[covered:]])


@scope("bytes")
def _unprep_words(out2d: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`_prep_words`: the 2D view flat, as ``dtype``
    (bytes, or elements of the kernel word's width)."""
    out = out2d.reshape(-1)
    return out if out.dtype == dtype else words_to_bytes(out)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def _pack_one(
    b: jax.Array, plan: _Plan, strat, interpret: bool, base: int
) -> jax.Array:
    """Pack one repetition (byte offsets shifted by ``base``)."""
    sb = plan.sb
    if base:
        sb = StridedBlock(sb.start + base, sb.counts, sb.strides)
    if sb.ndims == 1:
        return jax.lax.dynamic_slice(b, (sb.start,), (sb.counts[0],))
    geom = plan_geometry(sb) if base else plan.geom
    return strat.pack_leaf(b, sb, geom, interpret)


@scope("bytes")
def _plane_words(buf: jax.Array, plan: _Plan, incount: int):
    """``buf`` itself as the (planes, view_rows, pitch) word view of a 3D
    plane-block geometry, when its own shape already is that view.  The
    TPU stores a 3D array tiled over its last two dims, so flattening it
    to bytes and back costs a relayout copy of the whole buffer."""
    g = plan.geom
    if (
        incount != 1 or g is None or not g.plane_block or not g.plane_rows
        or buf.ndim != 3 or buf.dtype == jnp.bool_
        or buf.dtype.itemsize != g.word_bytes
        or buf.shape[1:] != (g.view_rows, g.pitch)
    ):
        return None
    return jax.lax.bitcast_convert_type(buf, _UINT[g.word_bytes])


@scope("pack")
def pack(
    buf: jax.Array,
    ct: CommittedType,
    incount: int = 1,
    strategy=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """MPI_Pack: gather the non-contiguous bytes ``ct`` describes from
    ``buf`` into a contiguous uint8 buffer of ``ct.size * incount``."""
    strat = _resolve(strategy)
    if interpret is None:
        interpret = _interpret_default()
    plan = _plan(ct, incount)
    view = _plane_words(buf, plan, incount)
    if view is not None:
        out = strat.pack_planes(view, plan.geom, interpret)
        if out is not None:
            return out
    geom = plan.narrowed(buf.dtype.itemsize)
    if strat.leaf_kernels and geom is not None:
        return strat.pack_leaf(_element_view(buf), plan.sb, geom, interpret)
    b = byte_view(buf)
    if plan.kind is KernelKind.GENERIC or plan.sb is None:
        return refk.pack_ref(b, ct.block, incount, ct.extent)  # pragma: no cover
    if plan.reps == 1:
        return _pack_one(b, plan, strat, interpret, 0)
    parts = [
        _pack_one(b, plan, strat, interpret, r * plan.rep_extent)
        for r in range(plan.reps)
    ]
    return jnp.concatenate(parts)


@scope("pack")
def pack_block(
    buf: jax.Array,
    sb: StridedBlock,
    strategy=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Low-level pack straight from a StridedBlock (no committed type).

    Used by the comm layer for shifted/derived blocks (e.g. extracting
    member bytes out of a received bounding window)."""
    strat = _resolve(strategy)
    if interpret is None:
        interpret = _interpret_default()
    if sb.ndims == 1:
        b = byte_view(buf)
        return jax.lax.dynamic_slice(b, (sb.start,), (sb.counts[0],))
    geom = plan_geometry(sb)
    narrow = _narrow_geometry(sb, geom, buf.dtype.itemsize)
    if strat.leaf_kernels and narrow is not None:
        return strat.pack_leaf(_element_view(buf), sb, narrow, interpret)
    return strat.pack_leaf(byte_view(buf), sb, geom, interpret)


def _unpack_one(
    b: jax.Array,
    packed: jax.Array,
    plan: _Plan,
    strat,
    interpret: bool,
    base: int,
) -> jax.Array:
    sb = plan.sb
    if base:
        sb = StridedBlock(sb.start + base, sb.counts, sb.strides)
    if sb.ndims == 1:
        return jax.lax.dynamic_update_slice(b, packed, (sb.start,))
    geom = plan_geometry(sb) if base else plan.geom
    return strat.unpack_leaf(b, packed, sb, geom, interpret)


@scope("unpack")
def unpack(
    buf: jax.Array,
    packed: jax.Array,
    ct: CommittedType,
    incount: int = 1,
    strategy=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """MPI_Unpack: scatter ``packed`` (uint8[size*incount]) into ``buf``
    per the committed datatype; returns the updated buffer (same
    shape/dtype as ``buf``)."""
    strat = _resolve(strategy)
    if interpret is None:
        interpret = _interpret_default()
    plan = _plan(ct, incount)
    packed = byte_view(packed)
    view = _plane_words(buf, plan, incount)
    if view is not None:
        g = plan.geom
        out = strat.unpack_planes(
            view,
            packed_words(packed, g.word_bytes, (g.planes, g.rows, g.lanes)),
            g, interpret,
        )
        if out is not None:
            return jax.lax.bitcast_convert_type(out, buf.dtype)
    geom = plan.narrowed(buf.dtype.itemsize)
    if strat.leaf_kernels and geom is not None:
        out = strat.unpack_leaf(
            _element_view(buf), packed, plan.sb, geom, interpret
        )
        return unbyte_view(out, buf.dtype, buf.shape)
    b = byte_view(buf)
    if plan.kind is KernelKind.GENERIC or plan.sb is None:  # pragma: no cover
        out = refk.unpack_ref(b, packed, ct.block, incount, ct.extent)
        return unbyte_view(out, buf.dtype, buf.shape)
    if plan.reps == 1:
        out = _unpack_one(b, packed, plan, strat, interpret, 0)
    else:
        out = b
        step = plan.sb.size
        for rep in range(plan.reps):
            out = _unpack_one(
                out,
                jax.lax.dynamic_slice(packed, (rep * step,), (step,)),
                plan,
                strat,
                interpret,
                rep * plan.rep_extent,
            )
    return unbyte_view(out, buf.dtype, buf.shape)
