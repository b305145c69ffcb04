"""Kernel geometry planning: StridedBlock -> TPU grid/BlockSpec parameters.

This is the TPU adaptation of the paper's §3.3 kernel selection.  On CUDA
the paper maps counts[0..2] to thread-block X/Y/Z and specializes a word
size W.  On TPU the equivalents are:

* word width W  -> view the buffer as uint{8,16,32}[.] so the 128-lane
  axis moves W bytes per lane (``repro.kernels.ops``).  The kernels run
  on the narrower of the type's word and the buffer's element width: on
  the TPU a flat narrow buffer keeps each element in its own 32-bit
  container, so viewing it as wider words is a relayout copy of the
  whole buffer, where a same-width view is free;
* thread grid   -> a Pallas grid over (planes, row-groups) with BlockSpec
  index maps that jump by the block stride — possible *because* the
  canonical StridedBlock has regular scalar strides (no per-block
  metadata, the paper's key property);
* block size    -> a row-group G (sublane dimension) chosen so the VMEM
  working set fits and G | rows.

All planning happens on host scalars at commit time; nothing here touches
device memory.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

from repro.core.strided_block import StridedBlock

__all__ = ["PackGeometry", "plan_geometry", "VMEM_BUDGET_BYTES"]

# Per-kernel-step VMEM working-set budget (v5e has 16 MiB less framework
# reserves; stay comfortably below half).
VMEM_BUDGET_BYTES = 4 * 1024 * 1024


@dataclass(frozen=True)
class PackGeometry:
    """Scalar parameters of the strided pack/unpack kernels.

    All units are W-byte words unless suffixed ``_bytes``.  The source is
    reshaped to a (row-pitch) 2D view ``(R, pitch)``; block ``(p, i)``'s
    first word then lives at row ``q + p*plane_rows + i`` column ``r``.

    A TPU block's second-minor extent must be a multiple of the 8-row
    sublane tile or span its whole dimension.  When no 8-aligned row
    group divides the layout (``group == 0``), the kernels instead move
    one whole *view plane* of ``view_rows`` rows per grid step — a plane
    of a 3D object, or the whole 2D view — and cut the object's rows out
    of it in fast memory (``plane_block``).
    """

    word_bytes: int      # W: the kernel word (a buffer's narrower itemsize
                         # where one is narrower, see ``word_bytes=``)
    lanes: int           # counts[0] // W — words per contiguous block
    rows: int            # counts[1]     — blocks per plane
    planes: int          # counts[2]     — plane count (1 for 2D)
    pitch: int           # strides[1] // W
    q: int               # start row of the 2D view
    r: int               # column offset within a row
    plane_rows: int      # strides[2] // strides[1] (0 for 2D)
    group: int           # G: rows handled per grid step (0: plane blocks)
    rows_padded: int     # 2D-view rows after tail padding (multiple of G)

    @property
    def plane_block(self) -> bool:
        return self.group == 0

    @property
    def view_rows(self) -> int:
        """Rows of one view plane (plane-block mode): a 3D object's plane
        stride, or the whole 2D view."""
        return self.plane_rows or self.rows_padded

    @property
    def out_words(self) -> int:
        return self.planes * self.rows * self.lanes

    @property
    def grid(self):
        if self.plane_block:
            return (self.planes,)
        return (self.planes, self.rows // self.group)

    @property
    def overfetch(self) -> float:
        """HBM words fetched per useful word (the kernels read the full
        pitch; plane blocks read whole planes).  Feeds the §5
        performance model."""
        if self.plane_block:
            return self.view_rows * self.pitch / max(self.rows * self.lanes, 1)
        return self.pitch / max(self.lanes, 1)


def _choose_group(rows: int, q: int, plane_rows: int, pitch: int, word: int) -> int:
    """Largest G in {64..8} with G | rows, G | q, G | plane_rows, and a
    G*pitch working set within the VMEM budget; 0 when there is none."""
    for g in (64, 32, 16, 8):
        if rows % g or q % g or (plane_rows % g if plane_rows else 0):
            continue
        if g * pitch * word <= VMEM_BUDGET_BYTES:
            return g
    return 0


def plan_geometry(
    sb: StridedBlock,
    word_bytes: Optional[int] = None,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> Optional[PackGeometry]:
    """Plan the aligned row-kernel geometry for a 2D/3D StridedBlock.

    ``word_bytes`` defaults to the widest word (up to 4 bytes) that the
    block's offsets and sizes allow.  ``repro.kernels.ops`` passes a
    buffer's narrower itemsize instead, so the kernels read the buffer at
    its own width rather than relayouting it into wider words.

    Returns None when the aligned path does not apply; callers fall back
    to the generic gather path.  Conditions (each checked on host
    scalars):

    * 2 <= ndims <= 3
    * W | start, strides, counts[0] (guaranteed by word_bytes selection)
    * the contiguous block does not straddle a pitch boundary
    * 3D: the plane stride is a whole number of pitches
    * one pitch row fits in VMEM
    * an 8-aligned row group exists, or else one view plane holding the
      object's rows of that plane fits in VMEM
    """
    if sb.ndims not in (2, 3):
        return None
    w = sb.word_bytes(max_word=4) if word_bytes is None else word_bytes
    c0, c1 = sb.counts[0], sb.counts[1]
    s1 = sb.strides[1]
    c2 = sb.counts[2] if sb.ndims == 3 else 1
    s2 = sb.strides[2] if sb.ndims == 3 else 0

    if s1 % w or sb.start % w or c0 % w or (s2 % w):
        return None
    lanes, pitch = c0 // w, s1 // w
    q, r = (sb.start // w) // pitch, (sb.start // w) % pitch
    if r + lanes > pitch:
        return None  # block straddles a pitch row
    if sb.ndims == 3:
        if s2 % s1:
            return None  # plane stride not a whole number of rows
        plane_rows = s2 // s1
    else:
        plane_rows = 0
    if pitch * w > vmem_budget:
        return None  # a single pitch row blows the VMEM budget

    g = _choose_group(c1, q, plane_rows, pitch, w)
    rows_needed = q + (c2 - 1) * plane_rows + c1
    if g:
        rows_padded = math.ceil(rows_needed / g) * g
    else:
        view = plane_rows or rows_needed
        if (q % view) + c1 > view or view * pitch * w > vmem_budget:
            return None  # rows leave their plane, or a plane blows VMEM
        rows_padded = math.ceil(rows_needed / view) * view
    return PackGeometry(
        word_bytes=w,
        lanes=lanes,
        rows=c1,
        planes=c2,
        pitch=pitch,
        q=q,
        r=r,
        plane_rows=plane_rows,
        group=g,
        rows_padded=rows_padded,
    )
