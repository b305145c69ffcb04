"""Plain reference of ``MPI_Pack``/``MPI_Unpack`` for a byte ``Vector``.

``Vector(count, blocklen, stride, BYTE)`` covers the bytes
``i*stride + j`` for ``i < count`` and ``j < blocklen``, packed in that
order.  Pack gathers them; unpack scatters them into a copy of the
destination and leaves every other byte as it was.
"""

from __future__ import annotations

import numpy as np

__all__ = ["member_index", "pack", "unpack"]


def member_index(count: int, blocklen: int, stride: int) -> np.ndarray:
    """Byte offsets of the members, in type-map order."""
    return (
        np.arange(count, dtype=np.int64)[:, None] * stride
        + np.arange(blocklen, dtype=np.int64)[None, :]
    ).reshape(-1)


def pack(src: np.ndarray, count: int, blocklen: int, stride: int
         ) -> np.ndarray:
    return np.asarray(src).reshape(-1)[member_index(count, blocklen, stride)]


def unpack(dst: np.ndarray, packed: np.ndarray, count: int, blocklen: int,
           stride: int) -> np.ndarray:
    out = np.array(dst, copy=True).reshape(-1)
    out[member_index(count, blocklen, stride)] = np.asarray(packed).reshape(-1)
    return out
