"""The bytes an operation needs to move, from its description alone.

These counts do not depend on which strategy or kernel the program
runs, so a share of a peak built on them cannot pass 100% when a later
change replaces a kernel.
"""

from __future__ import annotations

__all__ = ["pack_unpack_bytes"]


def pack_unpack_bytes(count: int, blocklen: int, element_size: int) -> int:
    """HBM bytes one ``MPI_Pack`` plus one ``MPI_Unpack`` of
    ``Vector(count, blocklen, stride, element)`` need: pack reads the
    members and writes the packed buffer, unpack reads it back and
    writes the members, so four times the packed size (the stride does
    not enter)."""
    return 4 * count * blocklen * element_size
