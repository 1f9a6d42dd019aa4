"""Bytes a kernel must move per call, from its shapes.

These follow each kernel's documented contract, not its source, so a
change to the kernel cannot change what its roofline share is measured
against.
"""

from __future__ import annotations

_ROWS = 32                 # vertices per uint32 frontier word


def fold_update(m: int, s: int) -> int:
    """``kernels/fold_update.fold_update`` on one shard of ``m`` owned
    vertices and ``s`` sources: it reads the merged candidate words
    ``(W, s)`` uint32 and the depths ``(m, s)`` int32, and writes the
    depths, the new-vertex byte mask ``(m, s)`` uint8 and the next
    frontier's words ``(W, s)`` uint32, with ``W = ceil(m / 32)``."""
    w = -(-m // _ROWS)
    return s * (4 * w + 4 * m + 4 * m + m + 4 * w)
