"""The plain reference: level-synchronous BFS depths in numpy.

Independent of the program under test (it imports nothing from
``src/``).  Up to 64 search keys run at once, one bit of a ``uint64``
word per key: each level gathers the frontier words of every vertex's
neighbours and OR-reduces them per vertex, so one pass over the edges
serves all 64 keys.
"""

from __future__ import annotations

import numpy as np

UNREACHED = 2 ** 30          # the depth of a vertex no key reaches
_LANES = 64


class Graph:
    """A symmetric edge list in CSR form, grouped by edge target."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        order = np.argsort(dst, kind="stable")
        self.n = int(n)
        self.nbr = np.asarray(src, dtype=np.int64)[order]
        self.degree = np.bincount(np.asarray(dst), minlength=n)
        self.indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(self.degree, out=self.indptr[1:])
        self._nz = np.flatnonzero(self.degree)

    def _pull(self, words: np.ndarray) -> np.ndarray:
        """OR of the neighbours' words, per vertex."""
        out = np.zeros(self.n, dtype=np.uint64)
        if self._nz.size:
            out[self._nz] = np.bitwise_or.reduceat(
                words[self.nbr], self.indptr[self._nz])
        return out

    def depths(self, keys, max_levels: int | None = None) -> np.ndarray:
        """``(n, len(keys))`` int32 depths, ``UNREACHED`` where a key does
        not reach.  ``max_levels`` stops the search after that many
        levels (used only to build the control, never for an answer)."""
        keys = np.asarray(keys, dtype=np.int64).reshape(-1)
        out = np.full((self.n, keys.size), UNREACHED, dtype=np.int32)
        for lo in range(0, keys.size, _LANES):
            self._depths_block(keys[lo:lo + _LANES], out[:, lo:lo + _LANES],
                               max_levels)
        return out

    def _depths_block(self, keys, out, max_levels):
        frontier = np.zeros(self.n, dtype=np.uint64)
        for j, k in enumerate(keys):
            frontier[k] |= np.uint64(1) << np.uint64(j)
            out[k, j] = 0
        visited = frontier.copy()
        level = 1
        while frontier.any() and (max_levels is None or level <= max_levels):
            new = self._pull(frontier) & ~visited
            visited |= new
            rows = np.flatnonzero(new)
            bits = new[rows]
            for j in range(keys.size):
                hit = ((bits >> np.uint64(j)) & np.uint64(1)).astype(bool)
                out[rows[hit], j] = level
            frontier = new
            level += 1

    def component_edges(self, depth_column: np.ndarray) -> int:
        """Undirected edges within the component a key reached: half the
        degree sum of its reached vertices (the graph is symmetric)."""
        return int(self.degree[depth_column < UNREACHED].sum()) // 2
