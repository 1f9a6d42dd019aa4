"""Exact BFS depths: every depth of every key, int32, bitwise equal to
the reference's (unreached vertices hold ``2**30``)."""

from __future__ import annotations

import numpy as np


def fetch(result):
    """The ``(n, keys)`` depths of one traversal, on the host."""
    return np.asarray(result.dist_host)


def check(graph, traversals, depths) -> dict:
    """``mismatched_depths``: depths that differ from the reference's,
    every column of every traversal (a column of the wrong shape counts
    whole); limit 0."""
    mismatched = 0
    for t in traversals:
        cols = depths(t["keys"])
        got = t["answer"]
        if got.shape != cols.shape:
            mismatched += cols.size
        else:
            mismatched += int((got != cols).sum())
    return {"mismatched_depths": (mismatched, 0)}
