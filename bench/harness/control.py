"""The control: the reference put in the program's place, with one
guarantee of the configuration broken.

Depths are exact by the configuration.  The control stops each key's
search one level short of its last level (the early exit a faster loop
might be tempted by), so the deepest vertices of every key read
unreached.  Its runs must come out not correct.
"""

from __future__ import annotations

import numpy as np

from harness import reference


def truncated_depths(graph: reference.Graph, keys) -> np.ndarray:
    d = graph.depths(keys)
    for j in range(d.shape[1]):
        col = d[:, j]
        reached = col[col < reference.UNREACHED]
        if reached.size and reached.max() > 0:
            col[col == reached.max()] = reference.UNREACHED
    return d


class _Stats:
    def __init__(self, levels: int):
        self.levels = levels

    def to_host(self) -> dict:
        return {"levels": self.levels, "comm_bytes": 0.0,
                "overflowed": False, "sieve_hits": 0,
                "mode_counts": {"dense": 0, "queue": 0, "bottom_up": 0}}


class Result:
    """What ``BFSEngine.run_async`` returns, for host depths."""

    def __init__(self, depths: np.ndarray):
        self.dist_host = depths
        reached = depths[depths < reference.UNREACHED]
        self.run_stats = _Stats(int(reached.max()) + 1 if reached.size
                                else 0)

    def block(self):
        return self


class ControlEngine:
    """Stands in for a compiled engine: ``run_async(keys)``."""

    def __init__(self, src, dst, n):
        self.graph = reference.Graph(src, dst, n)

    def run_async(self, keys) -> Result:
        return Result(truncated_depths(self.graph, keys))

    def run(self, keys) -> Result:
        return self.run_async(keys)


def install(runner) -> None:
    """Put the control in place of the program of a set-up runner."""
    engine = ControlEngine(runner.src, runner.dst, runner.n)
    if runner.record["kind"] == "engine":
        runner.engine = engine
        return
    service = runner.service

    def traverse_async(name, sources):
        plan_ = service.lane(name).plan_for(len(sources))
        return engine.run_async(list(sources)), plan_.num_sources

    service.traverse_async = traverse_async
