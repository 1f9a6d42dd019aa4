"""Closed-loop runner: Graph500-style traversals through ``BFSEngine``.

One compiled engine of ``keys_per_traversal`` sources runs the
configuration's seeded search keys back to back, cycling them in seed
order, each traversal dispatched (``run_async``) and synced
(``block``) before the next.  A mix that gives ``inputs_seed`` draws the
graph and the keys from that seed instead of the run's: where a window
holds only a few keys, which keys they are would otherwise set the
result more than the program does.  The window is timed from the first
dispatch to the last completion; the answers are fetched after it.

The configuration brings three things by name: its graph family
(``generator``, ``generators/<name>.py``), its answer and the check of
it (``answer``, ``answers/<name>.py``, ``depths`` where not given), and
its partition (``partition``: ``1d``, the paper's vertex blocks over a
mesh of ``chips``; or ``2d``, edge blocks over the ``grid`` [r, c]).
"""

from __future__ import annotations

import time

import numpy as np

from harness import graphgen, kernel_bytes, reference
from harness.common import Stopwatch, rng, say


class EngineRunner:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, devices, spec):
        self.config, self.traffic = config, traffic
        self.seed = int(traffic.get("inputs_seed", seed))
        self.devices = list(devices)
        self.s = int(traffic["keys_per_traversal"])
        self.record = {"kind": "engine", "keys_per_traversal": self.s}
        self.engine = None
        self.grid = grid(config)
        self.generator = spec.generator(config["generator"])
        self.answer = spec.answer(config.get("answer", "depths"))

    # -------------------------------------------------------------- set-up
    def setup(self, cache_events) -> None:
        from repro.core import BFSOptions, plan
        from repro.graphs import shard_graph

        sw = Stopwatch("setup")
        with sw.part("generate"):
            self.src, self.dst, self.n = graphgen.generate(
                self.config, self.seed, self.generator)
        degree = np.bincount(self.src, minlength=self.n)
        self.keys = search_keys(degree, int(self.config["search_keys"]),
                                self.seed)
        p = int(self.config["chips"])
        with sw.part("shard_graph"):
            graph = shard_graph(self.src, self.dst, self.n, p)
        before = cache_events.snapshot()
        with sw.part("compile"):
            pl = plan(graph, BFSOptions(**self.config["options"]),
                      num_sources=self.s, **self._placement(p))
            self.engine = pl.compile()
        after = cache_events.snapshot()
        desc = pl.describe()
        self.record["partition"] = desc["partition"]
        say("setup", edges=int(self.src.size), n=self.n, p=p, S=self.s,
            first_keys=",".join(str(k) for k in self.keys[:8]),
            mode=desc["mode"], partition=desc["partition"],
            fused_tail=pl.use_fused_tail,
            cache_requests=after["requests"] - before["requests"],
            cache_hits=after["hits"] - before["hits"])
        part = (pl.graph2d if self.grid else graph).part
        self.record["kernel_bytes"] = {
            "fold_update": kernel_bytes.fold_update(
                part.shard_size, self.s) if pl.use_fused_tail else None}
        # warm-up: one traversal through the compiled programs, from an
        # isolated vertex where the graph has one (a single level)
        isolated = np.flatnonzero(degree == 0)
        warm = [int(isolated[0])] if isolated.size else self.keys[:1]
        with sw.part("warm_up"):
            self.engine.run(warm)
        self.record["setup_parts"] = sw.parts

    def _placement(self, p: int) -> dict:
        """``plan``'s mesh arguments for the configuration's partition."""
        from jax.sharding import Mesh

        devices = np.asarray(self.devices[:p])
        if self.grid is None:
            return {"mesh": Mesh(devices.reshape(p), ("p",)), "axis": "p"}
        return {"mesh": Mesh(devices.reshape(self.grid), ("rows", "cols")),
                "partition": "2d"}

    # -------------------------------------------------------------- window
    def window(self, seconds: float, annotate) -> None:
        runs = []
        k = len(self.keys)
        i = 0
        t_first = time.perf_counter()
        stop = t_first + seconds
        with annotate("bench.window"):
            while True:
                batch = [self.keys[(i + j) % k] for j in range(self.s)]
                i += self.s
                t0 = time.perf_counter()
                with annotate("bench.dispatch"):
                    res = self.engine.run_async(batch)
                with annotate("bench.sync"):
                    res.block()
                t1 = time.perf_counter()
                runs.append((batch, res, t0, t1))
                if t1 >= stop:
                    break
        self.record["window_s"] = runs[-1][3] - t_first
        traversals = []
        with annotate("bench.fetch"):
            for batch, res, t0, t1 in runs:
                stats = res.run_stats.to_host()
                traversals.append({
                    "keys": batch, "wall_s": t1 - t0,
                    "levels": stats["levels"],
                    "mode_counts": stats["mode_counts"],
                    "answer": self.answer.fetch(res)})
        self.record["traversals"] = traversals
        self.record["attempted"] = len(traversals)
        self.record["failed"] = 0
        say("window", traversals=len(traversals),
            window_s=f"{self.record['window_s']:.4f}",
            first_walls_s=",".join(f"{t['wall_s']:.4f}"
                                   for t in traversals[:8]),
            first_levels=",".join(str(t["levels"]) for t in traversals[:8]))

    def release(self) -> None:
        self.engine = None

    # --------------------------------------------------------------- check
    def check(self) -> dict:
        """Every traversal of the window against the reference, by the
        configuration's answer; fills each key's component edge count
        for TEPS from the reference depths, which the answer's check
        gets too (``depths(keys)``: their ``(n, len(keys))`` columns)."""
        ref = reference.Graph(self.src, self.dst, self.n)
        traversals = self.record["traversals"]
        used = sorted({key for t in traversals for key in t["keys"]})
        col = {key: j for j, key in enumerate(used)}
        want = ref.depths(used)
        for t in traversals:
            t["edges"] = [ref.component_edges(want[:, col[key]])
                          for key in t["keys"]]
        checks = self.answer.check(
            ref, traversals, lambda keys: want[:, [col[k] for k in keys]])
        for t in traversals:
            del t["answer"]
        return checks


def grid(config: dict):
    """``None`` for the 1-D partition, ``(r, c)`` for a 2-D grid; any
    other partition, or a grid that does not multiply to ``chips``, is
    an error."""
    partition = config.get("partition", "1d")
    if partition == "1d":
        return None
    if partition != "2d":
        raise ValueError(f"unknown partition {partition!r}; expected "
                         "'1d' or '2d'")
    shape = config.get("grid")
    chips = int(config["chips"])
    if (not isinstance(shape, list) or len(shape) != 2
            or int(shape[0]) * int(shape[1]) != chips):
        raise ValueError(f"partition '2d' needs a grid [r, c] with r * c "
                         f"== chips ({chips}); got {shape!r}")
    return int(shape[0]), int(shape[1])


def search_keys(degree: np.ndarray, k: int, seed: int) -> list:
    """``k`` distinct search keys in seed order, drawn from the vertices
    of degree >= 1, as the Graph500 specification draws them."""
    cand = np.flatnonzero(degree)
    return [int(v) for v in rng(seed, 1).choice(cand, k, replace=False)]
