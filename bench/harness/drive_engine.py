"""Closed-loop runner: Graph500-style traversals through ``BFSEngine``.

One compiled engine of ``keys_per_traversal`` sources runs the
configuration's seeded search keys back to back, cycling them in seed
order, each traversal dispatched (``run_async``) and synced
(``block``) before the next.  A mix that gives ``inputs_seed`` draws the
graph and the keys from that seed instead of the run's: where a window
holds only a few keys, which keys they are would otherwise set the
result more than the program does.  The window is timed from the first
dispatch to the last completion; the depths are fetched after it.
"""

from __future__ import annotations

import time

import numpy as np

from harness import graphgen, kernel_bytes, reference
from harness.common import Stopwatch, rng, say


class EngineRunner:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, devices):
        self.config, self.traffic = config, traffic
        self.seed = int(traffic.get("inputs_seed", seed))
        self.devices = list(devices)
        self.s = int(traffic["keys_per_traversal"])
        self.record = {"kind": "engine", "keys_per_traversal": self.s}
        self.engine = None

    # -------------------------------------------------------------- set-up
    def setup(self, cache_events) -> None:
        from jax.sharding import Mesh

        from repro.core import BFSOptions, plan
        from repro.graphs import shard_graph

        sw = Stopwatch("setup")
        with sw.part("generate"):
            self.src, self.dst, self.n = graphgen.generate(
                self.config, self.seed)
        degree = np.bincount(self.src, minlength=self.n)
        self.keys = search_keys(degree, int(self.config["search_keys"]),
                                self.seed)
        p = int(self.config["chips"])
        with sw.part("shard_graph"):
            graph = shard_graph(self.src, self.dst, self.n, p)
        mesh = Mesh(np.asarray(self.devices[:p]).reshape(p), ("p",))
        before = cache_events.snapshot()
        with sw.part("compile"):
            pl = plan(graph, BFSOptions(**self.config["options"]),
                      mesh=mesh, axis="p", num_sources=self.s)
            self.engine = pl.compile()
        after = cache_events.snapshot()
        desc = pl.describe()
        say("setup", edges=int(self.src.size), n=self.n, p=p, S=self.s,
            first_keys=",".join(str(k) for k in self.keys[:8]),
            mode=desc["mode"], fused_tail=pl.use_fused_tail,
            cache_requests=after["requests"] - before["requests"],
            cache_hits=after["hits"] - before["hits"])
        self.record["kernel_bytes"] = {
            "fold_update": kernel_bytes.fold_update(
                graph.part.shard_size, self.s) if pl.use_fused_tail else None}
        # warm-up: one traversal through the compiled programs, from an
        # isolated vertex where the graph has one (a single level)
        isolated = np.flatnonzero(degree == 0)
        warm = [int(isolated[0])] if isolated.size else self.keys[:1]
        with sw.part("warm_up"):
            self.engine.run(warm)
        self.record["setup_parts"] = sw.parts

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
                    "depths": np.asarray(res.dist_host)})
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
        """Every traversal of the window against the reference; fills
        each key's component edge count for TEPS."""
        ref = reference.Graph(self.src, self.dst, self.n)
        used = sorted({key for t in self.record["traversals"]
                       for key in t["keys"]})
        col = {key: j for j, key in enumerate(used)}
        want = ref.depths(used)
        mismatched = 0
        for t in self.record["traversals"]:
            cols = want[:, [col[key] for key in t["keys"]]]
            got = t["depths"]
            if got.shape != cols.shape:
                mismatched += cols.size
            else:
                mismatched += int((got != cols).sum())
            t["edges"] = [ref.component_edges(want[:, col[key]])
                          for key in t["keys"]]
            del t["depths"]
        return {"mismatched_depths": (mismatched, 0)}


def search_keys(degree: np.ndarray, k: int, seed: int) -> list:
    """``k`` distinct search keys in seed order, drawn from the vertices
    of degree >= 1, as the Graph500 specification draws them."""
    cand = np.flatnonzero(degree)
    return [int(v) for v in rng(seed, 1).choice(cand, k, replace=False)]
