"""Subprocess harness: the auto engine at S = 1 on four forced host
devices, whose queue levels read the CSR view.

Run as: python tests/helpers/queue_csr_p4.py
Prints one JSON line per case (graph family x sieve x queue_threshold):
whether the depths equal the serial reference, the engine's
``mode_counts`` and the numpy hybrid reference's schedule counts.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.launch import host_devices  # noqa: E402

host_devices(4)  # must precede the jax import below

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import BFSOptions, plan  # noqa: E402
from repro.core.ref import bfs_reference, bfs_reference_2d  # noqa: E402
from repro.graphs import generate, shard_graph  # noqa: E402

N = 700
KW = {"rmat": dict(edge_factor=6), "erdos_renyi": dict(avg_degree=6),
      "small_world": dict(k=4, beta=0.1)}


def main():
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("p",))
    for kind, kw in KW.items():
        src, dst = generate(kind, N, seed=11, **kw)
        g = shard_graph(src, dst, N, 4)
        want = bfs_reference(src, dst, N, [5])
        for sieve in (False, True):
            for qt in (1 / 64, 0.5):
                pl = plan(g, BFSOptions(mode="auto", sieve=sieve,
                                        queue_threshold=qt),
                          mesh=mesh, axis="p")
                res = pl.compile().run([5])
                _, sched = bfs_reference_2d(
                    src, dst, N, [5], 1, 1, mode="auto", queue_threshold=qt,
                    return_schedule=True)
                print(json.dumps({
                    "case": f"{kind}/sieve={int(sieve)}/qt={qt}",
                    "sieve": pl.sieve,
                    "depths_equal": bool(np.array_equal(res.dist_host,
                                                        want)),
                    "mode_counts": res.stats().mode_counts,
                    "reference_counts": {
                        k: sum(e["kind"] == k for e in sched)
                        for k in ("dense", "queue", "bottom_up")},
                }), flush=True)


if __name__ == "__main__":
    main()
