"""Find a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` names them; each lives in a file of its own:

* ``configs/<config>.json``       the deployment: generator, chips,
  partition, options, and the answer it is checked by;
* ``traffic/<mix>.json``          the mix: which runner, and its parameters;
* ``metrics/<metric>.py``         a per-layer metric: ``read(record)``;
* ``answers/<answer>.py``         what a traversal answers and how it is
  checked: ``fetch(result)`` and ``check(graph, traversals, depths)``
  (a configuration's ``answer`` key, ``depths`` where it gives none);
* ``generators/<generator>.py``   a graph family: ``num_vertices(cfg)``
  and the jitted device draw ``draw(cfg, key)`` (its ``generator`` key).

A later change adds a configuration, a mix, a metric, an answer or a
graph family by adding such a file and an entry in ``BENCHMARK.json``;
nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class Spec:
    """``BENCHMARK.json`` and the directory that holds the cells' files."""

    def __init__(self, benchmark_path: str | None = None,
                 bench_dir: str = BENCH_DIR):
        path = benchmark_path or os.path.join(CHECKOUT, "BENCHMARK.json")
        with open(path) as f:
            self.doc = json.load(f)
        self.bench_dir = bench_dir

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def _json(self, kind: str, name: str) -> dict:
        with open(os.path.join(self.bench_dir, kind, f"{name}.json")) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def metrics(self, cell: str, trace: bool) -> list:
        """The metric entries the cell reports: its end-to-end ones in a
        plain run, its per-layer ones in a traced run."""
        group = self.doc["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", (cell,))]

    def metric_reader(self, name: str):
        """The ``read(record)`` function of ``metrics/<name>.py``."""
        return load(self.bench_dir, "metrics", name).read

    def answer(self, name: str):
        """The module of ``answers/<name>.py``."""
        return load(self.bench_dir, "answers", name)

    def generator(self, name: str):
        """The module of ``generators/<name>.py``."""
        return load(self.bench_dir, "generators", name)


def load(bench_dir: str, kind: str, name: str):
    """Import ``<bench_dir>/<kind>/<name>.py``; a name with no file is a
    ``KeyError`` that lists the names there are."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.join(bench_dir, kind))
                      if f.endswith(".py"))
        raise KeyError(f"no {kind}/{name}.py; have {have}")
    mod_name = f"bench_{kind}_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
