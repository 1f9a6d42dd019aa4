"""Find a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` names them; each lives in a file of its own:

* ``configs/<config>.json``  the deployment: generator, chips, options;
* ``traffic/<mix>.json``     the mix: which runner, and its parameters;
* ``metrics/<metric>.py``    a per-layer metric: ``read(record)``.

A later change adds a configuration, a mix or a metric by adding such a
file and an entry in ``BENCHMARK.json``; nothing here names one.
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
        path = os.path.join(self.bench_dir, "metrics", f"{name}.py")
        mod_name = "bench_metric_" + "".join(
            ch if ch.isalnum() else "_" for ch in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
