"""Fixtures of the benchmark's tests: a toy copy of the benchmark tree.

The toy tree holds every configuration, mix, metric, answer and
generator file of ``bench/`` and a ``BENCHMARK.json`` whose cells keep
their names and mixes but run configurations cut to a size the CPU
traverses in milliseconds.  It also holds what waits for a later
benchmark change (``HELD``): the served cell and the per-layer metrics
with no entry yet, whose files are under ``bench/``, so that their
runners, readers and faults stay tested.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, os.path.join(CHECKOUT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TOY_SIZES = {"scale": 9, "n": 2000}
TOY_PEAKS = {"hbm_bytes_per_s": 819e9}

SERVED = "served_er100k.zipf_open"
P4 = "graph500_s20_p4.key1"


def _layer(name, unit, layer, moves, cell, source="program_span"):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves, "workloads": [cell]}


# the entries a later change would add to BENCHMARK.json for the held
# cell and metrics
HELD = {
    "workloads": [
        {"name": SERVED, "config": "served_er100k", "traffic": "zipf_open",
         "chips": 1, "why": "held"},
    ],
    "end_to_end": [
        {"name": "latency_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": [SERVED]},
    ],
    "per_layer": [
        _layer("device_ms.served", "ms", "engine", "latency_p50_ms", SERVED),
        _layer("frontend_ms", "ms", "front-end", "latency_p50_ms", SERVED),
        _layer("queue_wait_ms", "ms", "service", "latency_p50_ms", SERVED),
        _layer("device_idle.served", "%", "device", "latency_p50_ms", SERVED,
               "device_trace"),
        _layer("collective_exposed_share", "%", "exchange", "teps", P4,
               "device_trace"),
        _layer("fold_update_roofline", "%", "kernels", "teps", P4,
               "device_trace"),
    ],
}


def make_toy_tree(root: str) -> str:
    """Copy the benchmark's data files under ``root`` with each
    configuration cut to a toy size; returns the toy BENCHMARK.json."""
    for sub in ("configs", "traffic", "metrics", "answers", "generators"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(root, sub))
    for name in os.listdir(os.path.join(root, "configs")):
        path = os.path.join(root, "configs", name)
        with open(path) as f:
            cfg = json.load(f)
        for key, value in TOY_SIZES.items():
            if key in cfg:
                cfg[key] = value
        if "undirected_edges" in cfg:
            del cfg["undirected_edges"]
            # the most edges a toy graph can have
            cfg["edge_capacity"] = cfg["n"] * cfg["avg_degree"]
        with open(path, "w") as f:
            json.dump(cfg, f)
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for group, entries in HELD.items():
        doc[group] += json.loads(json.dumps(entries))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


@pytest.fixture
def toy_spec(tmp_path):
    from harness.spec import Spec

    path = make_toy_tree(str(tmp_path))
    return Spec(path, str(tmp_path))


@pytest.fixture
def toy_run(toy_spec):
    """``toy_run(cell, seed=..., seconds=..., trace=..., runner=...)``:
    one run of a toy cell on this process's first CPU device."""
    import time

    import jax

    from harness.cell import run_cell

    def run(cell, seed=7, seconds=0.5, trace=False, runner=None):
        return run_cell(toy_spec, cell, seed, seconds, trace,
                        jax.devices()[:1], time.perf_counter(),
                        peaks=TOY_PEAKS, runner=runner)

    return run
