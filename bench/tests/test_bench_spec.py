"""BENCHMARK.json and the files it names: discovery by name, the
contract's rules on names and entries, and a mix added by files alone."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, CHECKOUT, make_toy_tree
from harness.cell import RUNNERS
from harness.spec import Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_named_file_is_found(doc):
    spec = Spec()
    for cell in doc["workloads"]:
        cfg = spec.config(cell["config"])
        assert int(cfg["chips"]) == cell["chips"]
        assert spec.traffic(cell["traffic"])["runner"] in RUNNERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for c in doc["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(CHECKOUT, c["file"]))


def test_names_units_and_keys_follow_the_contract(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"] and 1 <= doc["run_seconds"] <= 51
    cells = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"] for c in doc["configs"]}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 2)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", ())) <= set(cells)
    for m in doc["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", cells)
    for name in cells:
        spec = Spec()
        assert any(m["name"] != "setup_s" for m in spec.metrics(name, False))
        assert spec.metrics(name, True)


def test_a_mix_added_by_files_alone_runs(tmp_path):
    """A new traffic mix is one data file and one BENCHMARK.json entry."""
    import time

    import jax

    from harness.cell import run_cell
    from conftest import TOY_PEAKS

    path = make_toy_tree(str(tmp_path))
    with open(tmp_path / "traffic" / "toy_batch3.json", "w") as f:
        json.dump({"runner": "engine_closed", "keys_per_traversal": 3}, f)
    with open(path) as f:
        doc = json.load(f)
    doc["workloads"].append({"name": "graph500_s20.toy_batch3",
                             "config": "graph500_s20",
                             "traffic": "toy_batch3", "chips": 1,
                             "why": "three keys a traversal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "graph500_s20.key1" in m.get("workloads", ()):
            m["workloads"].append("graph500_s20.toy_batch3")
    with open(path, "w") as f:
        json.dump(doc, f)
    spec = Spec(path, str(tmp_path))
    res = run_cell(spec, "graph500_s20.toy_batch3", 5, 0.3, False,
                   jax.devices()[:1], time.perf_counter(), peaks=TOY_PEAKS)
    assert res["correct"] and res["metrics"]["teps"]["value"] > 0
    assert set(res["metrics"]) == {"teps", "setup_s"}


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "graph500_s20.key1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_run_exits_nonzero_without_a_tpu():
    proc = _run_py(CHECKOUT)
    assert proc.returncode != 0 and not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    import shutil

    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0 and not _has_result(proc.stdout)
