"""BENCHMARK.json and the files it names: discovery by name, the
contract's rules on names and entries, and a mix, an answer, a graph
family and a 2-D partition each added by files alone."""

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import BENCH_DIR, CHECKOUT, TOY_PEAKS, make_toy_tree
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
        cfg = spec.config(c["name"])
        gen = spec.generator(cfg["generator"])
        assert callable(gen.num_vertices) and callable(gen.draw)
        answer = spec.answer(cfg.get("answer", "depths"))
        assert callable(answer.fetch) and callable(answer.check)


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


def add_by_files(root, files: dict, config: str, cell: str, traffic: str,
                 chips: int, like: str) -> str:
    """A toy tree under ``root`` with ``files`` ({path under the tree:
    text}) written into it and BENCHMARK.json entries for configuration
    ``config`` and cell ``cell``, which reports the metrics that cell
    ``like`` reports.  Writes no file the tree already has."""
    path = make_toy_tree(str(root))
    for rel, text in files.items():
        full = os.path.join(root, rel)
        assert not os.path.exists(full)
        with open(full, "w") as f:
            f.write(textwrap.dedent(text))
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": config, "source": "toy",
                           "file": f"bench/configs/{config}.json",
                           "reduced": [], "why": "toy"})
    doc["workloads"].append({"name": cell, "config": config,
                             "traffic": traffic, "chips": chips,
                             "why": "toy"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def _toy_config(root, base: str, **changes) -> str:
    """The toy tree's configuration ``base`` with ``changes``, as JSON."""
    with open(os.path.join(root, "configs", f"{base}.json")) as f:
        cfg = json.load(f)
    cfg.update(changes)
    return json.dumps(cfg)


def _run(spec, cell, seed=5, runner=None):
    import time

    import jax

    from harness.cell import run_cell

    return run_cell(spec, cell, seed, 0.3, False, jax.devices()[:1],
                    time.perf_counter(), peaks=TOY_PEAKS, runner=runner)


TOY_REACH = """
    \"\"\"A toy answer: how many vertices each key reached.\"\"\"
    import numpy as np

    UNREACHED = 2 ** 30


    def fetch(result):
        return (np.asarray(result.dist_host) < UNREACHED).sum(axis=0)


    def check(graph, traversals, depths):
        wrong = 0
        for t in traversals:
            want = (depths(t["keys"]) < UNREACHED).sum(axis=0)
            wrong += int((t["answer"] != want).sum())
        return {"wrong_reach": (wrong, 0)}
"""


def test_an_answer_added_by_files_alone_decides_correct(tmp_path):
    """A configuration's ``answer`` names ``answers/<answer>.py``: its
    fetch is what the window copies, its check alone decides
    ``correct``, and the control fails it."""
    from harness import control
    from harness.cell import make_runner
    from harness.common import CompileCacheEvents

    make_toy_tree(str(tmp_path / "base"))
    cfg = _toy_config(tmp_path / "base", "graph500_s20", answer="toy_reach")
    path = add_by_files(tmp_path / "tree",
                        {"answers/toy_reach.py": TOY_REACH,
                         "configs/toy_reach.json": cfg},
                        "toy_reach", "toy_reach.key1", "key1", 1,
                        "graph500_s20.key1")
    spec = Spec(path, str(tmp_path / "tree"))
    res = _run(spec, "toy_reach.key1")
    assert res["correct"] and set(res["checks"]) == {"wrong_reach"}
    assert res["metrics"]["teps"]["value"] > 0

    import jax

    d = make_runner(spec, spec.cell("toy_reach.key1"), 5, 0.3,
                    jax.devices()[:1])
    d.setup(CompileCacheEvents())
    control.install(d)
    res = _run(spec, "toy_reach.key1", runner=d)
    assert not res["correct"]
    assert res["checks"]["wrong_reach"]["value"] > 0


TOY_RING = """
    \"\"\"A toy graph family: a ring of ``n`` vertices, its labels
    permuted by the key.\"\"\"
    import functools

    import jax
    import jax.numpy as jnp

    from harness.graphgen import canonical_unique


    def num_vertices(cfg):
        return int(cfg["n"])


    def draw(cfg, key):
        return _ring(key, num_vertices(cfg))


    @functools.partial(jax.jit, static_argnums=1)
    def _ring(key, n):
        perm = jax.random.permutation(key, n).astype(jnp.int32)
        i = jnp.arange(n, dtype=jnp.int32)
        return canonical_unique(perm[i], perm[(i + 1) % n])
"""


def test_a_generator_added_by_files_alone_runs(tmp_path):
    """A configuration's ``generator`` names ``generators/<name>.py``;
    the run traverses that family's graph and reads correct."""
    from harness.cell import make_runner
    from harness.common import CompileCacheEvents

    import jax

    cfg = {"generator": "toy_ring", "n": 64, "search_keys": 8, "chips": 1,
           "partition": "1d", "options": {"mode": "auto"}}
    path = add_by_files(tmp_path, {"generators/toy_ring.py": TOY_RING,
                                   "configs/toy_ring.json": json.dumps(cfg)},
                        "toy_ring", "toy_ring.key1", "key1", 1,
                        "graph500_s20.key1")
    spec = Spec(path, str(tmp_path))
    d = make_runner(spec, spec.cell("toy_ring.key1"), 5, 0.3,
                    jax.devices()[:1])
    d.setup(CompileCacheEvents())
    assert d.n == 64 and d.src.size == 128
    assert (np.bincount(d.src, minlength=64) == 2).all()
    res = _run(spec, "toy_ring.key1", runner=d)
    assert res["correct"] and res["checks"]["mismatched_depths"]["value"] == 0
    # a ring of 64 is 32 levels deep from every key
    assert all(t["levels"] == 33 for t in d.record["traversals"])


_GRID = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
    import jax
    import repro.graphs
    from conftest import TOY_PEAKS
    from harness.cell import make_runner, run_cell
    from harness.common import CompileCacheEvents
    from harness.spec import Spec
    from test_bench_spec import _toy_config, add_by_files
    from conftest import make_toy_tree

    make_toy_tree({base!r})
    cfg = _toy_config({base!r}, "graph500_s20_p4", partition="2d",
                      grid=[2, 2])
    path = add_by_files({root!r}, {{"configs/toy_2x2.json": cfg}},
                        "toy_2x2", "toy_2x2.key1", "key1", 4,
                        "graph500_s20_p4.key1")
    spec = Spec(path, {root!r})
    cell = "toy_2x2.key1"
    out = {{}}
    d = make_runner(spec, spec.cell(cell), 9, 0.3, jax.devices())
    d.setup(CompileCacheEvents())
    out["partition"] = d.record["partition"]
    out["sound"] = run_cell(spec, cell, 9, 0.3, False, jax.devices(),
                            time.perf_counter(), peaks=TOY_PEAKS,
                            runner=d)["correct"]
    real = repro.graphs.shard_graph

    def local_only(src, dst, n, p, **kw):
        shard = -(-n // p)
        keep = (src // shard) == (dst // shard)
        return real(src[keep], dst[keep], n, p, **kw)

    repro.graphs.shard_graph = local_only
    res = run_cell(spec, cell, 9, 0.3, False, jax.devices(),
                   time.perf_counter(), peaks=TOY_PEAKS)
    out["no_exchange"] = res["correct"]
    out["mismatched"] = res["checks"]["mismatched_depths"]["value"]
    out["devices"] = len(jax.devices())
    print(json.dumps(out))
""")


def test_a_2d_grid_added_by_files_alone_runs(tmp_path):
    """``"partition": "2d"`` with a ``grid`` runs the 2-D loop on a
    2 x 2 mesh (four CPU devices, in a child process): it reads
    correct, and with the edges between shards left out it does not."""
    script = _GRID.format(bench=BENCH_DIR, tests=os.path.dirname(__file__),
                          src=os.path.join(CHECKOUT, "src"),
                          base=str(tmp_path / "base"),
                          root=str(tmp_path / "tree"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["partition"] == "2d"
    assert out["sound"] is True
    assert out["no_exchange"] is False and out["mismatched"] > 0


@pytest.mark.parametrize("base,cell,changes,error,match", [
    ("graph500_s20", "graph500_s20.key1", {"answer": "no_such_answer"},
     KeyError, "no answers/no_such_answer.py"),
    ("graph500_s20", "graph500_s20.key1", {"generator": "no_such_family"},
     KeyError, "no generators/no_such_family.py"),
    ("graph500_s20", "graph500_s20.key1", {"partition": "3d"}, ValueError,
     "unknown partition"),
    ("graph500_s20_p4", "graph500_s20_p4.key1",
     {"partition": "2d", "grid": [2, 3]}, ValueError, "needs a grid"),
    ("graph500_s20_p4", "graph500_s20_p4.key1", {"partition": "2d"},
     ValueError, "needs a grid"),
    ("served_er100k", "served_er100k.zipf_open",
     {"partition": "2d", "grid": [1, 1]}, ValueError, "1-D partition only"),
], ids=["answer", "generator", "partition", "grid", "no_grid", "http_2d"])
def test_a_name_with_no_file_or_a_bad_partition_raises_at_setup(
        toy_spec, base, cell, changes, error, match):
    """Set-up refuses what it cannot run as named, before the window."""
    path = os.path.join(toy_spec.bench_dir, "configs", f"{base}.json")
    cfg = _toy_config(toy_spec.bench_dir, base, **changes)
    with open(path, "w") as f:
        f.write(cfg)
    with pytest.raises(error, match=match):
        _run(toy_spec, cell)


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
