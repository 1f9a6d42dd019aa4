"""``correct`` holds for the program and fails for the control and for
each fault a cell can have, planted under a run at toy size.

Faults: a traversal that returns its initial state; half of a batch left
out; one depth altered where the program produces it; the exchange
between chips left out (four CPU devices, in a child process)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import BENCH_DIR, CHECKOUT, SERVED
from harness import control, reference
from harness.cell import make_runner
from harness.common import CompileCacheEvents

CELLS = ["graph500_s20.key1", "graph500_s20.batch8", SERVED]


def _initial(n, keys):
    d = np.full((n, len(keys)), reference.UNREACHED, dtype=np.int32)
    d[list(keys), range(len(keys))] = 0
    return d


def _altered(depths, keys):
    d = np.array(depths, copy=True)
    far = np.flatnonzero((d[:, 0] > 0) & (d[:, 0] < reference.UNREACHED))
    d[far[0], 0] += 1
    return d


class Faulty:
    """A compiled engine whose answers a fault changes."""

    def __init__(self, engine, n, fault):
        self.engine, self.n, self.fault = engine, n, fault

    def run_async(self, keys):
        if self.fault == "unchanged":
            return control.Result(_initial(self.n, keys))
        if self.fault == "half":
            h = len(keys) // 2
            d = _initial(self.n, keys)
            d[:, :h] = self.engine.run(keys[:h]).dist_host
            return control.Result(d)
        d = self.engine.run(keys).dist_host
        return control.Result(_altered(d, keys))


def _runner(spec, cell, seed=7):
    import jax

    d = make_runner(spec, spec.cell(cell), seed, 0.5, jax.devices()[:1])
    d.setup(CompileCacheEvents())
    return d


@pytest.mark.parametrize("cell", CELLS)
def test_program_reads_correct(toy_run, cell):
    res = toy_run(cell, trace=cell.endswith("batch8"))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(toy_spec, toy_run, cell):
    d = _runner(toy_spec, cell)
    control.install(d)
    res = toy_run(cell, runner=d)
    assert not res["correct"]
    assert res["checks"]["mismatched_depths"]["value"] > 0


@pytest.mark.parametrize("cell,fault", [
    ("graph500_s20.key1", "unchanged"),
    ("graph500_s20.key1", "altered"),
    ("graph500_s20.batch8", "half"),
    ("graph500_s20.batch8", "altered"),
])
def test_engine_fault_reads_not_correct(toy_spec, toy_run, cell, fault):
    d = _runner(toy_spec, cell)
    d.engine = Faulty(d.engine, d.n, fault)
    res = toy_run(cell, runner=d)
    assert not res["correct"]
    assert res["checks"]["mismatched_depths"]["value"] > 0


def test_served_answer_altered_reads_not_correct(toy_spec, toy_run):
    d = _runner(toy_spec, SERVED)
    svc = d.service
    real = svc.traverse_async

    def altered(name, sources):
        res, bucket = real(name, sources)
        return control.Result(_altered(res.block().dist_host, sources)), \
            bucket

    svc.traverse_async = altered
    res = toy_run(SERVED, runner=d)
    assert not res["correct"]
    assert res["checks"]["mismatched_depths"]["value"] > 0


_FOUR_CHIPS = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
    import jax
    import repro.graphs
    from conftest import P4 as cell, TOY_PEAKS, make_toy_tree
    from harness.cell import run_cell
    from harness.spec import Spec

    spec = Spec(make_toy_tree({root!r}), {root!r})
    out = {{}}
    out["sound"] = run_cell(spec, cell, 9, 0.3, False, jax.devices(),
                            time.perf_counter(), peaks=TOY_PEAKS)["correct"]
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


def test_exchange_left_out_reads_not_correct(tmp_path):
    script = _FOUR_CHIPS.format(bench=BENCH_DIR,
                                tests=os.path.dirname(__file__),
                                src=os.path.join(CHECKOUT, "src"),
                                root=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json

    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["sound"] is True
    assert out["no_exchange"] is False and out["mismatched"] > 0
