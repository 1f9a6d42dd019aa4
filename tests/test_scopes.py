"""The traversal's own names: named scopes in the compiled level loop and
the init program."""

import json
import os
import subprocess
import sys

import pytest

from repro.core import BFSOptions, plan
from repro.core.bfs import INIT_SCOPE, MODE_SCOPES, PHASE_SCOPES
from repro.graphs import generate, shard_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINES = [f"{layout}_S{s}" for s in (1, 8)
           for layout in ("1d_p1", "1d_p4", "2d_2x2")]


@pytest.fixture(scope="module")
def scoped_hlo():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never the chip
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers",
                                      "scoped_hlo.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return {d["engine"]: d for d in map(json.loads, r.stdout.splitlines())}


@pytest.mark.parametrize("engine", ENGINES)
def test_loop_ops_carry_a_phase_and_names_change_no_op(scoped_hlo, engine):
    """Every fusion, custom-call and collective of the loop body's code
    carries a ``bfs.<phase>`` scope; each level's ops carry their mode;
    with metadata stripped the program equals the one compiled without
    the scopes."""
    d = scoped_hlo[engine]
    assert d["unscoped"] == []
    modes = {pair.split("/")[0] for pair in d["pairs"]}
    phases = {pair.split("/")[1] for pair in d["pairs"]}
    want = {"bfs.dense", "bfs.bottom_up"} | (
        {"bfs.queue"} if engine.endswith("_S1") else set())
    assert modes - {"-"} == want
    assert {"bfs.decide", "bfs.expand", "bfs.update"} <= phases
    if "_p1_" not in engine:
        assert "bfs.exchange" in phases
    assert d["same_program"] and d["names_dropped"]


def test_scope_vocabulary():
    assert MODE_SCOPES == ("bfs.dense", "bfs.queue", "bfs.bottom_up")
    assert PHASE_SCOPES == ("bfs.decide", "bfs.expand", "bfs.exchange",
                            "bfs.fold", "bfs.update")
    names = MODE_SCOPES + PHASE_SCOPES + (INIT_SCOPE,)
    assert len(set(names)) == len(names)
    assert all(n.startswith("bfs.") for n in names)


def _toy_graph():
    src, dst = generate("rmat", n=512, seed=1)
    return shard_graph(src, dst, 512, 1)


def test_init_program_is_scoped():
    eng = plan(_toy_graph(), BFSOptions(mode="auto")).compile()
    assert f"/{INIT_SCOPE}/" in eng._init_c.as_text()

