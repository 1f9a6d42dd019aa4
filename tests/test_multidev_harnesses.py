"""Subprocess wrappers for the 8-device harnesses (exchange byte model vs
HLO ground truth; owner-exchange GNN vs reference)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never the chip
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers", script)],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2500:]}"
    return r.stdout


def test_exchange_byte_model_matches_hlo():
    out = _run("exchange_bytes.py")
    assert "dense/allgather_merge" in out and "queue/alltoall_direct" in out


def test_owner_exchange_graphcast_matches_reference():
    out = _run("owner_gnn.py")
    assert "OK" in out and "MISMATCH" not in out


def test_grid_bfs_2d_matches_references():
    out = _run("grid_bfs.py")
    assert "grid/2x2" in out and "grid/4x1" in out and "grid/1x4" in out
    assert "MISMATCH" not in out
