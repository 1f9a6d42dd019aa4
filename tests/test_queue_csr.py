"""The 1-D queue level's CSR view: the on-device build, the compaction of
the frontier's edges into the candidate buffer, and the auto engine that
reads them (depths and level schedule against the numpy references)."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BFSOptions, plan
from repro.core import frontier as fr
from repro.core.ref import bfs_reference, bfs_reference_2d
from repro.graphs import csr_from_coo, generate, shard_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shards(p, n=301, seed=2):
    """Shard blocks of an RMAT graph: isolated vertices (degree 0), a
    last shard with padding vertices at p = 4, ``dst == -1`` slots."""
    src, dst = generate("rmat", n, seed=seed, edge_factor=4)
    g = shard_graph(src, dst, n, p)
    return g, [(g.src_local[j], g.dst_global[j]) for j in range(p)]


def _walk(frontier, src_local, dst_global, shard):
    """The frontier's out-edge targets in CSR order, by a plain walk."""
    valid = dst_global >= 0
    indptr, idx = csr_from_coo(src_local[valid], dst_global[valid], shard)
    return np.concatenate(
        [idx[indptr[v]:indptr[v + 1]] for v in np.flatnonzero(frontier)]
        + [np.zeros(0, np.int64)])


@pytest.mark.parametrize("p", [1, 4])
def test_csr_view_matches_csr_from_coo(p):
    g, shards = _shards(p)
    shard = g.part.shard_size
    for sl, dg in shards:
        row_ptr, csr_dst = map(np.asarray, fr.csr_view(
            jnp.asarray(sl), jnp.asarray(dg), shard))
        valid = dg >= 0
        indptr, idx = csr_from_coo(sl[valid], dg[valid], shard)
        np.testing.assert_array_equal(row_ptr, indptr)
        np.testing.assert_array_equal(csr_dst[:valid.sum()], idx)
        assert (csr_dst[valid.sum():] == -1).all()     # padding slots last


def _frontier(kind, shard, deg, rng):
    f = np.zeros(shard, bool)
    if kind == "random":                               # degree 0 included
        f[rng.random(shard) < 0.3] = True
        assert (f & (deg == 0)).any()
    elif kind == "zero_degree":                        # f_edges_local 0
        f[deg == 0] = True
    elif kind != "empty":
        raise ValueError(kind)
    return f


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("kind,slack", [
    ("random", 17),             # auto: the buffer has room to spare
    ("random", 1),              # f_edges_local == slots - 1
    ("random", 0),              # f_edges_local == slots, queue mode
    ("zero_degree", 5),         # frontier vertices without out-edges
    ("empty", 5),
])
def test_compact_frontier_edges_matches_csr_walk(p, kind, slack):
    g, shards = _shards(p)
    shard = g.part.shard_size
    rng = np.random.default_rng(7)
    for j, (sl, dg) in enumerate(shards):
        row_ptr, csr_dst = fr.csr_view(jnp.asarray(sl), jnp.asarray(dg),
                                       shard)
        deg = np.diff(np.asarray(row_ptr))
        if kind == "random" and not deg.any():
            continue                                   # an all-padding shard
        f = _frontier(kind, shard, deg, rng)
        fdeg = fr.frontier_out_degrees(jnp.asarray(f, jnp.uint8), row_ptr)
        want = _walk(f, sl, dg, shard)
        assert int(fdeg.sum()) == want.size
        slots = want.size + slack
        if slots == 0:
            continue
        tgt, active = map(np.asarray, fr.compact_frontier_edges(
            fdeg, row_ptr, csr_dst, slots))
        assert tgt.shape == active.shape == (slots,)
        np.testing.assert_array_equal(active, np.arange(slots) < want.size)
        np.testing.assert_array_equal(tgt[:want.size], want)
        assert (tgt[want.size:] == -1).all(), (j, kind)


def _schedule_counts(src, dst, n, source, qt):
    _, sched = bfs_reference_2d(src, dst, n, [source], 1, 1, mode="auto",
                                queue_threshold=qt, return_schedule=True)
    return {k: sum(e["kind"] == k for e in sched)
            for k in ("dense", "queue", "bottom_up")}


ENGINE_CASES = [(kind, sieve, qt)
                for kind in ("rmat", "erdos_renyi", "small_world")
                for sieve in (False, True)
                for qt in (1 / 64, 0.5)]
_KW = {"rmat": dict(edge_factor=6), "erdos_renyi": dict(avg_degree=6),
       "small_world": dict(k=4, beta=0.1)}


@pytest.mark.parametrize("kind,sieve,qt", ENGINE_CASES)
def test_auto_s1_engine_matches_reference_one_device(kind, sieve, qt):
    """Depths bitwise equal to the serial reference and the level
    schedule equal to the reference's, with most levels queue levels
    once ``queue_threshold`` is raised."""
    n = 700
    src, dst = generate(kind, n, seed=11, **_KW[kind])
    g = shard_graph(src, dst, n, p=1)
    res = plan(g, BFSOptions(mode="auto", sieve=sieve, queue_threshold=qt)
               ).compile().run([5])
    np.testing.assert_array_equal(res.dist_host,
                                  bfs_reference(src, dst, n, [5]))
    st = res.stats()
    assert st.mode_counts == _schedule_counts(src, dst, n, 5, qt)
    if qt == 0.5:
        assert st.mode_counts["queue"] >= 2


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never the chip
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers",
                                      "queue_csr_p4.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return {d["case"]: d for d in map(json.loads, r.stdout.splitlines())}


@pytest.mark.parametrize("kind,sieve,qt", ENGINE_CASES)
def test_auto_s1_engine_matches_reference_four_devices(four_device_runs,
                                                       kind, sieve, qt):
    d = four_device_runs[f"{kind}/sieve={int(sieve)}/qt={qt}"]
    assert d["depths_equal"]
    assert d["mode_counts"] == d["reference_counts"]
    assert d["sieve"] == sieve
    if qt == 0.5:
        assert d["mode_counts"]["queue"] >= 2
