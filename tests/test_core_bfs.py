"""Core BFS engine: single-device (p=1) correctness + multi-device subprocess."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import BFSOptions, bfs
from repro.core.partition import Partition1D, repartition
from repro.core.ref import INF, bfs_reference
from repro.graphs import generate, shard_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("kind,kw", [
    ("star", {}),
    ("erdos_renyi", dict(avg_degree=6)),
    ("small_world", dict(k=4, beta=0.2)),
    ("rmat", dict(edge_factor=6)),
])
@pytest.mark.parametrize("mode", ["dense", "queue", "auto"])
def test_bfs_p1_matches_reference(kind, kw, mode):
    n = 700
    src, dst = generate(kind, n, seed=11, **kw)
    g = shard_graph(src, dst, n, p=1)
    want = bfs_reference(src, dst, n, [0])
    opts = BFSOptions(mode=mode, queue_cap=8192)
    got, stats = bfs(g, [0], opts=opts)
    np.testing.assert_array_equal(got, want)
    assert stats.levels >= 1
    assert stats.visited == int((want < INF).sum())


def _loop_bfs_oracle(src, dst, n, sources):
    """Per-edge Python loop BFS: the plain oracle the vectorized
    ``bfs_reference`` must agree with exactly."""
    adj = [[] for _ in range(n)]
    for u, v in zip(np.asarray(src).tolist(), np.asarray(dst).tolist()):
        adj[u].append(v)
    out = np.full((n, len(sources)), INF, dtype=np.int32)
    for j, s0 in enumerate(sources):
        dist = out[:, j]
        dist[s0] = 0
        frontier, level = [s0], 1
        while frontier:
            nxt = []
            for u in frontier:
                for v in adj[u]:
                    if dist[v] == INF:
                        dist[v] = level
                        nxt.append(v)
            frontier, level = nxt, level + 1
    return out


@pytest.mark.parametrize("kind,n,kw", [
    ("star", 300, {}),
    ("erdos_renyi", 600, dict(avg_degree=3)),     # several components
    ("small_world", 500, dict(k=4, beta=0.1)),     # deep traversal
    ("rmat", 512, dict(edge_factor=4)),            # isolated vertices
])
def test_vectorized_reference_matches_loop_oracle(kind, n, kw):
    src, dst = generate(kind, n, seed=5, **kw)
    sources = [0, 7, n - 1]
    np.testing.assert_array_equal(bfs_reference(src, dst, n, sources),
                                  _loop_bfs_oracle(src, dst, n, sources))
    # directed: one orientation only, so reachability is asymmetric
    half = src.shape[0] // 2
    np.testing.assert_array_equal(
        bfs_reference(src[:half], dst[:half], n, sources),
        _loop_bfs_oracle(src[:half], dst[:half], n, sources))


def test_bfs_batched_sources_p1():
    n = 500
    src, dst = generate("erdos_renyi", n, seed=2, avg_degree=5)
    g = shard_graph(src, dst, n, p=1)
    sources = [0, 13, 250, 499]
    want = bfs_reference(src, dst, n, sources)
    got, _ = bfs(g, sources, opts=BFSOptions(mode="dense"))
    np.testing.assert_array_equal(got, want)


def test_bfs_unreachable_is_inf():
    # two cliques, no bridge
    a = np.array([0, 1, 2, 0]), np.array([1, 2, 0, 2])
    b = np.array([5, 6, 7, 5]), np.array([6, 7, 5, 7])
    src = np.concatenate([a[0], b[0]])
    dst = np.concatenate([a[1], b[1]])
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    g = shard_graph(src, dst, 8, p=1)
    got, _ = bfs(g, [0], opts=BFSOptions(mode="dense"))
    assert (got[5:8] == INF).all() and (got[:3] < INF).all()


def test_partition_roundtrip_properties():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(n=st.integers(1, 10_000), p=st.integers(1, 64),
           data=st.data())
    def prop(n, p, data):
        part = Partition1D(n, p)
        assert part.n >= n and part.n % p == 0
        v = data.draw(st.integers(0, part.n - 1))
        o = int(part.owner(v))
        assert 0 <= o < p
        assert int(part.global_id(o, part.local_id(v))) == v
        # repartition preserves the logical vertex set
        part2 = repartition(part, max(1, p // 2))
        assert part2.n_logical == part.n_logical

    prop()


def test_owner_matches_numpy_and_jnp():
    import jax.numpy as jnp
    part = Partition1D(1000, 7)
    v_np = np.arange(1000)
    v_j = jnp.arange(1000)
    np.testing.assert_array_equal(np.asarray(part.owner(v_np)),
                                  np.asarray(part.owner(v_j)))


def test_expand_bottom_up_masks_both_endpoints():
    """Regression: a padded in-edge whose destination is the -1 sentinel
    but whose source field holds a valid id used to wrap (``.at[-1]``)
    and scatter into the shard's *last* row; an out-of-range local id
    must be dropped too, not land anywhere."""
    import jax.numpy as jnp
    from repro.core import frontier as fr

    shard, n, s = 4, 8, 1
    fglob = jnp.ones((n, s), jnp.uint8)          # every vertex in frontier
    # one real edge (src 5 -> local 2); one pad with dst=-1 but src "valid";
    # one pad with dst == shard (out of range) and src valid
    in_src = jnp.array([5, 0, 3], jnp.int32)
    in_dst = jnp.array([2, -1, shard], jnp.int32)
    cand = fr.expand_bottom_up(fglob, in_src, in_dst, shard)
    np.testing.assert_array_equal(
        np.asarray(cand)[:, 0], np.array([0, 0, 1, 0], np.uint8))
    # fully padded block: nothing scatters
    cand0 = fr.expand_bottom_up(fglob, jnp.full((3,), -1, jnp.int32),
                                jnp.full((3,), -1, jnp.int32), shard)
    assert int(np.asarray(cand0).sum()) == 0


def test_multidevice_bfs_subprocess():
    """Full 8-device matrix: strategies x modes x graph families."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never the chip
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers", "multidev_bfs.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
