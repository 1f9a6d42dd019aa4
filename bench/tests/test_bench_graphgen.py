"""The benchmark's graph generators: determinism, edge counts and the
Graph500 vertex-label permutation."""

import numpy as np
import pytest

from harness import graphgen

KRON = {"generator": "kronecker", "scale": 12, "edgefactor": 16,
        "a": 0.57, "b": 0.19, "c": 0.19}
ER = {"generator": "erdos_renyi", "n": 3000, "avg_degree": 16}


@pytest.mark.parametrize("cfg", [KRON, ER], ids=["kronecker", "er"])
def test_same_seed_same_graph_other_seed_other_graph(cfg):
    a = graphgen.generate(cfg, 2**40 + 3)
    b = graphgen.generate(cfg, 2**40 + 3)
    c = graphgen.generate(cfg, 3)
    assert a[2] == b[2] == c[2] == graphgen.num_vertices(cfg)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    # seeds that differ only above 32 bits still give another graph
    assert a[0].shape != c[0].shape or not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("cfg", [KRON, ER], ids=["kronecker", "er"])
def test_edges_symmetric_without_loops_or_duplicates(cfg):
    src, dst, n = graphgen.generate(cfg, 11)
    assert src.dtype == dst.dtype == np.int32
    assert src.min() >= 0 and max(src.max(), dst.max()) < n
    assert not (src == dst).any()
    key = src.astype(np.int64) * n + dst
    assert np.unique(key).size == key.size
    half = src.size // 2
    assert src.size == 2 * half
    assert np.array_equal(src[:half], dst[half:])
    assert np.array_equal(dst[:half], src[half:])
    drawn = (cfg["edgefactor"] << cfg["scale"] if "scale" in cfg
             else cfg["n"] * cfg["avg_degree"] // 2)
    # duplicates and self loops only remove edges; most survive
    assert 0.5 * drawn < half <= drawn


def test_kronecker_labels_are_permuted():
    """Without the permutation the Kronecker hubs sit at the lowest ids
    (bit 0 of every endpoint is most likely 0); with it, the lowest
    quarter of the ids holds about a quarter of the edge ends, as the
    1-D block partition needs for balanced shards."""
    src, _, n = graphgen.generate(KRON, 5)
    low = (src < n // 4).mean()
    assert 0.15 < low < 0.35
    deg = np.bincount(src, minlength=n)
    assert deg.argmax() != 0

    from repro.graphs.generators import rmat

    raw_src, _ = rmat(KRON["scale"], KRON["edgefactor"], seed=5)
    assert (raw_src < n // 4).mean() > 0.5


def test_kronecker_quadrant_probabilities():
    """The share of edges with the top bit of the row set is C + D."""
    import jax

    cfg = dict(KRON, scale=14)
    lo, hi, keep = graphgen._kronecker(graphgen.prng_key(1), 14, 16, 0.57,
                                       0.19, 0.19)
    assert int(np.asarray(keep).sum()) > 0
    key = graphgen.prng_key(1)
    k_bits, _ = jax.random.split(key)
    r = np.asarray(jax.random.uniform(jax.random.fold_in(k_bits, 13),
                                      (16 << 14,)))
    assert abs((r >= cfg["a"] + cfg["b"]).mean() - 0.24) < 0.01


def test_fixed_edge_count_keeps_a_seeded_subset():
    cfg = dict(KRON, undirected_edges=20000)
    full = graphgen.generate(KRON, 8)
    a = graphgen.generate(cfg, 8)
    b = graphgen.generate(cfg, 9)
    assert a[0].size == b[0].size == 40000
    key = lambda g: set(zip(g[0].tolist(), g[1].tolist()))
    assert key(a) <= key(full) and key(a) != key(b)
    with pytest.raises(ValueError, match="fewer than"):
        graphgen.generate(dict(KRON, undirected_edges=10**7), 8)
