"""The benchmark's graph generators: determinism, edge counts, the
Graph500 vertex-label permutation, and the edges each family gave
before it moved into a file of its own."""

import hashlib

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
    assert a[2] == b[2] == c[2] == graphgen.family(cfg).num_vertices(cfg)
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
    kron = graphgen.family(KRON)
    lo, hi, keep = kron._kronecker(graphgen.prng_key(1), 14, 16, 0.57, 0.19,
                                   0.19)
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


# SHA-256 of src.tobytes() + dst.tobytes(), with the vertex and directed
# edge counts, as the generators gave them while they lived in
# harness/graphgen.py: every cell's graph and keys come from these draws
GOLDEN = {
    ("kronecker", 20261018): (512, 9504, "d3a7fbc6c0fa7b86209325329671b8bf"
                              "0eca3a3567244a07aeab2ec98a97f9be"),
    ("kronecker", 2**40 + 3): (512, 9524, "94cc7e5b2c12ea3d891d7a3a50f3d526"
                               "ee643bb8d1f519d36b5bdad3baee6296"),
    ("erdos_renyi", 20261018): (2000, 31846, "0da95b0252b3f3894c7b3e7778c04d"
                                "a7183d1818e0791b782a70b802bbe7f74b"),
    ("erdos_renyi", 2**40 + 3): (2000, 31842, "f76c4b290faa20457d99103f97520d"
                                 "d141162858096f455d1b6d2de3028a8dc0"),
    ("er_fixed", 20261018): (2000, 30000, "cd91d5caa32b9ecfa37f7b0c720899d9"
                             "906cc3d8461d180e27d40fccb5d55bb3"),
    ("er_fixed", 2**40 + 3): (2000, 30000, "004b8d6da2d87a717cc0ee61843faebf"
                              "d1380dc8c9d880ac8b1312fae71c57db"),
}
TOY = {"kronecker": dict(KRON, scale=9),
       "erdos_renyi": dict(ER, n=2000),
       "er_fixed": dict(ER, n=2000, undirected_edges=15000)}


@pytest.mark.parametrize("name,seed", sorted(GOLDEN))
def test_moved_generators_give_the_edges_they_gave(name, seed):
    src, dst, n = graphgen.generate(TOY[name], seed)
    digest = hashlib.sha256(src.tobytes() + dst.tobytes()).hexdigest()
    assert (n, src.size, digest) == GOLDEN[(name, seed)]
