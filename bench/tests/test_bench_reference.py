"""The benchmark's plain reference against the repo's serial oracle."""

import numpy as np
import pytest

from harness import control, graphgen, reference
from repro.core.ref import bfs_reference


def _graphs():
    yield "kronecker", graphgen.generate(
        {"generator": "kronecker", "scale": 10, "edgefactor": 8,
         "a": 0.57, "b": 0.19, "c": 0.19}, 21)
    yield "er", graphgen.generate(
        {"generator": "erdos_renyi", "n": 1500, "avg_degree": 3}, 22)
    path = np.arange(99, dtype=np.int32)
    yield "path", (np.concatenate([path, path + 1]),
                   np.concatenate([path + 1, path]), 100)


@pytest.mark.parametrize("name,graph", list(_graphs()),
                         ids=[g[0] for g in _graphs()])
def test_depths_match_core_reference(name, graph):
    src, dst, n = graph
    rng = np.random.default_rng(0)
    keys = rng.choice(n, 70, replace=False).tolist()   # > 64: two blocks
    keys[3] = keys[4]                                  # a repeated key
    got = reference.Graph(src, dst, n).depths(keys)
    want = bfs_reference(src, dst, n, keys)
    assert got.dtype == np.int32 and got.shape == (n, 70)
    assert np.array_equal(got, want)
    assert reference.UNREACHED == 2 ** 30


def test_component_edges_counts_undirected_edges_of_the_component():
    # a triangle (3 edges) and a separate path of 2 edges
    u = np.array([0, 1, 2, 3, 4])
    v = np.array([1, 2, 0, 4, 5])
    g = reference.Graph(np.concatenate([u, v]), np.concatenate([v, u]), 7)
    d = g.depths([0, 4, 6])
    assert [g.component_edges(d[:, j]) for j in range(3)] == [3, 2, 0]


def test_control_stops_one_level_short():
    path = np.arange(9)
    g = reference.Graph(np.concatenate([path, path + 1]),
                        np.concatenate([path + 1, path]), 10)
    full = g.depths([0, 5])
    ctl = control.truncated_depths(g, [0, 5])
    diff = full != ctl
    assert diff[:, 0].sum() == 1 and full[9, 0] == 9
    assert ctl[9, 0] == reference.UNREACHED
    assert diff[:, 1].sum() == 1 and ctl[0, 1] == reference.UNREACHED
