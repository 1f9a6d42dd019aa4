"""The metric readers' arithmetic and the open-loop schedule."""

import math

import numpy as np
import pytest

from harness.common import nearest_rank
from harness.drive_http import make_schedule
from harness.spec import Spec


@pytest.fixture(scope="module")
def read():
    spec = Spec()
    return lambda name, rec: spec.metric_reader(name)(rec)


def _engine_record():
    return {"kind": "engine", "window_s": 4.0, "setup_s": 12.5,
            "trace": None, "traversals": [
                {"edges": [15_000_000], "wall_s": 1.5, "levels": 6},
                {"edges": [14_000_000, 1_000_000], "wall_s": 2.5,
                 "levels": 10}]}


def test_teps_is_component_edges_over_the_window(read):
    rec = _engine_record()
    assert read("teps", rec) == pytest.approx(30_000_000 / 4.0)
    assert read("setup_s", rec) == 12.5
    assert read("ms_per_level", rec) == pytest.approx(1e3 * 4.0 / 16)
    assert read("latency_p50_ms", rec) is None


def test_teps_back_to_back_equals_harmonic_mean_of_key_teps(read):
    """Graph500 reports the harmonic mean of per-key TEPS; with keys of
    one component run back to back it equals total edges over total
    time."""
    edges, times = 1e7, [0.5, 1.0, 2.0]
    rec = {"kind": "engine", "window_s": sum(times),
           "traversals": [{"edges": [edges], "wall_s": t, "levels": 1}
                          for t in times]}
    harmonic = len(times) / sum(t / edges for t in times)
    assert read("teps", rec) == pytest.approx(harmonic)


def test_latency_percentiles_count_failures_as_missing(read):
    lat = [0.1 * k for k in range(1, 10)] + [math.inf]
    rec = {"kind": "http", "requests": [
        {"latency_s": v, "timing_ms": None} for v in lat]}
    assert read("latency_p50_ms", rec) == pytest.approx(500.0)
    assert nearest_rank(lat, 0.9) == pytest.approx(0.9)
    assert nearest_rank(lat, 1.0) == math.inf


def test_served_layer_means(read):
    reqs = [{"latency_s": 0.5, "timing_ms": {"queue_wait": 100.0,
                                             "device": 300.0}},
            {"latency_s": 0.3, "timing_ms": {"queue_wait": 0.0,
                                             "device": 250.0}},
            {"latency_s": math.inf, "timing_ms": None}]
    rec = {"kind": "http", "requests": reqs, "trace": None}
    assert read("device_ms.served", rec) == pytest.approx(275.0)
    assert read("queue_wait_ms", rec) == pytest.approx(50.0)
    assert read("frontend_ms", rec) == pytest.approx((100 + 50) / 2)
    assert read("device_idle.served", rec) is None


class _Trace:
    def kernel(self, name):
        return (4, 2e-5) if name == "fold_update" else (0, 0.0)


def test_fold_update_roofline_from_shape_bytes(read):
    from harness import kernel_bytes

    per_call = kernel_bytes.fold_update(262_144, 1)
    # words 8,192 x 4 B in and out, depths 1 MiB in and out, mask 256 KiB
    assert per_call == 2 * 8192 * 4 + 2 * 262_144 * 4 + 262_144
    rec = {"trace": _Trace(), "kernel_bytes": {"fold_update": per_call},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100 * 4 * per_call / 819e9 / 2e-5
    assert read("fold_update_roofline", rec) == pytest.approx(want)
    rec["kernel_bytes"] = {"fold_update": None}
    assert read("fold_update_roofline", rec) is None


def test_schedule_same_sizes_and_count_for_every_seed():
    traffic = {"rate_per_s": 2.0, "zipf_s": 1.0,
               "size_shares": [[1, 0.75], [[2, 8], 0.25]]}
    degree = np.ones(5000, dtype=np.int64)
    degree[::7] = 0
    a = make_schedule(traffic, degree, 1, 30.0)
    b = make_schedule(traffic, degree, 2**40, 30.0)
    assert len(a) == len(b) == 60
    sizes = sorted(len(r["sources"]) for r in a)
    assert sizes == sorted(len(r["sources"]) for r in b)
    assert sizes.count(1) == 45 and set(sizes[45:]) <= set(range(2, 9))
    for r in a:
        assert len(set(r["sources"])) == len(r["sources"])
        assert all(degree[s] > 0 for s in r["sources"])
        assert 0.0 <= r["at_s"] < 30.0
    assert [r["at_s"] for r in a] == sorted(r["at_s"] for r in a)
    gaps = lambda s: sorted(np.diff([r["at_s"] for r in s] + [30.0]))
    assert np.allclose(gaps(a), gaps(b))          # the same gaps, reordered
    assert a[0]["at_s"] == 0.0 and a != b
    # Zipf: the most popular source is drawn far more often than 1/n
    many = make_schedule(dict(traffic, rate_per_s=40.0), degree, 3, 30.0)
    picks = np.bincount([s for r in many for s in r["sources"]])
    assert picks.max() > 40 * picks.sum() / 5000



def test_inputs_seed_fixes_graph_and_keys(toy_spec):
    """A mix with ``inputs_seed`` draws its graph and keys from it, not
    from the run's seed; without it the run's seed draws them."""
    import jax

    from harness.drive_engine import EngineRunner

    cfg = toy_spec.config("graph500_s20")
    key1 = toy_spec.traffic("key1")
    batch8 = {k: v for k, v in toy_spec.traffic("batch8").items()
              if k != "inputs_seed"}

    def drawn(traffic, seed):
        d = EngineRunner(cfg, traffic, seed, 0.1, jax.devices()[:1],
                         toy_spec)
        assert d.seed == traffic.get("inputs_seed", seed)
        return d.seed

    assert drawn(key1, 1) == drawn(key1, 2) == key1["inputs_seed"]
    assert drawn(batch8, 1) != drawn(batch8, 2)


def test_search_keys_are_seeded_vertices_of_degree_one_or_more():
    """Keys come from the degrees alone, as the specification draws them:
    distinct, of degree >= 1, the same for one seed."""
    from harness.drive_engine import search_keys

    degree = np.zeros(1000, dtype=np.int64)
    degree[::3] = 2
    a = search_keys(degree, 64, 2**40 + 1)
    assert a == search_keys(degree, 64, 2**40 + 1)
    assert a != search_keys(degree, 64, 1)
    assert len(set(a)) == 64 and all(degree[k] > 0 for k in a)
