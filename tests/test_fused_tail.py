"""Fused fold/owner-update tail (ISSUE 9): ``kernels/fold_update``
bit-parity against an independent numpy reference (jnp path and Pallas
interpret path), plan-time resolution of ``use_fused_tail`` (auto / True
/ False, wire preconditions, plan_key and byte-model growth, roofline
rows), engine parity fused vs unfused across graph families x
partitions x modes, and the ``analysis.trace_model`` parser on the
checked-in synthetic profiler trace."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import trace_model
from repro.analysis.hlo_audit import variant_name
from repro.core import BFSOptions, plan
from repro.core.frontier import INF, pack_bits
from repro.core.ref import bfs_reference
from repro.graphs import generate, shard_graph
from repro.kernels.fold_update import fold_update

_DATA = os.path.join(os.path.dirname(__file__), "data")
_FIXTURE = os.path.join(_DATA, "synthetic.trace.json.gz")


# ---------------------------------------------------------------------------
# fold_update kernel: jnp and Pallas-interpret paths vs numpy reference
# ---------------------------------------------------------------------------

def _ref_fold_update(words, dist, level):
    """Independent numpy model of the fused tail (no shared code)."""
    w, s = words.shape
    m = dist.shape[0]
    bits = np.zeros((w * 32, s), np.uint8)
    for i in range(w * 32):
        bits[i] = (words[i // 32] >> np.uint32(i % 32)) & 1
    new = (bits[:m] > 0) & (dist == int(INF))
    dist2 = np.where(new, np.int32(level), dist)
    nw = np.zeros((w, s), np.uint32)
    for i in range(m):
        nw[i // 32] |= new[i].astype(np.uint32) << np.uint32(i % 32)
    return dist2, new.astype(np.uint8), nw


@pytest.mark.parametrize("m,s", [
    (32, 1),     # exactly one word
    (96, 2),     # word-aligned, multi-source
    (37, 3),     # ragged: 27 pad bits in the last word
    (1, 1),      # single vertex
    (64, 4),
])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_fold_update_matches_reference(m, s, use_pallas):
    rng = np.random.default_rng(m * 10 + s)
    mask = (rng.random((m, s)) < 0.5).astype(np.uint8)
    words = np.asarray(pack_bits(jnp.asarray(mask)))
    dist = np.where(rng.random((m, s)) < 0.5, np.int32(INF),
                    rng.integers(0, 5, (m, s)).astype(np.int32))
    want = _ref_fold_update(words, dist, 7)
    got = fold_update(jnp.asarray(words), jnp.asarray(dist), 7,
                      use_pallas=use_pallas, interpret=use_pallas)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_fold_update_already_discovered_rows_untouched():
    """A set candidate bit on a finite-depth row must not rewrite it."""
    dist = np.array([[3], [int(INF)], [0]], np.int32)
    words = np.asarray(pack_bits(jnp.asarray(
        np.ones((3, 1), np.uint8))))          # every vertex a candidate
    d2, new, nw = fold_update(jnp.asarray(words), jnp.asarray(dist), 9)
    np.testing.assert_array_equal(np.asarray(d2),
                                  [[3], [9], [0]])
    np.testing.assert_array_equal(np.asarray(new), [[0], [1], [0]])
    # only the newly discovered vertex carries into the next generation
    assert int(np.asarray(nw)[0, 0]) == 0b010


def test_fold_update_rejects_mismatched_shapes():
    words = jnp.zeros((2, 1), jnp.uint32)
    with pytest.raises(ValueError, match="packed_words"):
        fold_update(words, jnp.zeros((100, 1), jnp.int32), 1)
    with pytest.raises(ValueError, match="batch"):
        fold_update(words, jnp.zeros((64, 2), jnp.int32), 1)


# ---------------------------------------------------------------------------
# plan-time resolution of use_fused_tail
# ---------------------------------------------------------------------------

def _er_graph(n=400, seed=1):
    src, dst = generate("erdos_renyi", n, seed=seed, avg_degree=5.0)
    return src, dst, shard_graph(src, dst, n, p=1)


def test_fused_tail_resolution_and_metadata():
    _, _, g = _er_graph()
    # explicit True on a packed dense wire resolves on, in both schemes
    for partition in ("1d", "2d"):
        pl = plan(g, BFSOptions(mode="dense", wire_format="packed",
                                use_fused_tail=True), partition=partition)
        assert pl.use_fused_tail
        meta = pl.describe()
        assert meta["use_fused_tail"] is True
        assert meta["roofline"]["dense"]["model"] == "overlap(max)"
        assert variant_name(pl).endswith(":fused")
    # ... and True on a bytes wire is a loud contract violation
    with pytest.raises(ValueError, match="packed"):
        plan(g, BFSOptions(mode="dense", wire_format="bytes",
                           use_fused_tail=True))
    # auto: on for dense/auto modes over a packed wire ...
    assert plan(g, BFSOptions(mode="dense", wire_format="packed",
                              use_fused_tail="auto")).use_fused_tail
    assert plan(g, BFSOptions(mode="auto", wire_format="packed",
                              use_fused_tail="auto")).use_fused_tail
    # ... off for queue mode (no dense tail to fuse) and off when the
    # wire resolves to bytes (auto wire at p=1 keeps bytes)
    assert not plan(g, BFSOptions(mode="queue", wire_format="packed",
                                  use_fused_tail="auto")).use_fused_tail
    pl = plan(g, BFSOptions(mode="dense", wire_format="auto",
                            use_fused_tail="auto"))
    assert not pl.use_fused_tail
    assert not variant_name(pl).endswith(":fused")
    with pytest.raises(ValueError, match="use_fused_tail"):
        BFSOptions(use_fused_tail="maybe").validate()


def test_fused_tail_plan_key_and_device_bytes():
    _, _, g = _er_graph()
    for partition in ("1d", "2d"):
        keys, bytes_ = {}, {}
        for fused in (False, True):
            pl = plan(g, BFSOptions(mode="dense", wire_format="packed",
                                    use_fused_tail=fused),
                      partition=partition)
            keys[fused] = pl.plan_key()
            bytes_[fused] = pl.estimated_device_bytes()
        # distinct compiles in the EngineCache, and the fused plan is
        # charged for its double-buffered generation + kernel scratch
        assert keys[False] != keys[True], partition
        assert bytes_[True] > bytes_[False], partition


def test_fused_roofline_prices_the_eliminated_passes():
    """The fused dense row must model strictly less HBM traffic and a
    strictly smaller per-level step than its unfused twin (that modeled
    delta is what BENCH_latency.json asserts at >= 1.15x)."""
    _, _, g = _er_graph()
    for partition in ("1d", "2d"):
        rows = {}
        for fused in (False, True):
            meta = plan(g, BFSOptions(mode="dense", wire_format="packed",
                                      use_fused_tail=fused),
                        partition=partition).describe()
            rows[fused] = meta["roofline"]["dense"]
        assert rows[True]["hbm_bytes"] < rows[False]["hbm_bytes"]
        assert rows[True]["t_level_s"] < rows[False]["t_level_s"]
        assert rows[False]["model"] == "serial(sum)"
        assert rows[True]["model"] == "overlap(max)"
        # the wire payload is identical — fusion changes compute, not
        # what the collectives ship
        assert rows[True]["wire_bytes"] == rows[False]["wire_bytes"]


# ---------------------------------------------------------------------------
# engine parity: fused vs unfused, bitwise, across families x modes
# ---------------------------------------------------------------------------

_FAMILIES = [
    ("erdos_renyi", 400, {"avg_degree": 5.0}),
    ("star", 300, {}),
    ("chain", 64, {}),                 # one level per vertex: deep loop
    ("rmat", 400, {"edge_factor": 5}),
]


@pytest.mark.parametrize("kind,n,kw", _FAMILIES,
                         ids=[f[0] for f in _FAMILIES])
@pytest.mark.parametrize("partition", ["1d", "2d"])
@pytest.mark.parametrize("mode", ["dense", "auto"])
def test_engine_parity_fused_vs_unfused(kind, n, kw, partition, mode):
    src, dst = generate(kind, n, seed=3, **kw)
    g = shard_graph(src, dst, n, p=1)
    want = bfs_reference(src, dst, n, [0])
    dists = {}
    for fused in (False, True):
        eng = plan(g, BFSOptions(mode=mode, wire_format="packed",
                                 use_fused_tail=fused, queue_cap=2048),
                   num_sources=1, partition=partition).compile()
        res = eng.run([0])
        dists[fused] = res.dist_host
        np.testing.assert_array_equal(dists[fused], want)
        assert eng.trace_count == eng.compile_traces
    np.testing.assert_array_equal(dists[False], dists[True])


def test_engine_parity_fused_multi_source():
    src, dst = generate("erdos_renyi", 500, seed=9, avg_degree=6.0)
    g = shard_graph(src, dst, 500, p=1)
    want = bfs_reference(src, dst, 500, [0, 13, 99])
    eng = plan(g, BFSOptions(mode="dense", wire_format="packed",
                             use_fused_tail=True),
               num_sources=3, partition="2d").compile()
    np.testing.assert_array_equal(eng.run([0, 13, 99]).dist_host, want)


# ---------------------------------------------------------------------------
# trace_model on the checked-in synthetic profiler trace
# ---------------------------------------------------------------------------

def test_classify_op_names():
    assert trace_model.classify("all-to-all.1") == "collective"
    assert trace_model.classify("dynamic-slice_concatenate_fusion") \
        == "expand"
    assert trace_model.classify("bitcast_shift-left_fusion") == "fold"
    assert trace_model.classify("select_dynamic-update-slice_fusion") \
        == "owner_update"
    assert trace_model.classify("copy.3") == "other"


def test_synthetic_trace_loads_and_filters():
    ops = trace_model.load_events(_FIXTURE)
    # 11 real XLA op events survive; the while container, the $-prefixed
    # python frame, the hlo_op-less runtime event and the metadata event
    # are all dropped
    assert len(ops) == 11
    names = {op.hlo_op for op in ops}
    assert "while.12" not in names
    assert "gather.99" not in names
    t = trace_model.phase_timings(ops)
    assert t.n_ops == 11
    assert t.total_s["collective"] == pytest.approx(30e-6)
    assert t.total_s["expand"] == pytest.approx(8e-6)     # gather + iota
    assert t.total_s["fold"] == pytest.approx(9e-6)       # or + bitcast
    assert t.total_s["owner_update"] == pytest.approx(13e-6)
    assert t.total_s["other"] == pytest.approx(7e-6)      # copy
    assert t.span_s == pytest.approx(220e-6)


def test_synthetic_trace_level_segmentation():
    ops = trace_model.load_events(_FIXTURE)
    # with the level count known: cut at the n-1 largest collective gaps
    segs = trace_model.split_levels(ops, n_levels=3)
    assert [len(s) for s in segs] == [4, 4, 3]
    t = trace_model.parse_trace(_FIXTURE, n_levels=3)
    assert len(t.levels) == 3
    assert t.levels[0]["collective"] == pytest.approx(10e-6)
    assert t.levels[1]["collective"] == pytest.approx(12e-6)
    assert t.levels[2]["collective"] == pytest.approx(8e-6)
    # without it, evenly spaced collectives degrade to one segment (the
    # median-gap heuristic needs outlier gaps to cut at)
    assert len(trace_model.split_levels(ops)) == 1


def test_trace_file_resolution_and_cli(tmp_path, capsys):
    # a directory containing *.trace.json.gz resolves to the newest one
    assert trace_model.find_trace_file(_DATA) == _FIXTURE
    with pytest.raises(FileNotFoundError, match="trace"):
        trace_model.find_trace_file(str(tmp_path))
    assert trace_model.main([_FIXTURE, "--levels", "3", "--json"]) == 0
    out = capsys.readouterr().out
    assert '"total_s"' in out and '"levels"' in out
