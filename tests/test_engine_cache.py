"""Multi-tenant serving subsystem: ``plan_key`` canonicalization,
``EngineCache`` (counters, byte-budget LRU eviction, pinning, thread-safe
get-or-compile), ``GraphCatalog`` and the rewritten multi-graph
``BFSService`` — parity against dedicated per-graph engines over mixed
1-D / 2-D lanes, and the compile-exactly-once acceptance criterion."""

import threading

import numpy as np
import pytest

import jax

from repro.core import BFSOptions, plan
from repro.core.ref import bfs_reference
from repro.graphs import generate, shard_graph, to_2d
from repro.serve.bfs_service import BFSService, TraversalRequest
from repro.serve.engine_cache import (EngineCache, GraphCatalog,
                                      default_engine_cache,
                                      use_default_cache)

FAMILIES = (("erdos_renyi", dict(avg_degree=6)), ("star", {}), ("chain", {}),
            ("rmat", dict(edge_factor=4)))


def _graph(kind="erdos_renyi", n=200, seed=3, p=1, **kw):
    src, dst = generate(kind, n, seed=seed, **kw)
    return src, dst, shard_graph(src, dst, n, p)


# ---------------------------------------------------------------------------
# plan_key: canonical fingerprint
# ---------------------------------------------------------------------------

def test_plan_key_content_identity_and_distinctions():
    src, dst, g = _graph()
    opts = BFSOptions(mode="dense")
    base = plan(g, opts, num_sources=2).plan_key()

    # a separately built but block-identical graph keys the same
    g_twin = shard_graph(src, dst, 200, 1)
    assert plan(g_twin, opts, num_sources=2).plan_key() == base

    # every compile-relevant knob lands in the key
    assert plan(g, opts, num_sources=3).plan_key() != base
    assert plan(g, BFSOptions(mode="auto"), num_sources=2).plan_key() != base
    assert plan(g, BFSOptions(mode="dense", queue_cap=2048),
                num_sources=2).plan_key() != base
    assert plan(g, BFSOptions(mode="dense", max_levels=7),
                num_sources=2).plan_key() != base
    assert plan(g, opts, num_sources=2, partition="2d").plan_key() != base

    # different content -> different key
    _, _, g_other = _graph(seed=9)
    assert plan(g_other, opts, num_sources=2).plan_key() != base

    # "auto" strategies key as what they resolved to, so an explicit name
    # and the auto-pick that chose it share an engine
    resolved = plan(g, BFSOptions(mode="dense", dense_exchange="auto"),
                    num_sources=2)
    explicit = plan(g, BFSOptions(mode="dense",
                                  dense_exchange=resolved.dense_strategy.name),
                    num_sources=2)
    assert resolved.plan_key() == explicit.plan_key()


def test_plan_key_2d_same_from_either_entry_path():
    _, _, g = _graph(n=120)
    via_flag = plan(g, BFSOptions(mode="dense"), partition="2d")
    via_container = plan(to_2d(g, 1, 1), BFSOptions(mode="dense"))
    assert via_flag.plan_key() == via_container.plan_key()
    # and the conversion cache hands out one object per grid
    assert to_2d(g, 1, 1) is to_2d(g, 1, 1)


def test_estimated_device_bytes_tracks_static_shapes():
    _, _, g = _graph()
    p1 = plan(g, BFSOptions(mode="dense"), num_sources=1)
    p4 = plan(g, BFSOptions(mode="dense"), num_sources=4)
    assert p1.estimated_device_bytes() > 0
    # more source columns -> strictly more working-buffer bytes
    assert p4.estimated_device_bytes() > p1.estimated_device_bytes()
    # the engine reports its plan's estimate (what the cache charges)
    eng = p1.compile()
    assert eng.estimated_device_bytes() == p1.estimated_device_bytes()
    # a 2-D auto plan prices its lazily built bottom-up blocks
    p2d = plan(g, BFSOptions(mode="dense"), partition="2d")
    p2a = plan(g, BFSOptions(mode="auto"), partition="2d")
    assert p2a.estimated_device_bytes() > p2d.estimated_device_bytes()


@pytest.mark.parametrize("mode,s,partition,csr", [
    ("auto", 1, "1d", True),
    ("queue", 1, "1d", True),
    ("auto", 8, "1d", False),       # S > 1 never takes a queue level
    ("dense", 1, "1d", False),
    ("auto", 1, "2d", False),       # the 2-D loop has its own queue path
])
def test_csr_view_uploaded_only_where_queue_levels_run(mode, s, partition,
                                                       csr):
    _, _, g = _graph(n=100)
    eng = plan(g, BFSOptions(mode=mode), num_sources=s,
               partition=partition).compile()
    groups = {key[2] for key in g.__dict__["_device_blocks"].map.keys()}
    assert ("csr" in groups) == csr
    # every uploaded group feeds the loop: 4 edge arrays (2 in 2-D), the
    # 2-D auto bottom-up group's 3, the CSR view's 2
    n_edge = {"1d": 4, "2d": 2 + 3 * (mode == "auto")}[partition]
    assert len(eng._gbufs) == n_edge + 2 * csr


@pytest.mark.parametrize("mode,s", [("dense", 1), ("queue", 1), ("auto", 1),
                                    ("auto", 8)])
def test_estimated_device_bytes_bounds_live_buffers(mode, s):
    """The estimate charges every buffer the engine keeps on the device:
    the uploaded groups (the CSR view among them) and two generations of
    (n, S) dist and frontier working buffers."""
    _, _, g = _graph(kind="rmat", n=500, edge_factor=6)
    pl = plan(g, BFSOptions(mode=mode), num_sources=s)
    eng = pl.compile()
    res = eng.run(list(range(s)))
    live = (sum(a.nbytes for a in eng._gbufs) + eng._valid.nbytes
            + 2 * (res.dist.nbytes + res.dist.size))
    assert live <= pl.estimated_device_bytes()


def test_bottom_up_in_cap_is_exact_under_skew():
    """The budget must charge the bottom-up blocks at their *real* padded
    capacity: under degree skew (star hub) the in-edge blocks out-pad the
    forward blocks, so pricing them at e_cap would break the
    upper-bound contract ``EngineCache`` eviction relies on."""
    from repro.graphs import shard_graph_2d

    n = 6000
    src, dst = generate("star", n, seed=0)
    g2 = shard_graph_2d(src, dst, n, 2, 2)
    cap = g2.bottom_up_in_cap()            # computed without the blocks
    assert "_bottom_up_blocks" not in g2.__dict__
    assert cap > g2.e_cap                  # the skew case that undercounted
    assert cap == g2.in_e_cap              # matches the built blocks


# ---------------------------------------------------------------------------
# EngineCache: counters, LRU byte budget, pinning, thread safety
# ---------------------------------------------------------------------------

def test_cache_hit_miss_counters_and_dedup():
    _, _, g = _graph(n=100)
    cache = EngineCache()
    p_a = plan(g, BFSOptions(mode="dense", max_levels=3))
    e1 = cache.get_or_compile(p_a)
    e2 = cache.get_or_compile(plan(g, BFSOptions(mode="dense", max_levels=3)))
    assert e1 is e2
    st = cache.stats()
    assert (st["hits"], st["misses"], st["entries"]) == (1, 1, 1)
    assert st["compile_s_total"] > 0 and st["hit_rate"] == 0.5
    assert p_a in cache and e1 in cache       # plan- and engine-keyed lookup
    cache.get_or_compile(plan(g, BFSOptions(mode="dense", max_levels=4)))
    assert cache.stats()["misses"] == 2 and len(cache) == 2


def test_cache_byte_budget_evicts_lru_first():
    _, _, g = _graph(n=100)
    plans = [plan(g, BFSOptions(mode="dense", max_levels=3 + i))
             for i in range(3)]
    unit = plans[0].estimated_device_bytes()
    assert all(p.estimated_device_bytes() == unit for p in plans)
    cache = EngineCache(max_device_bytes=2 * unit)
    cache.get_or_compile(plans[0])
    cache.get_or_compile(plans[1])
    assert cache.stats()["evictions"] == 0
    cache.get_or_compile(plans[0])            # refresh: plans[1] is now LRU
    cache.get_or_compile(plans[2])            # over budget -> evict one
    st = cache.stats()
    assert st["evictions"] == 1 and st["entries"] == 2
    assert st["device_bytes"] <= 2 * unit
    assert plans[0] in cache and plans[2] in cache
    assert plans[1] not in cache              # LRU victim, not FIFO's [0]
    # an evicted plan recompiles on demand (miss, not error)
    cache.get_or_compile(plans[1])
    assert cache.stats()["misses"] == 4


def test_cache_pinned_engine_survives_eviction():
    _, _, g = _graph(n=100)
    plans = [plan(g, BFSOptions(mode="dense", max_levels=3 + i))
             for i in range(3)]
    unit = plans[0].estimated_device_bytes()
    cache = EngineCache(max_device_bytes=2 * unit)
    cache.get_or_compile(plans[0], pin=True)  # LRU but untouchable
    cache.get_or_compile(plans[1])
    cache.get_or_compile(plans[2])
    assert plans[0] in cache                  # pinned survived
    assert plans[1] not in cache              # the unpinned LRU went instead
    st = cache.stats()
    assert st["evictions"] == 1 and st["pinned"] == 1
    # pin() on a resident entry succeeds; on an evicted key it reports
    # failure instead of raising (the caller re-get_or_compiles)
    assert cache.pin(plans[2]) is True
    assert cache.pin(plans[1]) is False       # evicted above
    cache.unpin(plans[2])
    cache.unpin(plans[0])
    cache.get_or_compile(plan(g, BFSOptions(mode="dense", max_levels=9)))
    assert plans[0] not in cache              # unpinned -> evictable again


def test_cache_single_oversized_entry_is_kept():
    """An engine bigger than the whole budget still serves (the cache
    runs temporarily over rather than thrashing its own in-flight
    compile); the next insertion evicts it."""
    _, _, g = _graph(n=100)
    p_big = plan(g, BFSOptions(mode="dense", max_levels=3))
    cache = EngineCache(max_device_bytes=max(1,
                        p_big.estimated_device_bytes() // 2))
    eng = cache.get_or_compile(p_big)
    assert eng is not None and p_big in cache
    cache.get_or_compile(plan(g, BFSOptions(mode="dense", max_levels=4)))
    assert p_big not in cache


def test_device_blocks_dedup_across_engines_and_release_on_drop():
    """Engines of one graph share one upload per (mesh, axis, group); the
    graph-side map holds them weakly, so dropping every engine (e.g. a
    cache eviction) releases the device buffers instead of pinning them
    to the graph object forever."""
    import gc

    _, _, g = _graph(n=100)
    e1 = plan(g, BFSOptions(mode="dense", max_levels=3)).compile()
    e2 = plan(g, BFSOptions(mode="dense", max_levels=5)).compile()
    assert e1._gbufs[0] is e2._gbufs[0]       # shared edge-block upload
    assert e1._valid is e2._valid             # shared validity mask
    dev_map = g.__dict__["_device_blocks"]
    assert len(dev_map) == 2                  # edges + valid groups
    del e1, e2
    gc.collect()
    assert len(dev_map) == 0                  # weak map released the bufs


def test_cache_get_or_compile_coalesces_across_threads():
    _, _, g = _graph(n=150)
    cache = EngineCache()
    results, errors = [], []

    def worker():
        try:
            # each thread builds its own plan object; keys coincide
            results.append(cache.get_or_compile(
                plan(g, BFSOptions(mode="dense", max_levels=4))))
        except Exception as exc:  # pragma: no cover - surfaced by assert
            errors.append(exc)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 6
    assert all(r is results[0] for r in results)  # one engine object
    st = cache.stats()
    assert st["misses"] == 1 and st["hits"] == 5  # one compile paid


def test_cache_counters_consistent_under_threaded_ladder_load():
    """N threads hammering ``get_or_compile`` across a batch-size bucket
    ladder: the hit/miss/compile-seconds counters must balance exactly —
    misses == distinct rungs, hits + misses == total calls — i.e. no
    lost updates under contention (the serving front-end reads these
    counters live while handler threads admit)."""
    from repro.core.engine import plan_ladder

    _, _, g = _graph(n=140)
    cache = EngineCache()
    ladder = (1, 2, 4)
    n_threads, per_thread = 8, 9
    engines, errors = {s: [] for s in ladder}, []

    def worker(tid):
        try:
            for k in range(per_thread):
                # deterministic rung walk offset per thread: every rung
                # sees first-touch races from several threads
                s = ladder[(tid + k) % len(ladder)]
                eng = cache.get_or_compile(
                    plan(g, BFSOptions(mode="dense"), num_sources=s))
                engines[s].append(eng)
        except Exception as exc:  # pragma: no cover - surfaced by assert
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = cache.stats()
    total = n_threads * per_thread
    assert st["misses"] == len(ladder)            # one compile per rung
    assert st["hits"] + st["misses"] == total     # nothing lost
    assert st["hits"] == total - len(ladder)
    assert st["entries"] == len(ladder)
    assert st["compile_s_total"] > 0
    assert st["hit_rate"] == pytest.approx(st["hits"] / total)
    for s in ladder:                              # one object per rung
        assert engines[s] and all(e is engines[s][0] for e in engines[s])
    # the ladder helper keys identically to the per-rung plans above
    for s, p in plan_ladder(g, BFSOptions(mode="dense"),
                            ladder=ladder).items():
        assert cache.get(p) is engines[s][0]


def test_default_cache_env_and_swap():
    cache = EngineCache(max_entries=2)
    with use_default_cache(cache):
        assert default_engine_cache() is cache
    assert default_engine_cache() is not cache


def test_cache_rejects_bad_bounds():
    with pytest.raises(ValueError, match="max_device_bytes"):
        EngineCache(max_device_bytes=0)
    with pytest.raises(ValueError, match="max_entries"):
        EngineCache(max_entries=-1)


# ---------------------------------------------------------------------------
# GraphCatalog
# ---------------------------------------------------------------------------

def test_graph_catalog_register_lookup_and_2d_reuse():
    _, _, g = _graph(n=90)
    cat = GraphCatalog()
    cat.register("er", g)
    assert "er" in cat and cat.get("er") is g
    assert cat.names() == ["er"] and len(cat) == 1
    # same-object re-registration is a no-op; replacement is an error
    cat.register("er", g)
    _, _, g2 = _graph(n=90, seed=11)
    with pytest.raises(ValueError, match="already registered"):
        cat.register("er", g2)
    with pytest.raises(KeyError, match="not registered"):
        cat.get("missing")
    with pytest.raises(ValueError, match="non-empty"):
        cat.register("", g2)
    # the catalog's 2-D view is the same cached object plan() converts to
    assert cat.get_2d("er", 1, 1) is to_2d(g, 1, 1)
    # a registered 2-D container serves only its own grid
    cat.register("er2d", to_2d(g, 1, 1))
    assert cat.get_2d("er2d", 1, 1) is to_2d(g, 1, 1)
    with pytest.raises(ValueError, match="grid"):
        cat.get_2d("er2d", 2, 2)
    cat.unregister("er")
    assert "er" not in cat


# ---------------------------------------------------------------------------
# multi-graph BFSService: routing, parity, compile-once, eviction
# ---------------------------------------------------------------------------

def _submit_all(svc, requests):
    for r in requests:
        svc.submit(r)
    return svc.run_until_drained()


def test_multi_graph_service_parity_mixed_partitions():
    """One service, four graph families, mixed 1-D and 2-D lanes: every
    result bitwise-equal to a dedicated per-graph engine and the numpy
    reference (the acceptance criterion's parity clause)."""
    n = 160
    cache = EngineCache()
    svc = BFSService(opts=BFSOptions(mode="dense"), batch_slots=2,
                     cache=cache)
    data = {}
    for i, (kind, kw) in enumerate(FAMILIES):
        src, dst, g = _graph(kind, n=n, seed=5 + i, **kw)
        data[kind] = (src, dst, g)
        # alternate partition schemes across lanes
        svc.add_graph(kind, g, partition="2d" if i % 2 else "1d",
                      mesh=None)
    assert svc.graph_names() == [k for k, _ in FAMILIES]

    sources = {kind: [0, (7 * (i + 2)) % n, n - 1 - i]
               for i, kind in enumerate(data)}
    reqs = [TraversalRequest(rid=i * 10 + j, source=s, graph=kind)
            for i, kind in enumerate(data)
            for j, s in enumerate(sources[kind])]
    done = _submit_all(svc, reqs)
    assert len(done) == len(reqs) and svc.drained()

    for kind, (src, dst, g) in data.items():
        want = bfs_reference(src, dst, n, sources[kind])
        # dedicated engine, compiled outside the cache, same scheme
        dedicated = plan(g, BFSOptions(mode="dense"),
                         num_sources=len(sources[kind]),
                         partition=svc.lane(kind).plan.partition
                         ).compile().run(sources[kind]).dist_host
        np.testing.assert_array_equal(dedicated, want)
        for j, r in enumerate([r for r in reqs if r.graph == kind]):
            assert r.done
            np.testing.assert_array_equal(r.dist, want[:, j])
            np.testing.assert_array_equal(r.dist, dedicated[:, j])


def test_multi_graph_service_compiles_each_plan_once_under_budget():
    """Acceptance: >= 3 graphs through one service, budget large enough
    to hold all engines -> exactly one compile per (graph, plan), pinned
    by cache counters AND engine trace counts, across repeated rounds."""
    n = 140
    graphs = {}
    for i, (kind, kw) in enumerate(FAMILIES[:3]):
        _, _, g = _graph(kind, n=n, seed=2 + i, **kw)
        graphs[kind] = g
    cache = EngineCache()      # unbounded: every engine stays resident
    svc = BFSService(graphs, opts=BFSOptions(mode="dense"), batch_slots=2,
                     cache=cache)
    for rnd in range(3):       # several rounds of traffic per tenant
        reqs = [TraversalRequest(rid=rnd * 100 + i, source=rnd * 3 + i,
                                 graph=kind)
                for i, kind in enumerate(graphs)]
        done = _submit_all(svc, reqs)
        assert len(done) == len(reqs)
    st = cache.stats()
    assert st["misses"] == len(graphs)         # one compile per plan
    assert st["evictions"] == 0
    assert st["hits"] >= 2 * len(graphs)       # warm rounds all hit
    for kind in graphs:
        eng = cache.get(svc.lane(kind).plan)
        assert eng is not None
        assert eng.trace_count == eng.compile_traces   # never retraced


def test_multi_graph_service_recovers_from_budget_eviction():
    """A budget that cannot hold every tenant forces LRU eviction; lanes
    whose engine was evicted recompile transparently on their next step
    and results stay exact."""
    n = 150
    cache = None
    data, svc = {}, None
    for i, (kind, kw) in enumerate(FAMILIES[:3]):
        src, dst, g = _graph(kind, n=n, seed=4 + i, **kw)
        data[kind] = (src, dst, g)
        if svc is None:
            unit = plan(g, BFSOptions(mode="dense"),
                        num_sources=2).estimated_device_bytes()
            # room for ~1.5 engines: round-robin over 3 lanes must evict
            cache = EngineCache(max_device_bytes=int(1.5 * unit))
            svc = BFSService(opts=BFSOptions(mode="dense"), batch_slots=2,
                             cache=cache)
        svc.add_graph(kind, g)
    for rnd in range(2):
        reqs = [TraversalRequest(rid=rnd * 10 + i, source=rnd + i,
                                 graph=kind)
                for i, kind in enumerate(data)]
        for r in _submit_all(svc, reqs):
            src, dst, _ = data[r.graph]
            want = bfs_reference(src, dst, n, [r.source])[:, 0]
            np.testing.assert_array_equal(r.dist, want)
    st = cache.stats()
    assert st["evictions"] >= 1                # the budget bound
    assert st["misses"] > len(data)            # evicted lanes recompiled
    assert st["device_bytes"] <= cache.max_device_bytes


def test_service_routes_by_name_and_validates():
    n = 120
    src, dst, g = _graph(n=n)
    src2, dst2, g2 = _graph("chain", n=60)
    svc = BFSService({"er": g, "chain": g2}, opts=BFSOptions(mode="dense"),
                     batch_slots=2, cache=EngineCache())
    # multi-lane service refuses unrouted requests...
    with pytest.raises(ValueError, match="name their graph"):
        svc.submit(TraversalRequest(rid=0, source=0))
    with pytest.raises(KeyError, match="no serving lane"):
        svc.submit(TraversalRequest(rid=0, source=0, graph="nope"))
    # ...and per-lane source validation uses that lane's vertex range
    with pytest.raises(ValueError, match="outside"):
        svc.submit(TraversalRequest(rid=0, source=100, graph="chain"))
    svc.submit(TraversalRequest(rid=0, source=100, graph="er"))  # in range
    done = svc.run_until_drained()
    assert [r.rid for r in done] == [0]
    np.testing.assert_array_equal(
        done[0].dist, bfs_reference(src, dst, n, [100])[:, 0])
    # single-lane conveniences stay off in multi-lane mode
    with pytest.raises(ValueError, match="lanes"):
        _ = svc.engine
    # duplicate lanes and queue mode are rejected at registration
    with pytest.raises(ValueError, match="already has a serving lane"):
        svc.add_graph("er", g)
    with pytest.raises(ValueError, match="single-source"):
        svc.add_graph("q", g2, opts=BFSOptions(mode="queue"))


def test_services_share_engines_through_one_cache():
    """Two services (and the lifecycle API) serving the same graph and
    options share one compiled engine via the cache."""
    _, _, g = _graph(n=130)
    cache = EngineCache()
    svc_a = BFSService(g, opts=BFSOptions(mode="dense"), batch_slots=2,
                       cache=cache)
    svc_b = BFSService(g, opts=BFSOptions(mode="dense"), batch_slots=2,
                       cache=cache)
    svc_a.submit(TraversalRequest(rid=0, source=0))
    svc_b.submit(TraversalRequest(rid=1, source=1))
    svc_a.run_until_drained()
    svc_b.run_until_drained()
    st = cache.stats()
    assert st["misses"] == 1 and st["entries"] == 1
    assert svc_a.engine is svc_b.engine


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 devices (CI --devices 4 jobs)")
def test_multi_graph_service_parity_on_2x2_grid():
    """Mixed 1-D (p=4) and 2-D (2x2 grid) lanes in one service on real
    multi-device meshes, bitwise against dedicated engines."""
    from jax.sharding import Mesh
    from repro.launch.mesh import make_grid_mesh

    n, p = 160, 4
    mesh1 = Mesh(np.asarray(jax.devices()[:p]).reshape(p), ("p",))
    cache = EngineCache()
    svc = BFSService(opts=BFSOptions(mode="dense"), batch_slots=2,
                     mesh=mesh1, axis="p", cache=cache)
    data = {}
    for i, (kind, kw) in enumerate(FAMILIES):
        src, dst, g = _graph(kind, n=n, seed=6 + i, p=p, **kw)
        data[kind] = (src, dst, g)
        if i % 2:
            svc.add_graph(kind, g, mesh=make_grid_mesh(2, 2),
                          partition="2d")
        else:
            svc.add_graph(kind, g)
    reqs = [TraversalRequest(rid=i * 10 + j, source=(11 * j + i) % n,
                             graph=kind)
            for i, kind in enumerate(data) for j in range(3)]
    done = _submit_all(svc, reqs)
    assert len(done) == len(reqs)
    assert cache.stats()["misses"] == len(data)
    for r in done:
        src, dst, _ = data[r.graph]
        np.testing.assert_array_equal(
            r.dist, bfs_reference(src, dst, n, [r.source])[:, 0])
    for kind in data:
        eng = cache.get(svc.lane(kind).plan)
        assert eng.trace_count == eng.compile_traces
