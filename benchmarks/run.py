"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and emits a ``BENCH_*.json``
(``--out``, default ``BENCH_results.json``) recording, for every
engine-measured workload, *compile* wall time and *per-run* execute time
separately — the amortization ledger of the plan→compile→run lifecycle
(one compile per (graph, options, mesh), then device-only traversals).

Paper tables reproduced:
  * fig3/fig4  — star-graph strong scaling (p = 8/16/32)
  * fig5/fig6  — Erdős-Rényi strong scaling (100k vertices, p = 1..64)
  * fig7/fig8  — small-world strong scaling (100k vertices, p = 1..64)
  * §5.1       — exchange-strategy communication volume (the two paper
                 optimizations), cross-checked against compiled HLO by
                 tests/helpers/exchange_bytes.py
  * §5.2       — owner-local update / collective-merge payload reduction
  * §Roofline  — per-(arch x shape x mesh) terms from the dry-run JSON

Runtime here is a single CPU; per-level compute is *measured* on the real
engine and communication seconds are *modeled* from the HLO-validated
per-chip byte model at v5e link bandwidth — the same separation of
computation vs communication cost the paper uses to explain its scaling
curves (§4.2).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax

from repro.core import BFSOptions, Partition1D, plan
from repro.core import exchange as ex
from repro.graphs import generate, shard_graph
from repro.launch.hlo_stats import ICI_BW
from repro.launch.mesh import default_grid

_ROWS = []
_ENGINE_TIMINGS = {}   # bench key -> {compile_s, per_run_s, ...}
_PARTITION_SWEEP = []  # 1-D vs 2-D scheme rows (modeled + measured bytes)
_SERVING = {}          # multi-graph serving ledger (cold/warm/hit rate)
_WIRE_FORMAT = []      # packed vs bytes wire rows (own BENCH_wire_format
                       # ledger; see --wire-out)
_SERVING_LATENCY = {}  # remote front-end ledger: bucket ladder latencies +
                       # overload 429s (own BENCH_serving_latency ledger;
                       # see --serving-out)
_SPARSE_WIRE = []      # compressed sparse-id wire + sieve rows (own
                       # BENCH_sparse_wire ledger; see --sparse-wire-out)
_LATENCY = {}          # fused-tail latency-hiding ledger: per-level step
                       # times fused vs unfused + trace-validated roofline
                       # (own BENCH_latency ledger; see --latency-out)


def row(name: str, us: float, derived: str = ""):
    _ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def _measure_bfs(kind, n, opts, sources=(0,), seed=0, reps=3, **gkw):
    """Compile one engine, then time device-only traversals.

    Returns (per_run_s, stats, n_edges); compile wall time is recorded
    in the JSON ledger under ``bfs/<kind>/n=<n>/...``.
    """
    src, dst = generate(kind, n, seed=seed, **gkw)
    g = shard_graph(src, dst, n, p=1)
    t0 = time.time()
    engine = plan(g, opts, num_sources=len(sources)).compile()
    compile_s = time.time() - t0
    res = engine.run(list(sources))  # warmup (first dispatch)
    t0 = time.time()
    for _ in range(reps):
        res = engine.run(list(sources))
    dt = (time.time() - t0) / reps
    stats = res.stats()
    key = (f"bfs/{kind}/n={n}/mode={opts.mode}/S={len(sources)}"
           f"/ex={opts.dense_exchange}/lu={int(opts.local_update)}")
    _ENGINE_TIMINGS[key] = {
        "compile_s": compile_s, "per_run_s": dt, "levels": stats.levels,
    }
    return dt, stats, src.shape[0]


def _scaling_table(tag, kind, n, ps, strategy, gkw, mode="dense"):
    """Paper-style strong scaling: measured compute (perfect E/p split of
    the single-shard measurement) + modeled per-level exchange time."""
    opts = BFSOptions(mode=mode, dense_exchange=strategy, queue_cap=1 << 14)
    dt, stats, edges = _measure_bfs(kind, n, opts, **gkw)
    for p in ps:
        comp = dt / p
        if mode == "dense":
            per_level = ex.dense_level_bytes(strategy, n, p, 1, 1)
        else:
            per_level = ex.queue_level_bytes(strategy, p, 1 << 14)
        comm = stats.levels * per_level / ICI_BW
        total = comp + comm
        row(f"{tag}/p={p}", total * 1e6,
            f"levels={stats.levels};comp_us={comp*1e6:.1f};"
            f"comm_us={comm*1e6:.1f};strategy={strategy}")


def bench_fig3_star_scaling():
    """Paper fig. 3/4: star graph; measured at a reduced vertex count on
    the CPU runner (the 4M-vertex configuration is in BFS_WORKLOADS and is
    what examples/bfs_scaling.py sizes against)."""
    n = 200_000
    _scaling_table("fig3_star", "star", n, (8, 16, 32), "allgather_merge", {})
    _scaling_table("fig3_star_opt", "star", n, (8, 16, 32),
                   "alltoall_direct", {})


def bench_fig5_erdos_renyi_scaling():
    n = 100_000
    _scaling_table("fig5_erdos_renyi", "erdos_renyi", n,
                   (1, 2, 4, 8, 16, 32, 64), "allgather_merge",
                   {"avg_degree": 16.0})
    _scaling_table("fig5_erdos_renyi_opt", "erdos_renyi", n,
                   (1, 2, 4, 8, 16, 32, 64), "alltoall_direct",
                   {"avg_degree": 16.0})


def bench_fig7_small_world_scaling():
    n = 100_000
    _scaling_table("fig7_small_world", "small_world", n,
                   (1, 2, 4, 8, 16, 32, 64), "allgather_merge",
                   {"k": 16, "beta": 0.1})
    _scaling_table("fig7_small_world_opt", "small_world", n,
                   (1, 2, 4, 8, 16, 32, 64), "alltoall_direct",
                   {"k": 16, "beta": 0.1})


def bench_sec51_exchange_volume():
    """Paper §5.1: per-level exchange bytes, baseline vs both optimized
    paths (values cross-checked against compiled HLO by the test suite)."""
    n, cap = 1_000_000, 1 << 12
    for p in (8, 64, 256, 512):
        base = ex.dense_level_bytes("allgather_merge", n, p)
        direct = ex.dense_level_bytes("alltoall_direct", n, p)
        rs = ex.dense_level_bytes("reduce_scatter", n, p)
        row(f"sec51_dense_bytes/p={p}", 0.0,
            f"baseline={base:.0f};direct={direct:.0f};"
            f"reduce_scatter={rs:.0f};ratio={base/direct:.1f}")
        qb = ex.queue_level_bytes("allgather_merge", p, cap)
        qd = ex.queue_level_bytes("alltoall_direct", p, cap)
        row(f"sec51_queue_bytes/p={p}", 0.0,
            f"baseline={qb:.0f};direct={qd:.0f};ratio={qb/qd:.1f}")


def bench_sec52_local_update():
    """Paper §5.1-(1)/§5.2: owner-local update + dedupe shrink the queue
    payload; engine-measured wall time and modeled comm bytes."""
    n = 50_000
    for lu in (False, True):
        opts = BFSOptions(mode="queue", local_update=lu, dedupe=lu,
                          queue_cap=1 << 15)
        dt, stats, edges = _measure_bfs("erdos_renyi", n, opts,
                                        avg_degree=16.0)
        row(f"sec52_queue_local_update={int(lu)}", dt * 1e6,
            f"levels={stats.levels};comm_bytes={stats.comm_bytes:.0f}")


def bench_direction_optimizing():
    """Beyond-paper: auto (queue/dense/bottom-up) vs fixed modes."""
    n = 100_000
    for mode in ("dense", "queue", "auto"):
        opts = BFSOptions(mode=mode, queue_cap=1 << 15)
        dt, stats, edges = _measure_bfs("rmat", n, opts, edge_factor=16)
        row(f"direction_opt/{mode}", dt * 1e6,
            f"levels={stats.levels};modes={stats.mode_counts};"
            f"comm_bytes={stats.comm_bytes:.0f}")


def bench_engine_amortization():
    """The API-lifecycle result on the paper's erdos_renyi_100k workload:
    one-shot plan+compile+run per traversal (what the old ``bfs()``
    entrypoint cost) vs compile-once ``engine.run`` over fresh sources.
    The per-traversal time excluding compile is the serving-path number."""
    n = 100_000
    src, dst = generate("erdos_renyi", n, seed=0, avg_degree=16.0)
    g = shard_graph(src, dst, n, p=1)
    opts = BFSOptions(mode="dense")

    t0 = time.time()
    engine = plan(g, opts, num_sources=1).compile()
    compile_s = time.time() - t0
    t0 = time.time()
    engine.run([0])
    first_run_s = time.time() - t0

    reps = 5
    t0 = time.time()
    for s in range(1, reps + 1):       # fresh source per run: no retrace
        engine.run([s * 7])
    per_run_s = (time.time() - t0) / reps
    assert engine.trace_count == engine.compile_traces

    t0 = time.time()
    plan(g, opts, num_sources=1).compile().run([0])  # seed-style one-shot
    one_shot_s = time.time() - t0

    row("engine_amortized/erdos_renyi_100k", per_run_s * 1e6,
        f"compile_us={compile_s*1e6:.0f};first_run_us={first_run_s*1e6:.0f};"
        f"one_shot_us={one_shot_s*1e6:.0f};"
        f"speedup_vs_one_shot={one_shot_s/per_run_s:.1f}x")
    _ENGINE_TIMINGS["amortization/erdos_renyi_100k"] = {
        "compile_s": compile_s, "first_run_s": first_run_s,
        "per_run_s": per_run_s, "one_shot_s": one_shot_s,
        "speedup_vs_one_shot": one_shot_s / per_run_s,
    }


def bench_partition_1d_vs_2d():
    """1-D vertex blocks vs 2-D edge blocks on erdos_renyi_100k.

    For each shard count: per-level *modeled* exchange bytes of both
    schemes (1-D dense alltoall over p shards vs 2-D row-allgather +
    column-fold over an r x c grid — the r+c vs p communication argument),
    plus *measured* engine traversals for every grid the local device set
    can host (per-run wall time and the run's accumulated comm bytes).
    Everything lands in the BENCH_*.json ``partition_sweep`` ledger keyed
    by partition kind so 1-D and 2-D trajectories never collapse.
    """
    n, s = 100_000, 1
    graph_name = "erdos_renyi_100k"
    cap = 1024                        # sparse-level id-buffer capacity

    for p in (1, 4, 16, 64):
        r, c = default_grid(p)
        n_pad = Partition1D(n, p).n
        one_d = ex.dense_level_bytes("alltoall_direct", n_pad, p, s, 1)
        two_d = ex.grid_level_bytes("allgather", "alltoall_reduce",
                                    n_pad, r, c, s, 1)
        two_d_sparse = ex.grid_sparse_level_bytes(
            "allgather", "alltoall_direct", r, c, cap)
        _PARTITION_SWEEP.append({
            "graph": graph_name, "partition": "1d", "mode": "dense",
            "p": p, "r": 1, "c": p,
            "modeled_level_bytes": one_d,
            "phase_bytes": {"alltoall": one_d},
        })
        _PARTITION_SWEEP.append({
            "graph": graph_name, "partition": "2d", "mode": "dense",
            "p": p, "r": r, "c": c,
            "modeled_level_bytes": two_d,
            "phase_bytes": {
                "expand": ex.get_exchange(
                    "expand_row", "allgather").bytes_model(n_pad, r, c, s, 1),
                "fold": ex.get_exchange(
                    "fold_col", "alltoall_reduce").bytes_model(
                        n_pad, r, c, s, 1)},
        })
        # sparse (queue) 2-D levels: per-phase id buffers — the narrow
        # first/last levels of a traversal ride these instead of bitmaps
        _PARTITION_SWEEP.append({
            "graph": graph_name, "partition": "2d", "mode": "sparse",
            "p": p, "r": r, "c": c, "queue_cap": cap,
            "modeled_level_bytes": two_d_sparse,
            "phase_bytes": {
                "expand_sparse": ex.get_exchange(
                    "expand_row_sparse", "allgather").bytes_model(
                        r, c, cap, 4),
                "fold_sparse": ex.get_exchange(
                    "fold_col_sparse", "alltoall_direct").bytes_model(
                        r, c, cap, 4)},
        })
        ratio = one_d / two_d if two_d else float("inf")
        row(f"partition_bytes/p={p}", 0.0,
            f"1d={one_d:.0f};2d={two_d:.0f};2d_sparse={two_d_sparse:.0f};"
            f"grid={r}x{c};ratio={ratio:.2f}")

    # measured: every grid the local device set can host (p=1 always; the
    # CI 4-device runners also measure the real 2x2 collectives)
    src, dst = generate("erdos_renyi", n, seed=0, avg_degree=16.0)
    p_avail = jax.device_count()
    for p in {1, 4} & set(range(1, p_avail + 1)):
        import numpy as _np
        from jax.sharding import Mesh
        g = shard_graph(src, dst, n, p)
        r, c = default_grid(p)
        meshes = {
            "1d": (Mesh(_np.asarray(jax.devices()[:p]).reshape(p), ("p",)),
                   "p"),
            "2d": (Mesh(_np.asarray(jax.devices()[:p]).reshape(r, c),
                        ("rows", "cols")), None),
        }
        for kind, (mesh, axis) in meshes.items():
            t0 = time.time()
            eng = plan(g, BFSOptions(mode="dense"), mesh=mesh, axis=axis,
                       num_sources=s, partition=kind).compile()
            compile_s = time.time() - t0
            res = eng.run([0])             # warmup
            t0 = time.time()
            for i in range(3):
                res = eng.run([7 * i + 1])
            per_run = (time.time() - t0) / 3
            stats = res.stats()
            kr, kc = (r, c) if kind == "2d" else (1, p)
            _PARTITION_SWEEP.append({
                "graph": graph_name, "partition": kind, "p": p, "r": kr,
                "c": kc, "measured": True, "compile_s": compile_s,
                "per_run_s": per_run, "levels": stats.levels,
                "run_comm_bytes": stats.comm_bytes,
                "modeled_level_bytes": (stats.comm_bytes / stats.levels
                                        if stats.levels else 0.0),
            })
            row(f"partition_measured/{kind}/p={p}", per_run * 1e6,
                f"levels={stats.levels};comm_bytes={stats.comm_bytes:.0f};"
                f"compile_us={compile_s*1e6:.0f}")

    # direction-optimizing 2-D: measured per-level mode split on a
    # narrow-frontier graph (most levels ride the sparse phases) and on
    # the er workload (hybrid dense/bottom-up middle)
    for kind_name, gen_kw, n_small in (("chain", {}, 2_000),
                                       ("erdos_renyi",
                                        {"avg_degree": 16.0}, n)):
        gsrc, gdst = generate(kind_name, n_small, seed=0, **gen_kw)
        g = shard_graph(gsrc, gdst, n_small, 1)
        eng = plan(g, BFSOptions(mode="auto", queue_cap=1024),
                   num_sources=1, partition="2d").compile()
        res = eng.run([0])
        st = res.stats()
        _PARTITION_SWEEP.append({
            "graph": f"{kind_name}_{n_small}", "partition": "2d",
            "mode": "auto", "p": 1, "r": 1, "c": 1, "measured": True,
            "levels": st.levels, "mode_counts": st.mode_counts,
            "run_comm_bytes": st.comm_bytes,
        })
        row(f"partition_modes/2d_auto/{kind_name}", 0.0,
            f"levels={st.levels};modes={st.mode_counts};"
            f"comm_bytes={st.comm_bytes:.0f}")


def bench_wire_format_sweep():
    """Packed-bitset vs byte-mask dense wire format (the §5-adjacent
    "Compression and Sieve" optimization).

    Modeled rows price the per-level dense exchange of both formats for
    both partition schemes at growing shard counts (packed words model
    8× below the uint8 mask).  Measured rows compile real engines per
    (wire_format, partition) on every shard count the local device set
    hosts and record (a) the run's accumulated per-level exchange bytes
    and (b) the collective bytes XLA actually emitted in the compiled
    loop body (``hlo_stats.collective_bytes`` over the engine
    executable) — compiler ground truth for the on-wire reduction.  A
    final row per p records what ``wire_format="auto"`` resolved to.
    Everything lands in the ``BENCH_wire_format.json`` ledger
    (``--wire-out``), rendered by ``render_roofline.py``.
    """
    import numpy as _np
    from jax.sharding import Mesh
    from repro.launch.hlo_stats import collective_bytes
    from repro.launch.mesh import make_grid_mesh

    n_model, s = 100_000, 1
    pairs_1d = (("bytes", "alltoall_direct"),
                ("packed", "alltoall_direct_packed"))
    pairs_2d = (("bytes", ("allgather", "alltoall_reduce")),
                ("packed", ("allgather_packed", "alltoall_reduce_packed")))

    for p in (4, 16, 64):
        r, c = default_grid(p)
        n_pad = Partition1D(n_model, p).n
        modeled = {}
        for fmt, strat in pairs_1d:
            b = ex.dense_level_bytes(strat, n_pad, p, s, 1)
            modeled[("1d", fmt)] = b
            _WIRE_FORMAT.append({
                "graph": f"erdos_renyi_{n_model // 1000}k",
                "partition": "1d", "wire_format": fmt, "p": p, "r": 1,
                "c": p, "strategy": strat, "modeled_level_bytes": b,
            })
        for fmt, (es, fs) in pairs_2d:
            b = ex.grid_level_bytes(es, fs, n_pad, r, c, s, 1)
            modeled[("2d", fmt)] = b
            _WIRE_FORMAT.append({
                "graph": f"erdos_renyi_{n_model // 1000}k",
                "partition": "2d", "wire_format": fmt, "p": p, "r": r,
                "c": c, "strategy": f"{es}+{fs}", "modeled_level_bytes": b,
            })
        row(f"wire_modeled/p={p}", 0.0,
            f"1d_bytes={modeled['1d', 'bytes']:.0f};"
            f"1d_packed={modeled['1d', 'packed']:.0f};"
            f"2d_bytes={modeled['2d', 'bytes']:.0f};"
            f"2d_packed={modeled['2d', 'packed']:.0f};"
            f"ratio_1d={modeled['1d', 'bytes'] / modeled['1d', 'packed']:.1f}")

    # measured: real engines on the local device set (CI's 4-device job
    # measures the p=4 collectives; smaller n keeps the CPU loop fast)
    n_meas = 20_000
    src, dst = generate("erdos_renyi", n_meas, seed=0, avg_degree=16.0)
    p_avail = jax.device_count()
    for p in sorted({1, 4} & set(range(1, p_avail + 1))):
        g = shard_graph(src, dst, n_meas, p)
        r, c = default_grid(p)
        meshes = {
            "1d": (Mesh(_np.asarray(jax.devices()[:p]).reshape(p), ("p",)),
                   "p"),
            "2d": (make_grid_mesh(r, c), None),
        }
        for kind, (mesh, axis) in meshes.items():
            meas, hlo_meas = {}, {}
            for fmt in ("bytes", "packed"):
                pl = plan(g, BFSOptions(mode="dense", wire_format=fmt),
                          mesh=mesh, axis=axis, num_sources=s,
                          partition=kind)
                t0 = time.time()
                eng = pl.compile()
                compile_s = time.time() - t0
                res = eng.run([0])                 # warmup
                t0 = time.time()
                for i in range(3):
                    res = eng.run([7 * i + 1])
                per_run = (time.time() - t0) / 3
                stats = res.stats()
                hlo = collective_bytes(eng.compiled_hlo())
                level_bytes = (stats.comm_bytes / stats.levels
                               if stats.levels else 0.0)
                meas[fmt] = level_bytes
                hlo_meas[fmt] = hlo["total"]
                meta = pl.describe()
                _WIRE_FORMAT.append({
                    "graph": f"erdos_renyi_{n_meas // 1000}k",
                    "partition": kind, "wire_format": fmt, "p": p,
                    "r": r if kind == "2d" else 1,
                    "c": c if kind == "2d" else p, "measured": True,
                    "levels": stats.levels, "per_run_s": per_run,
                    "compile_s": compile_s,
                    "run_comm_bytes": stats.comm_bytes,
                    "measured_level_bytes": level_bytes,
                    "hlo_collective_bytes": hlo["total"],
                    "wire_formats": meta["wire_formats"],
                })
                row(f"wire_measured/{kind}/p={p}/{fmt}", per_run * 1e6,
                    f"levels={stats.levels};level_bytes={level_bytes:.0f};"
                    f"hlo_collective_bytes={hlo['total']:.0f}")
            if p > 1:
                # the tentpole claim, checked on compiler ground truth:
                # the collective buffer bytes XLA emitted for the packed
                # loop must be >= 4x below the bytes loop's (the run-stat
                # ratio is the analytic model and would hold trivially)
                assert (hlo_meas["bytes"] / max(hlo_meas["packed"], 1)
                        >= 4), hlo_meas
            # what "auto" resolves to at this topology (packed for dense
            # phases whenever p > 1 — the byte model decides)
            auto_meta = plan(g, BFSOptions(mode="dense", wire_format="auto"),
                             mesh=mesh, axis=axis, num_sources=s,
                             partition=kind).describe()
            _WIRE_FORMAT.append({
                "graph": f"erdos_renyi_{n_meas // 1000}k",
                "partition": kind, "wire_format": "auto", "p": p,
                "r": r if kind == "2d" else 1,
                "c": c if kind == "2d" else p,
                "resolved": auto_meta["wire_formats"],
            })
            row(f"wire_auto/{kind}/p={p}", 0.0,
                f"resolved={auto_meta['wire_formats']}")


def bench_sparse_wire_sweep():
    """Compressed sparse-id wire + visited sieve ("Compression and
    Sieve", the sparse-phase half of the adaptive wire stack).

    Modeled rows price the per-level sparse exchanges — 1-D queue and
    2-D expand/fold id buffers — raw int32 ids vs the delta+varint
    compressed payload at paper-like frontier densities (the codec's
    bitmap-adaptive branch shows up as the capacity clamp at high
    density).  Measured rows compile each sparse exchange *standalone*
    under shard_map on the local device set and parse the collective
    bytes XLA emitted (the engine loop's HLO carries identical
    dense-escalation-branch collectives under both wires, so the sparse
    phase must be isolated — the same compile_and_parse pattern as
    tests/helpers/exchange_bytes.py), asserting the >= 2x on-wire cut
    at p = 4.  Engine rows run queue-mode traversals raw vs compressed
    with the sieve on/off (bitwise-identical distances required) and a
    final row per topology records what ``wire_format="auto"`` /
    ``sieve="auto"`` resolved.  Everything lands in the
    ``BENCH_sparse_wire.json`` ledger (``--sparse-wire-out``).
    """
    import functools
    import numpy as _np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import frontier as frmod
    from repro.launch.hlo_stats import collective_bytes
    from repro.launch.mesh import make_grid_mesh

    cap = 256

    # --- modeled: raw vs compressed sparse-level bytes across densities
    for p in (4, 16, 64):
        r, c = default_grid(p)
        for density in (0.03125, 0.5):
            q_raw = ex.queue_level_bytes("alltoall_direct", p, cap, 4,
                                         density=density)
            q_comp = ex.queue_level_bytes("alltoall_direct_compressed", p,
                                          cap, 4, density=density)
            g_raw = ex.grid_sparse_level_bytes(
                "allgather", "alltoall_direct", r, c, cap, 4,
                density=density)
            g_comp = ex.grid_sparse_level_bytes(
                "allgather_compressed", "alltoall_direct_compressed",
                r, c, cap, 4, density=density)
            _SPARSE_WIRE.append({
                "kind": "modeled", "p": p, "r": r, "c": c, "cap": cap,
                "density": density,
                "queue_raw_bytes": q_raw, "queue_compressed_bytes": q_comp,
                "grid_sparse_raw_bytes": g_raw,
                "grid_sparse_compressed_bytes": g_comp,
            })
            row(f"sparse_wire_modeled/p={p}/density={density}", 0.0,
                f"queue_raw={q_raw:.0f};queue_comp={q_comp:.0f};"
                f"ratio_q={q_raw / q_comp:.1f};grid_raw={g_raw:.0f};"
                f"grid_comp={g_comp:.0f};ratio_g={g_raw / g_comp:.1f}")

    # --- measured: standalone sparse exchanges vs compiled-HLO bytes
    def hlo_total(fn, in_specs, out_specs, shapes, mesh):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        lowered = jax.jit(mapped).lower(*shapes)
        return collective_bytes(lowered.compile().as_text())["total"]

    import jax.numpy as jnp
    if jax.device_count() >= 4:
        p, density = 4, 0.5
        bc = frmod.compressed_capacity(cap, int(cap / density))
        mesh1 = Mesh(_np.asarray(jax.devices()[:p]).reshape(p), ("p",))
        q_raw_hlo = hlo_total(
            functools.partial(ex.exchange_queue, axis="p",
                              strategy="alltoall_direct"),
            P(None, None), P(None, None),
            (jax.ShapeDtypeStruct((p, cap), jnp.int32),), mesh1)
        q_comp_hlo = hlo_total(
            functools.partial(ex.exchange_queue, axis="p",
                              strategy="alltoall_direct_compressed"),
            P(None, None), P(None, None),
            (jax.ShapeDtypeStruct((p, bc), jnp.uint8),), mesh1)

        r, c = 2, 2
        mesh2 = make_grid_mesh(r, c)
        exp_raw = ex.get_exchange("expand_row_sparse", "allgather")
        exp_comp = ex.get_exchange("expand_row_sparse",
                                   "allgather_compressed")
        fold_raw = ex.get_exchange("fold_col_sparse", "alltoall_direct")
        fold_comp = ex.get_exchange("fold_col_sparse",
                                    "alltoall_direct_compressed")
        g_raw_hlo = hlo_total(
            lambda x: exp_raw.impl(x, "cols"), P(None), P(None),
            (jax.ShapeDtypeStruct((cap,), jnp.int32),), mesh2
        ) + hlo_total(
            lambda x: fold_raw.impl(x, "rows"), P(None, None), P(None, None),
            (jax.ShapeDtypeStruct((r, cap), jnp.int32),), mesh2)
        g_comp_hlo = hlo_total(
            lambda x: exp_comp.impl(x, "cols"), P(None), P(None),
            (jax.ShapeDtypeStruct((bc,), jnp.uint8),), mesh2
        ) + hlo_total(
            lambda x: fold_comp.impl(x, "rows"), P(None, None),
            P(None, None),
            (jax.ShapeDtypeStruct((r, bc), jnp.uint8),), mesh2)

        # the tentpole claim on compiler ground truth: >= 2x fewer
        # sparse-phase collective bytes at p = 4 under the compressed wire
        assert q_raw_hlo / max(q_comp_hlo, 1) >= 2.0, (q_raw_hlo,
                                                       q_comp_hlo)
        assert g_raw_hlo / max(g_comp_hlo, 1) >= 2.0, (g_raw_hlo,
                                                       g_comp_hlo)
        _SPARSE_WIRE.append({
            "kind": "measured_hlo", "p": p, "r": r, "c": c, "cap": cap,
            "density": density, "payload_bytes": bc,
            "queue_raw_hlo_bytes": q_raw_hlo,
            "queue_compressed_hlo_bytes": q_comp_hlo,
            "grid_sparse_raw_hlo_bytes": g_raw_hlo,
            "grid_sparse_compressed_hlo_bytes": g_comp_hlo,
        })
        row(f"sparse_wire_hlo/p={p}", 0.0,
            f"queue_raw={q_raw_hlo:.0f};queue_comp={q_comp_hlo:.0f};"
            f"ratio_q={q_raw_hlo / max(q_comp_hlo, 1):.1f};"
            f"grid_raw={g_raw_hlo:.0f};grid_comp={g_comp_hlo:.0f};"
            f"ratio_g={g_raw_hlo / max(g_comp_hlo, 1):.1f}")
    else:
        row("sparse_wire_hlo/skipped", 0.0,
            f"device_count={jax.device_count()}<4 (the 4-device CI job "
            "measures the real collectives)")

    # --- engine rows: queue-mode traversals, raw vs compressed + sieve
    n_meas = 20_000
    src, dst = generate("erdos_renyi", n_meas, seed=0, avg_degree=8.0)
    p_avail = jax.device_count()
    for p in sorted({1, 4} & set(range(1, p_avail + 1))):
        g = shard_graph(src, dst, n_meas, p)
        mesh = Mesh(_np.asarray(jax.devices()[:p]).reshape(p), ("p",))
        dists = {}
        for fmt in ("bytes", "compressed"):
            for sieve in (False, True):
                pl = plan(g, BFSOptions(mode="queue", wire_format=fmt,
                                        sieve=sieve, queue_cap=1 << 14),
                          mesh=mesh, axis="p", num_sources=1)
                t0 = time.time()
                eng = pl.compile()
                compile_s = time.time() - t0
                res = eng.run([0])
                h = res.run_stats.to_host()
                dists[(fmt, sieve)] = res.dist_host
                meta = pl.describe()
                _SPARSE_WIRE.append({
                    "kind": "engine", "p": p, "wire_format": fmt,
                    "sieve": sieve, "queue_cap": 1 << 14,
                    "graph": f"erdos_renyi_{n_meas // 1000}k",
                    "compile_s": compile_s, "levels": h["levels"],
                    "run_comm_bytes": h["comm_bytes"],
                    "sieve_hits": h["sieve_hits"],
                    "queue_level_bytes": meta["queue_level_bytes"],
                    "resolved_queue": meta["queue_exchange"],
                })
                row(f"sparse_wire_engine/p={p}/{fmt}/sieve={int(sieve)}",
                    0.0, f"levels={h['levels']};"
                    f"comm_bytes={h['comm_bytes']:.0f};"
                    f"sieve_hits={h['sieve_hits']}")
        # every wire x sieve combination must land bitwise-identical
        base = dists["bytes", False]
        assert all(_np.array_equal(d, base) for d in dists.values())

        # what auto resolves at this topology (records the adaptive stack)
        for part_kind in ("1d",) if p == 1 else ("1d", "2d"):
            r, c = default_grid(p) if part_kind == "2d" else (1, p)
            kmesh = make_grid_mesh(r, c) if part_kind == "2d" else mesh
            meta = plan(g, BFSOptions(mode="auto", wire_format="auto",
                                      sieve="auto", queue_cap=1024),
                        mesh=kmesh, axis="p" if part_kind == "1d" else None,
                        num_sources=1, partition=part_kind).describe()
            _SPARSE_WIRE.append({
                "kind": "auto_resolution", "p": p, "partition": part_kind,
                "resolved": meta["wire_formats"], "sieve": meta["sieve"],
            })
            row(f"sparse_wire_auto/{part_kind}/p={p}", 0.0,
                f"resolved={meta['wire_formats']};sieve={meta['sieve']}")


def bench_multi_graph_serving():
    """Multi-tenant serving: cross-graph compile amortization.

    Phase 1 (unbounded cache): register N graphs in one ``BFSService``
    and measure, per graph, the *cold* path (plan + compile through the
    shared ``EngineCache``) vs the *warm* path (cache hit + device-only
    run) — the amortization the cache buys every tenant after its first
    request.

    Phase 2 (byte budget sized to hold only part of the engine set):
    deal requests round-robin across all graphs so the LRU working set
    exceeds the budget — engines evict and recompile, and the ledger
    records the achieved hit rate and eviction count.  This is the cost
    envelope of over-subscribed multi-tenant serving.
    """
    from repro.serve.bfs_service import BFSService, TraversalRequest
    from repro.serve.engine_cache import EngineCache, GraphCatalog

    n = 20_000
    families = [
        ("er", "erdos_renyi", n, {"avg_degree": 8.0}),
        ("star", "star", n, {}),
        # chain traverses one level per vertex — keep it small so the
        # deep-traversal tenant doesn't dominate the serving rounds
        ("chain", "chain", 1_000, {}),
        ("rmat", "rmat", n, {"edge_factor": 8}),
    ]
    slots = 2
    opts = BFSOptions(mode="dense")
    graphs = {}
    for name, kind, gn, kw in families:
        src, dst = generate(kind, gn, seed=0, **kw)
        graphs[name] = shard_graph(src, dst, gn, p=1)

    # phase 1: cold compile vs warm run, unbounded budget
    cache = EngineCache()
    svc = BFSService(opts=opts, batch_slots=slots, cache=cache,
                     catalog=GraphCatalog())
    per_graph = {}
    for name, g in graphs.items():
        svc.add_graph(name, g)
    for rid, name in enumerate(graphs):
        t0 = time.time()
        svc.submit(TraversalRequest(rid=rid, source=0, graph=name))
        svc.run_until_drained()
        cold_s = time.time() - t0              # includes the lane's compile
        t0 = time.time()
        svc.submit(TraversalRequest(rid=100 + rid, source=1, graph=name))
        svc.run_until_drained()
        warm_s = time.time() - t0              # cache hit + device-only run
        per_graph[name] = {"cold_ms": cold_s * 1e3, "warm_ms": warm_s * 1e3,
                           "amortization": cold_s / max(warm_s, 1e-9)}
        row(f"serving_cold_vs_warm/{name}", warm_s * 1e6,
            f"cold_ms={cold_s*1e3:.1f};warm_ms={warm_s*1e3:.1f};"
            f"amortization={cold_s/max(warm_s, 1e-9):.1f}x")
    st = cache.stats()
    assert st["misses"] == len(graphs), st     # each plan compiled once
    total_engine_bytes = st["device_bytes"]    # whole fleet, all 4 engines

    # phase 2: budget admits ~half the engines -> forced LRU eviction
    budget = max(1, total_engine_bytes // 2)
    cache2 = EngineCache(max_device_bytes=budget)
    svc2 = BFSService(opts=opts, batch_slots=slots, cache=cache2,
                      catalog=GraphCatalog())
    for name, g in graphs.items():
        svc2.add_graph(name, g)
    t0 = time.time()
    rounds = 3
    for k in range(rounds):
        for rid, name in enumerate(graphs):
            svc2.submit(TraversalRequest(rid=k * 100 + rid, source=k,
                                         graph=name))
        svc2.run_until_drained()
    wall_s = time.time() - t0
    st2 = cache2.stats()
    assert st2["evictions"] >= 1, st2          # the budget must bind
    row("serving_under_budget", wall_s / (rounds * len(graphs)) * 1e6,
        f"budget_bytes={budget};evictions={st2['evictions']};"
        f"hit_rate={st2['hit_rate']:.2f};"
        f"recompiles={st2['misses'] - len(graphs)}")
    _SERVING.update({
        "graphs": per_graph,
        "unbounded": st,
        "eviction_pass": {"budget_bytes": budget, "rounds": rounds,
                          "wall_s": wall_s, **st2},
    })


def bench_serving_latency():
    """Remote front-end: bucket-ladder latency + bounded-queue overload.

    Drives the transport-agnostic ``BFSFrontend`` in process (the same
    submit/dispatch/complete path ``POST /v1/traverse`` rides, minus
    HTTP framing) over one lane compiled at the 1/8/64 bucket ladder.

    Phase 1 — per batch size: the *cold* request (first touch of its
    bucket pays the compile through the shared cache) vs *warm* repeats,
    with the dispatcher's own queue-wait/device split from the response
    timing.  Batch 3 lands between rungs and must be served by bucket 8
    — its warm per-source cost is the price of ladder padding.

    Phase 2 — overload: queue bound 1 with the dispatcher parked, then
    a synchronized 8-client burst.  Exactly one request is admitted and
    the rest get 429s with retry-after hints; the dispatcher then starts
    and drains the survivor.  Deterministic *and* concurrent.
    """
    import threading as _threading

    from repro.serve.bfs_service import BFSService
    from repro.serve.engine_cache import EngineCache, GraphCatalog
    from repro.serve.frontend import AdmissionError, BFSFrontend

    n, ladder = 20_000, (1, 8, 64)
    src, dst = generate("erdos_renyi", n, seed=0, avg_degree=8.0)
    g = shard_graph(src, dst, n, p=1)
    svc = BFSService(opts=BFSOptions(mode="dense"), batch_buckets=ladder,
                     cache=EngineCache(), catalog=GraphCatalog())
    svc.add_graph("er", g)

    fe = BFSFrontend(svc, max_queue_depth=64)
    per_batch = {}
    reps = 3
    for batch in (1, 8, 3, 64):        # 3 after 8: its bucket is pre-warmed
        t0 = time.time()
        out = fe.traverse("er", list(range(batch)))
        cold_s = time.time() - t0
        t0 = time.time()
        for i in range(reps):
            out = fe.traverse(
                "er", [(batch * 7 + i * 131 + v) % n for v in range(batch)])
        warm_s = (time.time() - t0) / reps
        per_batch[batch] = {
            "bucket": out["bucket"], "cold_ms": cold_s * 1e3,
            "warm_ms": warm_s * 1e3,
            "warm_us_per_source": warm_s * 1e6 / batch,
            "timing_ms": out["timing_ms"],
        }
        row(f"serving_latency/batch={batch}", warm_s * 1e6 / batch,
            f"bucket={out['bucket']};cold_ms={cold_s*1e3:.1f};"
            f"warm_ms={warm_s*1e3:.1f};"
            f"queue_wait_ms={out['timing_ms']['queue_wait']:.1f};"
            f"device_ms={out['timing_ms']['device']:.1f}")
    assert per_batch[3]["bucket"] == 8, per_batch   # between-rung routing
    lane_snap = fe.metrics_payload()
    fe.shutdown()

    clients = 8
    fe2 = BFSFrontend(svc, max_queue_depth=1, start_dispatcher=False)
    admitted, rejected = [], []
    lock = _threading.Lock()
    barrier = _threading.Barrier(clients)

    def fire(i):
        barrier.wait()
        try:
            p = fe2.submit("er", [i])
            with lock:
                admitted.append(p)
        except AdmissionError as exc:
            with lock:
                rejected.append(exc)

    threads = [_threading.Thread(target=fire, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(admitted) == 1 and len(rejected) == clients - 1, (
        len(admitted), len(rejected))
    fe2.start()                        # un-park: drain the one survivor
    for p in admitted:
        fe2.wait(p, timeout_s=60.0)
    fe2.shutdown()
    row("serving_overload", 0.0,
        f"clients={clients};queue_depth=1;admitted={len(admitted)};"
        f"rejected_429={len(rejected)};"
        f"retry_after_s={rejected[0].retry_after_s:.3f}")

    _SERVING_LATENCY.update({
        "ladder": list(ladder),
        "graph": {"kind": "erdos_renyi", "n": n, "avg_degree": 8.0},
        "batches": {str(k): v for k, v in sorted(per_batch.items())},
        "overload": {
            "clients": clients, "queue_depth": 1,
            "admitted": len(admitted), "rejected_429": len(rejected),
            "retry_after_s": sorted(round(e.retry_after_s, 3)
                                    for e in rejected),
        },
        "lane_metrics": lane_snap["lanes"]["er"],
        "engine_cache": lane_snap["engine_cache"],
    })


def bench_latency():
    """Fused fold/owner-update tail + collective-compute overlap (the
    profile-driven latency-hiding stack), fused vs the unfused baseline
    on the same packed wire at p = 4 over the 2x2 grid.

    Step-time rows: dense and auto modes on a sparse Erdős-Rényi
    workload (avg_degree 2 — where the tail's eliminated byte passes
    are the largest share of the level).  The asserted >= 1.15x
    improvement is the *modeled* per-level step time from the
    describe() roofline (v5e bandwidths), weighted by the run's
    measured per-mode level counts — the same compiler-/model-ground-
    truth convention the wire benches use, because on the CPU host
    backend wall time is per-op dispatch + barrier wait, not bandwidth
    (the measured wall ratio is recorded honestly next to it).  The
    auto rows disable queue escalation (``queue_threshold=0``) so every
    level rides the dense/bottom-up phases the fused tail optimizes;
    the sparse path has its own ledger (BENCH_sparse_wire).

    Roofline validation (the model must be *measured*, not assumed):
    one small dense traversal per variant — sized so the profiler's
    event buffer does not truncate — is captured with ``jax.profiler``
    and parsed by ``analysis.trace_model``.  The calibration scale
    (host seconds per modeled v5e second) is fit on the *unfused*
    engine's compute phases only, then the *fused* engine's measured
    compute must land within 3x of the calibrated prediction — a
    cross-engine check the fit cannot satisfy by construction.  The
    collective term is validated in the byte domain instead (modeled
    wire bytes vs the collective bytes in the compiled HLO, within
    3x): measured collective *durations* on the host backend are
    barrier wait, which no wire model should be tuned to reproduce.
    """
    if jax.device_count() < 4:
        row("latency/skipped", 0.0,
            f"device_count={jax.device_count()}<4 (the 4-device CI job "
            "measures the 2x2 grid)")
        return

    import shutil
    import tempfile

    from repro.analysis import trace_model
    from repro.launch.hlo_stats import collective_bytes
    from repro.launch.mesh import make_grid_mesh

    mesh = make_grid_mesh(2, 2)
    compute_phases = ("expand", "fold", "owner_update")

    def weighted_model_step(meta, mode_counts):
        rf = meta["roofline"]
        total = sum(mode_counts.values()) or 1
        return sum(rf[k]["t_level_s"] * v
                   for k, v in mode_counts.items()) / total

    # --- step-time rows: fused vs unfused, dense + auto ----------------
    n, deg, reps = 30_000, 2.0, 3
    src, dst = generate("erdos_renyi", n, seed=0, avg_degree=deg)
    g = shard_graph(src, dst, n, 4)
    mode_rows = {}
    for mode, extra in (("dense", {}), ("auto", {"queue_threshold": 0.0})):
        variants = {}
        for label, fused in (("unfused", False), ("fused", True)):
            opts = BFSOptions(mode=mode, wire_format="packed",
                              use_fused_tail=fused, queue_cap=1 << 12,
                              **extra)
            pl = plan(g, opts, mesh=mesh, num_sources=1, partition="2d")
            t0 = time.time()
            eng = pl.compile()
            compile_s = time.time() - t0
            res = eng.run([0])                 # warmup
            best = float("inf")
            for i in range(reps):
                t0 = time.time()
                res = eng.run([7 * i + 1])
                best = min(best, time.time() - t0)
            stats = res.stats()
            meta = pl.describe()
            variants[label] = {
                "use_fused_tail": meta["use_fused_tail"],
                "levels": stats.levels,
                "mode_counts": stats.mode_counts,
                "compile_s": compile_s,
                "wall_per_level_s": best / max(1, stats.levels),
                "model_per_level_s": weighted_model_step(
                    meta, stats.mode_counts),
                "roofline": meta["roofline"],
            }
        un, fu = variants["unfused"], variants["fused"]
        # both variants must have traversed the same level/mode profile
        # for the per-level comparison to be meaningful
        assert un["mode_counts"] == fu["mode_counts"], (un, fu)
        improvement = un["model_per_level_s"] / fu["model_per_level_s"]
        wall_ratio = un["wall_per_level_s"] / fu["wall_per_level_s"]
        mode_rows[mode] = {**{"variants": variants},
                           "model_step_improvement": improvement,
                           "wall_step_ratio": wall_ratio}
        row(f"latency/{mode}", fu["wall_per_level_s"] * 1e6,
            f"levels={fu['levels']};modes={fu['mode_counts']};"
            f"model_improvement={improvement:.2f}x;"
            f"wall_ratio={wall_ratio:.2f}x")
        # the tentpole claim: >= 1.15x modeled per-level step-time win
        # for the fused+overlap plan in both modes
        assert improvement >= 1.15, (mode, improvement)

    # --- roofline validation: traced compute + HLO collective bytes ----
    nv, degv = 2048, 8.0
    vsrc, vdst = generate("erdos_renyi", nv, seed=0, avg_degree=degv)
    gv = shard_graph(vsrc, vdst, nv, 4)
    traced = {}
    for label, fused in (("unfused", False), ("fused", True)):
        opts = BFSOptions(mode="dense", wire_format="packed",
                          use_fused_tail=fused)
        pl = plan(gv, opts, mesh=mesh, num_sources=1, partition="2d")
        eng = pl.compile()
        res = eng.run([0])                     # warmup outside the trace
        logdir = tempfile.mkdtemp(prefix=f"bench_latency_{label}_")
        try:
            with trace_model.capture(logdir):
                res = eng.run([1])
            stats = res.stats()
            t = trace_model.parse_trace(logdir, n_levels=stats.levels)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        # a truncated trace silently undercounts phases — refuse it
        assert t.n_ops < 900_000, f"profiler event buffer hit: {t.n_ops}"
        rf = pl.describe()["roofline"]["dense"]
        traced[label] = {
            "levels": stats.levels,
            "n_ops": t.n_ops,
            "level_segments": len(t.levels),
            "measured_compute_per_level_s":
                sum(t.total_s[p] for p in compute_phases)
                / max(1, stats.levels),
            "measured_collective_per_level_s":
                t.total_s["collective"] / max(1, stats.levels),
            "model_compute_per_level_s": rf["t_compute_s"],
            "model_wire_bytes_per_level": rf["wire_bytes"],
            "hlo_collective_bytes_per_level":
                collective_bytes(eng.compiled_hlo())["total"],
        }
    un, fu = traced["unfused"], traced["fused"]
    scale = (un["measured_compute_per_level_s"]
             / un["model_compute_per_level_s"])
    predicted = scale * fu["model_compute_per_level_s"]
    compute_ratio = fu["measured_compute_per_level_s"] / predicted
    wire_ratios = {
        label: tr["hlo_collective_bytes_per_level"]
               / max(1.0, tr["model_wire_bytes_per_level"])
        for label, tr in traced.items()}
    row("latency/roofline_validation", 0.0,
        f"scale={scale:.3e};compute_pred_ratio={compute_ratio:.2f};"
        f"wire_hlo_ratio_unfused={wire_ratios['unfused']:.2f};"
        f"wire_hlo_ratio_fused={wire_ratios['fused']:.2f}")
    assert 1 / 3 <= compute_ratio <= 3, compute_ratio
    for label, wr in wire_ratios.items():
        assert 1 / 3 <= wr <= 3, (label, wr)

    _LATENCY.update({
        "graph": {"kind": "erdos_renyi", "n": n, "avg_degree": deg},
        "grid": "2x2", "p": 4, "wire_format": "packed",
        "modes": mode_rows,
        "per_level_step_time_improvement": {
            m: r["model_step_improvement"] for m, r in mode_rows.items()},
        "trace_validation": {
            "graph": {"kind": "erdos_renyi", "n": nv, "avg_degree": degv},
            "engines": traced,
            "calibration_scale": scale,
            "fused_compute_pred_vs_measured": compute_ratio,
            "wire_model_vs_hlo": wire_ratios,
            "note": ("calibration fit on the unfused engine's compute "
                     "phases; collective term validated in the byte "
                     "domain (host-backend collective durations are "
                     "barrier wait)"),
        },
    })


def bench_multi_source_throughput():
    """Batched multi-source BFS (the MXU formulation): us per source."""
    n = 30_000
    for s in (1, 8, 64):
        opts = BFSOptions(mode="dense")
        dt, stats, _ = _measure_bfs("erdos_renyi", n, opts,
                                    sources=tuple(range(s)),
                                    avg_degree=8.0)
        row(f"multi_source/S={s}", dt * 1e6 / s,
            f"total_us={dt*1e6:.0f};levels={stats.levels}")


def bench_kernels():
    import jax.numpy as jnp
    from repro.graphs import block_sparse_adjacency, erdos_renyi
    from repro.kernels.bsr_spmm import ops as spmm_ops
    from repro.kernels.embedding_bag import ops as bag_ops

    n = 1024
    src, dst = erdos_renyi(n, avg_degree=16, seed=0)
    blocks, br, bc, n_pad = block_sparse_adjacency(src, dst, n)
    x = jnp.ones((n_pad, 128), jnp.float32)
    args = (jnp.asarray(blocks), jnp.asarray(br), jnp.asarray(bc), x)
    f = jax.jit(lambda *a: spmm_ops.spmm(*a, n_rows_pad=n_pad,
                                         interpret=True))
    f(*args).block_until_ready()
    t0 = time.time()
    f(*args).block_until_ready()
    row("kernel_bsr_spmm_interp", (time.time() - t0) * 1e6,
        f"blocks={blocks.shape[0]};d=128")

    table = jnp.ones((10_000, 128), jnp.float32)
    idx = jnp.zeros((256, 8), jnp.int32)
    g = jax.jit(lambda i, t: bag_ops.embedding_bag(i, t, interpret=True))
    g(idx, table).block_until_ready()
    t0 = time.time()
    g(idx, table).block_until_ready()
    row("kernel_embedding_bag_interp", (time.time() - t0) * 1e6,
        "B=256;L=8;D=128")


def bench_roofline_table():
    """§Roofline: per-cell terms from the dry-run sweep (if present)."""
    path = os.path.join(os.path.dirname(__file__), "dryrun_results.json")
    if not os.path.exists(path):
        row("roofline_table", 0.0, "missing dryrun_results.json (run "
            "python -m repro.launch.dryrun --all --mesh both --out ...)")
        return
    with open(path) as f:
        data = json.load(f)
    for r in data["rows"]:
        tt = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        row(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", tt * 1e6,
            f"bottleneck={r['bottleneck']};"
            f"compute_us={r['t_compute_s']*1e6:.1f};"
            f"memory_us={r['t_memory_s']*1e6:.1f};"
            f"collective_us={r['t_collective_s']*1e6:.1f};"
            f"mem_gib={r['bytes_per_device']/2**30:.2f}")


BENCHES = [
    bench_fig3_star_scaling,
    bench_fig5_erdos_renyi_scaling,
    bench_fig7_small_world_scaling,
    bench_sec51_exchange_volume,
    bench_sec52_local_update,
    bench_direction_optimizing,
    bench_engine_amortization,
    bench_partition_1d_vs_2d,
    bench_wire_format_sweep,
    bench_sparse_wire_sweep,
    bench_multi_graph_serving,
    bench_serving_latency,
    bench_latency,
    bench_multi_source_throughput,
    bench_kernels,
    bench_roofline_table,
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_results.json",
                    help="JSON ledger path (compile vs per-run split)")
    ap.add_argument("--wire-out", default="BENCH_wire_format.json",
                    help="wire-format sweep ledger path (written when the "
                         "wire_format bench runs)")
    ap.add_argument("--serving-out", default="BENCH_serving_latency.json",
                    help="serving front-end ledger path (written when the "
                         "serving_latency bench runs)")
    ap.add_argument("--sparse-wire-out", default="BENCH_sparse_wire.json",
                    help="compressed sparse-wire + sieve ledger path "
                         "(written when the sparse_wire bench runs)")
    ap.add_argument("--latency-out", default="BENCH_latency.json",
                    help="fused-tail latency ledger path (written when "
                         "the latency bench runs)")
    ap.add_argument("--only", default=None,
                    help="substring filter on bench function names")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the selected "
                         "benches into DIR and print the parsed per-phase "
                         "device-time summary after the run")
    args = ap.parse_args(argv)

    if args.only and args.out == ap.get_default("out"):
        # don't let a filtered run clobber the full default ledger
        args.out = f"BENCH_results.{args.only}.json"

    profile_cm = contextlib.nullcontext()
    if args.profile:
        from repro.analysis import trace_model
        profile_cm = trace_model.capture(args.profile)

    print("name,us_per_call,derived")
    with profile_cm:
        for b in BENCHES:
            if args.only and args.only not in b.__name__:
                continue
            b()
    if args.profile:
        from repro.analysis import trace_model
        print(trace_model.format_summary(
            trace_model.parse_trace(args.profile)))

    ledger = {
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in _ROWS],
        "engine_timings": _ENGINE_TIMINGS,
        "partition_sweep": _PARTITION_SWEEP,
        "serving": _SERVING,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(args.out, "w") as f:
        json.dump(ledger, f, indent=2, sort_keys=True)
    print(f"# wrote {args.out} ({len(_ROWS)} rows, "
          f"{len(_ENGINE_TIMINGS)} engine timings)", flush=True)

    if _WIRE_FORMAT:
        wire_ledger = {
            "wire_format": _WIRE_FORMAT,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        with open(args.wire_out, "w") as f:
            json.dump(wire_ledger, f, indent=2, sort_keys=True)
        print(f"# wrote {args.wire_out} ({len(_WIRE_FORMAT)} wire rows)",
              flush=True)

    if _SPARSE_WIRE:
        sparse_ledger = {
            "sparse_wire": _SPARSE_WIRE,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        with open(args.sparse_wire_out, "w") as f:
            json.dump(sparse_ledger, f, indent=2, sort_keys=True)
        print(f"# wrote {args.sparse_wire_out} "
              f"({len(_SPARSE_WIRE)} sparse-wire rows)", flush=True)

    if _LATENCY:
        latency_ledger = {
            "latency": _LATENCY,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        with open(args.latency_out, "w") as f:
            json.dump(latency_ledger, f, indent=2, sort_keys=True)
        print(f"# wrote {args.latency_out} "
              f"({len(_LATENCY['modes'])} mode rows)", flush=True)

    if _SERVING_LATENCY:
        serving_ledger = {
            "serving_latency": _SERVING_LATENCY,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        with open(args.serving_out, "w") as f:
            json.dump(serving_ledger, f, indent=2, sort_keys=True)
        print(f"# wrote {args.serving_out} "
              f"({len(_SERVING_LATENCY['batches'])} batch rows)", flush=True)


if __name__ == "__main__":
    main()
