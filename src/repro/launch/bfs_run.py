"""BFS launcher: run any BFS workload on the local device set.

    PYTHONPATH=src python -m repro.launch.bfs_run --workload erdos_renyi_100k
    PYTHONPATH=src python -m repro.launch.bfs_run --graph star --n 4000000
    PYTHONPATH=src python -m repro.launch.bfs_run \
        --graph erdos_renyi:100000 --graph star:50000 --repeats 2

Uses every visible device (or the first ``--devices N``) as one 1-D
shard row, or — with ``--partition 2d`` — as an ``r x c`` grid
(``--grid 2x2``; defaults to the most-square factorization) running the
two-phase edge-partitioned engine.
On the CPU ``--devices N`` forces N host devices for a local multi-shard
run (applied before jax initializes via ``repro.launch.host_devices``);
on a TPU host it meshes the first N chips, and exits when fewer are
visible.

The launcher drives the compile-once lifecycle: one ``plan().compile()``
per (graph, options, mesh), then ``--repeats`` traversals from rotating
source sets against the same engine — compile wall time and per-traversal
wall time are reported separately, which is the paper's amortization story
at the CLI.  ``--graph`` is repeatable (``KIND[:N]``): every engine
resolves through the process-wide shared ``EngineCache``, and the final
stats line shows the cross-graph compile amortization (hits / misses /
evictions / compile seconds).
"""

from repro.launch import (host_devices_from_argv, launch_devices,
                          parse_graph_spec, use_compile_cache)

host_devices_from_argv()  # must precede the jax import below

import argparse  # noqa: E402
import contextlib  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.analysis import trace_model  # noqa: E402
from repro.configs.base import BFS_WORKLOADS  # noqa: E402
from repro.core import BFSOptions, plan  # noqa: E402
from repro.graphs import generate, shard_graph, shard_graph_2d  # noqa: E402
from repro.launch.mesh import default_grid, make_grid_mesh  # noqa: E402
from repro.serve.engine_cache import default_engine_cache  # noqa: E402


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=None,
                    choices=[w.name for w in BFS_WORKLOADS])
    ap.add_argument("--graph", action="append", default=None,
                    metavar="KIND[:N]",
                    help="graph to traverse; repeatable — each runs "
                         "against its own cached engine (default: one "
                         "erdos_renyi of --n vertices)")
    ap.add_argument("--n", type=int, default=100_000,
                    help="default vertex count for --graph without :N")
    ap.add_argument("--mode", default="auto",
                    choices=["dense", "queue", "auto"])
    ap.add_argument("--exchange", default="alltoall_direct")
    ap.add_argument("--wire-format", default="auto",
                    choices=["packed", "bytes", "compressed", "auto"],
                    help="wire layout: packed uint32 bitset words (dense, "
                         "8x smaller), uint8 mask bytes / raw int32 ids, "
                         "delta+varint compressed ids (sparse phases), or "
                         "byte-model auto-selection per phase")
    ap.add_argument("--sieve", default="auto",
                    choices=["auto", "on", "off"],
                    help="visited-sieve: filter candidate ids against a "
                         "replicated coarse visited-summary bitmap before "
                         "the sparse exchange (auto: on when p>1 and the "
                         "plan has a sparse phase)")
    ap.add_argument("--describe", action="store_true",
                    help="print the compiled plan's full describe() "
                         "metadata — per-phase strategies, the wire "
                         "format 'auto' chose for each, and per-level "
                         "byte pricing")
    ap.add_argument("--audit", action="store_true",
                    help="run the HLO plan auditor on each compiled "
                         "engine (collective census vs resolved "
                         "strategies and modeled bytes, donation, "
                         "host-transfer checks) and print the census "
                         "next to the modeled bytes; exits 1 if any "
                         "engine fails the audit")
    ap.add_argument("--sources", type=int, default=1)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the timed "
                         "traversals into DIR and print the per-phase "
                         "device-time summary (expand / collective / "
                         "fold / owner_update) parsed from it")
    ap.add_argument("--repeats", type=int, default=3,
                    help="traversals to run against each compiled engine")
    ap.add_argument("--devices", type=int, default=0)  # parsed above
    ap.add_argument("--partition", default="1d", choices=["1d", "2d"],
                    help="vertex blocks over all p shards (1d) or edge "
                         "blocks over an r x c grid (2d)")
    ap.add_argument("--grid", default=None, metavar="RxC",
                    help="2-D grid shape, e.g. 2x2 (default: most-square "
                         "factorization of the device count)")
    args = ap.parse_args()

    if args.workload and args.graph:
        ap.error("--graph and --workload are mutually exclusive; pass the "
                 "workload's graph as a --graph spec instead")
    if args.workload:
        wl = next(w for w in BFS_WORKLOADS if w.name == args.workload)
        graphs = [(wl.graph, wl.n_vertices, dict(wl.gen_kwargs))]
    elif args.graph:
        graphs = []
        for spec in args.graph:
            _, kind, n, grid = parse_graph_spec(spec, args.n)
            if grid is not None:
                ap.error(f"--graph {spec}: per-spec grids are a bfs_serve "
                         "feature; here use --partition 2d --grid "
                         f"{grid[0]}x{grid[1]} (applies to every graph)")
            graphs.append((kind, n, {}))
    else:
        graphs = [("erdos_renyi", args.n, {})]

    devs = launch_devices(args.devices)
    p = len(devs)
    sieve = {"auto": "auto", "on": True, "off": False}[args.sieve]
    if args.partition == "2d":
        if args.grid:
            r, c = (int(x) for x in args.grid.lower().split("x"))
        else:
            r, c = default_grid(p)
        mesh = make_grid_mesh(r, c, devices=devs)
        axis = None                          # plan uses the mesh's two axes
        # --exchange names a *dense* (1-D) strategy; the 2-D phases use
        # expand/fold strategies.  Honor it when it is also a registered
        # fold strategy, otherwise say so instead of silently dropping it.
        from repro.core import FOLD_COL_STRATEGIES
        fold = "alltoall_reduce"
        if args.exchange in FOLD_COL_STRATEGIES:
            fold = args.exchange
        elif args.exchange != ap.get_default("exchange"):
            print(f"partition=2d ignores --exchange={args.exchange} "
                  f"(uses expand/fold strategies; fold options: "
                  f"{tuple(FOLD_COL_STRATEGIES)})")
        # every mode works over grids: queue levels bucket fold-layout ids
        # down grid columns, auto switches per level (sparse needs S=1)
        opts = BFSOptions(mode=args.mode, fold_exchange=fold,
                          wire_format=args.wire_format, sieve=sieve,
                          queue_cap=1 << 15)
        print(f"grid={r}x{c} (p={r*c}) mode={args.mode} "
              f"wire={args.wire_format} sieve={args.sieve}")
    else:
        mesh = Mesh(np.asarray(devs).reshape(p), ("p",))
        axis = "p"
        opts = BFSOptions(mode=args.mode, dense_exchange=args.exchange,
                          wire_format=args.wire_format, sieve=sieve,
                          queue_cap=1 << 15)
        print(f"shards={p} mode={args.mode} wire={args.wire_format} "
              f"sieve={args.sieve}")

    cache = default_engine_cache()
    audit_failed = False
    for kind, n, kw in graphs:
        t0 = time.time()
        src, dst = generate(kind, n, seed=0, **kw)
        if args.partition == "2d":
            # bucket straight into the r x c edge blocks; the bottom-up
            # in-edge blocks build lazily iff mode=auto compiles them
            g = shard_graph_2d(src, dst, n, r, c)
        else:
            g = shard_graph(src, dst, n,
                            int(np.prod(list(mesh.shape.values()))))
        print(f"graph={kind} n={n}: generated {src.shape[0]} edges "
              f"in {time.time()-t0:.1f}s")

        t0 = time.time()
        engine = cache.get_or_compile(
            plan(g, opts, mesh=mesh, axis=axis, num_sources=args.sources,
                 partition=args.partition))
        compile_s = time.time() - t0
        meta = engine.plan.describe()
        exchanges = (f"{meta['expand_exchange']}+{meta['fold_exchange']}"
                     if args.partition == "2d" else meta["dense_exchange"])
        wires = meta["wire_formats"]
        print(f"plan+get_or_compile: {compile_s:.2f}s (S={args.sources}, "
              f"{exchanges}, "
              f"level_bytes/chip={meta['dense_level_bytes']:.2e})")
        # per-level-variant pricing with the wire format each phase
        # resolved to (what "auto" actually chose for this topology); a
        # 2-D dense level has two phases which may resolve differently
        # (a degenerate grid's peerless phase keeps bytes), so both show
        dense_wire = (wires["dense"] if args.partition != "2d"
                      else f"{wires['expand']}+{wires['fold']}")
        queue_wire = wires["queue" if args.partition != "2d"
                           else "fold_sparse"]
        print("  level variants: "
              f"dense={meta['dense_level_bytes']:.2e}B[{dense_wire}]  "
              f"queue={meta['queue_level_bytes']:.2e}B[{queue_wire}]  "
              f"bottom_up={meta['bottom_up_level_bytes']:.2e}B"
              f"[{wires['bottom_up']}]")
        if args.describe:
            for k in sorted(meta):
                print(f"  describe.{k} = {meta[k]}")
        if args.audit:
            from repro.analysis import hlo_audit
            rep = hlo_audit.audit_engine(engine, run_check=False)
            print(f"  {rep.summary()}")
            print(hlo_audit.census_table(rep))
            for v in rep.violations:
                print(f"  {v}")
            audit_failed |= not rep.ok()

        rng = np.random.default_rng(0)
        profile_cm = (trace_model.capture(args.profile) if args.profile
                      else contextlib.nullcontext())
        total_levels = 0
        with profile_cm:
            for rep in range(max(1, args.repeats)):
                sources = (list(range(args.sources)) if rep == 0 else
                           sorted(rng.choice(n, size=args.sources,
                                             replace=False).tolist()))
                t0 = time.time()
                res = engine.run(sources)
                run_s = time.time() - t0
                stats = res.stats()
                total_levels += stats.levels
                hits = int(stats.sieve_hits)
                # hit-rate: share of would-be enqueued candidates the
                # sieve dropped before they reached the wire (visited ids
                # that the coarse replicated summary could already prove
                # discovered)
                rate = hits / max(1, hits + stats.visited)
                sieve_str = (f" sieve_hits={hits} ({rate:.0%})"
                             if meta["sieve"] else "")
                print(f"run[{rep}] sources={sources[:4]}"
                      f"{'...' if len(sources) > 4 else ''}: "
                      f"levels={stats.levels} visited={stats.visited} "
                      f"modes={stats.mode_counts} "
                      f"comm_bytes/chip={stats.comm_bytes:.2e} "
                      f"wall={run_s:.3f}s{sieve_str}")
        if args.profile:
            timings = trace_model.parse_trace(args.profile,
                                              n_levels=total_levels)
            print(trace_model.format_summary(timings))
        assert engine.trace_count == engine.compile_traces, \
            "engine retraced after compile — amortization broken"

    st = cache.stats()
    print(f"engine cache: hits={st['hits']} misses={st['misses']} "
          f"evictions={st['evictions']} entries={st['entries']} "
          f"bytes={st['device_bytes']} "
          f"compile_s={st['compile_s_total']:.2f}")
    if audit_failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
