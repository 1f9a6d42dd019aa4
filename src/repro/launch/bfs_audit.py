"""Audit gate: statically verify compiled plans + repo conventions.

    PYTHONPATH=src python -m repro.launch.bfs_audit \
        --graph er:4096 --all-variants --devices 4

For each partition x wire-format x mode x fused-tail variant, compile
the plan (via the shared EngineCache, so twins that resolve to the same
plan key cost one compile) and run the HLO plan auditor
(analysis/hlo_audit): the
collective census must match the resolved strategies, modeled bytes
must agree with HLO received bytes within the documented tolerance, the
dist buffer must be donated, no host transfer may hide in the loop, and
two distinct-source runs must not retrace.  The registry/loop lint
(analysis/lint) and the serve/ lock-discipline pass (analysis/locks)
run once alongside.

Exit code 0 iff every report is clean (suppressed violations carry
their reasons in the report but do not gate).  ``--out`` writes the
full machine-readable ledger (``BENCH_audit.json`` in CI).
"""

from repro.launch import (host_devices_from_argv, launch_devices,
                          parse_graph_spec, use_compile_cache)

host_devices_from_argv()  # must precede the jax import below

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.analysis import hlo_audit  # noqa: E402
from repro.analysis.lint import lint_tree  # noqa: E402
from repro.analysis.locks import analyze_serve  # noqa: E402
from repro.core import BFSOptions, plan  # noqa: E402
from repro.graphs import generate, shard_graph, shard_graph_2d  # noqa: E402
from repro.launch.mesh import default_grid, make_grid_mesh  # noqa: E402
from repro.serve.engine_cache import default_engine_cache  # noqa: E402

MODES = ("dense", "queue", "auto")
WIRES = ("bytes", "packed", "compressed", "auto")
# the fused-tail axis doubles the gate: every wire x mode compiles its
# unfused twin and its "auto"-resolved twin (which turns the fused tail
# on exactly where it can exist — packed dense/fold wire + a dense-path
# mode; elsewhere both resolve to the same plan_key and the EngineCache
# dedups the compile, so the doubling is nominal)
FUSED = (False, "auto")


def _variants(p: int, all_variants: bool, args):
    if not all_variants:
        yield (args.partition, args.mode, args.wire_format,
               {"on": True, "off": False, "auto": "auto"}[args.fused_tail])
        return
    partitions = ("1d", "2d") if p > 1 else ("1d",)
    for part in partitions:
        for wire in WIRES:
            for mode in MODES:
                for fused in FUSED:
                    yield part, mode, wire, fused


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="static audit of compiled BFS plans (HLO census, "
                    "donation, retrace) + registry lint + lock pass")
    ap.add_argument("--graph", default="er:4096", metavar="KIND[:N]",
                    help="graph spec to audit plans against")
    ap.add_argument("--all-variants", action="store_true",
                    help="audit every partition x wire-format x mode "
                         "variant (the CI gate); default audits the "
                         "single variant named by --partition/--mode/"
                         "--wire-format")
    ap.add_argument("--partition", default="1d", choices=["1d", "2d"])
    ap.add_argument("--mode", default="auto", choices=list(MODES))
    ap.add_argument("--wire-format", default="auto", choices=list(WIRES))
    ap.add_argument("--fused-tail", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused fold/owner-update tail for the single-"
                         "variant audit (--all-variants always audits "
                         "both twins)")
    ap.add_argument("--grid", default=None, metavar="RxC",
                    help="2-D grid (default: most-square factorization)")
    ap.add_argument("--sources", type=int, default=1,
                    help="compiled source-batch capacity S")
    ap.add_argument("--devices", type=int, default=0)  # parsed above
    ap.add_argument("--tolerance", default=None, metavar="LO,HI",
                    help="HLO-vs-model byte ratio band "
                         f"(default {hlo_audit.DEFAULT_TOLERANCE})")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the full audit ledger json (BENCH_audit)")
    ap.add_argument("--census", action="store_true",
                    help="print the per-variant census table")
    ap.add_argument("--skip-lint", action="store_true")
    ap.add_argument("--skip-locks", action="store_true")
    ap.add_argument("--skip-run-check", action="store_true",
                    help="skip the two-run retrace check (HA006)")
    args = ap.parse_args(argv)

    tol = hlo_audit.DEFAULT_TOLERANCE
    if args.tolerance:
        lo, hi = (float(x) for x in args.tolerance.split(","))
        tol = (lo, hi)

    use_compile_cache()
    _, kind, n, spec_grid = parse_graph_spec(args.graph, 4096)
    devs = launch_devices(args.devices)
    p = len(devs)
    grid = spec_grid
    if grid is None:
        grid = (int(x) for x in args.grid.lower().split("x")) \
            if args.grid else default_grid(p)
    r, c = grid
    print(f"audit: graph={kind}:{n} p={p} grid={r}x{c} "
          f"tolerance={list(tol)}")

    src, dst = generate(kind, n, seed=0)
    mesh_1d = Mesh(np.asarray(devs).reshape(p), ("p",))
    g1 = shard_graph(src, dst, n, p)
    g2 = shard_graph_2d(src, dst, n, r, c) if p > 1 else None
    mesh_2d = make_grid_mesh(r, c, devices=devs) if p > 1 else None

    cache = default_engine_cache()
    reports = []
    failed = False
    for part, mode, wire, fused in _variants(p, args.all_variants, args):
        opts = BFSOptions(mode=mode, wire_format=wire,
                          use_fused_tail=fused)
        t0 = time.time()
        if part == "2d":
            pl = plan(g2, opts, mesh=mesh_2d, num_sources=args.sources,
                      partition="2d")
        else:
            pl = plan(g1, opts, mesh=mesh_1d, axis="p",
                      num_sources=args.sources)
        if (args.all_variants and fused == "auto"
                and not pl.use_fused_tail):
            # "auto" resolved the fused tail off — this plan_key is the
            # fused=False twin already audited; skip the duplicate report
            continue
        engine = cache.get_or_compile(pl)
        fused_tag = ":fused" if pl.use_fused_tail else ""
        rep = hlo_audit.audit_engine(
            engine, tolerance=tol, run_check=not args.skip_run_check,
            name=f"hlo:{part}:{mode}:{wire}:S{args.sources}{fused_tag}")
        coll = rep.info["collectives"]
        print(f"{rep.summary()}  "
              f"[{coll['loop_data']} data + {coll['loop_control']} control "
              f"collectives, {time.time() - t0:.1f}s]")
        if args.census:
            print(hlo_audit.census_table(rep))
        for v in rep.violations:
            print(f"  {v}")
        failed |= not rep.ok()
        reports.append(rep)

    if not args.skip_lint:
        rep = lint_tree()
        print(rep.summary() + f"  [{len(rep.info['registrations'])} "
              "registrations checked]")
        for v in rep.violations:
            print(f"  {v}")
        failed |= not rep.ok()
        reports.append(rep)
    if not args.skip_locks:
        rep = analyze_serve()
        print(rep.summary() + f"  [{len(rep.info['lock_edges'])} lock "
              "edges]")
        for v in rep.violations:
            print(f"  {v}")
        failed |= not rep.ok()
        reports.append(rep)

    st = cache.stats()
    print(f"engine cache: hits={st['hits']} misses={st['misses']} "
          f"compile_s={st['compile_s_total']:.1f}")
    if args.out:
        ledger = {
            "audit": {
                "graph": {"kind": kind, "n": n}, "p": p,
                "grid": [r, c], "tolerance": list(tol),
                "ok": not failed,
                "reports": [rep.to_dict() for rep in reports],
            },
        }
        with open(args.out, "w") as f:
            json.dump(ledger, f, indent=2, sort_keys=True, default=str)
        print(f"wrote {args.out}")
    print("audit: " + ("FAIL" if failed else "PASS"))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
