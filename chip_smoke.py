#!/usr/bin/env python3
"""Chip smoke run: the BFS engine and the traversal service on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the paths across chips

With no arguments it drives the main path once on one TPU chip, through
the public API, at Graph500 scale 20 (the ``rmat_1m`` workload):

  * engine   — ``plan(..., BFSOptions(mode="auto")).compile()`` on
    ``rmat_1m``; seeded search keys run at S = 1, then one batch at S = 8;
  * kernels  — the Pallas kernels one chip never selects on its own: the
    fused ``fold_update`` tail on ``rmat_1m`` and the ``bsr_spmm`` +
    ``bitpack_words`` expansion on a scale-14 Graph500 graph (small
    enough for its dense 128x128 tiles); each compiled engine must hold
    its kernels as ``tpu_custom_call``s;
  * served   — ``BFSService`` behind the HTTP front-end on an ephemeral
    port, one ``erdos_renyi_100k`` lane with a ``1,8`` bucket ladder,
    1- and 3-source ``POST /v1/traverse`` requests, drained through
    ``/admin/shutdown``.

``--chips 4`` runs only ``rmat_1m`` under default options on a 1-D mesh
over four chips and on a 2x2 grid, and checks that the compiled loop's
collectives span all four devices.

Every distance column is checked bitwise against the numpy reference
(``repro.core.ref.bfs_reference``).  Lines before the last are one-off
smoke readings (compile seconds, wall times, peak device bytes), not
benchmark metrics.  The last line is one JSON object naming the device;
the script prints it only when every phase passed, and exits non-zero
without it when no TPU is visible or anything fails.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SEED = 0
RMAT_1M = "rmat_1m"
SERVED = "erdos_renyi_100k"
KERNEL_SCALE = 14            # bsr_spmm graph: 2**14 vertices, edgefactor 16


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileCacheEvents:
    """Counts JAX's persistent compilation cache requests and hits."""

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> tuple:
        return (self.counts["compile_requests_use_cache"],
                self.counts["cache_hits"])


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def workload(name: str):
    from repro.configs.base import BFS_WORKLOADS

    return next(w for w in BFS_WORKLOADS if w.name == name)


def build_graph(kind: str, n: int, gen_kwargs: dict, p: int):
    from repro.graphs import generate, shard_graph

    t0 = time.perf_counter()
    src, dst = generate(kind, n, seed=SEED, **gen_kwargs)
    g = shard_graph(src, dst, n, p)
    say("graph", kind=kind, n=n, edges=src.shape[0], p=p,
        build_s=f"{time.perf_counter() - t0:.2f}")
    return src, dst, g


def search_keys(src, n: int, k: int, seed: int):
    """``k`` distinct seeded search keys of degree >= 1 (Graph500 rule)."""
    import numpy as np

    deg = np.bincount(src, minlength=n)
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(np.flatnonzero(deg), k, replace=False).tolist())


def compile_engine(phase, plan_, cache_events):
    req0, hit0 = cache_events.snapshot()
    t0 = time.perf_counter()
    eng = plan_.compile()
    compile_s = time.perf_counter() - t0
    req1, hit1 = cache_events.snapshot()
    d = plan_.describe()
    say(phase, compile_s=f"{compile_s:.2f}", S=plan_.num_sources,
        mode=d["mode"], partition=d["partition"], p=d["p"],
        wire=json.dumps(d["wire_formats"], separators=(",", ":")),
        fused_tail=plan_.use_fused_tail, use_kernel=plan_.opts.use_kernel,
        cache_requests=req1 - req0, cache_hits=hit1 - hit0)
    return eng


def run_and_check(phase, eng, sources, want, dev):
    """One traversal from ``sources``; its columns must equal ``want``."""
    import numpy as np

    t0 = time.perf_counter()
    res = eng.run(sources)                 # blocks until the device is done
    wall = time.perf_counter() - t0
    got = res.dist_host
    stats = res.run_stats.to_host()
    check(got.shape == want.shape,
          f"{phase}: dist shape {got.shape} != reference {want.shape}")
    bad = np.flatnonzero((got != want).any(axis=1))
    check(bad.size == 0,
          f"{phase}: sources {sources} differ from the reference at "
          f"{bad.size} vertices (first {bad[:5].tolist()})")
    say(phase, sources=sources if len(sources) <= 3 else
        f"{sources[:3]}+{len(sources) - 3}", wall_s=f"{wall:.4f}",
        levels=stats["levels"],
        modes=json.dumps(stats["mode_counts"], separators=(",", ":")),
        visited=int((got < int(np.int32(2**30))).sum()),
        peak_bytes=peak_bytes(dev), bitwise="ok")


def kernel_names(eng) -> collections.Counter:
    """Pallas kernels in an engine's compiled program, by name."""
    names = collections.Counter()
    for ln in eng.compiled_hlo().splitlines():
        if 'custom_call_target="tpu_custom_call"' in ln:
            m = re.search(r"%([A-Za-z_]+)", ln)
            names[m.group(1) if m else "?"] += 1
    return names


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_engine(src, dst, g, n, dev, cache_events):
    from repro.core import BFSOptions, plan
    from repro.core.ref import bfs_reference

    keys = search_keys(src, n, 3 + 8, SEED)
    single, batch = keys[:3], keys[3:]
    t0 = time.perf_counter()
    want = bfs_reference(src, dst, n, keys)
    say("engine", reference_s=f"{time.perf_counter() - t0:.2f}",
        keys=len(keys))
    eng = compile_engine("engine", plan(g, BFSOptions(mode="auto"),
                                        num_sources=1), cache_events)
    for j, s0 in enumerate(single):
        run_and_check("engine", eng, [s0], want[:, j:j + 1], dev)
    eng8 = compile_engine("engine", plan(g, BFSOptions(mode="auto"),
                                         num_sources=8), cache_events)
    run_and_check("engine", eng8, batch, want[:, 3:], dev)
    check(eng.trace_count == eng.compile_traces
          and eng8.trace_count == eng8.compile_traces,
          "engine: an engine retraced after compile")


def phase_fused_tail(src, dst, g, n, dev, cache_events):
    from repro.core import BFSOptions, plan
    from repro.core.ref import bfs_reference

    keys = search_keys(src, n, 8, SEED + 1)
    want = bfs_reference(src, dst, n, keys)
    eng = compile_engine("fused_tail", plan(
        g, BFSOptions(mode="dense", wire_format="packed",
                      use_fused_tail=True), num_sources=8), cache_events)
    kernels = kernel_names(eng)
    say("fused_tail", kernels=dict(kernels))
    check(kernels["fold_update"] >= 1,
          f"fused_tail: no compiled fold_update kernel ({dict(kernels)})")
    run_and_check("fused_tail", eng, keys, want, dev)


def phase_spmm_kernel(dev, cache_events):
    from repro.core import BFSOptions, plan
    from repro.core.ref import bfs_reference
    from repro.kernels.bsr_spmm.ops import pack_branch

    n = 1 << KERNEL_SCALE
    src, dst, g = build_graph("rmat", n, {"edge_factor": 16}, 1)
    kmax, blk = g.bsr_shard_caps()
    branch = pack_branch(n, g.p)
    say("spmm_kernel", K=kmax, tile_bytes=kmax * blk * blk * 4,
        pack_branch=branch)
    check(branch == "pallas",
          f"spmm_kernel: n={n} takes the {branch} pack, not bitpack_words")
    keys = search_keys(src, n, 8, SEED + 2)
    want = bfs_reference(src, dst, n, keys)
    eng = compile_engine("spmm_kernel", plan(
        g, BFSOptions(mode="dense", use_kernel=True, wire_format="packed"),
        num_sources=8), cache_events)
    kernels = kernel_names(eng)
    say("spmm_kernel", kernels=dict(kernels))
    check(kernels["bsr_spmm"] >= 1 and kernels["bitpack_words"] >= 1,
          f"spmm_kernel: bsr_spmm/bitpack_words not compiled "
          f"({dict(kernels)})")
    run_and_check("spmm_kernel", eng, keys, want, dev)


def _http(url: str, body=None) -> tuple:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method="GET" if data is None else "POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=600) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode(errors="replace")


def phase_served(dev, cache_events):
    import numpy as np

    from repro.core import BFSOptions
    from repro.core.ref import bfs_reference
    from repro.serve.bfs_service import BFSService
    from repro.serve.engine_cache import EngineCache
    from repro.serve.frontend import serve_http

    wl = workload(SERVED)
    src, dst, g = build_graph(wl.graph, wl.n_vertices, dict(wl.gen_kwargs), 1)
    n = wl.n_vertices
    svc = BFSService(opts=BFSOptions(mode="auto"), batch_buckets=(1, 8),
                     cache=EngineCache())
    svc.add_graph(SERVED, g)
    httpd, _ = serve_http(svc, "127.0.0.1", 0, log=lambda *a: None)
    server = threading.Thread(target=httpd.serve_forever,
                              name="smoke-http", daemon=True)
    server.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    keys = search_keys(src, n, 8, SEED + 3)
    requests = [keys[:1], keys[1:4], keys[4:5], keys[5:8]]
    want = bfs_reference(src, dst, n, keys)
    col = {k: j for j, k in enumerate(keys)}
    try:
        buckets = set()
        for sources in requests:
            t0 = time.perf_counter()
            status, body = _http(f"{base}/v1/traverse",
                                 {"graph": SERVED, "sources": sources})
            wall = time.perf_counter() - t0
            check(status == 200,
                  f"served: POST /v1/traverse {sources} -> {status}: {body}")
            got = np.asarray(body["depths"], dtype=np.int64).T
            ref = want[:, [col[s] for s in sources]]
            check(got.shape == ref.shape and bool((got == ref).all()),
                  f"served: depths for {sources} differ from the reference")
            buckets.add(body["bucket"])
            say("served", sources=sources, status=status,
                bucket=body["bucket"], wall_s=f"{wall:.4f}",
                device_ms=body["timing_ms"]["device"],
                levels=body["stats"]["levels"], peak_bytes=peak_bytes(dev),
                bitwise="ok")
        check(buckets == {1, 8}, f"served: rungs used {sorted(buckets)}, "
              "expected both of 1 and 8")
        status, metrics = _http(f"{base}/metrics")
        check(status == 200, f"served: GET /metrics -> {status}")
        lane = metrics["lanes"][SERVED]
        check(lane["completed"] == len(requests) and lane["failed"] == 0
              and not lane["degraded"] and lane["retries"] == 0,
              f"served: lane counters {json.dumps(lane)[:400]}")
    finally:
        status, _ = _http(f"{base}/admin/shutdown", {})
        server.join(timeout=120)
        httpd.server_close()
    check(status == 200, f"served: POST /admin/shutdown -> {status}")
    check(not server.is_alive(), "served: HTTP server did not stop")
    req, hits = cache_events.snapshot()
    say("served", drained=True, cache_requests_total=req,
        cache_hits_total=hits)


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def check_spans(phase, eng, devs) -> None:
    """The compiled loop's collectives must run over the mesh's devices
    — all of them, not device 0 alone."""
    from repro.analysis import hlo_audit

    k = len(devs)
    mesh_ids = sorted(int(d.id) for d in eng.plan.mesh.devices.flat)
    check(mesh_ids == sorted(int(d.id) for d in devs),
          f"{phase}: mesh devices {mesh_ids} are not the {k} chips")
    rep = hlo_audit.audit_engine(eng, run_check=False)
    loop_ops = [op for op in rep.info["census"] if op["in_loop"]]
    spans = sorted({(op["group_size"], op["n_groups"]) for op in loop_ops})
    check(loop_ops and all(g * ng == k for g, ng in spans),
          f"{phase}: loop collectives do not cover {k} devices: {spans}")
    check(any(g == k for g, _ in spans),
          f"{phase}: no loop collective spans all {k} devices: {spans}")
    say(phase, collectives=len(loop_ops),
        group_spans=json.dumps(spans, separators=(",", ":")),
        audit=rep.summary())
    for v in rep.violations:
        say(phase, audit_violation=str(v))


def phase_four_chips(devs, cache_events):
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import BFSOptions, plan
    from repro.core.ref import bfs_reference
    from repro.launch.mesh import make_grid_mesh

    wl = workload(RMAT_1M)
    n = wl.n_vertices
    src, dst, g = build_graph(wl.graph, n, dict(wl.gen_kwargs), len(devs))
    keys = search_keys(src, n, 1 + 8, SEED)
    want = bfs_reference(src, dst, n, keys)
    meshes = (("1d", Mesh(np.asarray(devs).reshape(len(devs)), ("p",)),
               "p"),
              ("2d", make_grid_mesh(2, 2, devices=devs), None))
    for partition, mesh, axis in meshes:
        phase = f"chips4_{partition}"
        for s, srcs, ref in ((1, keys[:1], want[:, :1]),
                             (8, keys[1:], want[:, 1:])):
            pl = plan(g, BFSOptions(mode="auto"), mesh=mesh, axis=axis,
                      num_sources=s, partition=partition)
            check(pl.use_fused_tail,
                  f"{phase}: default options did not resolve the fused "
                  f"tail on (wire {pl.describe()['wire_formats']})")
            eng = compile_engine(phase, pl, cache_events)
            kernels = kernel_names(eng)
            check(kernels["fold_update"] >= 1,
                  f"{phase}: no compiled fold_update ({dict(kernels)})")
            check_spans(phase, eng, devs)
            run_and_check(phase, eng, srcs, ref, devs[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: engine, kernels and served path on one chip; "
                         "4: rmat_1m on a 1-D mesh and a 2x2 grid only")
    args = ap.parse_args(argv)

    from repro.launch import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform {devs[0].platform!r});"
              " this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} TPU "
              "device(s) are visible", file=sys.stderr)
        return 2
    devs = devs[:args.chips]
    print("# one-off smoke readings on "
          f"{devs[0].device_kind} x{len(devs)}; not benchmark metrics",
          flush=True)
    say("setup", jax=jax.__version__, compile_cache=cache_dir)
    cache_events = CompileCacheEvents()
    t_start = time.perf_counter()

    if args.chips == 4:
        phase_four_chips(devs, cache_events)
    else:
        dev = devs[0]
        wl = workload(RMAT_1M)
        src, dst, g = build_graph(wl.graph, wl.n_vertices,
                                  dict(wl.gen_kwargs), 1)
        phase_engine(src, dst, g, wl.n_vertices, dev, cache_events)
        phase_fused_tail(src, dst, g, wl.n_vertices, dev, cache_events)
        del src, dst, g
        phase_spmm_kernel(dev, cache_events)
        phase_served(dev, cache_events)

    say("done", total_s=f"{time.perf_counter() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
