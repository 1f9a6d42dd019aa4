"""Find the knee of an open-loop cell: the highest offered rate at which
completions keep pace with arrivals.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

One process sets the cell up once, then runs one window per rate with
the cell's traffic at that rate.  For each rate it prints the rate
offered, the rate completed within the window, the backlog (requests
sent in the window and not answered when it closed) and the p90
latency, and checks every answer against the reference at the end.
Not one of the benchmark's runs: a benchmark PR uses it to set the rate
it writes into a traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from harness.cell import make_runner
    from harness.common import Annotator, CompileCacheEvents, nearest_rank
    from harness.drive_http import make_schedule
    from harness.spec import Spec
    from repro.launch import use_compile_cache

    use_compile_cache()
    import jax
    import numpy as np

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: no TPU visible", file=sys.stderr)
        return 2
    spec = Spec()
    cell = spec.cell(args.workload)
    runner = make_runner(spec, cell, args.seed, args.seconds, devices)
    runner.setup(CompileCacheEvents())
    degree = np.bincount(runner.src, minlength=runner.n)
    all_requests, rows = [], []
    for rate in (float(r) for r in args.rates.split(",")):
        runner.traffic = dict(runner.traffic, rate_per_s=rate)
        runner.schedule = make_schedule(runner.traffic, degree, args.seed,
                                        args.seconds)
        runner.window(args.seconds, Annotator(False))
        reqs = runner.record["requests"]
        done_in = sum(r["done_s"] <= args.seconds for r in reqs)
        row = {"offered_per_s": rate, "sent": len(reqs),
               "completed_in_window_per_s": done_in / args.seconds,
               "backlog_at_close": len(reqs) - done_in,
               "failed": runner.record["failed"],
               "p50_ms": 1e3 * nearest_rank([r["latency_s"] for r in reqs],
                                            0.5),
               "p90_ms": 1e3 * nearest_rank([r["latency_s"] for r in reqs],
                                            0.9),
               "lateness_max_s": max(runner.record["lateness_s"],
                                     default=0.0)}
        rows.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
        all_requests += reqs
        time.sleep(2.0)            # let the queue drain between rates
    runner.release()
    runner.record["requests"] = all_requests
    runner.record["failed"] = sum(r["status"] != 200 for r in all_requests)
    checks = runner.check()
    print(json.dumps({"rows": rows, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
