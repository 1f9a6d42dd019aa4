"""Run a cell with the control in the program's place, on several seeds.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Each seed sets the cell up as a benchmark run does, replaces the program
with the control (``harness/control.py``: the reference, stopped one
level short of each key's last level), drives the window and compares
with the reference.  Prints each seed's compared numbers; every run must
read not correct.  The benchmark's own runs never run this.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from harness import control
    from harness.cell import make_runner, run_cell
    from harness.common import CompileCacheEvents
    from harness.spec import Spec
    from repro.launch import use_compile_cache

    use_compile_cache()
    import jax

    devices = jax.devices()
    spec = Spec()
    cell = spec.cell(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        runner = make_runner(spec, cell, seed, args.seconds, devices)
        runner.setup(CompileCacheEvents())
        control.install(runner)
        res = run_cell(spec, args.workload, seed, args.seconds, False,
                       devices, t0, runner=runner)
        rows.append({"seed": seed, "correct": res["correct"],
                     "checks": res["checks"]})
        print("[control] " + json.dumps(rows[-1]), flush=True)
    print(json.dumps({"control": args.workload, "runs": rows,
                      "all_incorrect": not any(r["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
