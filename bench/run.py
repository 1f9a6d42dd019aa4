"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and metrics are found by name under
``bench/``.  Set-up facts go to standard output as they happen, the
numbers compared with the reference go to standard error, and the last
line of standard output is the result as one JSON object.  With no TPU,
or fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.spec import Spec

    spec = Spec()
    cell = spec.cell(args.workload)

    from repro.launch import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU visible (platform {devices[0].platform!r}); "
              "the benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, "
              f"{len(devices)} visible", file=sys.stderr)
        return 2
    print(f"[setup] workload={args.workload} seed={args.seed} "
          f"device={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    from harness.cell import run_cell

    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
