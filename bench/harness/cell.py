"""One run of one cell: set-up, the measured window, the check against
the reference, and the result line."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

from harness import peaks as peaks_mod
from harness import traces
from harness.common import Annotator, CompileCacheEvents, say
from harness.drive_engine import EngineRunner
from harness.drive_http import HttpRunner

# a traffic mix names its runner; every mix of one runner differs only in
# the parameters of its file
RUNNERS = {"engine_closed": EngineRunner, "http_open": HttpRunner}


def make_runner(spec, cell: dict, seed: int, seconds: float, devices):
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    return RUNNERS[traffic["runner"]](config, traffic, seed, seconds,
                                      devices)


def run_cell(spec, name: str, seed: int, seconds: float, trace: bool,
             devices, t_start: float, peaks: dict | None = None,
             runner=None) -> dict:
    """Run cell ``name`` once on ``devices``; returns the result object.

    ``runner`` lets a caller hand in a runner whose set-up it has
    altered (the control and the fault tests do)."""
    cell = spec.cell(name)
    devices = list(devices)[:int(cell["chips"])]
    peaks = peaks or peaks_mod.for_device(devices[0].device_kind)
    entries = spec.metrics(name, trace)
    readers = {m["name"]: spec.metric_reader(m["name"]) for m in entries}
    if runner is None:
        runner = make_runner(spec, cell, seed, seconds, devices)
        runner.setup(CompileCacheEvents())
    record = runner.record
    record.update(cell=name, seed=seed, seconds=seconds, peaks=peaks,
                  chips=len(devices), trace=None)
    record["setup_s"] = time.perf_counter() - t_start
    say("setup", setup_s=f"{record['setup_s']:.4f}")

    annotate = Annotator(trace)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        traces.start(trace_dir)
    try:
        runner.window(seconds, annotate)
    finally:
        if trace:
            traces.stop()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(_peak_bytes(d) for d in devices)}
    if trace:
        path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        record["trace"] = traces.load(path, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record["trace"].busy_s()
        device["window_s"] = record["trace"].window_s()
    runner.release()

    checks = runner.check()
    correct = all(v <= lim for v, lim in checks.values())
    metrics = {}
    for m in entries:
        value = readers[m["name"]](record)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']!r} found "
                                   "nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = record["trace"].breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    return result


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
