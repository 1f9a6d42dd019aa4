"""Cut a profiler trace and its loop's HLO to a fixture of the scope split.

    python3 bench/tests/data/cut_scoped_trace.py <in.xplane.pb> <loop.hlo.txt> <out.pbtxt> [--ms 16000] [--devices 1]

Like ``trim_trace.py``, and besides keeps what ``harness.scopes`` reads:
the ``XLA Ops`` events of the first ``--devices`` TPU planes that start
in the first ``--ms`` milliseconds of the ``bench.window`` span, the
``XLA Modules`` events over that time, and the ``bench.*`` host spans
that start in it, rebased to the window's start and cut at
its end.  It also writes ``<out>.op_names.json``: ``scopes.op_names`` of
the loop's optimized HLO, kept to the instructions the fixture holds.
The fixture reads back with ``jax.profiler.ProfileData.from_text_proto``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPANS = ("bench.",)


def cut(data, ms: float, devices: int) -> tuple:
    """``(text, names)``: the fixture and the event names it holds."""
    host = [(e.name, e.start_ns, e.end_ns)
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith(SPANS)]
    t0 = min(s for n, s, _ in host if n == "bench.window")
    t1 = t0 + ms * 1e6
    planes = []
    for p in data.planes:
        if (not p.name.startswith("/device:TPU:")
                or int(p.name.rsplit(":", 1)[1]) >= devices):
            continue
        lines = []
        for line in p.lines:
            if line.name == "XLA Ops":
                evs = [(e.name, e.start_ns, min(e.end_ns, t1))
                       for e in line.events if t0 <= e.start_ns < t1]
            elif line.name == "XLA Modules":
                evs = [(e.name, max(e.start_ns, t0), min(e.end_ns, t1))
                       for e in line.events
                       if e.end_ns > t0 and e.start_ns < t1]
            else:
                continue
            lines.append((line.name, evs))
        planes.append((p.name, sorted(lines)))
    planes.append(("/host:CPU", [("host spans", [
        (n, s, min(e, t1)) for n, s, e in host if t0 <= s < t1])]))
    out, held = [], set()
    for pid, (pname, lines) in enumerate(planes, 1):
        ids = {n: k for k, n in enumerate(
            sorted({e[0] for _, evs in lines for e in evs}), 1)}
        held |= set(ids)
        out.append(f"planes {{ id: {pid} name: {json.dumps(pname)}")
        for lid, (lname, evs) in enumerate(lines, 1):
            out.append(f"  lines {{ id: {lid} name: {json.dumps(lname)} "
                       "timestamp_ns: 0")
            out += [f"    events {{ metadata_id: {ids[n]} "
                    f"offset_ps: {int(round((s - t0) * 1000))} "
                    f"duration_ps: {int(round((e - s) * 1000))} }}"
                    for n, s, e in evs]
            out.append("  }")
        out += [f"  event_metadata {{ key: {k} value {{ id: {k} "
                f"name: {json.dumps(n)} }} }}" for n, k in ids.items()]
        out.append("}")
    return "\n".join(out) + "\n", held


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("src")
    ap.add_argument("hlo")
    ap.add_argument("dst")
    ap.add_argument("--ms", type=float, default=16000.0)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from jax.profiler import ProfileData

    from harness import scopes
    from harness.traces import op_name

    text, held = cut(ProfileData.from_file(args.src), args.ms, args.devices)
    with open(args.dst, "w") as f:
        f.write(text)
    with open(args.hlo) as f:
        names = scopes.op_names(f.read())
    instr = {op_name(n) for n in held}
    names["ops"] = {k: v for k, v in sorted(names["ops"].items())
                    if k in instr}
    with open(os.path.splitext(args.dst)[0] + ".op_names.json", "w") as f:
        json.dump(names, f, indent=0)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
