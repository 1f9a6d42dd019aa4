"""Trim a profiler trace to a small text-proto fixture for the tests.

    python3 bench/tests/data/trim_trace.py <in.xplane.pb> <out.pbtxt> [--ms 40] [--devices 1]

Keeps the ``XLA Ops`` events of the first ``--devices`` TPU planes and
the ``bench.*`` host spans that fall in the first ``--ms`` milliseconds
of the ``bench.window`` span, rebased to the window's start; the
``bench.window`` span itself is cut to that length.  The result reads
back with ``jax.profiler.ProfileData.from_text_proto``.
"""

from __future__ import annotations

import argparse
import json
import sys


def _quote(s: str) -> str:
    return json.dumps(s)


def trim(data, ms: float, devices: int) -> str:
    host = [(e.name, e.start_ns, e.duration_ns)
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("bench.")]
    win = [h for h in host if h[0] == "bench.window"]
    t0 = win[0][1] if win else min(h[1] for h in host)
    t1 = t0 + ms * 1e6
    planes = []
    for p in data.planes:
        if not p.name.startswith("/device:TPU:"):
            continue
        if int(p.name.rsplit(":", 1)[1]) >= devices:
            continue
        evs = [(e.name, e.start_ns, e.duration_ns)
               for line in p.lines if line.name == "XLA Ops"
               for e in line.events if t0 <= e.start_ns < t1]
        planes.append((p.name, "XLA Ops", evs))
    spans = [(n, s, min(d, t1 - s)) for n, s, d in host if t0 <= s < t1]
    planes.append(("/host:CPU", "bench spans", spans))
    out = []
    for pid, (pname, lname, evs) in enumerate(planes, 1):
        names = {n: k for k, n in enumerate(sorted({e[0] for e in evs}), 1)}
        body = [f"planes {{ id: {pid} name: {_quote(pname)}",
                f"  lines {{ id: 1 name: {_quote(lname)} timestamp_ns: 0"]
        for n, s, d in evs:
            body.append(f"    events {{ metadata_id: {names[n]} "
                        f"offset_ps: {int(round((s - t0) * 1000))} "
                        f"duration_ps: {int(round(d * 1000))} }}")
        body.append("  }")
        for n, k in names.items():
            body.append(f"  event_metadata {{ key: {k} value {{ id: {k} "
                        f"name: {_quote(n)} }} }}")
        body.append("}")
        out += body
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--ms", type=float, default=40.0)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    text = trim(ProfileData.from_file(args.src), args.ms, args.devices)
    with open(args.dst, "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
