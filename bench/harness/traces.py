"""Reduce a profiler trace to device busy time, idle gaps, collective
exposure and kernel time.

The trace is the ``.xplane.pb`` the JAX profiler writes, read with
``jax.profiler.ProfileData``.  A device's work is the events of the
``XLA Ops`` line of its plane (``/device:TPU:<i>``).  On a TPU that line
nests: a ``while`` op holds ``conditional`` ops that hold the fusions,
so only the leaves (events that hold no other event) count as work; a
gap between leaves inside a loop is time no operation ran.  Each event
is named by its HLO text (``%fusion.7 = s32[...] fusion(...)``); the
instruction name before `` = `` is kept.  The benchmark's own host spans
are the events named ``bench.*`` on the host plane, and the window is
the ``bench.window`` span.
"""

from __future__ import annotations

import collections

# HLO opcodes of the collectives; an op is one when its name (the HLO
# instruction name, which XLA derives from the opcode) starts with one
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans only, no py calls
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(merged, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` that ``merged`` intervals leave free."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def covered(merged, a: float, b: float) -> float:
    """Length of ``[a, b]`` that ``merged`` intervals cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged
               if y > a and x < b)


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def op_name(text: str) -> str:
    """``fusion.7`` from ``%fusion.7 = s32[...] fusion(...)``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(events) -> list:
    """The ``(name, start, end)`` events that hold no other event."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(order)
    stack = []
    for i, (_, a, b) in enumerate(order):
        while stack and order[stack[-1]][2] <= a:
            stack.pop()
        if stack and order[stack[-1]][2] >= b:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


class Trace:
    """Device ops per chip and host spans, on one clock (nanoseconds)."""

    def __init__(self, device_ops, host_spans, window=None):
        self.device_ops = [sorted(ops, key=lambda e: e[1])
                           for ops in device_ops]
        self.host_spans = sorted(host_spans, key=lambda e: e[1])
        if window is None:
            win = [s for s in self.host_spans if s[0] == WINDOW_SPAN]
            if win:
                window = (win[0][1], win[-1][2])
            else:
                ends = [t for ops in self.device_ops for _, a, b in ops
                        for t in (a, b)]
                window = (min(ends), max(ends)) if ends else (0.0, 0.0)
        self.window = window
        lo, hi = window
        self._busy = [union(clip([(a, b) for _, a, b in ops], lo, hi))
                      for ops in self.device_ops]

    @property
    def chips(self) -> int:
        return max(1, len(self.device_ops))

    def has_device_ops(self) -> bool:
        return any(self.device_ops)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(b - a for m in self._busy for a, b in m) * 1e-9 \
            / self.chips

    def has_collectives(self) -> bool:
        return any(is_collective(n) for ops in self.device_ops
                   for n, _, _ in ops)

    def collective_exposed_s(self) -> float:
        """Collective time with no other op running on that chip,
        averaged over the chips."""
        lo, hi = self.window
        total = 0.0
        for ops in self.device_ops:
            coll = union(clip([(a, b) for n, a, b in ops
                               if is_collective(n)], lo, hi))
            other = union(clip([(a, b) for n, a, b in ops
                                if not is_collective(n)], lo, hi))
            total += sum((b - a) - covered(other, a, b) for a, b in coll)
        return total * 1e-9 / self.chips

    def kernel(self, name: str) -> tuple:
        """``(calls, seconds)`` of the op ``name`` (or ``name.<k>``),
        summed over the chips, within the window."""
        lo, hi = self.window
        calls, ns = 0, 0.0
        for ops in self.device_ops:
            for n, a, b in ops:
                if (n == name or n.startswith(name + ".")) and b > lo \
                        and a < hi:
                    calls += 1
                    ns += min(b, hi) - max(a, lo)
        return calls, ns * 1e-9

    def top_ops(self, k: int = 10) -> list:
        lo, hi = self.window
        per = collections.Counter()
        for ops in self.device_ops:
            for n, a, b in clip_named(ops, lo, hi):
                per[n] += (b - a) * 1e-9 / self.chips
        return [[n, s] for n, s in per.most_common(k)]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest device idle gaps (chip 0), each named by the
        benchmark's host span that covers most of it."""
        lo, hi = self.window
        free = gaps(self._busy[0] if self._busy else [], lo, hi)
        free.sort(key=lambda g: g[0] - g[1])
        spans = [s for s in self.host_spans if s[0] != WINDOW_SPAN]
        out = []
        for a, b in free[:k]:
            best, best_cov = "no host span", 0.0
            for n, x, y in spans:
                cov = max(0.0, min(b, y) - max(a, x))
                if cov > best_cov:
                    best, best_cov = n, cov
            out.append([best, (b - a) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def clip_named(ops, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops
            if b > lo and a < hi]


def from_profile(data, n_devices: int) -> Trace:
    """A ``Trace`` of the first ``n_devices`` TPU planes of ``data`` (a
    ``jax.profiler.ProfileData``)."""
    device_ops, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                idx = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if idx >= n_devices:
                continue
            device_ops[idx] = leaves(
                (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for ev in line.events)
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(SPAN_PREFIX)]
    return Trace([device_ops[i] for i in sorted(device_ops)], host)


def load(path: str, n_devices: int) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path), n_devices)
