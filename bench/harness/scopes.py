"""Device time of a traced window under the traversal's own names.

The engine runs each BFS level under ``jax.named_scope`` names: a mode
(``bfs.dense``, ``bfs.queue``, ``bfs.bottom_up``) around the level's
branch and a phase (``bfs.decide``, ``bfs.expand``, ``bfs.exchange``,
``bfs.fold``, ``bfs.update``) around each of its steps.  XLA keeps them
as each instruction's ``op_name`` metadata in the optimized HLO, while
the trace names an op only by its HLO instruction.  So ``op_names``
reads the loop program's module name and instruction -> ``op_name`` map
from its optimized HLO (``BFSEngine.compiled_hlo()``), and a leaf op of
the window takes the names of its instruction when the ``XLA Modules``
event around it (``module_runs``) is that module: the init program has
instructions of the same names.  Where scopes nest, the outermost mode
and the innermost phase count.

Busy time is split without counting any instant twice: where leaf ops
overlap on a chip, the one that started last holds the chip.  The
phases, with ``other`` for busy time under no phase, so add up to the
busy time; each mode holds the time of its levels' ops.

The ``level_ms.*`` readers read ``split``'s result from a record's
``scopes`` key; the cell runner does not fill that key yet (``PERF.md``,
section 7, lists the hooks it needs).
"""

from __future__ import annotations

import bisect
import collections
import re

MODULES = "XLA Modules"
PREFIX = "bfs."
MODES = ("dense", "queue", "bottom_up")
PHASES = ("decide", "expand", "exchange", "fold", "update")
OTHER = "other"

_MODULE_RE = re.compile(r"^HloModule ([^\s,]+)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                       r'metadata=\{[^}]*op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """``{"module": name, "ops": {instruction: op_name}}`` of an optimized
    HLO module's text, keeping the instructions under a ``bfs.`` scope."""
    m = _MODULE_RE.match(hlo_text)
    ops = {}
    for line in hlo_text.splitlines():
        i = _INSTR_RE.match(line)
        if i and PREFIX in i.group(2):
            ops[i.group(1)] = i.group(2)
    return {"module": m.group(1) if m else "", "ops": ops}


def scope_of(op_name: str) -> tuple:
    """``(mode, phase)`` of an ``op_name``; ``None`` where it has none."""
    parts = [p[len(PREFIX):] for p in op_name.split("/")
             if p.startswith(PREFIX)]
    modes = [p for p in parts if p in MODES]
    phases = [p for p in parts if p in PHASES]
    return (modes[0] if modes else None, phases[-1] if phases else None)


def holders(ops) -> list:
    """``(start, end, label)`` pieces of the busy time of labelled
    ``(start, end, label)`` ops, each instant held by the op that started
    last among those running."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    cuts = sorted({t for a, b, _ in ops for t in (a, b)})
    out, running, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(ops) and ops[k][0] <= a:
            running.append(ops[k])
            k += 1
        running = [o for o in running if o[1] > a]
        if not running:
            continue
        label = running[-1][2]
        if out and out[-1][1] == a and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def module_runs(data, n_devices: int) -> list:
    """Per chip, the ``(module, start, end)`` events of the ``XLA
    Modules`` line of the first ``n_devices`` TPU planes of ``data`` (a
    ``jax.profiler.ProfileData``), in the chip order of
    ``traces.from_profile``."""
    runs = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        try:
            idx = int(plane.name.rsplit(":", 1)[1])
        except ValueError:
            continue
        if idx < n_devices:
            runs[idx] = [(ev.name, ev.start_ns, ev.end_ns)
                         for line in plane.lines if line.name == MODULES
                         for ev in line.events]
    return [runs[i] for i in sorted(runs)]


def _inside(starts, ends, t) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < ends[i]


def split(trace, runs, names) -> dict | None:
    """Seconds of the window's busy time, averaged over the chips, under
    each phase (``other`` for none) and each mode, from a
    ``traces.Trace``, its ``module_runs`` and the loop's ``op_names``;
    ``None`` where no leaf op carries a phase (a program without the
    scopes, no device trace)."""
    if trace is None or not names or not names.get("ops"):
        return None
    lo, hi = trace.window
    module, table = names["module"], names["ops"]
    phase, mode = collections.Counter(), collections.Counter()
    for ops, chip_runs in zip(trace.device_ops, runs):
        loop = sorted((a, b) for n, a, b in chip_runs
                      if n.split("(", 1)[0] == module)
        starts, ends = [a for a, _ in loop], [b for _, b in loop]
        labelled = []
        for n, a, b in ops:
            if b <= lo or a >= hi:
                continue
            scope = (None, None)
            if n in table and _inside(starts, ends, a):
                scope = scope_of(table[n])
            labelled.append((max(a, lo), min(b, hi), scope))
        for a, b, (m, p) in holders(labelled):
            phase[p or OTHER] += b - a
            if m:
                mode[m] += b - a
    if not any(phase[p] for p in PHASES):
        return None
    chips = trace.chips
    return {"phase": {p: phase[p] * 1e-9 / chips for p in PHASES + (OTHER,)},
            "mode": {m: mode[m] * 1e-9 / chips for m in MODES}}


def level_ms(rec, kind: str, name: str):
    """Milliseconds per level of the window's device time under phase or
    mode ``name`` (``kind`` ``"phase"`` or ``"mode"``): over the levels of
    the window's traversals, or over the levels that ran in that mode;
    ``None`` where there is nothing to read or the mode ran no level."""
    scopes = rec.get("scopes")
    if scopes is None or rec.get("kind") != "engine":
        return None
    if kind == "mode":
        levels = sum(t["mode_counts"][name] for t in rec["traversals"])
    else:
        levels = sum(t["levels"] for t in rec["traversals"])
    if not levels:
        return None
    return 1e3 * scopes[kind][name] / levels

