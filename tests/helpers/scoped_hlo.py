"""Subprocess harness: the level loop's named scopes in compiled HLO.

Run as: python tests/helpers/scoped_hlo.py
Compiles the auto-mode engine of a toy RMAT graph at S = 1 and S = 8,
1-D on one and on four forced host devices and 2-D on a 2x2 grid, and
prints one JSON line per engine: the fusions, custom-calls and
collectives that the loop body's code emits (their ``op_name`` lies in
the ``while`` body) and that carry no ``bfs.<phase>`` scope, loop
plumbing excepted; the (mode, phase) pairs the body's ops carry; and
whether the optimized HLO with its metadata stripped equals that of the
same engine compiled with the scopes turned off.  Ops the compiler makes
itself (tree reductions, hoisted broadcasts) carry no ``op_name`` of the
body and are not the loop code's to name.
"""

import contextlib
import importlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.launch import host_devices  # noqa: E402

host_devices(4)  # must precede the jax import below

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.analysis.hlo_audit import _loop_computations  # noqa: E402
from repro.core import BFSOptions, plan  # noqa: E402
from repro.core.bfs import MODE_SCOPES, PHASE_SCOPES  # noqa: E402
from repro.graphs import generate, shard_graph  # noqa: E402
from repro.launch.hlo_parse import _split_computations  # noqa: E402
from repro.launch.mesh import make_grid_mesh  # noqa: E402

bfs_mod = importlib.import_module("repro.core.bfs")

N = 2048
# fusions, kernels and collectives: every op of the loop that does work
_OP_RE = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = .*? (fusion|custom-call|"
                    r"all-reduce|all-gather|all-to-all|reduce-scatter|"
                    r"collective-permute)(?:-start)?\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# loop plumbing: a lax.cond turns its predicate into a branch index
# outside the branches, so under no phase
PLUMBING = ("convert_element_type",)
_SECTIONS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip_metadata(hlo: str) -> str:
    """The HLO text without op metadata and its source-location tables."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in _SECTIONS:
            skip = True
        elif not line:
            skip = False
        if not skip:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def loop_ops(hlo: str):
    """``(instruction, op_name)`` of the work ops of the while body and
    the computations it calls, fused computations aside."""
    comps = _split_computations(hlo)
    fused = set()
    for lines in comps.values():
        if not isinstance(lines, str):
            for ln in lines:
                fused.update(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)",
                                        ln))
    for comp in sorted(_loop_computations(comps) - fused):
        for ln in comps[comp]:
            m = _OP_RE.match(ln)
            if m:
                on = _OPNAME_RE.search(ln)
                yield m.group(1), on.group(1) if on else ""


def check(label, build):
    hlo = build().compiled_hlo()
    unscoped, pairs = [], set()
    for instr, on in loop_ops(hlo):
        parts = on.split("/")
        phases = [p for p in parts if p in PHASE_SCOPES]
        modes = [p for p in parts if p in MODE_SCOPES]
        if phases:
            pairs.add(f"{modes[0] if modes else '-'}/{phases[-1]}")
        elif "while/body" in on and parts[-1] not in PLUMBING:
            unscoped.append([instr, on])
    scope = bfs_mod._scope
    bfs_mod._scope = lambda name: contextlib.nullcontext()
    try:
        bare = build().compiled_hlo()
    finally:
        bfs_mod._scope = scope
    print(json.dumps({"engine": label, "unscoped": unscoped,
                      "pairs": sorted(pairs),
                      "names_dropped": not re.search(
                          r'op_name="[^"]*/bfs\.', bare),
                      "same_program": strip_metadata(hlo)
                      == strip_metadata(bare)}), flush=True)


def main():
    src, dst = generate("rmat", n=N, seed=0)
    devs = jax.devices()
    g1, g4 = shard_graph(src, dst, N, 1), shard_graph(src, dst, N, 4)
    mesh1 = Mesh(np.asarray(devs[:1]).reshape(1), ("p",))
    mesh4 = Mesh(np.asarray(devs[:4]).reshape(4), ("p",))
    grid = make_grid_mesh(2, 2)
    opts = BFSOptions(mode="auto")
    for s in (1, 8):
        check(f"1d_p1_S{s}", lambda: plan(g1, opts, mesh=mesh1, axis="p",
                                          num_sources=s).compile())
        check(f"1d_p4_S{s}", lambda: plan(g4, opts, mesh=mesh4, axis="p",
                                          num_sources=s).compile())
        check(f"2d_2x2_S{s}", lambda: plan(g4, opts, mesh=grid,
                                           num_sources=s,
                                           partition="2d").compile())


if __name__ == "__main__":
    main()
