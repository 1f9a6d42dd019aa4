"""Static-analysis passes: HLO plan auditor, registry lint, lock pass.

Unit-level: the census parser / donation / host-transfer checks run on
synthetic HLO text; the lints and the lock pass run on known-bad source
fixtures that must fail with exactly the right rule ids, and on the real
tree, which must be clean.  A subprocess harness (helpers/audit_bad.py)
compiles a deliberately mis-registered exchange on 4 host devices and
checks the auditor catches the lie.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.analysis import hlo_audit
from repro.analysis.report import AuditReport, RULES
from repro.analysis.lint import lint_sources, lint_tree
from repro.analysis.locks import analyze_lock_source, analyze_serve
from repro.core import BFSOptions, plan
from repro.graphs import generate, shard_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# census parser on synthetic HLO
# ---------------------------------------------------------------------------

SYNTH_HLO = """\
HloModule synth, input_output_alias={ {0}: (2, {}, may-alias) }

%body (arg: (s32[], u8[4096])) -> (s32[], u8[4096]) {
  %ag = u8[4096]{0} all-gather(%f), replica_groups={{0,1,2,3}}, channel_id=1, metadata={op_name="jit(run)/while/body/all_gather" source_file="/x/exchange.py" source_line=42}
  %ctrl = s32[] all-reduce(%h), replica_groups={{0,1,2,3}}, to_apply=%sum, metadata={op_name="jit(run)/while/body/psum" source_file="/x/bfs.py" source_line=99}
  %a2a = (s32[64]{0}, s32[64]{0}) all-to-all(%q0, %q1), replica_groups=[2,2]<=[4], metadata={op_name="jit(run)/while/body/all_to_all" source_file="/x/exchange.py" source_line=50}
}

%cond (arg: (s32[], u8[4096])) -> pred[] {
  %lt = pred[] compare(%i, %k), direction=LT
}

ENTRY %main (p0: s32[4,8], p1: u8[4096], p2: s32[4096,1]) -> (s32[4096,1], s32[]) {
  %p2 = s32[4096,1]{1,0} parameter(2)
  %outside = u8[4096]{0} all-gather(%p1), replica_groups={{0,1,2,3}}, channel_id=9
  %w = (s32[], u8[4096]) while(%t), condition=%cond, body=%body
}
"""


def test_census_parses_kinds_groups_and_loop_membership():
    ops = hlo_audit.census(SYNTH_HLO)
    by_kind = {(op.kind, op.computation): op for op in ops}

    ag = by_kind[("all-gather", "body")]
    assert ag.in_loop and ag.group_size == 4 and ag.n_groups == 1
    assert ag.out_bytes == 4096
    assert ag.recv_bytes == pytest.approx(4096 * 3 / 4)
    assert ag.source == "exchange.py:42"

    # tuple-variadic all-to-all with iota replica_groups=[2,2]<=[4]
    a2a = by_kind[("all-to-all", "body")]
    assert a2a.group_size == 2 and a2a.n_groups == 2
    assert a2a.out_bytes == 2 * 64 * 4
    assert a2a.recv_bytes == pytest.approx(2 * 64 * 4 / 2)

    ctrl = by_kind[("all-reduce", "body")]
    assert ctrl.in_loop and ctrl.out_bytes == 4
    assert ctrl.recv_bytes == pytest.approx(4 * 2 * 3 / 4)

    outside = by_kind[("all-gather", "main")]
    assert not outside.in_loop


def test_recv_bytes_conversions():
    assert hlo_audit._recv_bytes("all-gather", 800, 4) == pytest.approx(600)
    assert hlo_audit._recv_bytes("all-to-all", 800, 4) == pytest.approx(600)
    assert hlo_audit._recv_bytes("reduce-scatter", 100, 4) == pytest.approx(300)
    assert hlo_audit._recv_bytes("all-reduce", 100, 4) == pytest.approx(150)
    assert hlo_audit._recv_bytes("all-gather", 800, 1) == 0.0


def test_match_census_control_unpriced_and_tie_steal():
    mk = lambda kind, out, g, comp="body": hlo_audit.CollectiveOp(
        kind=kind, out_bytes=out,
        recv_bytes=hlo_audit._recv_bytes(kind, out, g), group_size=g,
        n_groups=1, computation=comp, in_loop=True, source="s:1")

    # small all-reduce -> control plane, never priced
    rep = AuditReport("t")
    ops = [mk("all-reduce", 4, 4)]
    hlo_audit.match_census(ops, [], rep)
    assert ops[0].role == "control" and rep.ok()

    # data-sized op with no candidate role -> HA002
    rep = AuditReport("t")
    ops = [mk("all-to-all", 4096, 4)]
    hlo_audit.match_census(ops, [], rep)
    assert "HA002" in rep.rules() and not rep.ok()

    # exact-size tie: two identical gathers, two roles with equal models.
    # Greedy alone would stack both ops on one role and HA001 the other;
    # the steal pass must give each required role one op.
    rep = AuditReport("t")
    ops = [mk("all-gather", 512, 4), mk("all-gather", 512, 4)]
    roles = [
        hlo_audit.Role("sieve", ("all-gather",), 384.0, 4, True),
        hlo_audit.Role("bottom_up", ("all-gather",), 384.0, 4, True),
    ]
    assigned = hlo_audit.match_census(ops, roles, rep)
    assert rep.ok(), [str(v) for v in rep.violations]
    assert len(assigned["sieve"]) == 1 and len(assigned["bottom_up"]) == 1


def test_donation_check_ok_missing_and_wrong_dtype():
    rep = AuditReport("t")
    hlo_audit.donation_check(SYNTH_HLO, rep)
    assert rep.ok() and rep.info["donation"]["dist_param"] == 2

    # alias stripped -> the dist buffer is copied, not donated
    rep = AuditReport("t")
    stripped = SYNTH_HLO.replace(
        ", input_output_alias={ {0}: (2, {}, may-alias) }", "")
    hlo_audit.donation_check(stripped, rep)
    assert "HA004" in rep.rules() and not rep.ok()

    # alias points at a non-dist (u8) parameter -> wrong buffer donated
    rep = AuditReport("t")
    wrong = SYNTH_HLO.replace(
        "%p2 = s32[4096,1]{1,0} parameter(2)",
        "%p2 = u8[4096]{0} parameter(2)")
    hlo_audit.donation_check(wrong, rep)
    assert "HA004" in rep.rules()


def test_host_transfer_check_flags_loop_outfeed_only():
    rep = AuditReport("t")
    hlo_audit.host_transfer_check(SYNTH_HLO, rep)
    assert rep.ok()

    rep = AuditReport("t")
    bad = SYNTH_HLO.replace(
        "%ctrl = s32[] all-reduce(%h)",
        "%of = token[] outfeed(%h, %tok)\n  %ctrl = s32[] all-reduce(%h)")
    hlo_audit.host_transfer_check(bad, rep)
    assert "HA005" in rep.rules()


# ---------------------------------------------------------------------------
# the auditor end-to-end on a real (p=1) engine
# ---------------------------------------------------------------------------

def _engine(n=256, **opts):
    src, dst = generate("erdos_renyi", n, seed=0)
    g = shard_graph(src, dst, n, 1)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("p",))
    return plan(g, BFSOptions(**opts), mesh=mesh, axis="p").compile()


def test_audit_engine_clean_on_p1_and_catches_stripped_donation():
    engine = _engine(mode="auto", wire_format="auto")
    rep = hlo_audit.audit_engine(engine, run_check=True)
    assert rep.ok(), [str(v) for v in rep.violations]
    assert rep.info["trace_count"] == engine.compile_traces
    assert rep.name.startswith("hlo:1d:auto:")
    # the machine-readable report round-trips
    d = rep.to_dict()
    assert d["ok"] and d["name"] == rep.name
    assert all(r in RULES for r in
               {v["rule"] for v in d["violations"]} | set())

    # same engine's HLO with donation erased must fail HA004
    rep2 = AuditReport("t")
    text = engine.compiled_hlo()
    import re
    stripped = re.sub(r",?\s*input_output_alias=\{[^}]*\{[^}]*\}[^}]*\}",
                      "", text, count=1)
    hlo_audit.donation_check(stripped, rep2)
    assert "HA004" in rep2.rules()


def test_census_table_renders_loop_rows():
    engine = _engine(mode="dense")
    rep = hlo_audit.audit_engine(engine)
    table = hlo_audit.census_table(rep)
    assert table.splitlines()[0].startswith("role")


# ---------------------------------------------------------------------------
# registry / compiled-loop lint on known-bad fixtures and the real tree
# ---------------------------------------------------------------------------

BAD_REGISTRY = '''
import jax.numpy as jnp
from repro.core.exchange import register_exchange

def wrong_arity(n, p):
    return float(n * p)

@register_exchange("dense", "weird", wrong_arity)
def impl_a(x, axis):
    return x

def impure(p, cap, itemsize, density=1.0):
    return jnp.float32(cap)

@register_exchange("queue", "impure_model", impure)
def impl_b(x, axis):
    return x
'''

BAD_TRACED = '''
import time
import jax.numpy as jnp

def traversal(x):
    t0 = time.time()
    if jnp.any(x > 0):
        x = x + 1
    return x, t0
'''


def test_lint_flags_bad_registrations():
    rep = lint_sources({"core/custom.py": BAD_REGISTRY})
    rules = rep.rules()
    assert "RX001" in rules          # wrong_arity: 2 args, dense needs 5
    assert "RX002" in rules          # impure: jnp inside the byte model
    assert "RX003" in rules          # no packed/compressed twins
    assert not rep.ok()
    assert len(rep.info["registrations"]) == 2


def test_lint_flags_traced_if_and_host_clock():
    rep = lint_sources({"core/bfs.py": BAD_TRACED})
    assert {"RX004", "RX005"} <= rep.rules()
    # same source under a non-traced path: loop-hygiene rules don't apply
    rep2 = lint_sources({"serve/tools.py": BAD_TRACED})
    assert not ({"RX004", "RX005"} & rep2.rules())


def test_lint_suppression_and_bare_allow():
    suppressed = BAD_TRACED.replace(
        "t0 = time.time()",
        "t0 = time.time()  # audit: allow(RX005) -- wall-clock fixture")
    rep = lint_sources({"core/bfs.py": suppressed})
    assert "RX005" not in rep.rules()          # suppressed with a reason
    assert any(v.rule == "RX005" and v.suppressed for v in rep.violations)

    bare = BAD_TRACED.replace(
        "t0 = time.time()",
        "t0 = time.time()  # audit: allow(RX005)")
    rep2 = lint_sources({"core/bfs.py": bare})
    assert "SUP001" in rep2.rules()            # reason string is required


def test_lint_tree_real_repo_is_clean():
    rep = lint_tree()
    assert rep.ok(), [str(v) for v in rep.violations]
    assert len(rep.info["registrations"]) >= 20


# ---------------------------------------------------------------------------
# lock-discipline pass on known-bad fixtures and the real serve/ tree
# ---------------------------------------------------------------------------

BAD_LOCKS = '''
import threading

class Leaky:
    # guarded-by(_lock): _x
    def __init__(self):
        self._lock = threading.Lock()
        self._x = 0            # __init__ is exempt

    def bump(self):
        with self._lock:
            self._x += 1

    def peek(self):
        return self._x         # LK001: no lock held


class Deadlocky:
    # guarded-by(_a): _y
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._y = 0

    def ab(self):
        with self._a:
            with self._b:
                self._y += 1

    def ba(self):
        with self._b:
            with self._a:
                self._y += 1


class Phantom:
    # guarded-by(_missing): _z
    def __init__(self):
        self._z = 0
'''


def test_locks_flag_unguarded_access_cycle_and_unknown_lock():
    rep = analyze_lock_source(BAD_LOCKS, "serve/bad.py")
    rules = rep.rules()
    assert "LK001" in rules          # Leaky.peek
    assert "LK002" in rules          # Deadlocky: _a->_b and _b->_a
    assert "LK003" in rules          # Phantom: annotation names no lock
    # __init__ writes never count
    assert not any(v.rule == "LK001" and "__init__" in v.message
                   for v in rep.violations)


def test_locks_def_level_suppression_covers_method():
    fixed = BAD_LOCKS.replace(
        "    def peek(self):",
        "    # audit: allow(LK001) -- read-only probe, callers tolerate"
        " races\n    def peek(self):")
    rep = analyze_lock_source(fixed, "serve/bad.py")
    assert "LK001" not in rep.rules()
    assert any(v.rule == "LK001" and v.suppressed for v in rep.violations)


def test_analyze_serve_real_tree_is_clean():
    rep = analyze_serve()
    assert rep.ok(), [str(v) for v in rep.violations]
    # the documented false positive stays visible, suppressed, reasoned
    sup = [v for v in rep.violations if v.suppressed]
    assert sup and all(v.suppress_reason for v in sup)


# ---------------------------------------------------------------------------
# serve regression: shutdown is prompt now that _running flips under _cv
# ---------------------------------------------------------------------------

def test_frontend_stats_loop_exits_promptly_on_shutdown():
    import time as _time
    from repro.serve.bfs_service import BFSService
    from repro.serve.engine_cache import EngineCache
    from repro.serve.frontend import BFSFrontend

    src, dst = generate("erdos_renyi", 96, seed=1)
    g = shard_graph(src, dst, 96, 1)
    svc = BFSService(opts=BFSOptions(mode="dense"), batch_buckets=(1,),
                     cache=EngineCache())
    svc.add_graph("er", g, partition="1d", mesh=None)
    lines = []
    fe = BFSFrontend(svc, stats_interval_s=0.05, log=lines.append)
    fe.wait(fe.submit("er", [0]), timeout_s=60.0)
    t0 = _time.monotonic()
    assert fe.shutdown(timeout_s=30.0)
    assert _time.monotonic() - t0 < 5.0
    if fe._stats_thread is not None:
        fe._stats_thread.join(timeout=1.0)
        assert not fe._stats_thread.is_alive()
    assert fe.metrics_payload()["draining"] is True


# ---------------------------------------------------------------------------
# 4-device subprocess: known-bad byte model fails with HA003
# ---------------------------------------------------------------------------

def test_audit_known_bad_fixture_multidev():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never the chip
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers",
                                      "audit_bad.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2500:]}"
    assert "GOOD" in r.stdout and "HA003" in r.stdout
