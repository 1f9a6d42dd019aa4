"""Device time by the traversal's named scopes: the op_name map, the
split of busy time by phase and mode, and the ``level_ms.*`` readers."""

import pytest

from harness import scopes, traces
from harness.spec import Spec

# two chips over a 100 ns window; the init program runs fusion.1 too
#   chip 0: init fusion.1 0-5; loop: while 10-92 holding fusion.1 10-40
#           (dense expand), all-gather.2 30-50 (dense exchange; holds
#           30-40), fusion.3 50-60 (update), copy.4 60-65 (no scope),
#           fusion.5 70-90 (queue level escalated to its dense expand)
#   chip 1: loop fusion.1 10-30
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 30000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 50000 duration_ps: 10000 }
    events { metadata_id: 4 offset_ps: 60000 duration_ps: 5000 }
    events { metadata_id: 5 offset_ps: 70000 duration_ps: 20000 }
    events { metadata_id: 6 offset_ps: 10000 duration_ps: 82000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 0 duration_ps: 6000 }
    events { metadata_id: 8 offset_ps: 8000 duration_ps: 87000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u8[8]{0} fusion(s32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.2 = u8[8]{0} all-gather(u8[2]{0} %f)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %a)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.4 = s32[8]{0} copy(s32[8]{0} %a)" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = u8[8]{0} fusion(s32[8]{0} %b)" } }
  event_metadata { key: 6 value { id: 6 name: "%while.9 = (s32[8]{0}) while((s32[8]{0}) %t)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_init_fn(11)" } }
  event_metadata { key: 8 value { id: 8 name: "jit_shard_fn(12)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 8000 duration_ps: 87000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "jit_shard_fn(12)" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
"""

BODY = "jit(shard_fn)/while/body"
NAMES = {"module": "jit_shard_fn", "ops": {
    "fusion.1": f"{BODY}/cond/branch_0_fun/bfs.dense/bfs.expand/scatter",
    "all-gather.2": f"{BODY}/cond/branch_0_fun/bfs.dense/bfs.exchange/"
                    "all_gather",
    "fusion.3": f"{BODY}/bfs.update/add",
    "fusion.5": f"{BODY}/cond/branch_1_fun/bfs.queue/bfs.exchange/cond/"
                "branch_1_fun/bfs.dense/bfs.expand/scatter"}}

HLO = """HloModule jit_shard_fn, is_scheduled=true, entry_computation_layout={()->()}

%body (p: s32[8]) -> s32[8] {
  %fusion.1 = u8[8]{0} fusion(s32[8]{0} %p), kind=kLoop, calls=%f1, metadata={op_name="jit(shard_fn)/while/body/bfs.decide/reduce_sum" stack_frame_id=3}
  %copy.4 = s32[8]{0} copy(s32[8]{0} %a)
  ROOT %fusion.3 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop, calls=%f3, metadata={op_name="jit(shard_fn)/while/body/bfs.update/add" source_file="bfs.py" source_line=4}
  %constant.2 = s32[] constant(0), metadata={op_name="jit(shard_fn)/while/body/add"}
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto(SYNTHETIC)
    return traces.from_profile(data, 2), scopes.module_runs(data, 2)


def test_scope_names_match_the_program():
    from repro.core.bfs import MODE_SCOPES, PHASE_SCOPES

    assert MODE_SCOPES == tuple(scopes.PREFIX + m for m in scopes.MODES)
    assert PHASE_SCOPES == tuple(scopes.PREFIX + p for p in scopes.PHASES)


def test_op_names_from_optimized_hlo():
    names = scopes.op_names(HLO)
    assert names == {"module": "jit_shard_fn", "ops": {
        "fusion.1": "jit(shard_fn)/while/body/bfs.decide/reduce_sum",
        "fusion.3": "jit(shard_fn)/while/body/bfs.update/add"}}


@pytest.mark.parametrize("op_name, want", [
    (f"{BODY}/bfs.update/add", (None, "update")),
    (f"{BODY}/cond/branch_0_fun/bfs.bottom_up/bfs.expand/gather",
     ("bottom_up", "expand")),
    # the outermost mode, the innermost phase
    (NAMES["ops"]["fusion.5"], ("queue", "expand")),
    (f"{BODY}/cond/convert_element_type", (None, None)),
    ("", (None, None)),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_holders_give_each_instant_to_the_latest_op():
    ops = [(0, 30, "a"), (20, 50, "b"), (25, 28, "c"), (60, 70, "a")]
    assert scopes.holders(ops) == [
        (0, 20, "a"), (20, 25, "b"), (25, 28, "c"), (28, 50, "b"),
        (60, 70, "a")]


def test_split_partitions_busy_time(synthetic):
    tr, runs = synthetic
    assert [sorted({r[0] for r in chip}) for chip in runs] == [
        ["jit_init_fn(11)", "jit_shard_fn(12)"], ["jit_shard_fn(12)"]]
    got = scopes.split(tr, runs, NAMES)
    ns = 1e-9 / 2                            # averaged over two chips
    assert got["phase"] == pytest.approx({
        "decide": 0.0, "expand": (20 + 20 + 20) * ns, "exchange": 20 * ns,
        "fold": 0.0, "update": 10 * ns,
        # the init program's fusion.1 and the unnamed copy
        "other": (5 + 5) * ns})
    assert sum(got["phase"].values()) == pytest.approx(tr.busy_s())
    assert got["mode"] == pytest.approx({
        "dense": (40 + 20) * ns, "queue": 20 * ns, "bottom_up": 0.0})


def test_split_reads_nothing_without_names(synthetic):
    tr, runs = synthetic
    assert scopes.split(tr, runs, {"module": "jit_shard_fn", "ops": {}}) \
        is None
    assert scopes.split(None, runs, NAMES) is None
    # another program's module: every op is under no scope
    assert scopes.split(tr, runs, dict(NAMES, module="jit__run")) is None


@pytest.fixture(scope="module")
def read():
    spec = Spec()
    return lambda name, rec: spec.metric_reader(name)(rec)


PHASE_READERS = [f"level_ms.{p}" for p in scopes.PHASES + (scopes.OTHER,)]
MODE_READERS = [f"level_ms.{m}" for m in scopes.MODES]


def _record(split):
    return {"kind": "engine", "scopes": split, "traversals": [
        {"levels": 3, "mode_counts": {"dense": 1, "queue": 1,
                                      "bottom_up": 1}},
        {"levels": 2, "mode_counts": {"dense": 1, "queue": 1,
                                      "bottom_up": 0}}]}


def test_level_ms_readers(synthetic, read):
    tr, runs = synthetic
    rec = _record(scopes.split(tr, runs, NAMES))
    per_level = {n: read(n, rec) for n in PHASE_READERS}
    assert sum(per_level.values()) == pytest.approx(1e3 * tr.busy_s() / 5)
    assert per_level["level_ms.expand"] == pytest.approx(1e3 * 30e-9 / 5)
    assert read("level_ms.dense", rec) == pytest.approx(1e3 * 30e-9 / 2)
    assert read("level_ms.queue", rec) == pytest.approx(1e3 * 10e-9 / 2)
    assert read("level_ms.bottom_up", rec) == 0.0
    rec["traversals"][0]["mode_counts"]["bottom_up"] = 0
    assert read("level_ms.bottom_up", rec) is None     # no such level ran


@pytest.mark.parametrize("name", PHASE_READERS + MODE_READERS)
def test_level_ms_reads_nothing_where_there_is_nothing(read, name):
    """A program without the scopes, a record the runner never filled,
    or a served record: no reading, and no error."""
    assert read(name, _record(None)) is None
    assert read(name, {"kind": "engine", "traversals": []}) is None
    assert read(name, {"kind": "http", "requests": []}) is None


def _chip_trace(name, chips):
    """``(trace, module runs, op names)`` of the fixture
    ``data/<name>.pbtxt``, cut by ``data/cut_scoped_trace.py``."""
    import json
    import os

    from jax.profiler import ProfileData

    data_dir = os.path.join(os.path.dirname(__file__), "data")
    with open(os.path.join(data_dir, f"{name}.pbtxt")) as f:
        data = ProfileData.from_text_proto(f.read())
    with open(os.path.join(data_dir, f"{name}.op_names.json")) as f:
        names = json.load(f)
    return (traces.from_profile(data, chips),
            scopes.module_runs(data, chips), names)


def test_chip_trace_scoped_key1(read):
    """One key1 traversal on a v5e (7 levels: 1 dense, 4 queue, 2
    bottom-up).  On the chip the ``XLA Ops`` events carry no ``op_name``
    (their stats are device offsets and durations), so the names come
    from the loop's optimized HLO, and the module decides: the init
    program runs instructions of the loop's names
    (``and_reduce_fusion``), which stay unnamed."""
    tr, runs, names = _chip_trace("v5e_scoped_key1", 1)
    assert tr.window_s() == pytest.approx(15.983)
    assert tr.busy_s() == pytest.approx(15.980438108)
    ran_in = {}
    for n, a, _ in tr.device_ops[0]:
        ran_in.setdefault(n, set()).update(
            m.split("(")[0] for m, x, y in runs[0] if x <= a < y)
    assert ran_in["and_reduce_fusion"] == {"jit_init_fn", "jit_shard_fn"}
    assert "and_reduce_fusion" in names["ops"]

    got = scopes.split(tr, runs, names)
    busy = sum(got["phase"].values())
    assert busy == pytest.approx(tr.busy_s())
    assert got["phase"]["other"] < 0.05 * busy
    rec = {"kind": "engine", "scopes": got, "traversals": [
        {"levels": 7, "mode_counts": {"dense": 1, "queue": 4,
                                      "bottom_up": 2}}]}
    per_level = {n: read(n, rec) for n in PHASE_READERS}
    assert sum(per_level.values()) == pytest.approx(1e3 * busy / 7,
                                                    rel=0.005)
    # a queue level costs about five dense ones on one chip
    assert read("level_ms.queue", rec) > 4 * read("level_ms.dense", rec)
    top = {n: scopes.scope_of(names["ops"].get(n, ""))
           for n, _ in tr.top_ops(10)}
    assert all(phase for _, phase in top.values())
    assert top["fusion.7"] == ("queue", "expand")
    assert top["fusion.68"] == (None, "decide")
    # the old reduction reads this trace as before
    assert 100 * (1 - tr.busy_s() / tr.window_s()) == pytest.approx(
        read("device_idle.teps", {"trace": tr}))


def test_chip_trace_scoped_p4(read):
    """The first key of ``graph500_s20_p4.key1`` on four v5e chips (7
    levels: 1 dense, 4 queue, 2 bottom-up): the split holds on every
    chip, and the exchange does real work, most of it the queue level's
    overflow all-reduce."""
    tr, runs, names = _chip_trace("v5e_scoped_p4", 4)
    assert tr.chips == 4 and len(runs) == 4
    assert tr.window_s() == pytest.approx(3.478)
    got = scopes.split(tr, runs, names)
    busy = sum(got["phase"].values())
    assert busy == pytest.approx(tr.busy_s())
    assert got["phase"]["other"] < 0.05 * busy
    rec = {"kind": "engine", "scopes": got, "traversals": [
        {"levels": 7, "mode_counts": {"dense": 1, "queue": 4,
                                      "bottom_up": 2}}]}
    per_level = {n: read(n, rec) for n in PHASE_READERS}
    assert sum(per_level.values()) == pytest.approx(1e3 * busy / 7,
                                                    rel=0.005)
    assert per_level["level_ms.exchange"] > 0.01 * sum(per_level.values())
    assert read("level_ms.queue", rec) > 4 * read("level_ms.dense", rec)
    top = {n: scopes.scope_of(names["ops"].get(n, ""))
           for n, _ in tr.top_ops(10)}
    assert all(phase for _, phase in top.values())
    assert top["fusion.8"] == ("queue", "expand")
    assert top["fusion.121"] == (None, "decide")
    assert scopes.scope_of(names["ops"]["all-reduce.6"]) == (
        "queue", "exchange")
    assert 100 * (1 - tr.busy_s() / tr.window_s()) == pytest.approx(
        read("device_idle.teps", {"trace": tr}))
