"""The trace reduction: busy union, idle gaps and their host spans,
collective exposure and kernel time."""

import os

import pytest

from harness import traces

DATA = os.path.join(os.path.dirname(__file__), "data")

# two chips over a 100 us window (times in ns):
#   chip 0: a while op 0-95 holding fusion 0-30, all-reduce 20-50
#           (exposed 30-50), fusion 70-80, fold_update 80-90
#   chip 1: fusion 0-40, all-to-all 60-100 (all exposed)
#   host:   bench.window 0-100, bench.dispatch 0-10, bench.sync 10-100
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000 }
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 30000 }
    events { metadata_id: 1 offset_ps: 70000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 80000 duration_ps: 10000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 95000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.3 = s32[8]{0} all-reduce(s32[8]{0} %f)" } }
  event_metadata { key: 3 value { id: 3 name: "%fold_update.7 = (s32[8]{0}) custom-call()" } }
  event_metadata { key: 4 value { id: 4 name: "jit_loop" } }
  event_metadata { key: 5 value { id: 5 name: "%while.2 = (s32[8]{0}) while((s32[8]{0}) %t)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 60000 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-to-all.7" } } }
planes { id: 3 name: "/device:TPU:2"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } } }
planes { id: 4 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 10000 duration_ps: 90000 }
    events { metadata_id: 4 offset_ps: 10000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.sync" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(x)" } } }
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return traces.from_profile(ProfileData.from_text_proto(SYNTHETIC), 2)


def test_leaves_and_op_names():
    ev = [("while.1", 0, 100), ("cond.2", 0, 60), ("fusion.3", 0, 30),
          ("fusion.4", 30, 60), ("fusion.5", 70, 90), ("copy.6", 95, 99)]
    assert sorted(e[0] for e in traces.leaves(ev)) == [
        "copy.6", "fusion.3", "fusion.4", "fusion.5"]
    assert traces.op_name("%all-to-all.3 = s32[4]{0} all-to-all(...)") == \
        "all-to-all.3"
    assert traces.is_collective("all-gather-start.1")
    assert not traces.is_collective("fusion.all-reduce")


def test_interval_arithmetic():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert traces.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert traces.gaps([], 2, 4) == [(2, 4)]
    assert traces.covered([(0, 3), (5, 8)], 2, 6) == 2
    assert traces.clip([(0, 3), (5, 8), (9, 12)], 1, 10) == [
        (1, 3), (5, 8), (9, 10)]


def test_window_busy_and_idle(synthetic):
    tr = synthetic
    assert tr.chips == 2                     # the third plane is not ours
    assert tr.window_s() == pytest.approx(100e-9)
    # chip 0 busy 0-50, 70-90 = 70 ns; chip 1 busy 0-40, 60-100 = 80 ns
    assert tr.busy_s() == pytest.approx(75e-9)


def test_collective_exposure(synthetic):
    assert synthetic.has_collectives()
    # chip 0: 20 ns of its all-reduce alone; chip 1: 40 ns; mean 30 ns
    assert synthetic.collective_exposed_s() == pytest.approx(30e-9)


def test_kernel_time_and_top_ops(synthetic):
    assert synthetic.kernel("fold_update") == (1, pytest.approx(10e-9))
    assert synthetic.kernel("fold") == (0, 0.0)         # whole names only
    assert synthetic.kernel("fusion.9") == (0, 0.0)     # not a chip of ours
    top = dict(synthetic.top_ops())
    assert top["fusion.1"] == pytest.approx((40 + 40) * 1e-9 / 2)
    assert top["all-to-all.7"] == pytest.approx(20e-9)


def test_idle_gaps_named_by_host_span(synthetic):
    gaps = synthetic.idle_gaps()
    # chip 0 idles 50-70 and 90-100, both under bench.sync
    assert [g[0] for g in gaps] == ["bench.sync", "bench.sync"]
    assert gaps[0][1] == pytest.approx(20e-9)
    assert gaps[1][1] == pytest.approx(10e-9)
    assert set(synthetic.breakdown()) == {"device_ops", "idle_gaps"}


def test_no_device_planes_reads_nothing():
    from jax.profiler import ProfileData

    host_only = SYNTHETIC[SYNTHETIC.index('planes { id: 4'):]
    tr = traces.from_profile(ProfileData.from_text_proto(host_only), 1)
    assert not tr.has_device_ops() and tr.busy_s() == 0.0


def _chip_fixture(name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        return traces.from_profile(ProfileData.from_text_proto(f.read()), 1)


def test_chip_trace_fused_tail():
    """A one-chip v5e trace of a fused-tail traversal (scale 16, S = 8):
    the nested while/conditional ops are dropped, the kernel is found by
    its stable name, and the gaps fall under the benchmark's spans."""
    tr = _chip_fixture("v5e_fused_tail_s8.pbtxt")
    assert tr.window_s() == pytest.approx(0.254494628)
    assert 0.95 * tr.window_s() < tr.busy_s() < tr.window_s()
    calls, seconds = tr.kernel("fold_update")
    assert calls == 7 and seconds == pytest.approx(291.42e-6)
    names = [n for n, _ in tr.top_ops()]
    assert names[0] == "fusion.22" and "fold_update.7" in names
    assert not any(n.startswith(("while", "cond")) for n in names)
    assert not tr.has_collectives()
    gaps = tr.idle_gaps()
    assert {g[0] for g in gaps} <= {"bench.dispatch", "bench.sync"}
    assert sum(g[1] for g in gaps) <= tr.window_s() - tr.busy_s() + 1e-12


def test_chip_trace_auto_key1():
    """A one-chip v5e trace of a single-key auto traversal, cut to its
    first 120 ms: busy time is the union of the leaf ops only."""
    tr = _chip_fixture("v5e_auto_key1.pbtxt")
    assert tr.window_s() == pytest.approx(0.120)
    assert tr.busy_s() == pytest.approx(0.116970856)
    assert tr.kernel("fold_update") == (0, 0.0)
    assert tr.top_ops(1)[0][0] == "fusion.7"
