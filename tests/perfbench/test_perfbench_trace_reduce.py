"""perfbench.trace_reduce: hand-worked numbers on a synthetic trace, and the
recorded chip trace under perfbench/testdata/."""

import json
import os

import pytest

from perfbench import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTDATA = os.path.join(ROOT, "perfbench", "testdata")

# one chip: a while loop (1-9 us) around a fusion, an all-reduce and the
# attention kernel, then a second fusion after a 3 us gap; a second chip that
# ran one fusion only. Host: step (0-10), wait (10-12), step (12-14).
TRACE = {
    "devices": {
        "/device:TPU:0": [["while.1", 1000, 8000], ["fusion.1", 1000, 2000],
                          ["all-reduce.1", 3000, 1000],
                          ["ragged_paged_attention", 4000, 4000],
                          ["fusion.2", 12000, 1000]],
        "/device:TPU:1": [["fusion.1", 1000, 2000]],
    },
    # programs of chip 0: one whole run (1-9 us), one cut by the window's
    # end (12-16 us of which 2 lie inside), and a small other program
    "modules": {"/device:TPU:0": [["jit_run(123)", 1000, 8000],
                                  ["jit_run(123)", 12000, 4000],
                                  ["jit_convert(9)", 9500, 100]]},
    "host": [["bench.sched_step", 0, 10000], ["bench.wait_arrival", 10000, 2000],
             ["bench.sched_step", 12000, 2000]],
}


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.total([(1, 4), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_self_times_subtract_direct_children_only():
    got = {n: (t, leaf) for n, _, _, t, leaf in tr.self_times(
        [["outer", 0, 100], ["mid", 10, 50], ["leaf", 20, 10],
         ["late", 70, 10]])}
    assert got == {"outer": (40, False), "mid": (40, False),
                   "leaf": (10, True), "late": (10, True)}


def test_reduce_synthetic_trace():
    s = tr.reduce_trace(TRACE)
    assert s.chips == 2
    assert s.window_s == pytest.approx(14e-6)
    assert s.busy_s == pytest.approx((9e-6 + 2e-6) / 2)
    assert s.idle_share == pytest.approx(1 - 5.5 / 14)
    assert s.idle_share_worst == pytest.approx(1 - 2 / 14)
    # by-name self times are means over the chips and add up to the busy time
    assert s.op_seconds["while.1"] == pytest.approx(0.5e-6)
    assert s.op_seconds["ragged_paged_attention"] == pytest.approx(2e-6)
    assert s.op_seconds["fusion.1"] == pytest.approx(2e-6)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    assert sorted(n for n, _ in s.top_ops(2)) == [
        "fusion.1", "ragged_paged_attention"]
    assert s.seconds_of("ragged_paged_attention") == pytest.approx(2e-6)
    assert s.seconds_of(r"^fusion") == pytest.approx(2.5e-6)
    # the all-reduce ran with no compute beside it on chip 0
    assert s.collective_s == pytest.approx(0.5e-6)
    assert s.collective_exposed_s == pytest.approx(0.5e-6)
    # one dispatch lasts what the whole execution lasted; the cut one counts
    # by its share
    assert s.dispatch_s == pytest.approx(8e-6)
    assert s.dispatches == pytest.approx(1.5)
    # idle gaps of the first chip, longest first, by what the host was doing
    assert s.idle_gaps[0] == ("bench.wait_arrival", pytest.approx(3e-6))
    assert sorted(g[0] for g in s.idle_gaps[1:]) == ["bench.sched_step"] * 2


def test_events_are_clipped_to_the_host_window():
    trace = {"devices": {"/device:TPU:0": [["a", 0, 100], ["b", 150, 100]]},
             "host": [["bench.train_step", 50, 150]]}       # window 50-200
    s = tr.reduce_trace(trace)
    assert s.busy_s == pytest.approx(100e-9)                # 50 of a, 50 of b
    assert s.idle_share == pytest.approx(1 / 3)


def test_without_host_spans_the_window_is_the_devices_extent():
    s = tr.reduce_trace({"devices": {"/device:TPU:0": [["a", 10, 10],
                                                       ["b", 40, 10]]}})
    assert s.window_s == pytest.approx(40e-9)
    assert s.idle_gaps == [("outside any bench span", pytest.approx(20e-9))]


def test_no_device_operation_gives_nothing():
    assert tr.reduce_trace({"devices": {}, "host": TRACE["host"]}) is None
    assert tr.reduce_trace({"devices": {"/device:TPU:0": []}}) is None


def test_op_name_keeps_the_instruction_name():
    long = ("%ragged_paged_attention.3 = bf16[32,32,128]{2,1,0} custom-call("
            "s32[32,256]{1,0} %broadcast_add_fusion.2), custom_call_target="
            "\"tpu_custom_call\"")
    assert tr.op_name(long) == "ragged_paged_attention.3"
    assert tr.op_name("fusion.1") == "fusion.1"


def test_collective_names():
    for name in ("all-reduce.3", "all-reduce-start.1", "all-gather-done",
                 "%reduce-scatter.2", "collective-permute.7", "all-to-all"):
        assert tr.COLLECTIVE.match(name), name
    for name in ("fusion.4", "reduce.1", "ragged_paged_attention"):
        assert not tr.COLLECTIVE.match(name), name


def test_recorded_chip_trace_gives_known_numbers():
    """A trace recorded on the v5e (perfbench/testdata/README.md says how):
    the loader finds the device plane, its programs and the benchmark's
    spans, and the reduction gives the numbers written beside it."""
    with open(os.path.join(TESTDATA, "recorded.expected.json")) as f:
        want = json.load(f)
    trace = tr.load_xplane(os.path.join(TESTDATA, "recorded.xplane.pb"))
    assert sorted(trace["devices"]) == want["device_planes"]
    assert sorted(trace["modules"]) == want["device_planes"]
    assert sorted({n for n, _, _ in trace["host"]}) == want["host_spans"]
    assert all(" = " not in n and not n.startswith("%")
               for n, _, _ in trace["devices"]["/device:TPU:0"])
    s = tr.reduce_trace(trace)
    for key in ("window_s", "busy_s", "idle_share", "dispatch_s",
                "dispatches"):
        assert getattr(s, key) == pytest.approx(want[key], rel=1e-9), key
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert s.top_ops(4) == [[n, pytest.approx(t, rel=1e-9)]
                            for n, t in want["top_ops"]]
    assert s.top_ops(1)[0][0].startswith("ragged_paged_attention")
    assert s.seconds_of("ragged_paged_attention") == pytest.approx(
        want["ragged_paged_attention_s"], rel=1e-9)
    assert [g[0] for g in s.idle_gaps[:3]] == want["gap_labels"]
