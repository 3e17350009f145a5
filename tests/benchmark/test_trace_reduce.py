"""The reduction from a profiler trace to busy, idle and the gaps
(``benchmarks/trace_reduce.py``): the interval arithmetic on intervals made
by hand, and the reading of an ``.xplane.pb`` on a small trace recorded
beside this file. The trace was made on the CPU backend
(``make_recorded_trace.py`` says how and what is in it): that checks the
structure the reduction walks, not a TPU's device planes."""

import os

import pytest

from benchmarks import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_cpu_trace.xplane.pb")


def _trace(ops, host=(), window=(0.0, 10.0), modules=None):
    return tr.Trace(ops=ops, modules=modules or {},
                    host=[("bench:window", *window), *host], window=window)


def test_union_and_clip():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.clip([(-1, 1), (2, 3), (9, 12), (20, 21)], 0, 10) == [
        (0, 1), (2, 3), (9, 10)]


def test_overlapping_events_count_once_and_a_known_gap_is_found():
    # two operations overlap from 1.5 to 2; nothing runs from 3 to 7
    t = _trace({"/device:TPU:0": [("fusion.1", 1.0, 2.0),
                                  ("copy.2", 1.5, 3.0),
                                  ("fusion.1", 7.0, 9.0)]},
               host=[("bench:wait", 2.5, 7.5), ("bench:tail", 9.0, 10.0)])
    r = tr.reduce(t)
    assert r["busy_s"] == pytest.approx(4.0)       # not 4.5
    assert r["window_s"] == pytest.approx(10.0)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["longest_gap_s"] == pytest.approx(4.0)
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(3.0)
    assert ops["copy.2"] == pytest.approx(1.5)
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    # [0,1) has no span of the benchmark over it; [3,7) lies under "wait"
    assert gaps["bench:wait"] == pytest.approx(4.0)
    assert gaps["bench:tail"] == pytest.approx(1.0)
    assert gaps["host: no benchmark span"] == pytest.approx(1.0)


def test_events_outside_the_window_are_clipped_and_chips_are_averaged():
    t = _trace({"/device:TPU:0": [("a", -5.0, 1.0), ("a", 9.0, 15.0)],
                "/device:TPU:1": [("a", 4.0, 6.0)]})
    r = tr.reduce(t, chips=2)
    assert r["busy_s"] == pytest.approx((2.0 + 2.0) / 2)
    # a cell of four chips of which two ran nothing: they were idle
    assert tr.reduce(t, chips=4)["busy_s"] == pytest.approx(1.0)


def test_a_trace_with_no_device_operation_is_all_idle_and_none_without_window():
    r = tr.reduce(_trace({}))
    assert r["busy_s"] == 0 and r["idle_share"] == 1.0
    assert tr.reduce(tr.Trace({}, {}, [], None)) is None


def test_module_durations_keep_to_the_window_and_the_name():
    t = _trace({}, modules={"/device:TPU:0": [
        ("jit_bench_job_step(123)", 1.0, 1.5), ("jit_other", 2.0, 4.0),
        ("jit_bench_job_step(123)", 9.8, 10.4)]})
    assert tr.module_durations(t, "bench_job_step") == [pytest.approx(0.5)]


def test_the_recorded_trace_reads_as_it_was_made():
    t = tr.extract(RECORDED)
    assert t.window is not None
    assert sorted(t.ops) == ["/device:CPU:0", "/device:CPU:1"]
    names = [n for n, _s, _e in t.host]
    assert names.count("bench:window") == 1
    for span in ("bench:phase one", "bench:known idle gap",
                 "bench:phase two"):
        assert span in names
    # three executions on device 0, then three on each of two devices
    runs = tr.module_durations(t, "bench_job_step")
    assert len(runs) == 9 and all(0 < d < 0.05 for d in runs)
    assert len(t.modules["/device:CPU:0"]) == 6
    assert len(t.modules["/device:CPU:1"]) == 3

    r = tr.reduce(t, chips=2)
    assert 0 < r["busy_s"] < r["window_s"]
    # the 50 ms sleep is the longest gap on both devices, and it is given
    # to the host span it was made under
    assert r["longest_gap_s"] >= 0.05
    top_gap, seconds = r["breakdown"]["idle_gaps"][0]
    assert top_gap == "bench:known idle gap" and seconds >= 0.05
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    # overlapping events: the two devices' operations of phase two overlap
    # in time; busy is per device and averaged, never their plain sum
    d0 = [(s, e) for _n, s, e in t.ops["/device:CPU:0"]]
    d1 = [(s, e) for _n, s, e in t.ops["/device:CPU:1"]]
    assert any(s0 < e1 and s1 < e0 for s0, e0 in d0 for s1, e1 in d1)
    lo, hi = t.window
    one = sum(e - s for s, e in tr.union(tr.clip(d0, lo, hi)))
    two = sum(e - s for s, e in tr.union(tr.clip(d1, lo, hi)))
    assert r["busy_s"] == pytest.approx((one + two) / 2)
