"""The trace reduction on hand-made traces and on a recorded slice of a
v5e trace of the decode cell."""
import os

import pytest

import devtrace
from devtrace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
D = "/device:TPU:0"


def _trace():
    # window 0..100; ops overlap at 10..30 and 20..40; 60..70 is cut by
    # the window end nowhere; 95..120 is clipped to 95..100
    return Trace(
        ops={D: [("fusion.1", 10.0, 20.0, "jit_step"),
                 ("custom-call.3", 20.0, 20.0, "jit_attn"),
                 ("fusion.1", 60.0, 10.0, "jit_step"),
                 ("fusion.2", 95.0, 25.0, "jit_step"),
                 ("fusion.9", -50.0, 10.0, "jit_before")]},
        marks=[("bench.window", 0.0, 100.0),
               ("bench.step", 5.0, 45.0),
               ("bench.idle", 41.0, 18.0),
               ("bench.step", 59.0, 41.0)])


def test_busy_union_and_idle_share():
    t = _trace()
    assert t.busy_intervals(D) == [(10.0, 40.0), (60.0, 70.0),
                                   (95.0, 100.0)]
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.window_s() == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.55)


def test_op_time_by_name_is_clipped_to_the_window():
    t = _trace()
    assert t.op_seconds(lambda n, p: n == "fusion.1") == pytest.approx(30e-9)
    assert t.op_seconds(lambda n, p: p == "jit_step") == pytest.approx(35e-9)
    assert t.op_seconds(lambda n, p: p == "jit_before") == 0.0
    top = t.top_ops(2)
    assert top[0][0] == "jit_step/fusion.1"
    assert top[0][1] == pytest.approx(30e-9)


def test_idle_gaps_named_by_innermost_annotation():
    gaps = Trace.idle_gaps(_trace(), 10)
    names = {round(s * 1e9): n for n, s in gaps}
    assert names[20] == "bench.idle"            # 40..60, midpoint 50
    assert names[25] == "bench.step"            # 70..95, midpoint 82.5
    assert names[10] == "bench.step"            # 0..10, midpoint 5
    assert [round(s * 1e9) for _, s in gaps] == [25, 20, 10]


def test_no_device_ops_reads_nothing():
    t = Trace(ops={}, marks=[("bench.window", 0.0, 10.0)])
    assert t.busy_s() is None and t.idle_share() is None
    assert t.idle_gaps() == []


def test_recorded_v5e_slice():
    """A 0.25 s slice of the decode cell's traced window on a v5e (its
    op events as recorded), with the numbers this reduction gave."""
    t = devtrace.load_json(os.path.join(
        HERE, "recorded", "v5e-longctx-decode-slice.json"))
    assert t.window_s() == pytest.approx(0.25)
    assert t.busy_s() == pytest.approx(0.008657603, rel=1e-6)
    assert t.idle_share() == pytest.approx(0.965369588, rel=1e-6)
    top = t.top_ops(3)
    assert top[0][0] == "copy-done"
    assert top[0][1] == pytest.approx(0.008489484, rel=1e-6)
    gaps = t.idle_gaps(3)
    assert [g[0] for g in gaps] == ["outside any harness call"] * 3
    assert gaps[0][1] == pytest.approx(0.001510729, rel=1e-6)
