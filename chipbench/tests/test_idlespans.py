"""Device idle laid against the program's host spans: on a hand-made
trace, on the recorded slice made before the program had spans, and
through a tiny traced run on the CPU."""
import json
import os
import types

import pytest

import devtrace
import idlespans as I
from devtrace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
D = "/device:TPU:0"
OLD_SLICE = os.path.join(HERE, "recorded", "v5e-longctx-decode-slice.json")


def _trace():
    # window 0..100; busy 10..40, 60..70, 95..100; idle 0..10, 40..60,
    # 70..95 (55 in all)
    t = Trace(
        ops={D: [("fusion.1", 10.0, 30.0, "jit_step"),
                 ("fusion.2", 60.0, 10.0, "jit_step"),
                 ("fusion.3", 95.0, 5.0, "jit_step")]},
        marks=[("bench.window", 0.0, 100.0), ("bench.step", 5.0, 45.0),
               ("bench.step", 50.0, 50.0)])
    eng = [("repro.step", 6, 43), ("repro.pipe.step", 8, 38),
           ("repro.pipe.start", 8, 1), ("repro.pipe.r_wait", 9, 3),
           ("repro.pipe.gather", 12, 1), ("repro.pipe.advance", 13, 7),
           ("repro.pipe.r_wait", 20, 24), ("repro.step.sample", 47, 2),
           ("repro.step", 52, 46), ("repro.pipe.step", 53, 37),
           ("repro.pipe.r_wait", 53, 27), ("repro.pipe.dispatch", 80, 1),
           ("repro.gc", 85, 3), ("repro.step.emit", 92, 5)]
    r1 = [("repro.r.kernel", 40, 12), ("repro.r.grow", 41, 4),
          ("repro.r.to_host", 72, 6)]
    r2 = [("repro.r.post", 56, 2)]
    spans = [(n, float(s), float(d), 0) for n, s, d in eng] \
        + [(n, float(s), float(d), 1) for n, s, d in r1] \
        + [(n, float(s), float(d), 2) for n, s, d in r2]
    return t, spans


def test_idle_by_innermost_engine_span():
    t, spans = _trace()
    att = I.attribute(t, spans)
    want = {I.OUTSIDE: 5, "bench.step": 4, "repro.step": 6,
            "repro.pipe.start": 1, "repro.pipe.r_wait": 22,
            "repro.pipe.step": 8, "repro.step.sample": 2,
            "repro.pipe.dispatch": 1, "repro.gc": 3, "repro.step.emit": 3}
    assert {k: round(v * 1e9, 6) for k, v in att.items() if k != "r_host"} \
        == want
    # idle under named spans plus what none holds is 1 - busy
    idle = sum(v for k, v in att.items() if k != "r_host")
    assert idle == pytest.approx(t.idle_share() * t.window_s())
    # r_wait idle while an R-worker grows tables, copies or posts:
    # 41..44, 56..58, 72..78 (an R-worker only in its kernel counts not)
    assert att["r_host"] * 1e9 == pytest.approx(11.0)


def test_shares_add_up_to_device_idle():
    t, spans = _trace()
    sh = I.shares(t, spans)
    assert sh["idle_r_wait"] == pytest.approx(22.0)
    assert sh["idle_r_host"] == pytest.approx(11.0)
    assert sh["idle_s_dispatch"] == pytest.approx(2.0)
    assert sh["idle_step_edge"] == pytest.approx(15.0)
    assert sh["idle_rest"] == pytest.approx(16.0)   # outside, pipe, gc
    assert sh["idle_r_host"] <= sh["idle_r_wait"]
    assert sum(v for k, v in sh.items() if k != "idle_r_host") == \
        pytest.approx(100.0 * t.idle_share())


def test_gaps_named_by_engine_and_worker_spans():
    t, spans = _trace()
    gaps = I.idle_gaps(t, spans, 3)
    assert [g[0] for g in gaps] == [
        "repro.pipe.step",                   # 70..95, midpoint 82.5
        "bench.step + repro.r.kernel",       # 40..60, midpoint 50
        "bench.step"]                        # 0..10, midpoint 5
    assert [round(g[1] * 1e9) for g in gaps] == [25, 20, 10]


def test_without_program_spans_nothing_is_read():
    t, _ = _trace()
    assert I.attribute(t, []) is None and I.shares(t, []) is None
    assert I.idle_gaps(t, [], 10) == t.idle_gaps(10)
    # R-worker spans alone name no engine thread
    assert I.shares(t, [("repro.r.post", 1.0, 2.0, 0)]) is None


def test_cut_and_save_round_trip(tmp_path):
    t, spans = _trace()
    part, sp = I.cut(t, spans, 35.0, 30e-9)
    assert part.window() == (35.0, 65.0)
    assert part.busy_intervals(D) == [(35.0, 40.0), (60.0, 65.0)]
    assert "repro.pipe.start" not in {s[0] for s in sp}
    path = str(tmp_path / "slice.json")
    I.save(part, sp, path)
    back, sp2 = I.load(path)
    assert sp2 == sp
    assert I.shares(back, sp2) == I.shares(part, sp)
    # the plain record still loads it, spans left aside
    assert devtrace.load_json(path).marks == part.marks


def test_old_recorded_slice_reads_as_before():
    """The slice recorded before the program had spans: no spans, the
    device's numbers and gap names exactly as devtrace gives them, and
    the device-idle reader's value unchanged."""
    import run
    old = devtrace.load_json(OLD_SLICE)
    t, spans = I.load(OLD_SLICE)
    assert spans == []
    assert t.busy_s() == old.busy_s() == pytest.approx(0.008657603,
                                                       rel=1e-6)
    assert t.top_ops(3) == old.top_ops(3)
    assert I.idle_gaps(t, spans, 3) == old.idle_gaps(3)
    assert [g[0] for g in I.idle_gaps(t, spans, 3)] == \
        ["outside any harness call"] * 3
    reader = run.load_reader("device_idle.decode")
    got = reader.read(types.SimpleNamespace(trace=t))
    assert got == reader.read(types.SimpleNamespace(trace=old))
    assert got == pytest.approx(96.5369588, rel=1e-6)
    assert I.shares(t, spans) is None


def test_tiny_traced_run_keeps_program_spans():
    """A traced run at a tiny size on the CPU: the program's spans come
    back with the run; the CPU has no device plane, so nothing is
    attributed."""
    import run
    from tiny import DECODE, SPECS
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    mix = dict(DECODE, engine=dict(DECODE["engine"], num_r_workers=1))
    got = I.traced_run(SPECS["qwen"], mix, 2**31 + 5, 1.0, [], peaks,
                       say=lambda m: None, cache=False)
    assert got["result"]["correct"], got["result"]["checks"]
    names = {s[0] for s in got["spans"]}
    assert {"repro.step", "repro.pipe.step", "repro.pipe.r_wait",
            "repro.r.kernel", "repro.r.grow"} <= names
    engine, workers = I._threads(got["spans"])
    assert engine is not None and len(workers) == 1
    assert I.shares(got["trace"], got["spans"]) is None
    assert run.Tracer is not None and run.Tracer.__name__ == "Tracer"


def test_recorded_v5e_slice_with_program_spans():
    """A 13.7 ms slice from the middle of a traced window of the decode
    cell on a v5e (seed 1300000201), ops, marks and the program's spans
    as recorded, with the shares this reduction gave on it."""
    t, spans = I.load(os.path.join(
        HERE, "recorded", "v5e-longctx-decode-spans-slice.json"))
    assert len(spans) == 59
    assert t.window_s() == pytest.approx(0.013743895, rel=1e-6)
    assert t.idle_share() == pytest.approx(0.387907155, rel=1e-6)
    sh = I.shares(t, spans)
    assert sh["idle_r_wait"] == pytest.approx(18.964713, rel=1e-6)
    assert sh["idle_r_host"] == pytest.approx(17.633555, rel=1e-6)
    assert sh["idle_s_dispatch"] == pytest.approx(19.553707, rel=1e-6)
    assert sh["idle_step_edge"] == 0.0
    assert sh["idle_rest"] == pytest.approx(0.272295, rel=1e-5)
    # the bounds a traced chip run is held to: the three named shares
    # hold at least 90% of the device's idle time, and R-worker host
    # work is a part of the r_wait share
    named = sh["idle_r_wait"] + sh["idle_s_dispatch"] + sh["idle_step_edge"]
    assert named >= 0.9 * 100.0 * t.idle_share()
    assert sh["idle_r_host"] <= sh["idle_r_wait"]
    assert [g[0] for g in I.idle_gaps(t, spans, 3)] == [
        "repro.pipe.gather + repro.r.kernel",
        "repro.pipe.r_wait + repro.r.to_host",
        "repro.pipe.r_wait + repro.r.to_host"]
