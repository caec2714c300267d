"""The hot path's named spans (repro.obs.spans) under jax.profiler on the
CPU: every name is recorded, the engine thread's leaf spans tile the
pipeline step, each span's time agrees with the hotpath_stats counter
that times the same region, the dispatch -> kernel link shares its args,
and no span stays open when an R-worker fault aborts a step."""
import gc
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.chaos import FaultPlan, FaultSpec
from repro.core.hetero import HeteroPipelineEngine, WorkerStepError
from repro.models import model as M
from repro.obs import spans as S
from repro.serving.engine import ServingEngine
from repro.serving.request import Request

from conftest import tiny_cfg

# the engine thread's leaves in a pipeline step (a collector pause,
# repro.gc, nests in whichever span it interrupts)
LEAVES = {S.PIPE_START, S.PIPE_R_WAIT, S.PIPE_GATHER, S.PIPE_ADVANCE,
          S.PIPE_DISPATCH}


def _threads(path):
    """{thread index: [(name, start_ns, end_ns, args)]} of every host
    thread that ran a repro.* or test.* span."""
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(("repro.", "test."))]
            if evs:
                out[len(out)] = sorted(evs, key=lambda e: (e[1], -e[2]))
    return out


def _profile(tmp, fn):
    with jax.profiler.trace(str(tmp)):
        fn()
    (path,) = glob.glob(os.path.join(str(tmp), "**", "*.xplane.pb"),
                        recursive=True)
    return _threads(path)


def _engine_thread(threads):
    (evs,) = [e for e in threads.values()
              if any(n == S.PIPE_STEP for n, *_ in e)]
    return evs


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Five traced serving steps of a tiny paged engine with chunked
    prefill: some rows still prefill, the rest decode; plus one
    collection of the garbage collector on the engine thread."""
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, batch=4, cache_len=64,
                        backend="hetero", num_microbatches=2, kv_chunk=64,
                        paged_kv=True, page_size=8, prefill_chunk=8)
    rng = np.random.default_rng(0)
    try:
        for i in range(3):
            eng.submit(Request(rid=i, prompt=rng.integers(
                1, cfg.vocab_size, (12,)).astype(np.int32),
                max_new_tokens=30))
        for _ in range(4):           # compile, prefill, start decoding
            eng.step()
        eng.submit(Request(rid=3, prompt=rng.integers(
            1, cfg.vocab_size, (20,)).astype(np.int32), max_new_tokens=30))

        def serve():
            for _ in range(5):
                eng.step()
            gc.collect()
        return _profile(tmp_path_factory.mktemp("prof"), serve)
    finally:
        eng.close()


def test_every_span_name_is_recorded(served):
    threads = served
    seen = {n for evs in threads.values() for n, *_ in evs}
    assert set(S.NAMES) <= seen, set(S.NAMES) - seen
    steps = [a for evs in threads.values() for n, _, _, a in evs
             if n == S.STEP]
    assert len(steps) == 5 and all("step_num" in a for a in steps)
    # R-worker spans run on the R-worker threads only
    eng = _engine_thread(threads)
    assert not any(n.startswith("repro.r.") for n, *_ in eng)


def test_engine_leaf_spans_tile_the_pipeline_step(served):
    """The leaves follow the loop's order (each dispatch right after the
    S callable that made its shards, each advance right after its
    gather) with short gaps between them: the pipeline's host time is
    all under a named span.  The gaps are host time on a loaded CPU, so
    the bound is on their median and on the share they leave."""
    eng = _engine_thread(served)
    steps = [e for e in eng if e[0] == S.PIPE_STEP]
    assert len(steps) == 5
    for _, a, b, _ in steps:
        leaves = [e for e in eng if e[0] in LEAVES and a <= e[1] < b]
        # leaves nest in the step and never overlap each other
        assert all(e[2] <= b for e in leaves)
        for x, y in zip(leaves, leaves[1:]):
            assert x[2] <= y[1], (x, y)
        names = [e[0] for e in leaves]
        for prev, name in zip(names, names[1:]):
            if name == S.PIPE_DISPATCH:
                assert prev in (S.PIPE_START, S.PIPE_ADVANCE), names
            if name == S.PIPE_ADVANCE:
                assert prev == S.PIPE_GATHER, names
        gaps = sorted(y[1] - x[2] for x, y in zip(leaves, leaves[1:]))
        assert gaps[len(gaps) // 2] < 0.5e6, gaps       # ns
        covered = sum(e[2] - e[1] for e in leaves)
        assert covered >= 0.5 * (b - a), covered / (b - a)


def _pipeline(chaos=None):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = HeteroPipelineEngine(params, cfg, batch=4, cache_len=16,
                               num_r_workers=2, num_microbatches=2,
                               chaos=chaos)
    eng.load_prefill(0, jnp.ones((2, 4), jnp.int32), jnp.full((2,), 4))
    eng.load_prefill(1, jnp.ones((2, 4), jnp.int32), jnp.full((2,), 4))
    return eng, [jnp.ones((2, 1), jnp.int32)] * 2


def test_span_time_matches_hotpath_counters(tmp_path):
    """Decode steps alone (chunk work is billed to prefill_s), so within
    each step a span's sum and its counter time the same regions: the
    counted region lies inside its span, and the span adds no more than
    5%.  A dispatch is tens of microseconds, the size of a span's own
    entry and exit, so its spans are matched one to one with the rounds
    they enqueue and may add their own cost, measured in the same
    profile, on top of the 5%."""
    eng, toks = _pipeline()
    per_step, own = [], [0.0]
    pc = time.perf_counter
    try:
        eng.decode_step(toks)        # compile outside the profile

        def decode():
            for _ in range(5):
                eng.decode_step(toks)
                per_step.append(dict(eng.last_step_stats))
            # a span with args around an empty counted region
            for i in range(200):
                with TraceAnnotation("test.own", mb=i, layer=0, phase=0):
                    t0 = pc()
                    own[0] += pc() - t0
        threads = _profile(tmp_path, decode)
    finally:
        eng.close()
    evs = _engine_thread(threads)
    steps = [e for e in evs if e[0] == S.PIPE_STEP]
    assert len(steps) == len(per_step) == 5
    cal = [e for e in evs if e[0] == "test.own"]
    own_s = (sum(e[2] - e[1] for e in cal) * 1e-9 - own[0]) / len(cal)

    def in_step(names, a, b):
        return [e for e in evs if e[0] in names and a <= e[1] < b]

    workers = [w for w in threads.values()
               if any(e[0] == S.R_KERNEL for e in w)]
    assert len(workers) == 2
    for names, key in [((S.PIPE_R_WAIT,), "r_wait_s"),
                       ((S.PIPE_GATHER,), "collect_s"),
                       ((S.PIPE_START, S.PIPE_ADVANCE), "s_dispatch_s"),
                       ((S.PIPE_DISPATCH,), "dispatch_s")]:
        excess = []
        for (_, a, b, _), hot in zip(steps, per_step):
            mine = in_step(names, a, b)
            sec = sum(e[2] - e[1] for e in mine) * 1e-9
            # the two clocks may drift apart by a few hundred ppm
            assert sec >= hot[key] * (1 - 1e-3), (key, sec, hot[key])
            slack = 0.05 * hot[key]
            if key == "dispatch_s":
                # one dispatch span per round enqueued: each R-worker
                # runs it once, and one gather collects it
                assert len(mine) == len(in_step((S.PIPE_GATHER,), a, b))
                for w in workers:
                    assert len(mine) == len([
                        e for e in w if e[0] == S.R_KERNEL and a <= e[1] < b])
                slack += len(mine) * own_s
            excess.append(sec - hot[key] - slack)
        # the median step: a thread switch inside one span's entry can
        # put milliseconds into one step on a loaded machine
        assert sorted(excess)[2] <= 0.0, (key, excess, own_s)


def test_dispatch_and_kernel_share_their_args(served):
    threads = served
    eng = _engine_thread(threads)
    kernels = [e for evs in threads.values() for e in evs
               if e[0] == S.R_KERNEL]
    assert kernels
    for _, a, b, _ in [e for e in eng if e[0] == S.PIPE_STEP]:
        disp = {}
        for n, s, _, args in eng:
            if n == S.PIPE_DISPATCH and a <= s < b:
                disp[(args["mb"], args["layer"], args["phase"])] = s
        mine = [k for k in kernels if a <= k[1] < b]
        assert mine
        for _, s, _, args in mine:
            key = (args["mb"], args["layer"], args["phase"])
            assert key in disp and disp[key] <= s, key


def test_no_span_left_open_after_a_worker_fault(tmp_path):
    eng, toks = _pipeline(FaultPlan([FaultSpec(site="r_step", kind="error",
                                               wid=0, after=7)]))
    try:
        eng.decode_step(toks)        # compile outside the profile

        def fail():
            with pytest.raises(WorkerStepError):
                eng.decode_step(toks)
            with TraceAnnotation("test.after"):
                pass
        threads = _profile(tmp_path, fail)
    finally:
        eng.close()
    eng_evs = _engine_thread(threads)
    (after,) = [e for e in eng_evs if e[0] == "test.after"]
    steps = [e for e in eng_evs if e[0] == S.PIPE_STEP]
    assert len(steps) == 1
    # every engine span of the failed step closed before the next span
    # on its thread began
    for e in eng_evs:
        if e[0].startswith("repro."):
            assert e[2] <= after[1], e


def test_gc_hook_is_registered_once():
    assert gc.callbacks.count(S._on_gc) == 1
