"""Decode hot-path breakdown — the orchestration tax the event-driven
loop removes, and the OoO-vs-FIFO scheduling A/B.

Rows (us_per_call is per decode step unless noted):

  hotpath_event_step      event-driven decode_step wall time
  hotpath_legacy_step     pre-fusion FIFO decode_step_legacy wall time
  hotpath_event_overhead  dispatch+collect per step, event-driven path
  hotpath_legacy_overhead dispatch+collect per step, legacy path —
                          derived reports the reduction (target >= 30%)
  hotpath_breakdown_*     dispatch / collect / s_dispatch / r_wait split
  hotpath_ooo_skew        OoO schedule under a 2x-slow straggler worker
                          (sim_slowdown=2.0) posting over a congested
                          link (delivery jitter): mean token-emission
                          latency per micro-batch
  hotpath_fifo_skew       same engine, FIFO schedule — derived reports
                          the OoO emission speedup (must be > 1x) and
                          the wall-clock ratio

The A/B toggles ``engine.schedule`` on ONE engine in alternating rounds
and reports the median of paired ratios, so machine drift hits both
modes equally.  Delivery jitter is what makes completion order diverge
from issue order (thread workers drain their inbox FIFO, so without it
completions are monotone in dispatch order and OoO == FIFO by
construction).  The metric is per-micro-batch token EMISSION latency:
with a per-step barrier both schedules end a step at the same last
chain, but FIFO holds every ready micro-batch's token behind the
straggler's delivery (head-of-line), which is exactly the streaming
latency a serving deployment feels; see docs/ARCHITECTURE.md
"Hot path".

  hotpath_model_tok_s     perfmodel tokens/s with the calibrated
                          orchestration-overhead term vs the ideal
  hotpath_obs_overhead    observability-on per-step wall vs off (the
                          repro.* spans swapped for a no-op in paired
                          rounds on one engine, median of paired
                          ratios) — the <5% overhead guard; then one
                          round under jax.profiler, reported as
                          profiling/off and written as the Perfetto
                          trace CI uploads (BENCH_hotpath_trace.json.gz)
"""
from __future__ import annotations

import os
import time

import jax.numpy as jnp

from benchmarks.common import REPO_ROOT, bench_model, csv_row, smoke
from repro.core.hetero import HeteroPipelineEngine

BATCH, NUM_MB, WORKERS = 16, 2, 3
PROMPT = 16


def _make_engine(params, cfg, cache_len, schedule="ooo", **kw):
    eng = HeteroPipelineEngine(params, cfg, batch=BATCH,
                               cache_len=cache_len,
                               num_r_workers=WORKERS,
                               num_microbatches=NUM_MB,
                               kv_chunk=cache_len, schedule=schedule, **kw)
    h = BATCH // NUM_MB
    for mb in range(NUM_MB):
        eng.load_prefill(mb, jnp.ones((h, PROMPT), jnp.int32),
                         jnp.full((h,), PROMPT))
    return eng


def _run_steps(eng, step_fn, iters, warmup=2):
    h = BATCH // NUM_MB
    tok = [jnp.ones((h, 1), jnp.int32)] * NUM_MB
    for _ in range(warmup):
        step_fn(tok)
    eng.reset_step_stats()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step_fn(tok)
    jnp.stack(out).block_until_ready()
    wall = (time.perf_counter() - t0) / iters
    stats = dict(eng.step_stats)
    per_step = {k: v / iters for k, v in stats.items() if k != "steps"}
    return wall, per_step


def run(print_fn=print):
    iters = 4 if smoke() else 20
    cfg, params = bench_model(layers=2, d_model=128)
    cache_len = PROMPT + 8 * (2 + iters)

    # --- overhead A/B: event-driven vs pre-fusion legacy, same fleet ----
    eng = _make_engine(params, cfg, cache_len)
    ev_wall, ev = _run_steps(eng, eng.decode_step, iters)
    eng.close()

    eng = _make_engine(params, cfg, cache_len)
    lg_wall, lg = _run_steps(eng, eng.decode_step_legacy, iters)
    eng.close()

    ev_ovh = ev["dispatch_s"] + ev["collect_s"]
    lg_ovh = lg["dispatch_s"] + lg["collect_s"]
    red = 100.0 * (1.0 - ev_ovh / lg_ovh) if lg_ovh > 0 else 0.0
    print_fn(csv_row("hotpath_event_step", ev_wall * 1e6,
                     f"{BATCH / ev_wall:.0f}tok/s"))
    print_fn(csv_row("hotpath_legacy_step", lg_wall * 1e6,
                     f"{BATCH / lg_wall:.0f}tok/s"))
    print_fn(csv_row("hotpath_event_overhead", ev_ovh * 1e6,
                     "dispatch+collect"))
    print_fn(csv_row("hotpath_legacy_overhead", lg_ovh * 1e6,
                     f"reduction={red:.0f}%"))
    for k in ("dispatch_s", "collect_s", "s_dispatch_s", "r_wait_s"):
        print_fn(csv_row(f"hotpath_breakdown_{k[:-2]}", ev[k] * 1e6,
                         "event-driven,per-step"))

    # --- OoO vs FIFO under a straggler with async delivery -------------
    # worker 0 runs 2x slow (sim_slowdown=2.0, plus 2x row cost) and
    # posts over a congested link (20ms delivery jitter); the paired
    # schedule-toggle on one engine cancels machine drift
    skew, jitter, row_cost = 2.0, 20e-3, 3e-4
    num_mb, ab_batch = 6, 12
    rounds = 4 if smoke() else 12
    ab_cfg, ab_params = bench_model(layers=2, d_model=32, vocab=128)
    eng = HeteroPipelineEngine(ab_params, ab_cfg, batch=ab_batch,
                               cache_len=256, num_r_workers=2,
                               num_microbatches=num_mb, kv_chunk=256)
    h = ab_batch // num_mb
    for mb in range(num_mb):
        eng.load_prefill(mb, jnp.ones((h, PROMPT), jnp.int32),
                         jnp.full((h,), PROMPT))
    for w in eng.workers:
        w.sim_row_cost = row_cost
    eng.workers[0].slowdown = skew
    eng.workers[0].sim_row_cost = row_cost * skew
    eng.workers[0].sim_deliver_jitter = jitter
    tok = [jnp.ones((h, 1), jnp.int32)] * num_mb
    for _ in range(2):
        eng.decode_step(tok)
    wall_ratios, emit_ratios, res = [], [], {}
    emit_tot = {"ooo": 0.0, "fifo": 0.0}
    for _ in range(rounds):
        for schedule in ("ooo", "fifo"):
            eng.schedule = schedule
            eng.reset_step_stats()
            t0 = time.perf_counter()
            for _ in range(2):
                eng.decode_step(tok)
            res[schedule] = (time.perf_counter() - t0,
                             eng.step_stats["emit_mean_s"])
            emit_tot[schedule] += res[schedule][1]
        wall_ratios.append(res["fifo"][0] / res["ooo"][0])
        emit_ratios.append(res["fifo"][1] / res["ooo"][1])
    eng.close()
    wall_ratios.sort()
    emit_ratios.sort()
    wall_x = wall_ratios[len(wall_ratios) // 2]
    emit_x = emit_ratios[len(emit_ratios) // 2]
    print_fn(csv_row("hotpath_ooo_skew",
                     emit_tot["ooo"] / rounds / 2 * 1e6,
                     f"emit_latency,slowdown={skew},"
                     f"jitter={jitter * 1e3:.0f}ms"))
    print_fn(csv_row("hotpath_fifo_skew",
                     emit_tot["fifo"] / rounds / 2 * 1e6,
                     f"ooo_emit_speedup={emit_x:.2f}x,"
                     f"wall_ratio={wall_x:.2f}x"))

    # --- observability overhead guard: paired spans on/off A/B ---------
    # same engine, alternating rounds with the hot path's repro.* spans
    # as they run in production (no profile recording: about a
    # microsecond each) and swapped for a no-op, plus a registry
    # histogram observe per step, the serving layer's per-token cost
    # shape — the paired toggle cancels machine drift, and the median
    # ratio must stay under the 5% budget that keeps observability safe
    # to leave on in production
    import contextlib
    import glob
    import shutil
    import tempfile

    import jax
    from repro.obs import MetricsRegistry
    from repro.obs import spans as S
    obs_rounds = 4 if smoke() else 10
    obs_iters = 2
    cache2 = PROMPT + 8 + 2 * obs_iters * 2 * (obs_rounds + 3)
    eng = _make_engine(params, cfg, cache2)
    hist = MetricsRegistry().histogram("step_s")
    h = BATCH // NUM_MB
    tok = [jnp.ones((h, 1), jnp.int32)] * NUM_MB
    for _ in range(2):
        eng.decode_step(tok)
    span_on = S.span

    def span_off(name, **args):
        return contextlib.nullcontext()

    def timed(mode):
        t0 = time.perf_counter()
        for _ in range(obs_iters):
            out = eng.decode_step(tok)
            if mode == "on":
                hist.observe(time.perf_counter() - t0)
        jnp.stack(out).block_until_ready()
        return time.perf_counter() - t0

    ratios, walls, pair = [], {"off": 0.0, "on": 0.0}, {}
    try:
        for _ in range(obs_rounds):
            for mode in ("off", "on"):
                S.span = span_on if mode == "on" else span_off
                pair[mode] = timed(mode)
                walls[mode] += pair[mode]
            ratios.append(pair["on"] / pair["off"])
    finally:
        S.span = span_on
    # one round under a jax.profiler trace: the Perfetto trace CI
    # uploads (spans and ops on one clock) and what recording costs
    off = timed("off")
    prof_dir = tempfile.mkdtemp(prefix="hotpath-profile-")
    jax.profiler.start_trace(prof_dir, create_perfetto_trace=True)
    prof = timed("on")
    jax.profiler.stop_trace()
    eng.close()
    found = glob.glob(os.path.join(prof_dir, "**",
                                   "perfetto_trace.json.gz"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"jax.profiler wrote {len(found)} Perfetto "
                           f"traces under {prof_dir}, expected one: "
                           f"{found}")
    perfetto = found[0]
    shutil.copyfile(perfetto, os.path.join(REPO_ROOT,
                                           "BENCH_hotpath_trace.json.gz"))
    shutil.rmtree(prof_dir, ignore_errors=True)
    ratios.sort()
    obs_x = ratios[len(ratios) // 2]
    print_fn(csv_row("hotpath_obs_overhead",
                     walls["on"] / obs_rounds / obs_iters * 1e6,
                     f"obs_on/off={obs_x:.3f}x,"
                     f"profiling/off={prof / off:.3f}x"))
    assert obs_x < 1.05, (
        f"observability overhead regression: obs-on/off per-step wall "
        f"ratio {obs_x:.3f}x exceeds the 1.05x budget")

    # --- calibrated orchestration term feeds the perfmodel -------------
    from repro.core import perfmodel as P
    ovh = P.calibrate_orchestration(dict(ev, steps=1.0), cfg, NUM_MB,
                                    WORKERS)
    ideal = BATCH / (2 * cfg.num_layers * P.t_of_b(cfg, P.TPU_V5E, BATCH))
    with_ovh = P.tokens_per_s_with_overhead(cfg, P.TPU_V5E, BATCH, NUM_MB,
                                            WORKERS, ovh)
    print_fn(csv_row("hotpath_model_tok_s", 1e6 / max(with_ovh, 1e-9),
                     f"{with_ovh:.0f}tok/s,ideal={ideal:.0f}"))
    return {"overhead_reduction_pct": red, "ooo_emit_speedup": emit_x,
            "ooo_wall_ratio": wall_x}


if __name__ == "__main__":
    run()
