"""One run of one benchmark cell, on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``; the configuration is ``chipbench/configs/<config>.json``
and the mix ``chipbench/traffic/<traffic>.json``.  The configuration's
family, ``chipbench/archs/<family>.py`` (``family.py``), gives its
sizes, weights, program configuration and reference.  In order, a run
finds the chip (and fails without one), turns on the compile cache in
the checkout, makes the weights on the device from the seed, builds the
hetero serving engine, warms up the shapes the mix uses, builds the
state the mix needs, measures for ``--seconds``, checks the served
tokens against the float32 reference, and prints one JSON line last.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the window is profiled and it carries the per-layer
metrics, each read by ``chipbench/metrics/<metric>.py``.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import family  # noqa: E402
import traffic  # noqa: E402

# the persistent compile cache lives at a fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def pct(values, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    if not len(values):
        return None
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


# --------------------------------------------------------------------------
# what a run records
# --------------------------------------------------------------------------
@dataclass
class RunData:
    """What the per-layer readers see (``chipbench/metrics/*.py``)."""
    spec: object
    mix: dict
    peaks: dict
    window_s: float = 0.0
    # per step of the window: the cached tokens of each decoding row
    steps: List[List[int]] = field(default_factory=list)
    hot0: Dict[str, float] = field(default_factory=dict)
    hot1: Dict[str, float] = field(default_factory=dict)
    gaps_ms: List[float] = field(default_factory=list)
    tokens: int = 0
    trace: object = None


class CompileCounter:
    """Counts programs lowered (compiled or fetched from the cache)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


class Annotate:
    """``bench.*`` host annotations in the profiler trace, or nothing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)


# --------------------------------------------------------------------------
# the serving loop
# --------------------------------------------------------------------------
class Server:
    """Drives the engine through ``submit`` and ``step`` and timestamps
    every request's output tokens on the host clock."""

    def __init__(self, eng, data: RunData, ann: Annotate):
        import system
        self.eng, self.data, self.ann = eng, data, ann
        self.system = system
        self.last_tok: Dict[int, float] = {}
        self.seen: Dict[int, int] = {}
        self.finish: Dict[int, float] = {}
        self.reqs: Dict[int, object] = {}
        self.failed = 0
        self.n_finished = 0
        self.in_window = False

    def submit(self, plan) -> None:
        req = self.system.request(plan)
        with self.ann("submit"):
            try:
                self.eng.submit(req)
            except ValueError as e:
                log(f"request {plan.idx} refused: {e}")
                self.failed += 1
                return
        self.reqs[plan.idx] = req
        self.seen[plan.idx] = 0

    def step(self) -> None:
        eng = self.eng
        contexts = [r.feed_len for r in eng.slots
                    if r is not None and self.system.running(r)]
        with self.ann("step"):
            eng.step()
        t1 = time.perf_counter()
        live = [r for r in eng.slots if r is not None]
        done = eng.finished[self.n_finished:]
        self.n_finished = len(eng.finished)
        for r in live + done:
            n_new = len(r.generated) - self.seen[r.rid]
            if n_new <= 0:
                continue
            self.seen[r.rid] = len(r.generated)
            if self.in_window:
                if r.rid in self.last_tok:
                    self.data.gaps_ms.append(
                        (t1 - self.last_tok[r.rid]) * 1e3)
                self.data.tokens += n_new
            self.last_tok[r.rid] = t1
        for r in done:
            self.finish[r.rid] = t1
        if self.in_window:
            self.data.steps.append(contexts)


def serve_closed(eng, plan, mix, seconds, data, ann, counter, tracer):
    srv = Server(eng, data, ann)
    t0 = time.perf_counter()
    for p in plan:
        srv.submit(p)
    # set-up the mix needs: prefill every row's context, then a few steps
    # so that every row decodes
    while srv.reqs and not all(srv.system.running(r)
                               for r in srv.reqs.values()):
        srv.step()
    for _ in range(int(mix.get("warm_decode_steps", 2))):
        srv.step()
    log(f"contexts prefilled, {counter.n} programs lowered so far")
    lowered_pre = counter.n
    prefill_s = time.perf_counter() - t0
    tracer.start()
    ann_win = ann("window")
    ann_win.__enter__()
    w0 = time.perf_counter()
    srv.in_window = True
    data.hot0 = dict(eng.hotpath_stats())
    while time.perf_counter() < w0 + seconds:
        srv.step()
    end = time.perf_counter()
    ann_win.__exit__(None, None, None)
    srv.in_window = False
    data.hot1 = dict(eng.hotpath_stats())
    data.trace = tracer.stop()
    data.window_s = end - w0
    info = {
        "prefill_and_warm_s": prefill_s,
        "programs_lowered_before_window": lowered_pre,
        "rows": len(plan),
        "steps_in_window": len(data.steps),
        "programs_lowered_in_window": counter.n - lowered_pre,
        "rows_finished_in_window": sum(
            1 for t in srv.finish.values() if t >= w0),
    }
    return srv, w0, info


class Tracer:
    """Starts and stops the profiler around the window (``--trace 1``)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self) -> None:
        if not self.on:
            return
        import tempfile
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if not self.on:
            return None
        import glob
        import shutil
        import jax
        import devtrace
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return devtrace.Trace.load(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------
def pick_checked(srv, chk: dict, seed: int) -> List[int]:
    """Request ids to compare: rows that served tokens, the longest among
    them and the rest drawn from the seed."""
    cand = [rid for rid in srv.reqs if srv.reqs[rid].generated]
    if not cand:
        return []
    size = {rid: srv.reqs[rid].prompt_len + len(srv.reqs[rid].generated)
            for rid in cand}
    longest = max(cand, key=lambda r: (size[r], r))
    rest = sorted(set(cand) - {longest})
    rng = np.random.default_rng([seed, 1])
    k = min(len(rest), int(chk["requests"]) - 1)
    pick = list(rng.choice(rest, size=k, replace=False)) if k else []
    return [longest] + [int(x) for x in pick]


def compare(fam, spec, seed, srv, rids, control=False):
    seqs, starts = [], []
    for rid in rids:
        r = srv.reqs[rid]
        seqs.append(np.concatenate([np.asarray(r.prompt, np.int32),
                                    np.asarray(r.generated, np.int32)]))
        starts.append(r.prompt_len)
    return fam.gaps(spec, seed, seqs, starts, control=control)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------
def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def execute(spec, mix: dict, seed: int, seconds: float, trace: bool,
            metrics: List[dict], peaks: dict, control: bool = False,
            say=print, cache: bool = True):
    """Set up, measure and check one run; returns (result, checks, info).

    With ``control`` the fp8 control's first tokens take the served
    tokens' place in the check, so a sound program reads not correct."""
    import jax
    from repro.launch.cache import enable_compile_cache
    import system

    fam = family.load(spec.family)
    if cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    devs = jax.devices()
    dev = devs[0]
    cfg = fam.model_config(spec)
    t0 = time.perf_counter()
    params = fam.program_params(spec, seed)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    build = getattr(fam, "build_engine", system.build_engine)
    eng = build(params, cfg, mix["engine"], seed)
    del params
    phases = {"setup_to_weights_s": t0 - T_PROC, "weights_s": t1 - t0,
              "engine_s": time.perf_counter() - t1}
    built = dev.memory_stats() or {}
    phases["bytes_in_use_after_engine"] = int(built.get("bytes_in_use", 0))
    phases["peak_bytes_after_engine"] = int(
        built.get("peak_bytes_in_use", 0))
    plan = traffic.generate(mix, seed, spec.vocab)
    data = RunData(spec=spec, mix=mix, peaks=peaks)
    ann = Annotate(trace)
    tracer = Tracer(trace)
    readers = {m["name"]: load_reader(m["name"]) for m in metrics} \
        if trace else {}
    try:
        srv, w0, info = serve_closed(eng, plan, mix, seconds, data, ann,
                                     counter, tracer)
        setup_s = w0 - T_PROC
        stats = dev.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        em = eng.metrics()
        faults = int(em.get("fault_count", 0) + em.get("recovered_count", 0))
        bad_tok = sum(1 for r in srv.reqs.values() for t in r.generated
                      if not 0 <= t < spec.vocab)
    finally:
        eng.close()
    # free the program's weights and pools before the reference runs
    srv.eng = None
    del eng
    gc.collect()
    info = dict(phases, **info)
    info["memory_bytes_limit"] = int(stats.get("bytes_limit", 0))
    say_info(say, info)

    # end-to-end metrics, from the host clock
    e2e = {
        "itl_p95_ms": pct(data.gaps_ms, 95),
        "out_tok_per_s": data.tokens / data.window_s if data.window_s
        else None,
        "setup_s": setup_s,
    }
    if data.gaps_ms:
        say(f"inter-token gaps: {len(data.gaps_ms)}, p50 "
            f"{pct(data.gaps_ms, 50):.1f} ms, p95 {e2e['itl_p95_ms']:.1f} ms "
            f"with {len(data.gaps_ms) - math.ceil(0.95 * len(data.gaps_ms))}"
            f" beyond it; {data.tokens} tokens in {data.window_s:.3f} s")
    out: Dict[str, dict] = {}
    for m in metrics:
        if trace:
            val = readers[m["name"]].read(data)
        else:
            val = e2e.get(m["name"])
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}

    # correctness: teacher-forced reference over the served tokens
    chk = mix["check"]
    rids = pick_checked(srv, chk, seed)
    t_ref = time.perf_counter()
    if rids:
        res = compare(fam, spec, seed, srv, rids, control=control)
        served, ctrl = res if control else (res, None)
        n_tok = int(sum(len(g) for g in served))
        max_gap = float(max(g.max() for g in served))
        if control:
            info["program_max_logit_gap"] = max_gap
            say(f"control: the served tokens' widest gap is {max_gap}; the "
                f"fp8 control's first tokens take their place in the check")
            max_gap = float(max(g.max() for g in ctrl))
    else:
        n_tok, max_gap = 0, float("inf")
    say(f"reference over {len(rids)} requests, {n_tok} served tokens, "
        f"took {time.perf_counter() - t_ref:.1f} s")
    checks = {
        "max_logit_gap": {"value": max_gap, "limit": chk["max_gap"],
                          "must_be": "at_most"},
        "tokens_compared": {"value": n_tok,
                            "limit": chk["min_tokens"],
                            "must_be": "at_least"},
        "faults": {"value": faults, "limit": 0, "must_be": "at_most"},
        "failed_requests": {"value": srv.failed, "limit": 0,
                            "must_be": "at_most"},
        "out_of_vocab_tokens": {"value": bad_tok, "limit": 0,
                                "must_be": "at_most"},
    }
    correct = all(
        c["value"] <= c["limit"] if c["must_be"] == "at_most"
        else c["value"] >= c["limit"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": len(plan),
              "failed": srv.failed, "metrics": out,
              "device": dict(device_info(devs), memory_peak_bytes=peak)}
    if trace and data.trace is not None:
        busy = data.trace.busy_s()
        if busy is not None:
            result["device"]["busy_s"] = busy
        result["device"]["window_s"] = data.trace.window_s()
        result["breakdown"] = {"device_ops": data.trace.top_ops(10),
                               "idle_gaps": data.trace.idle_gaps(10)}
    return result, checks, info


def say_info(say, info: dict) -> None:
    say("run: " + ", ".join(f"{k}={v}" for k, v in info.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="check the fp8 control's tokens in the served "
                    "tokens' place (calibration only: it reads not correct)")
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell["chips"]:
        log(f"need {cell['chips']} accelerator chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return EXIT_NO_CHIP
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks_all = json.load(f)
    if devs[0].device_kind not in peaks_all:
        log(f"no peaks for device kind {devs[0].device_kind!r} in "
            f"chipbench/peaks.json")
        return EXIT_NO_CHIP
    dev = device_info(devs)
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"

    def say(msg):
        print(f"{tag} {msg}", flush=True)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = [m for m in bench[kind]
               if args.workload in m.get("workloads", [args.workload])]
    spec = family.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    result, checks, _ = execute(
        spec, mix, args.seed, args.seconds, bool(args.trace), metrics,
        peaks_all[devs[0].device_kind], control=bool(args.control), say=say)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']}, "
            f"{c['must_be'].replace('_', ' ')})")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
