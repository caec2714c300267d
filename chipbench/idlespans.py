"""The device's idle time, laid against the program's own host spans.

The program annotates its serving hot path with ``repro.*`` spans on
the profiler's clock (``src/repro/obs/spans.py``).  ``load_spans`` keeps
them from a profile, with the thread each ran on; ``attribute`` splits
every idle interval of the device inside the window by the innermost
engine-thread span open across it (``repro.step*``, ``repro.pipe.*``,
``repro.gc``, else the harness's ``bench.*``), and notes where an
R-worker was at the same time in host work of its own (``repro.r.grow``,
``repro.r.to_host``, ``repro.r.post``).  ``shares`` turns that into four
shares of the window; ``idle_gaps`` names the longest gaps by those
spans.

    python3 chipbench/idlespans.py --workload <cell> --seed <n> \
        --seconds <s> [--slice <path>]

runs one traced run of the cell as ``run.py --trace 1`` does (its JSON
line included), keeps the program's spans from the profile, and prints
one more JSON line: the shares, the idle seconds by span and the ten
longest gaps by name.  ``--slice`` also writes a short recorded slice
of the window (device ops, marks and spans) for the tests.

Without program spans in the profile every reader here returns None and
``idle_gaps`` names gaps as ``devtrace`` does.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import devtrace  # noqa: E402

REPRO = "repro."
BENCH = devtrace.BENCH
WINDOW = BENCH + "window"
OUTSIDE = "outside any harness call"
R_PREFIX = "repro.r."
# engine-thread spans whose idle the four shares count
R_WAIT = {"repro.pipe.r_wait"}
S_DISPATCH = {"repro.pipe.start", "repro.pipe.gather",
              "repro.pipe.advance", "repro.pipe.dispatch"}
STEP_EDGE = {"bench.step", "repro.step", "repro.step.admit",
             "repro.step.sample", "repro.step.emit",
             "repro.step.prefill_results"}
R_HOST = {"repro.r.grow", "repro.r.to_host", "repro.r.post"}

# (name, start_ns, dur_ns, thread): thread numbers the host threads that
# ran program spans, in the profile's order
Span = Tuple[str, float, float, int]


def load_spans(path: str) -> List[Span]:
    """The ``repro.*`` host spans of an ``.xplane.pb`` profile."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns), thread)
                   for e in line.events if e.name.startswith(REPRO)]
            if evs:
                out.extend(evs)
                thread += 1
    return out


def _threads(spans: Sequence[Span]):
    """(engine thread, R-worker threads): the engine thread runs the
    pipeline's step; an R-worker thread runs ``repro.r.*`` spans."""
    engine = {t for n, _, _, t in spans
              if n.startswith("repro.pipe.") or n.startswith("repro.step")}
    if len(engine) > 1:
        raise ValueError(f"program spans on {len(engine)} engine threads")
    workers = {t for n, _, _, t in spans if n.startswith(R_PREFIX)}
    return (next(iter(engine)) if engine else None), workers - engine


def _innermost(spans) -> List[Tuple[float, float, str]]:
    """Non-overlapping (start, end, name) pieces of one thread's spans,
    each named by the innermost span open over it (the latest started;
    of two started together, the shorter)."""
    edges = []
    for i, (name, s, d) in enumerate(spans):
        edges.append((s, 1, i))
        edges.append((s + d, 0, i))
    edges.sort()
    out: List[Tuple[float, float, str]] = []
    open_: Dict[int, float] = {}
    prev = None
    for t, kind, i in edges:
        if open_ and prev is not None and t > prev:
            top = max(open_, key=lambda j: (open_[j], -spans[j][2]))
            name = spans[top][0]
            if out and out[-1][2] == name and out[-1][1] == prev:
                out[-1] = (out[-1][0], t, name)
            else:
                out.append((prev, t, name))
        if kind:
            open_[i] = t
        else:
            open_.pop(i, None)
        prev = t
    return out


def _idle(trace: devtrace.Trace) -> List[Tuple[float, float]]:
    """The window's intervals with no op on the first device plane."""
    planes = sorted(p for p in trace.ops if trace.ops[p])
    a, b = trace.window()
    out, cur = [], a
    for lo, hi in trace.busy_intervals(planes[0]):
        if lo > cur:
            out.append((cur, lo))
        cur = max(cur, hi)
    if b > cur:
        out.append((cur, b))
    return out


def _overlap(a: List[Tuple], b: List[Tuple]) -> List[Tuple]:
    """Pieces in both sorted interval lists; each keeps ``b``'s name
    (``b`` entries are (start, end, name))."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi, b[j][2]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _engine_pieces(trace, spans, engine):
    marks = [(n, s, d) for n, s, d in trace.marks if n != WINDOW]
    own = [(n, s, d) for n, s, d, t in spans if t == engine]
    return _innermost(own + marks)


def _r_host(spans, workers) -> List[Tuple[float, float, str]]:
    """Where some R-worker's innermost span is host work of its own,
    merged across workers."""
    pieces = []
    for w in workers:
        pieces += [p for p in _innermost(
            [(n, s, d) for n, s, d, t in spans if t == w]) if p[2] in R_HOST]
    merged: List[Tuple[float, float, str]] = []
    for lo, hi, _ in sorted(pieces):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(hi, merged[-1][1]), "r_host")
        else:
            merged.append((lo, hi, "r_host"))
    return merged


def attribute(trace: devtrace.Trace,
              spans: Sequence[Span]) -> Optional[Dict[str, float]]:
    """Seconds of device idle in the window by the innermost engine-thread
    span over it (``outside any harness call`` where none is), plus
    ``r_host``: the part of the ``repro.pipe.r_wait`` idle in which an
    R-worker was in host work of its own.  None without device ops or
    without program spans."""
    if trace.busy_s() is None:
        return None
    engine, workers = _threads(spans)
    if engine is None:
        return None
    idle = _idle(trace)
    pieces = _overlap(idle, _engine_pieces(trace, spans, engine))
    out: Dict[str, float] = {}
    for lo, hi, name in pieces:
        out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
    named = sum(hi - lo for lo, hi, _ in pieces)
    out[OUTSIDE] = (sum(hi - lo for lo, hi in idle) - named) * 1e-9
    wait = [p for p in pieces if p[2] in R_WAIT]
    out["r_host"] = sum(hi - lo for lo, hi, _ in
                        _overlap(wait, _r_host(spans, workers))) * 1e-9
    return out


def shares(trace: devtrace.Trace,
           spans: Sequence[Span]) -> Optional[Dict[str, float]]:
    """The four shares of the window, in percent: device idle while the
    engine thread waits on R-workers (``idle_r_wait``), of which an
    R-worker was in host work (``idle_r_host``), while it runs S
    callables or moves R work (``idle_s_dispatch``), and while it is in
    a harness step outside the pipeline (``idle_step_edge``); and what
    no named engine span holds (``idle_rest``)."""
    att = attribute(trace, spans)
    if att is None:
        return None
    w = trace.window_s()

    def pct(names) -> float:
        return 100.0 * sum(att.get(n, 0.0) for n in names) / w

    out = {"idle_r_wait": pct(R_WAIT), "idle_r_host": 100.0 * att["r_host"] / w,
           "idle_s_dispatch": pct(S_DISPATCH),
           "idle_step_edge": pct(STEP_EDGE)}
    out["idle_rest"] = (100.0 * (1.0 - trace.busy_s() / w)
                        - out["idle_r_wait"] - out["idle_s_dispatch"]
                        - out["idle_step_edge"])
    return out


def _open_at(pieces, t) -> List[str]:
    return [p[2] for p in pieces if p[0] <= t < p[1]][:1]


def idle_gaps(trace: devtrace.Trace, spans: Sequence[Span],
              n: int = 10) -> List[List]:
    """The longest gaps with no op on the first device, each named by the
    innermost engine-thread span at its midpoint, then `` + `` the
    innermost span of each R-worker in one there; without program spans,
    as ``devtrace`` names them."""
    engine, workers = _threads(spans)
    if engine is None or trace.busy_s() is None:
        return trace.idle_gaps(n)
    eng = _engine_pieces(trace, spans, engine)
    rw = [_innermost([(nm, s, d) for nm, s, d, t in spans if t == w])
          for w in sorted(workers)]
    out = []
    for lo, hi in sorted(_idle(trace), key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (lo + hi)
        names = _open_at(eng, mid) or [OUTSIDE]
        for pieces in rw:
            for nm in _open_at(pieces, mid):
                if nm not in names:
                    names.append(nm)
        out.append([" + ".join(names), (hi - lo) * 1e-9])
    return out


# --------------------------------------------------------------------------
# recorded slices
# --------------------------------------------------------------------------
def cut(trace: devtrace.Trace, spans: Sequence[Span], start_ns: float,
        seconds: float):
    """The part of a trace and its spans inside [start, start + seconds),
    with that as its window."""
    a, b = start_ns, start_ns + seconds * 1e9

    def inside(s, d):
        return s < b and s + d > a

    t = devtrace.Trace(
        ops={p: [e for e in evs if inside(e[1], e[2])]
             for p, evs in trace.ops.items()},
        marks=[(WINDOW, a, b - a)] + [m for m in trace.marks
                                      if m[0] != WINDOW and inside(m[1], m[2])])
    return t, [s for s in spans if inside(s[1], s[2])]


def save(trace: devtrace.Trace, spans: Sequence[Span], path: str) -> None:
    """A recorded slice: ``devtrace``'s plain record plus ``spans``."""
    with open(path, "w") as f:
        json.dump(dict(trace.to_json(), spans=[list(s) for s in spans]), f)


def load(path: str):
    """(trace, spans) of a recorded slice; spans are empty in a record
    made before the program had them."""
    with open(path) as f:
        d = json.load(f)
    return devtrace.Trace.from_json(d), [tuple(s) for s in d.get("spans", [])]


# --------------------------------------------------------------------------
# one traced run that keeps the program's spans
# --------------------------------------------------------------------------
def traced_run(spec, mix: dict, seed: int, seconds: float, metrics,
               peaks: dict, say=print, cache: bool = True) -> dict:
    """``run.execute`` with ``--trace 1``, keeping the profile's program
    spans: its result line, its info, the window's trace and spans."""
    import glob
    import shutil

    import run

    kept = {}

    class KeepSpans(run.Tracer):
        """``run.Tracer`` that also keeps the profile's program spans."""

        def stop(self):
            if not self.on:
                return None
            import jax
            jax.profiler.stop_trace()
            try:
                path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True)[0]
                kept["trace"] = devtrace.Trace.load(path)
                kept["spans"] = load_spans(path)
                return kept["trace"]
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    plain = run.Tracer
    run.Tracer = KeepSpans
    try:
        result, checks, info = run.execute(spec, mix, seed, seconds, True,
                                           metrics, peaks, say=say,
                                           cache=cache)
    finally:
        run.Tracer = plain
    result["checks"] = checks
    return dict(result=result, info=info, **kept)


def report(trace: devtrace.Trace, spans: Sequence[Span]) -> dict:
    return {"spans": len(spans), "shares": shares(trace, spans),
            "idle_s_by_span": attribute(trace, spans),
            "idle_gaps": idle_gaps(trace, spans, 10)}


def main(argv=None) -> int:
    import argparse

    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice", default="",
                    help="write a recorded slice of the window here")
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)[jax.devices()[0].device_kind]
    metrics = [m for m in bench["per_layer"]
               if args.workload in m.get("workloads", [args.workload])]
    got = traced_run(run.family.load_config(cell["config"]),
                     run.traffic.load(cell["traffic"]), args.seed,
                     args.seconds, metrics, peaks)
    print(json.dumps(got["result"]), flush=True)
    trace, spans = got["trace"], got["spans"]
    out = dict(seed=args.seed, info=got["info"], **report(trace, spans))
    if args.slice:
        # the longest slice from the window's middle, up to 0.25 s, that
        # stays under 200 KB
        a, b = trace.window()
        sec = 0.25
        while True:
            t, s = cut(trace, spans, 0.5 * (a + b), sec)
            save(t, s, args.slice)
            if os.path.getsize(args.slice) <= 200_000 or sec < 0.01:
                break
            sec *= 0.8
        out["slice"] = dict(path=args.slice, seconds=sec,
                            bytes=os.path.getsize(args.slice),
                            busy_s=t.busy_s(), idle_share=t.idle_share(),
                            **report(t, s))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
