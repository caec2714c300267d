"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows and writes a
machine-readable ``BENCH_<name>.json`` per module at the repo root (the
perf trajectory CI uploads as an artifact).

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--no-json]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BENCHES = [
    ("perfmodel", "benchmarks.bench_perfmodel", "Tables 1/2/3 + eq.7-11"),
    ("sls", "benchmarks.bench_sls", "Fig. 6/7/11/12 SLS schedule"),
    ("throughput", "benchmarks.bench_throughput", "Fig. 9 throughput"),
    ("latency", "benchmarks.bench_latency", "Fig. 10 latency"),
    ("scalability", "benchmarks.bench_scalability", "Fig. 13/14 scaling"),
    ("fig8", "benchmarks.bench_fig8", "Fig. 8 layer-count linearity"),
    ("kernels", "benchmarks.bench_kernels", "§5.1/5.2 R-Part kernels"),
    ("paged", "benchmarks.bench_paged", "Paged vs dense R-worker KV"),
    ("prefill", "benchmarks.bench_prefill",
     "Chunked-vs-monolithic prefill, continuous arrivals"),
    ("prefix", "benchmarks.bench_prefix",
     "Shared-prefix KV reuse: capacity + TTFT vs share ratio"),
    ("fleet", "benchmarks.bench_fleet", "Fleet skew/rebalance/recovery"),
    ("tiering", "benchmarks.bench_tiering",
     "KV lifecycle tiering: restore-vs-reprefill TTFT, multi-turn"),
    ("spec", "benchmarks.bench_spec",
     "Speculative decoding: accepted/step + tokens/s vs vanilla"),
    ("strategies", "benchmarks.bench_strategies", "§Perf strategy A/B tables"),
    ("roofline", "benchmarks.bench_roofline", "§Roofline (from dry-run)"),
    ("hotpath", "benchmarks.bench_hotpath", "Hot-path overhead + OoO A/B"),
    ("chaos", "benchmarks.bench_chaos",
     "Seeded fault injection: MTTR, recovery dip, chaos-off A/B"),
]

# benches that may legitimately emit zero rows (they render whatever
# artifacts exist on disk); every other silent bench fails --smoke
MAY_BE_EMPTY = {"strategies", "roofline"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_<name>.json files")
    ap.add_argument("--smoke", action="store_true",
                    help="CI regression gate: tiny iteration counts, no "
                         "JSON artifacts, fail unless EVERY bench module "
                         "runs clean (ok: true) and emits rows")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        # must be set before bench modules import/run (common.smoke())
        os.environ["BENCH_SMOKE"] = "1"
        args.no_json = True
    from benchmarks.common import RowCollector, write_bench_json

    print("name,us_per_call,derived")
    failures = 0
    for name, mod, what in BENCHES:
        if args.only and name != args.only:
            continue
        print(f"# --- {name}: {what}", flush=True)
        t0 = time.time()
        collector = RowCollector()
        error = ""
        try:
            import importlib
            importlib.import_module(mod).run(print_fn=collector)
            if args.smoke and not collector.rows:
                if name in MAY_BE_EMPTY:
                    print(f"# note: {name} emitted no rows (no artifacts "
                          f"on disk)", flush=True)
                else:
                    raise RuntimeError(f"bench {name} emitted no rows")
            if args.smoke and collector.dropped:
                raise RuntimeError(
                    f"bench {name} dropped {collector.dropped} malformed "
                    f"row(s), e.g. {collector.dropped_lines[:3]!r}")
        except Exception:
            failures += 1
            error = traceback.format_exc(limit=3)
            print(f"{name}_FAILED,0,{error!r}")
        dt = time.time() - t0
        if not args.no_json:
            path = write_bench_json(name, collector.rows, what=what,
                                    duration_s=dt, error=error)
            print(f"# wrote {os.path.relpath(path)}", flush=True)
        print(f"# {name} done in {dt:.1f}s", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == '__main__':
    main()
