"""The system under test, reached only through its public entry points.

The benchmark takes from the program its serving engine and the counters
that engine exposes; everything else (weights, traffic, the reference)
is the benchmark's own, and the program's configuration comes from the
configuration's family (``family.py``).  ``src/`` is put on the import
path here.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))


def pool_pages(eng: dict) -> int:
    """Pages of one (R-worker, micro-batch) pool: every row's whole
    cache."""
    rows = -(-eng["batch"] // eng["num_microbatches"]
             // eng["num_r_workers"])
    return rows * -(-eng["cache_len"] // eng["page_size"])


def build_engine(params, cfg, eng: dict, seed: int):
    """The hetero serving engine over paged KV, with chunked prefill."""
    from repro.serving.engine import ServingEngine
    return ServingEngine(
        params, cfg, batch=eng["batch"], cache_len=eng["cache_len"],
        backend="hetero", paged_kv=True, page_size=eng["page_size"],
        pages_per_worker=pool_pages(eng),
        num_r_workers=eng["num_r_workers"],
        num_microbatches=eng["num_microbatches"],
        prefill_chunk=eng["prefill_chunk"], seed=seed)


def request(plan):
    """A greedy request of the plan."""
    from repro.serving.request import Request
    return Request(rid=plan.idx, prompt=plan.prompt,
                   max_new_tokens=plan.max_new)


def running(req) -> bool:
    from repro.serving.request import Status
    return req.status is Status.RUNNING
