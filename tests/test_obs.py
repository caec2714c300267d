"""Unified observability layer (repro.obs): metrics registry, stats-key
schema + compat shim, request lifecycle timelines, and the perfmodel
drift monitor on a skewed-worker scenario.  The hot path's profiler
spans are tested in test_spans.py."""
import os
import sys
import threading

import numpy as np
import pytest

from repro.models import model as M
from repro.obs import (LEGACY_ALIASES, MetricsRegistry, ObsConfig, StatsDict,
                       assert_conforms, check_key, normalize, timeline)
from repro.serving.engine import ServingEngine
from repro.serving.request import Request

from conftest import tiny_cfg


# --------------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------------- #

def test_registry_counter_gauge_snapshot():
    r = MetricsRegistry()
    c = r.counter("submitted_count")
    c.inc()
    c.inc(4)
    g = r.gauge("queue_depth_count")
    g.set(7)
    g.set(3)
    snap = r.snapshot()
    assert snap["submitted_count"] == 5.0
    assert snap["queue_depth_count"] == 3.0
    # get-or-create returns the same object
    assert r.counter("submitted_count") is c
    # one key, one meaning: re-registering under a different type raises
    with pytest.raises(TypeError):
        r.histogram("submitted_count")


def test_histogram_percentiles_log_buckets():
    r = MetricsRegistry()
    h = r.histogram("lat_s")
    vals = [i / 1000.0 for i in range(1, 1001)]    # uniform 1ms..1s
    for v in vals:
        h.observe(v)
    p50, p90, p99 = h.percentile(.5), h.percentile(.9), h.percentile(.99)
    # log-bucket resolution is one geometric sub-bucket (~19% worst case)
    assert p50 == pytest.approx(0.5, rel=0.25)
    assert p90 == pytest.approx(0.9, rel=0.25)
    assert p99 == pytest.approx(0.99, rel=0.25)
    assert 0 < p50 <= p90 <= p99 <= h.vmax == 1.0
    assert h.mean == pytest.approx(sum(vals) / len(vals))
    snap = h.snapshot()
    assert snap["lat_s_count"] == 1000.0
    assert snap["lat_s_max"] == 1.0
    assert set(snap) == {"lat_s_count", "lat_s_mean", "lat_s_p50",
                         "lat_s_p90", "lat_s_p99", "lat_s_max"}
    # percentiles clamp to the observed range, never report outside it
    h2 = r.histogram("one_s")
    h2.observe(0.123)
    assert h2.percentile(0.5) == 0.123
    assert h2.percentile(0.99) == 0.123
    # negatives clamp to zero, zero is representable
    h3 = r.histogram("z_s")
    h3.observe(0.0)
    h3.observe(-1.0)
    assert h3.count == 2 and h3.vmax == 0.0
    assert h3.percentile(0.9) == 0.0


def test_registry_thread_safety():
    r = MetricsRegistry()
    c = r.counter("n_count")
    h = r.histogram("v_s")
    n, per = 8, 2000

    def work(seed):
        for i in range(per):
            c.inc()
            h.observe((seed + i) % 10 / 1000.0 + 1e-6)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n * per
    assert h.count == n * per
    assert sum(h.buckets) == n * per


# --------------------------------------------------------------------------- #
# stats-key schema + compat shim
# --------------------------------------------------------------------------- #

def test_schema_check_key():
    for good in ("dispatch_s", "host_tier_bytes", "cached_tokens",
                 "swapped_pages", "steps_count", "token_hit_rate",
                 "tokens_per_s", "last_skew_ratio", "ttft_s_p50",
                 "hotpath_collect_s", "queue_wait_s_p99"):
        assert check_key(good), good
    for bad in ("steps", "ooo_advances", "hits", "bytes_out", "sim_seconds",
                "last_skew", "dispatch"):
        assert not check_key(bad), bad
    with pytest.raises(AssertionError) as ei:
        assert_conforms({"dispatch_s": 1.0, "steps": 2.0, "hits": 3.0})
    assert "steps" in str(ei.value) and "hits" in str(ei.value)
    assert_conforms({"dispatch_s": 1.0})    # no raise


def test_stats_dict_legacy_compat():
    d = normalize({"steps": 7.0, "ooo_advances": 2.0, "dispatch_s": 0.5})
    assert isinstance(d, StatsDict)
    # canonical keys only in iteration / conformance
    assert_conforms(d)
    assert set(d) == {"steps_count", "ooo_advances_count", "dispatch_s"}
    # ... but every legacy spelling still reads through the shim
    assert d["steps"] == 7.0
    assert d.get("ooo_advances") == 2.0
    assert "steps" in d and "steps_count" in d
    assert "nope" not in d
    assert d.get("nope") is None and d.get("nope", -1) == -1
    with pytest.raises(KeyError):
        d["nope"]
    # every alias target is schema-conformant (sources may be too —
    # e.g. host_bytes was renamed for clarity, not units)
    for legacy, canon in LEGACY_ALIASES.items():
        assert check_key(canon), canon
        assert legacy != canon


# --------------------------------------------------------------------------- #
# timeline helpers
# --------------------------------------------------------------------------- #

def test_timeline_derivations():
    ev = [("submitted", 0, 10.0, None), ("admitted", 1, 10.5, None),
          ("first_token", 2, 11.0, None), ("token", 3, 11.2, None),
          ("token", 4, 11.4, None), ("preempted", 5, 11.5, None),
          ("submitted", 5, 11.5, None), ("admitted", 8, 13.0, None),
          ("first_token", 9, 13.1, None), ("token", 10, 13.3, None),
          ("finished", 10, 13.3, None)]
    assert timeline.queue_wait_s(ev) == pytest.approx(0.5)
    assert timeline.ttft_s(ev) == pytest.approx(1.0)
    assert timeline.e2e_s(ev) == pytest.approx(3.3)
    # the preemption resets the inter-token chain: the 11.4 -> 13.1
    # re-prefill stall must NOT appear as a giant gap
    gaps = timeline.inter_token_s(ev)
    assert gaps == pytest.approx([0.2, 0.2, 0.2])
    s = timeline.summarize(ev)
    assert s["events_count"]["token"] == 3
    assert s["inter_token_mean_s"] == pytest.approx(0.2)
    assert timeline.queue_wait_s([("submitted", 0, 1.0, None)]) is None


# --------------------------------------------------------------------------- #
# end-to-end: serving engine with observability on
# --------------------------------------------------------------------------- #

def _mk_reqs(rng, cfg, n, max_new=4):
    return [Request(rid=i,
                    prompt=np.asarray(rng.integers(
                        1, cfg.vocab_size, (int(rng.integers(3, 8)),)),
                        np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def test_serving_engine_metrics_and_timeline(rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    eng = ServingEngine(params, cfg, batch=4, cache_len=48,
                        backend="hetero", num_microbatches=2, kv_chunk=48,
                        observability=True)
    try:
        for r in _mk_reqs(rng, cfg, 6):
            eng.submit(r)
        eng.run(max_steps=100)
        m = eng.metrics()
        # the whole snapshot follows one documented key schema
        assert_conforms(m)
        # lifecycle counters
        assert m["submitted_count"] == 6.0
        assert m["admitted_count"] >= 6.0
        assert m["finished_count"] == 6.0
        assert m["generated_tokens"] == 6 * 4
        # serving latency histograms, percentiles included
        assert m["ttft_s_count"] == 6.0
        assert 0 < m["ttft_s_p50"] <= m["ttft_s_p99"] <= m["ttft_s_max"]
        assert m["queue_wait_s_count"] == 6.0
        assert m["inter_token_s_count"] == 6 * 3   # max_new-1 gaps each
        assert m["e2e_s_p50"] >= m["ttft_s_p50"] * 0.5
        # legacy stats surfaces ride along under namespace prefixes
        assert m["hotpath_dispatch_s"] > 0.0
        assert m["hotpath_steps_count"] >= 1.0
        assert m["steps_count"] == float(eng.step_idx)
        # drift monitor is present (still calibrating — short run)
        assert "drift_calibrated_count" in m
        # hotpath_stats keeps the legacy spellings readable via the shim
        hp = eng.hotpath_stats()
        assert hp["steps"] == hp["steps_count"]

        # -- per-request lifecycle timeline ---------------------------- #
        ev = eng.request_timeline(0)
        kinds = [e[0] for e in ev]
        assert kinds[0] == "submitted"
        for k in ("admitted", "first_token", "finished"):
            assert k in kinds, kinds
        # causal ordering of the derived latencies
        assert timeline.first_t(ev, "submitted") \
            <= timeline.first_t(ev, "admitted") \
            <= timeline.first_t(ev, "first_token") \
            <= timeline.last_t(ev, "finished")
        assert timeline.ttft_s(ev) >= timeline.queue_wait_s(ev)
        assert len(timeline.inter_token_s(ev)) == 3
        assert [e[0] for e in ev].count("token") == 3
        with pytest.raises(KeyError):
            eng.request_timeline(999)
    finally:
        eng.close()


def test_serving_engine_obs_with_prefix_and_preempt(rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    shared = np.asarray(rng.integers(1, cfg.vocab_size, (12,)), np.int32)
    eng = ServingEngine(params, cfg, batch=2, cache_len=64,
                        backend="hetero", num_microbatches=2, kv_chunk=64,
                        num_r_workers=1, paged_kv=True, page_size=8,
                        pages_per_worker=64, prefix_cache=True,
                        observability=True)
    try:
        # sequential arrivals: rid 0 prefills and registers the prefix,
        # rid 1 then admits as a prefix hit
        eng.submit(Request(rid=0, prompt=shared.copy(), max_new_tokens=8))
        for _ in range(4):
            eng.step()
        eng.submit(Request(rid=1, prompt=shared.copy(), max_new_tokens=8))
        for _ in range(3):
            eng.step()
        assert eng.preempt(1)
        fin = eng.run(max_steps=100)
        assert len(fin) == 2
        m = eng.metrics()
        assert_conforms(m)
        assert m["preempted_count"] == 1.0
        assert m["prefix_hit_count"] >= 1.0
        assert m["prefix_hits_count"] >= 1.0     # admission-level stat
        ev = eng.request_timeline(1)
        kinds = [e[0] for e in ev]
        assert "preempted" in kinds
        # preempted request re-enters the queue and finishes
        assert kinds.index("preempted") < len(kinds) - 1
        assert kinds[-1] == "finished"
        assert kinds.count("admitted") == 2
    finally:
        eng.close()


def test_observability_off_and_toggle(rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    eng = ServingEngine(params, cfg, batch=2, cache_len=32)
    for r in _mk_reqs(rng, cfg, 2, max_new=3):
        eng.submit(r)
    eng.run(max_steps=50)
    # off: no registry, no drift — but metrics() still works
    m = eng.metrics()
    assert_conforms(m)
    assert "ttft_s_p50" not in m
    assert m["steps_count"] > 0
    assert eng.request_timeline(0) == []     # no events recorded
    with pytest.raises(RuntimeError):
        eng.set_observability(True)
    with pytest.raises(RuntimeError):
        eng.drift_report()


def test_observability_colocated_backend(rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    eng = ServingEngine(params, cfg, batch=2, cache_len=32,
                        observability=True)
    for r in _mk_reqs(rng, cfg, 2, max_new=3):
        eng.submit(r)
    eng.run(max_steps=50)
    m = eng.metrics()
    assert_conforms(m)
    assert m["finished_count"] == 2.0
    assert m["ttft_s_count"] == 2.0
    # colocated backend has no pipeline, hence no drift monitor
    with pytest.raises(RuntimeError):
        eng.drift_report()


# --------------------------------------------------------------------------- #
# perfmodel drift monitor
# --------------------------------------------------------------------------- #

def test_drift_monitor_flags_skewed_worker(rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    ocfg = ObsConfig(drift_warmup_steps=4, drift_calibration_steps=6,
                     drift_tolerance=0.5)
    eng = ServingEngine(params, cfg, batch=4, cache_len=80,
                        backend="hetero", num_microbatches=2, kv_chunk=80,
                        observability=ocfg)
    try:
        for i in range(4):
            eng.submit(Request(
                rid=i,
                prompt=np.asarray(rng.integers(1, cfg.vocab_size, (4,)),
                                  np.int32),
                max_new_tokens=60))
        # warmup (JIT compile, excluded) + calibration: healthy fleet
        for _ in range(10):
            eng.step()
        rep0 = eng.drift_report()
        assert rep0.calibrated
        # watch phase: one worker degrades hard (bandwidth-bound
        # straggler — deterministic per-row service time)
        eng.engine.workers[0].sim_row_cost = 0.05
        for _ in range(8):
            eng.step()
        rep = eng.drift_report()
        assert rep.calibrated and rep.steps_count >= 8
        keys = {r.key for r in rep.records}
        # residuals reported for the dispatch-overhead fit and tokens/s
        assert "dispatch_s" in keys
        assert "tokens_per_s" in keys
        tps = rep.record("tokens_per_s")
        # the straggler collapses throughput well past the tolerance
        assert tps.measured < tps.predicted
        assert tps.rel < -0.5
        assert "tokens_per_s" in rep.flagged
        assert "DRIFTED" in str(rep)
        # the report is exported through metrics() under drift_*
        m = eng.metrics()
        assert m["drift_flagged_count"] >= 1.0
        assert m["drift_tokens_per_s_rel"] == pytest.approx(tps.rel)
        assert_conforms(m)
    finally:
        eng.close()


def test_drift_monitor_quiet_on_healthy_fleet(rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    ocfg = ObsConfig(drift_warmup_steps=4, drift_calibration_steps=6,
                     drift_tolerance=3.0)
    eng = ServingEngine(params, cfg, batch=4, cache_len=64,
                        backend="hetero", num_microbatches=2, kv_chunk=64,
                        observability=ocfg)
    try:
        for i in range(4):
            eng.submit(Request(
                rid=i,
                prompt=np.asarray(rng.integers(1, cfg.vocab_size, (4,)),
                                  np.int32),
                max_new_tokens=40))
        for _ in range(18):
            eng.step()
        rep = eng.drift_report()
        assert rep.calibrated
        # a generous tolerance on an unchanged fleet flags nothing
        assert rep.flagged == []
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# benchmark harness: malformed-row accounting (satellite)
# --------------------------------------------------------------------------- #

def test_row_collector_counts_dropped_lines():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import RowCollector
    c = RowCollector(echo=None)
    c("name,us_per_call,derived")          # header: expected non-row
    c("# comment")                         # comment: expected non-row
    c("")                                  # blank: expected non-row
    c("good_row,12.5,extra")
    c("garbage")                           # no comma -> dropped
    c("bad_row,not_a_float,x")             # unparseable -> dropped
    assert [r["name"] for r in c.rows] == ["good_row"]
    assert c.dropped == 2
    assert c.dropped_lines == ["garbage", "bad_row,not_a_float,x"]
