"""Continuous-batching serving engine with the paper's admission schedules.

A fixed pool of ``batch`` sequence slots is decoded every step; finished
sequences free their slot and the admission policy decides *when* queued
requests may take one:

  * ``greedy``  — fill any free slot immediately (vLLM/Orca-style
                  continuous batching; the paper's baseline behavior).
  * ``sls``     — fixed-interval micro-batches of M = B·F/S every F steps
                  (FastDecode §4.2 cold-start rule).
  * ``loadctl`` — Algorithm 1: earliest step under the W_lim peak bound.

Backends: ``colocated`` (single-device decode, the vanilla baseline) or
``hetero`` (the S-/R-worker pipeline of core.hetero).  Both expose the
same row-replacement protocol so continuous batching works identically.

With ``paged_kv=True`` (hetero only) the R-workers store self-attention
KV block-granular (serving.paged_cache): admission allocates only the
pages a prompt needs, decode grows tables page-by-page, and a finished
sequence's pages are freed the step it completes — so R-side resident KV
tracks the actual token count instead of batch*cache_len.

With ``prefix_cache=True`` (hetero + paged, pure self-attention archs)
shared prompt prefixes are deduplicated across requests: the paged
allocator ref-counts pages with copy-on-write, a per-(worker,
micro-batch) prefix index maps page-aligned token blocks to resident
pages, admission is prefix-AWARE (a queued request takes the free slot
whose pool caches the longest prefix of its prompt, and the page
budget credits adopted pages), and a hit prefills ONLY the uncached
suffix through the chunk machinery.  See docs/ARCHITECTURE.md
"Shared-prefix KV reuse".

With ``fleet=FleetManager(...)`` (hetero only) the R-worker pool is
fleet-managed: heterogeneity-aware partition planning, straggler
rebalancing, and failure recovery run around each step (``pre_step`` /
``post_step``), lost rows are re-prefilled exactly from the token
history (``_replay_rows``), and admission is re-costed after a topology
change (``_recost_admission``).  See repro.fleet and
docs/ARCHITECTURE.md ("Fleet management").

With ``prefill_chunk=C`` (hetero only) prompts are prefilled CHUNKED:
admission assigns a slot and marks the request PREFILLING, then each
step streams one C-token chunk through the pipelined engine — executed
on the S-worker inside the decode event loop wherever R-worker waits
leave it idle, each chunk's per-layer KV rows shipped incrementally to
the owning R-worker — and the sequence joins the decode batch the step
its last chunk lands.  Decode for resident sequences never stalls on a
prompt (``prefill_chunk=0`` keeps the monolithic whole-prompt path as
the A/B baseline; see benchmarks/bench_prefill.py).

The hetero decode step is event-driven (core.hetero ``CompletionSink``):
``schedule="ooo"`` (default) advances whichever micro-batch's R-results
land first, ``"fifo"`` pins issue order (the A/B baseline);
``collect_timeout_s`` bounds how long a step waits on a straggler before
raising a RuntimeError that names the missing worker/micro-batch/layer/
phase.  Per-step dispatch/collect/S-dispatch/R-wait breakdowns are at
``hotpath_stats()`` (benchmarks/bench_hotpath.py).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig
from repro.core.hetero import (ColocatedEngine, HeteroPipelineEngine,
                               StepFault, batch_slice, per_layer_state)
from repro.core import decompose as D
from repro.core.schedule import LoadController, microbatch_size, w_prime_max
from repro.models import model as M
from repro.obs import Observability, coerce_obs_config, schema
from repro.obs import spans as S
from repro.obs.drift import DriftMonitor
from repro.serving.request import Request, Status
from repro.serving.sampler import sample, spec_accept


def _pad_pow2(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


@dataclass
class StepRecord:
    """Per-step accounting.  ``prefill_wall`` is time spent admitting/
    prefilling (monolithic _place, chunk queueing + the S-side chunk
    work inside the pipelined step), ``decode_wall`` is the decode step
    net of that chunk work, ``fleet_wall`` covers the fleet pre/post
    hooks.  ``wall`` (the pre-split total) remains as a property so old
    consumers keep working — but latency benchmarks should report
    ``decode_wall``, which no longer conflates admission bursts with
    steady-state decode."""
    step: int
    prefill_wall: float
    decode_wall: float
    fleet_wall: float
    active: int
    resident_len: int
    admitted: int

    @property
    def wall(self) -> float:
        return self.prefill_wall + self.decode_wall + self.fleet_wall


@dataclass
class SpecConfig:
    """Speculative decoding through the hetero pipeline.

    Each decode step drafts ``k`` tokens per sequence GREEDILY on an
    S-worker-resident drafter (a plain dense-state model — no R-worker
    round-trips), then verifies all k+1 candidates (the pending token
    plus the drafts) in ONE pipelined step as a verify chunk: the
    R-Part sweeps each row's cached KV once for the whole candidate
    block instead of once per token, which is the entire point on a
    bandwidth-bound R side.  Accepted prefixes commit via modified
    rejection sampling (sampler.spec_accept — greedy traces bit-exact,
    sampled traces token-exact in expectation) and the rejected tail's
    KV is rolled back (``HeteroPipelineEngine.truncate_rows``).

    ``draft_cfg``/``draft_params`` select the drafter model; both None
    means SELF-speculation (the target model drafts for itself —
    acceptance ~1, useful for tests and acceptance-favorable benches).
    """
    k: int = 4
    draft_cfg: Optional[ModelConfig] = None
    draft_params: Any = None


class ServingEngine:
    @classmethod
    def from_plan(cls, params, cfg, *, seq_len: int, hw_s=None, hw_r=None,
                  latency_slo: Optional[float] = None, max_batch: int = 4096,
                  **kw):
        """Size the engine with the paper's §4.3 performance model:
        batch from eq. 7/8, R-worker count from eq. 11."""
        from repro.core import perfmodel as P
        hw_s = hw_s or P.TPU_V5E
        hw_r = hw_r or P.TPU_V5E
        # windowed archs fall back to dense KV at runtime (RWorker.
        # _pageable), so don't plan with paged terms there either
        page = (kw.get("page_size", 16)
                if kw.get("paged_kv") and cfg.window == 0 else 0)
        # expected shared-prefix workload terms (fraction of admissions
        # that hit the cache, and the shared prefix length) — they
        # shrink eq. 9's residency demand and scale w_lim (see
        # perfmodel.prefix_dedup_factor)
        prefix_hit = kw.pop("prefix_hit_rate", 0.0)
        prefix_len = kw.pop("prefix_len", 0)
        if not kw.get("prefix_cache"):
            prefix_hit = 0.0        # no cache, no dedup to plan for
        # spec_k="plan" lets the model pick the draft length maximizing
        # spec_speedup at the expected acceptance rate (spec_alpha —
        # mirror of prefill_chunk="plan"); an int passes through
        spec_k = kw.pop("spec_k", None)
        spec_alpha = kw.pop("spec_alpha", 0.8)
        plan = P.plan(cfg, hw_s, hw_r, seq_len=seq_len,
                      latency_slo=latency_slo, page=page,
                      prefix_hit_rate=prefix_hit, prefix_len=prefix_len,
                      spec_alpha=spec_alpha if spec_k == "plan" else 0.0)
        if spec_k == "plan":
            kw["spec_decode"] = SpecConfig(k=int(plan["spec_k"]))
        elif spec_k:
            kw["spec_decode"] = SpecConfig(k=int(spec_k))
        batch = int(min(max_batch, max(2, plan["batch"])))
        if batch % 2:
            batch += 1
        # clamp the planned fleet to one row per worker within a
        # micro-batch (the constructor's hard floor — a clipped batch
        # can undercut an eq. 11 worker count computed for the full one)
        mb_size = batch // kw.get("num_microbatches", 2)
        workers = int(max(1, min(8, mb_size, plan["workers"])))
        if kw.get("prefill_chunk") == "plan":
            # let the §4.3 model pick the chunk: largest pow2 whose
            # S-cost fits the decode bubble (perfmodel.
            # optimal_prefill_chunk) — clamped so one chunk never
            # exceeds the prompt budget
            kw["prefill_chunk"] = int(min(plan["prefill_chunk"], seq_len))
        if kw.get("admission") == "loadctl" and kw.get("w_lim") is None \
                and plan.get("w_lim_scale", 1.0) != 1.0 \
                and kw.get("target_len"):
            # credit deduplicated residency against the Algorithm 1 peak
            # bound: shared prefix tokens are resident once, not per row
            s = max(1, kw["target_len"])
            f = max(1, kw.get("interval", 1) or 1)
            kw["w_lim"] = w_prime_max(batch, s, f) * plan["w_lim_scale"]
        eng = cls(params, cfg, batch=batch, cache_len=seq_len,
                  backend=kw.pop("backend", "hetero"),
                  num_r_workers=workers, **kw)
        eng.plan = plan
        if eng._obs_obj is not None and eng._obs_obj.drift is not None:
            # the drift monitor compares measured tokens/s against the
            # analytic plan's promise too, when there is one
            eng._obs_obj.drift.plan = plan
        return eng

    def __init__(self, params, cfg: ModelConfig, *, batch: int,
                 cache_len: int, backend: str = "colocated",
                 admission: str = "greedy", target_len: int = 0,
                 interval: int = 0, w_lim: Optional[float] = None,
                 num_r_workers: int = 2, num_microbatches: int = 2,
                 kv_chunk: int = 1024, quantized_kv: bool = False,
                 paged_kv: bool = False, page_size: int = 16,
                 pages_per_worker: Optional[int] = None, seed: int = 0,
                 fleet=None, schedule: str = "ooo",
                 collect_timeout_s: float = 600.0,
                 profile_timing: bool = False, prefill_chunk: int = 0,
                 prefix_cache: bool = False, kv_tiering=None,
                 spec_decode: Optional[SpecConfig] = None,
                 preempt_after: int = 0,
                 observability=False,
                 chaos=None,
                 suspect_after_s: float = 120.0,
                 suspect_strikes: int = 2,
                 max_step_retries: int = 4,
                 retry_backoff_s: float = 0.02):
        if backend not in ("colocated", "hetero"):
            raise ValueError(
                f"backend must be 'colocated' or 'hetero', got {backend!r}")
        # KV lifecycle tiering: True (default TierConfig), a TierConfig,
        # or a ready HostTier (share one across engines in tests).
        # Implies prefix_cache — the tier is keyed by its digest chains.
        self.kv_tier = None
        if kv_tiering:
            from repro.serving.paged_cache import HostTier, TierConfig
            if backend != "hetero" or not paged_kv:
                raise ValueError(
                    "kv_tiering requires backend='hetero' with "
                    "paged_kv=True — the tier swaps paged R-worker pool "
                    "pages")
            if isinstance(kv_tiering, HostTier):
                self.kv_tier = kv_tiering
            elif isinstance(kv_tiering, TierConfig):
                self.kv_tier = HostTier(kv_tiering)
            else:
                self.kv_tier = HostTier()
            prefix_cache = True
        if prefix_cache:
            from repro.core.config import ATTN as _ATTN
            if backend != "hetero" or not paged_kv:
                raise ValueError(
                    "prefix_cache=True requires backend='hetero' with "
                    "paged_kv=True — shared prefixes live in the paged "
                    "R-worker pools")
            if any(k != _ATTN for k in cfg.layer_pattern) \
                    or cfg.window > 0 or cfg.is_encdec:
                raise ValueError(
                    "prefix_cache=True requires a pure self-attention "
                    "arch with window=0: recurrent/windowed/cross-"
                    "attention R-state cannot be shared page-wise, so "
                    "the skipped-prefill admission would be wrong")
        if spec_decode is not None:
            from repro.core.config import ATTN as _ATTN
            if backend != "hetero":
                raise ValueError(
                    "spec_decode requires backend='hetero' — the verify "
                    "step rides the pipelined chunk machinery")
            if spec_decode.k < 1:
                raise ValueError(
                    f"spec_decode.k must be >= 1, got {spec_decode.k}")
            if any(kk != _ATTN for kk in cfg.layer_pattern) \
                    or cfg.window > 0 or cfg.is_encdec:
                raise ValueError(
                    "spec_decode requires a pure self-attention arch "
                    "with window=0: rejected-KV rollback is positional "
                    "truncation, which recurrent/windowed/cross-"
                    "attention R-state does not support")
            if (spec_decode.draft_cfg is None) \
                    != (spec_decode.draft_params is None):
                raise ValueError(
                    "spec_decode needs BOTH draft_cfg and draft_params "
                    "(or neither, for self-speculation)")
        if prefill_chunk:
            if backend != "hetero":
                raise ValueError(
                    "prefill_chunk requires backend='hetero' — the "
                    "colocated engine keeps the monolithic prefill "
                    "(it IS the A/B baseline)")
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1 (0 disables), got "
                    f"{prefill_chunk}")
            from repro.core.config import DEC_XATTN as _DX, XATTN as _XA
            if cfg.is_encdec or _DX in cfg.layer_pattern \
                    or _XA in cfg.layer_pattern:
                raise ValueError(
                    "chunked prefill does not support cross-attention "
                    "archs (enc-dec / vision) — use prefill_chunk=0")
        if batch < 1 or cache_len < 1:
            raise ValueError(
                f"batch ({batch}) and cache_len ({cache_len}) must be >= 1")
        if backend == "hetero" and batch % num_microbatches != 0:
            raise ValueError(
                f"batch ({batch}) must be divisible by num_microbatches "
                f"({num_microbatches}); round batch up to "
                f"{-(-batch // num_microbatches) * num_microbatches} or "
                f"change num_microbatches")
        if fleet is not None and backend != "hetero":
            raise ValueError("fleet management requires backend='hetero'")
        self.params, self.cfg = params, cfg
        self.batch, self.cache_len = batch, cache_len
        self.backend = backend
        self.paged_kv = paged_kv and backend == "hetero"
        self.prefill_chunk = int(prefill_chunk)
        self.prefix_cache = bool(prefix_cache)
        self.spec = spec_decode
        # prefix-hit admissions stream their uncached suffix through the
        # chunk machinery even when prefill_chunk=0 (one whole-suffix
        # chunk), so the chunk plumbing runs whenever either is on;
        # spec decode's verify steps ARE chunk work, so it joins too
        self._uses_chunks = bool(prefill_chunk) or self.prefix_cache \
            or self.spec is not None
        self.prefix_stats = {"hits": 0, "misses": 0, "cached_tokens": 0,
                             "prompt_tokens": 0}
        # auto-preemption: after this many consecutive steps in which
        # the paged admission cap blocked a queued request despite free
        # slots, the least-finished RUNNING row is parked and requeued
        # (0 disables); swap-vs-recompute gating: restores are consulted
        # only when the tier's stream bandwidth makes them worthwhile
        # (see core.perfmodel.kv_restore_break_even)
        self.preempt_after = int(preempt_after)
        self._stall_steps = 0
        self.preemptions = 0
        self._restore_ok = (self.kv_tier is not None
                            and self.kv_tier.cfg.dram_gbps > 0)
        self.admission = admission
        self.target_len = target_len            # S in the paper's schedule
        self.interval = interval                # F
        self.rng = jax.random.PRNGKey(seed)
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * batch
        self.step_idx = 0
        self.records: List[StepRecord] = []
        self.finished: List[Request] = []
        self._last_tok = np.zeros((batch,), np.int32)
        self.fleet = fleet
        # self-healing supervision: chaos is the (optional) fault plan
        # injected into every layer below; the retry/failover loop in
        # _decode_supervised runs regardless (real faults need no plan)
        self.chaos = chaos
        self.max_step_retries = max(0, int(max_step_retries))
        self.retry_backoff_s = max(0.0, float(retry_backoff_s))
        self.faults = 0
        self.recoveries = 0
        # forensic log: one dict per detected fault ({step, kind, wids,
        # transient, recovered, mttr_s}) — bench_chaos reads this
        self.fault_events: List[Dict[str, Any]] = []
        if self.kv_tier is not None and chaos is not None:
            self.kv_tier.chaos = chaos

        if backend == "hetero":
            self.engine = HeteroPipelineEngine(
                params, cfg, batch=batch, cache_len=cache_len,
                num_r_workers=num_r_workers,
                num_microbatches=num_microbatches, kv_chunk=kv_chunk,
                quantized_kv=quantized_kv, paged_kv=paged_kv,
                page_size=page_size, pages_per_worker=pages_per_worker,
                prefix_cache=self.prefix_cache,
                kv_tier=self.kv_tier,
                fleet=fleet, schedule=schedule,
                collect_timeout_s=collect_timeout_s,
                profile_timing=profile_timing,
                chaos=chaos, suspect_after_s=suspect_after_s,
                suspect_strikes=suspect_strikes)
            self.num_mb = num_microbatches
            self.mb_size = batch // num_microbatches
            # stall messages name the in-flight rids of each micro-batch
            self.engine.rids_of = self._rids_of_mb
            for mb in range(self.num_mb):
                self._hetero_init_empty(mb)
        else:
            self.engine = ColocatedEngine(params, cfg, batch=batch,
                                          cache_len=cache_len)
            self.engine.state = M.init_decode_state(cfg, batch, cache_len)
            self.num_mb = 1
            self.mb_size = batch

        # speculative decoding: the S-resident drafter — a plain dense-
        # state model advanced with the single-device callables, no
        # R-worker involvement.  Capacity cache_len + k so throwaway
        # draft runs near capacity never wrap the ring.  ``_spec_dirty``
        # drives lazy resync: a row is dirty whenever its token history
        # changed outside the commit path (admission, fault replay) and
        # is re-fed feed_tokens[:-1] before the next draft.
        self._spec_dirty: set = set()
        # plain counters (always on, unlike obs): bench_spec and the
        # acceptance-rate assertions read these
        self.spec_stats = {"drafted_tokens": 0, "accepted_tokens": 0,
                           "steps": 0}
        if self.spec is not None:
            self._spec_cfg = self.spec.draft_cfg or cfg
            self._spec_params = (params if self.spec.draft_params is None
                                 else self.spec.draft_params)
            self._spec_cache = cache_len + self.spec.k
            self._spec_state = M.init_decode_state(
                self._spec_cfg, batch, self._spec_cache)
            self._spec_decode_fn = jax.jit(partial(
                M.decode_step, cfg=self._spec_cfg))
            self._spec_commit_fn = jax.jit(partial(
                M.prefill_chunk, cfg=self._spec_cfg))
            self._spec_sync_fn = jax.jit(partial(
                M.prefill, cfg=self._spec_cfg,
                cache_len=self._spec_cache))

        if admission == "loadctl":
            s = max(1, target_len)
            if w_lim is None:
                f = max(1, interval)
                w_lim = w_prime_max(batch, s, f)
            self.load_ctl = LoadController(w_lim=w_lim, seq_len=s)
        else:
            self.load_ctl = None
        self._w_lim0 = w_lim if self.load_ctl is not None else None
        self._prefill_cache: Dict[int, callable] = {}
        self._topo_seen = (tuple(self.engine.slices)
                           if backend == "hetero" else None)
        self._choice_cache: Tuple[int, list] = (-1, [])

        # unified observability (repro.obs): off by default, and when
        # off every hot-path hook is a single `self.obs is None` test.
        # `observability=True` enables the defaults; pass an ObsConfig
        # to tune drift calibration.
        self._obs_obj: Optional[Observability] = None
        self.obs: Optional[Observability] = None
        ocfg = coerce_obs_config(observability)
        if ocfg is not None:
            self._obs_obj = Observability(ocfg)
            if ocfg.drift and backend == "hetero":
                self._obs_obj.drift = DriftMonitor(
                    cfg, self.num_mb, len(self.engine.workers),
                    calibration_steps=ocfg.drift_calibration_steps,
                    tolerance=ocfg.drift_tolerance,
                    warmup_steps=ocfg.drift_warmup_steps)
            self.set_observability(True)
        # wall time of each row's previous emitted token, for the
        # inter-token latency histogram (obs only)
        self._tok_t: List[float] = [0.0] * batch
        # tier restore counter watermark, to attribute "restored"
        # timeline events to the admissions whose probe restored pages
        self._restored_seen = 0

    def set_observability(self, on: bool) -> None:
        """Toggle observability on an engine constructed with it (the
        paired-overhead bench flips this between rounds).  A no-op if
        the engine was built with observability=False."""
        if self._obs_obj is None:
            if on:
                raise RuntimeError(
                    "engine was constructed with observability=False — "
                    "pass observability=True|ObsConfig() to enable")
            return
        self.obs = self._obs_obj if on else None

    # ------------------------------------------------------------------ #
    def _hetero_init_empty(self, mb: int) -> None:
        state = M.init_decode_state(self.cfg, self.mb_size, self.cache_len)
        layer_states = per_layer_state(state, self.cfg)
        for li, (kind, _) in enumerate(self.engine.layers):
            r_st, s_st = D.split_block_state(kind, layer_states[li])
            for w in self.engine.workers:
                w.load_state(self.engine._lkey(mb, li),
                             batch_slice(r_st, w.lo, w.hi))
            self.engine.s_states[mb][li] = s_st

    # ------------------------------------------------------------------ #
    def _paged_pool_min(self) -> Optional[int]:
        """Pages in the scarcest per-(worker, micro-batch) pool, or None
        when nothing is paged (dense fallback — e.g. windowed archs)."""
        pools = [a.num_pages for w in self.engine.workers
                 for a in w.allocators.values()]
        return min(pools) if pools else None

    def _length_cap_reason(self) -> Optional[str]:
        """The reason prompt + max_new_tokens must fit cache_len on
        this engine configuration, or None when the dense ring may
        legally wrap (monolithic dense serving; windowed archs wrap by
        design).  One helper so every configuration that cannot honor
        an over-length request rejects it with the SAME message — the
        two former copies of this check had drifted apart."""
        if self.spec is not None:
            return ("speculative decoding rolls rejected tokens back "
                    "by positional KV truncation, which a wrapped ring "
                    "would corrupt")
        if self.prefill_chunk and self.cfg.window == 0:
            # chunked prefill streams KV incrementally and relies on
            # the ring never wrapping (windowed archs wrap by design
            # and are exempt); the monolithic path's silent wrap is
            # not reproducible chunk-wise
            return "required with prefill_chunk > 0"
        if self.paged_kv and self._paged_pool_min() is not None:
            # the dense ring silently wraps past cache_len; the paged
            # path would silently drop tokens past capacity
            return "the paged path would drop tokens past capacity"
        return None

    def submit(self, req: Request) -> None:
        reason = self._length_cap_reason()
        if reason is not None \
                and req.prompt_len + req.max_new_tokens > self.cache_len:
            # the request could never finish within the cache: reject
            # up front instead of wrapping/dropping KV mid-serve
            raise ValueError(
                f"request {req.rid}: prompt ({req.prompt_len}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds cache_len "
                f"({self.cache_len}) — {reason}")
        pool_min = self._paged_pool_min() if self.paged_kv else None
        if pool_min is not None:
            need = self._paged_pages_for(req)
            if need > pool_min:
                # pool capacity is static — fail at submit, not from a
                # later step() while other requests are in flight
                raise ValueError(
                    f"request {req.rid} needs {need} pages, more than a "
                    f"worker pool holds — raise pages_per_worker")
        req.arrive_step = self.step_idx
        if self.obs is not None:
            req.mark("submitted", self.step_idx)
            self.obs.submitted.inc()
        self.queue.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    @property
    def prefill_queue(self) -> List[Request]:
        """Sequences currently mid-chunked-prefill (PREFILLING state,
        slot-resident, advancing one chunk per step), in row order."""
        return [r for r in self.slots
                if r is not None and r.status is Status.PREFILLING]

    def resident_len(self) -> int:
        tot = 0
        for r in self.slots:
            if r is not None:
                tot += r.prompt_len + len(r.generated)
        return tot

    # ------------------------------------------------------------------ #
    def _paged_pages_for(self, req: Request) -> int:
        """Worst-case pages a request will ever hold: its full target
        length (prompt + max_new_tokens, which submit() bounds by
        cache_len), page-rounded."""
        page = self.engine.page_size
        return -(-min(req.target_len, self.cache_len) // page)

    def _paged_admit_cap(self, n: int) -> int:
        """Page-aware admission backpressure with COMMITMENT accounting
        from LIVE allocator state: every resident request still owes
        (full-target pages − pages already mapped) of future growth —
        plus one potential CoW clone while any of its pages is shared —
        and a queued request is admitted only if its own worst case,
        net of the prefix pages it would adopt, fits its prospective
        (worker, micro-batch) pool on top of those debts.  Without
        prefix sharing this reduces exactly to the old full-reservation
        rule; with it, adopted pages held by another resident cost
        nothing and refcount-zero cached pages come out of the
        LRU-evictable budget — so shared-prefix workloads admit
        strictly larger batches while decode-time growth still can
        never exhaust the pool (PagedAllocator.ensure_lengths' degrade
        path stays unreachable under policy-admitted load).  A fleet
        migration duplicates shared pages (the wire format is per-row)
        and can transiently exceed this model — see
        docs/ARCHITECTURE.md "Shared-prefix KV reuse"."""
        if self._paged_pool_min() is None:
            return n        # dense fallback (e.g. windowed arch): no cap
        budget: Dict[Tuple[int, int], int] = {}
        for w in self.engine.workers:
            for mb, a in w.allocators.items():
                budget[(w.wid, mb)] = a.available_pages()
        for row, req in enumerate(self.slots):
            if req is None:
                continue
            w, mb, local = self.engine.worker_for(row)
            a = w.allocators[mb]
            debt = self._paged_pages_for(req) - a.mapped_pages(local)
            ids = a.tables[local][a.tables[local] >= 0]
            if len(ids) and bool((a.refcount[ids] > 1).any()):
                debt += 1             # a divergence may CoW one clone
            budget[(w.wid, mb)] -= max(0, debt)
        m = 0
        for row, r, ids, eff in self._choose_rows(list(self.queue)[:n]):
            w, mb, _ = self.engine.worker_for(row)
            a = w.allocators.get(mb)
            need = self._paged_pages_for(r)   # submit() bounds it by pool
            if eff > 0 and a is not None:
                held = sum(1 for pid in ids if a.refcount[pid] > 0)
                # pages held by a resident sharer are free to adopt;
                # +1 covers the boundary-page CoW clone
                need += 1 - held
            if need > budget[(w.wid, mb)]:
                break
            budget[(w.wid, mb)] -= need
            m += 1
        return m

    def _admit_count(self) -> int:
        """How many queued requests may start THIS step, per policy."""
        free = len(self._free_slots())
        avail = min(free, len(self.queue))
        if self.paged_kv and avail > 0:
            # cap BEFORE the policy so loadctl only records admissions
            # that actually happen
            avail = self._paged_admit_cap(avail)
        if avail == 0:
            return 0
        if self.admission == "greedy":
            n = avail
        elif self.admission == "sls":
            f = max(1, self.interval)
            if self.step_idx % f != 0:
                return 0
            m = microbatch_size(self.batch, max(1, self.target_len), f)
            n = min(avail, m)
        elif self.admission == "loadctl":
            m = 0
            lc = self.load_ctl
            f = max(1, self.interval)
            mb = microbatch_size(self.batch, max(1, self.target_len), f)
            queued = list(self.queue)
            while m < avail:
                chunk = min(mb, avail - m)   # tail of the queue may be < M
                # prefill-cost-aware admission: the candidates' prompt
                # tokens are resident KV from step one and count against
                # w_lim (the paper's schedule models generated tokens
                # only — long prompts used to ride in for free).  Under
                # chunked prefill, generation starts only after the
                # prompt has streamed in — track the micro-batch at its
                # TRUE generation span (shifted by the prefill delay) so
                # the controller doesn't retire it d steps early and
                # over-admit while it is still fully resident
                cand = queued[m:m + chunk]
                ptoks = sum(r.prompt_len for r in cand)
                d = 0
                if self.prefill_chunk:
                    d = -(-max(r.prompt_len for r in cand)
                          // self.prefill_chunk)
                elif self.prefix_cache:
                    # a prefix-cache hit streams its whole suffix as ONE
                    # chunk and starts generating a step later; track
                    # the span shifted by that step (misses shift too —
                    # conservative, holds capacity one step longer)
                    d = 1
                t = self.step_idx + d
                if lc.earliest_step(t, chunk, prompt_tokens=ptoks) > t:
                    break
                lc.add_microbatch(t, chunk, prompt_tokens=ptoks)
                m += chunk
            n = m
        else:
            raise ValueError(self.admission)
        return n

    # ------------------------------------------------------------------ #
    _PREFILL_FN_KEEP = 4     # jitted prefill fns retained (LRU)

    def _prefill_fn(self, n_pad: int):
        """Whole-prompt prefill callable for a batch padded to ``n_pad``
        rows — LRU-bounded: each entry accumulates one trace per s_pad
        it ever sees, so an unbounded dict leaks executables over a
        long serve with varied admission-group sizes (same policy as
        the hetero engine's per-partition trace caches)."""
        cache = self._prefill_cache
        fn = cache.pop(n_pad, None)
        if fn is None:
            fn = jax.jit(partial(
                M.prefill, cfg=self.cfg, cache_len=self.cache_len))
        cache[n_pad] = fn                     # most-recently-used last
        while len(cache) > self._PREFILL_FN_KEEP:
            cache.pop(next(iter(cache)))
        return fn

    def _sample_tokens(self, logits, reqs) -> np.ndarray:
        """Sample one token per row of ``logits``; ``reqs`` aligns a
        Request (or None) with each row — callers pass None for rows
        whose token will be DISCARDED (mid-prefill, released), so no
        RNG is split and no per-row dispatch runs for them and the
        surviving rows' draw sequence is independent of unrelated
        rows' prefill state.  Greedy rows ride one batch argmax; rows
        whose request sets temperature > 0 are re-drawn individually
        with their own temperature/top_k/top_p."""
        self.rng, sub = jax.random.split(self.rng)
        toks = np.asarray(sample(logits, sub)).copy()
        for i, r in enumerate(reqs):
            if r is None or r.temperature <= 0.0:
                continue
            self.rng, sub = jax.random.split(self.rng)
            toks[i] = int(np.asarray(sample(
                logits[i:i + 1], sub, temperature=r.temperature,
                top_k=r.top_k, top_p=r.top_p))[0])
        return toks

    # -- park / retire / preempt ------------------------------------------ #
    def _finish_row(self, row: int, r: Request, reason: str) -> None:
        """THE finish site: every path that ends a sequence (monolithic
        admit, chunked-prefill token 0, the decode token loop, the
        spec-decode commit walk) funnels through here exactly once, so
        the finish bookkeeping — status, step, reason, slot release,
        page retirement, observability — can never half-happen or
        double-record.  ``reason`` comes from
        :meth:`Request.finish_reason_for`, whose precedence rule makes
        a stop token landing exactly at the max_new_tokens cap report
        "stop" (token semantics outrank budget exhaustion)."""
        r.status = Status.DONE
        r.finish_step = self.step_idx
        r.finish_reason = reason
        self.finished.append(r)
        self.slots[row] = None
        self._retire_row(row, r)
        if self.obs is not None:
            self._obs_finish(r)
        if self._uses_chunks:
            # freed slots stop decoding entirely (no KV append, no
            # length bump) until readmission re-prefills them
            self.engine.set_row_active(row, False)

    def _retire_row(self, row: int, req: Request) -> None:
        """A finished sequence's pages: with tiering, PARK the written
        chain (prompt + generated minus the never-appended last token)
        so a later same-history request restores it without re-prefill;
        otherwise free them as before."""
        if not self.paged_kv:
            return
        if self.kv_tier is not None:
            chain = req.feed_tokens[:-1] if req.generated \
                else req.feed_tokens
            if self.engine.park_row(row, chain):
                return
        self.engine.release_row(row)

    def _preempt_row(self, row: int) -> None:
        """Evict a resident request back to the queue (admission
        pressure): its written KV chain is parked (tiering) or dropped
        (the dense/colocated path replays it at readmission), the slot
        freed, and the request requeued at the BACK with its generated
        tokens kept — resume re-prefills ``feed_tokens`` and continues
        generating token-exactly (greedy sampling is a pure function of
        the token history)."""
        r = self.slots[row]
        if r is None:
            return
        parked = False
        if self.paged_kv:
            if r.status is Status.PREFILLING:
                chain = r.feed_tokens[:r.prefill_pos]
            else:
                chain = r.feed_tokens[:-1] if r.generated \
                    else r.feed_tokens
            parked = bool(self.kv_tier is not None and len(chain)
                          and self.engine.park_row(row, chain))
            if not parked:
                self.engine.release_row(row)
        self.slots[row] = None
        if self._uses_chunks:
            self.engine.set_row_active(row, False)
        r.status = Status.QUEUED
        r.slot = -1
        r.prefill_pos = 0
        self.preemptions += 1
        if self.obs is not None:
            r.mark("preempted", self.step_idx)
            self.obs.preempted.inc()
            if parked:
                r.mark("parked", self.step_idx)
        self.queue.append(r)

    def preempt(self, rid: int) -> bool:
        """Preempt the resident request with id ``rid`` (False if it is
        not currently slot-resident).  Call between steps."""
        for row, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                self._preempt_row(row)
                return True
        return False

    def _auto_preempt(self) -> None:
        """Admission has been page-blocked for ``preempt_after``
        consecutive steps: park the least-finished RUNNING row (most
        generation budget left — it holds its pages longest) to relieve
        the pressure."""
        best, best_rem = -1, -1
        for row, r in enumerate(self.slots):
            if r is None or r.status is not Status.RUNNING:
                continue
            rem = r.max_new_tokens - len(r.generated)
            if rem > best_rem:
                best, best_rem = row, rem
        if best >= 0:
            self._preempt_row(best)

    # -- shared-prefix probing ------------------------------------------- #
    def _probe_prefix(self, row: int, req: Request):
        """(page_ids, cached_eff) for ``req`` landing on ``row`` —
        clamped so at least the feed's LAST token is always
        recomputed: its logits seed generation (the same rule as the
        monolithic prefill), and recomputing it through the chunk path
        is what forces the shared partial tail page onto a private CoW
        clone before this sequence writes into it.  With tiering the
        probe also restores swapped-out pages from the host tier."""
        if not self.prefix_cache:
            return [], 0
        ids, cached = self.engine.probe_prefix(row, req.feed_tokens,
                                               restore=self._restore_ok)
        eff = min(int(cached), req.feed_len - 1)
        if eff <= 0:
            return [], 0
        return ids[:-(-eff // self.engine.page_size)], eff

    def _note_prefix(self, req: Request, eff: int) -> None:
        st = self.prefix_stats
        st["hits" if eff else "misses"] += 1
        st["cached_tokens"] += eff
        st["prompt_tokens"] += req.feed_len
        obs = self.obs
        if obs is not None and eff > 0:
            req.mark("prefix_hit", self.step_idx, extra=eff)
            obs.prefix_hits.inc()
            if self.kv_tier is not None:
                # the probe restores swapped pages as a side effect —
                # attribute the tier's restore-counter advance to this
                # admission's timeline
                restored = int(self.kv_tier.stats.get("restored", 0))
                if restored > self._restored_seen:
                    self._restored_seen = restored
                    req.mark("restored", self.step_idx)
                    obs.restores.inc()

    # -- lifecycle observation (every hook is obs-gated by the caller) --- #
    def _obs_admit(self, reqs: List[Request]) -> None:
        obs = self.obs
        t = time.perf_counter()
        for r in reqs:
            r.mark("admitted", self.step_idx, t)
            obs.admitted.inc()
            # queue wait restarts at preemption: the re-queued request
            # waits from its preempt, not its original arrival
            t0 = r.event_t("preempted", last=True)
            if t0 is None:
                t0 = r.event_t("submitted")
            if t0 is not None:
                obs.queue_wait.observe(t - t0)

    def _obs_first_token(self, r: Request, row: int) -> None:
        obs = self.obs
        t = r.mark("first_token", self.step_idx)
        obs.generated.inc()
        t0 = r.event_t("submitted")
        if t0 is not None:
            obs.ttft.observe(t - t0)
        self._tok_t[row] = t

    def _obs_finish(self, r: Request) -> None:
        obs = self.obs
        t = r.mark("finished", self.step_idx)
        obs.finished.inc()
        t0 = r.event_t("submitted")
        if t0 is not None:
            obs.e2e.observe(t - t0)

    def _choose_rows(self, reqs: List[Request]):
        """Prefix-AWARE row assignment: a cached prefix is only
        adoptable by rows of the (worker, micro-batch) pool that holds
        it, so each request takes the free slot whose pool caches the
        longest prefix of its prompt (misses and the prefix-cache-off
        path fall back to first-free-slot order).  Returns
        [(row, req, page_ids, cached_eff)] in queue order — the same
        deterministic choice `_paged_admit_cap` budgets against (its
        result is memoized per step so placement does not re-walk the
        blake2b hash chains the cap already probed)."""
        step, cached = self._choice_cache
        if step == self.step_idx and len(cached) >= len(reqs) \
                and all(c[1] is r for c, r in zip(cached, reqs)):
            return cached[:len(reqs)]
        free = self._free_slots()
        out = []
        for r in reqs:
            if not free:
                break
            best, best_ids, best_eff = free[0], [], 0
            if self.prefix_cache:
                seen: Dict[Tuple[int, int], Tuple[list, int]] = {}
                for row in free:
                    w, mb, _ = self.engine.worker_for(row)
                    key = (w.wid, mb)
                    if key not in seen:      # one probe per pool
                        seen[key] = self._probe_prefix(row, r)
                    ids, eff = seen[key]
                    if eff > best_eff:
                        best, best_ids, best_eff = row, ids, eff
            out.append((best, r, best_ids, best_eff))
            free.remove(best)
        self._choice_cache = (self.step_idx, out)
        return out

    def _reregister_prefixes(self) -> None:
        """A topology change (migration/recovery) rebuilt the changed
        workers' allocators, dropping their prefix indexes and
        un-sharing their pages (the dense wire format is per-row).
        Re-index every live row's streamed prompt prefix so FUTURE
        admissions share again."""
        for row, r in enumerate(self.slots):
            if r is None:
                continue
            n = (r.prefill_pos if r.status is Status.PREFILLING
                 else r.feed_len - 1)     # written chain (last token
            if n > 0:                     # sampled, never appended)
                self.engine.register_prefix(row, r.feed_tokens[:n])

    def _place(self, reqs: List[Request]) -> None:
        if self.prefill_chunk:
            self._place_chunked(reqs)
            return
        if self.prefix_cache:
            # prefix hits stream their (suffix-only) prefill through the
            # chunk machinery — one whole-suffix chunk rides the next
            # decode step; misses keep the monolithic same-step prefill
            hit_reqs, hit_rows, miss_reqs, miss_rows = [], [], [], []
            for row, r, ids, eff in self._choose_rows(reqs):
                self._note_prefix(r, eff)
                if eff > 0:
                    self.engine.adopt_prefix(row, ids, eff)
                    r.prefill_pos = eff
                    hit_reqs.append(r)
                    hit_rows.append(row)
                else:
                    miss_reqs.append(r)
                    miss_rows.append(row)
            if hit_reqs:
                self._begin_chunked(hit_reqs, hit_rows)
            if miss_reqs:
                self._place_monolithic(miss_reqs, miss_rows)
            return
        self._place_monolithic(reqs, self._free_slots()[:len(reqs)])

    def _place_monolithic(self, reqs: List[Request],
                          rows: List[int]) -> None:
        if self.obs is not None:
            self._obs_admit(reqs)
        max_p = max(r.feed_len for r in reqs)
        n_pad = _pad_pow2(len(reqs))
        s_pad = _pad_pow2(max_p, 8)
        toks = np.zeros((n_pad, s_pad), np.int32)
        plens = np.zeros((n_pad,), np.int32)
        for i, r in enumerate(reqs):
            # feed_tokens == prompt for fresh requests; a preempted
            # request resumes by prefilling its whole history
            toks[i, :r.feed_len] = r.feed_tokens
            plens[i] = r.feed_len
        last_logits, sub = self._prefill_fn(n_pad)(
            self.params, tokens=jnp.asarray(toks),
            prompt_lens=jnp.asarray(plens))
        rows_np = np.asarray(rows)
        sub_rows = np.arange(len(reqs))
        if self.backend == "hetero":
            self._hetero_scatter(rows_np, sub, sub_rows)
        else:
            self.engine.state = M.scatter_rows(self.engine.state, sub,
                                               rows_np, sub_rows)
        # the prefill's last-token logits ARE the first generation step:
        # sample token 0 here (re-feeding the prompt tail through decode
        # would write a duplicate KV entry and shift all positions)
        tok0 = self._sample_tokens(
            last_logits, reqs + [None] * (last_logits.shape[0] - len(reqs)))
        for i, r in enumerate(reqs):
            r.status = Status.RUNNING
            r.start_step = self.step_idx
            r.slot = rows[i]
            t0 = int(tok0[i])
            r.generated.append(t0)
            self._last_tok[rows[i]] = t0
            if self.obs is not None:
                self._obs_first_token(r, rows[i])
            reason = r.finish_reason_for(t0)
            if reason is not None:
                self._finish_row(rows[i], r, reason)
            else:
                self.slots[rows[i]] = r
                if self._uses_chunks:
                    # a slot freed by a finished sequence was marked
                    # decode-inactive — this monolithic readmission must
                    # re-activate it, or the row decodes against frozen
                    # KV forever (the chunked path re-activates in
                    # _process_prefill_results)
                    self.engine.set_row_active(rows[i], True)
                if self.spec is not None:
                    # the drafter has no KV for this fresh history yet
                    self._spec_dirty.add(rows[i])
        if self.prefix_cache:
            for row, r in zip(rows, reqs):
                if self.slots[row] is not None:
                    self.engine.register_prefix(row, r.feed_tokens)

    def _hetero_scatter(self, rows: np.ndarray, sub, sub_rows: np.ndarray):
        eng = self.engine
        layer_states = per_layer_state(sub, self.cfg)
        # group admitted rows by owning (worker, micro-batch) so each
        # layer issues ONE write_rows per group — dense_rows_to_pages'
        # batched scatter (and the dense slab's batched .at[rows].set)
        # would otherwise copy the pool/slab once per row
        groups: Dict[Tuple[int, int], Tuple[object, list, list]] = {}
        for gi, row in zip(sub_rows, rows):
            w, mb, local = eng.worker_for(int(row))
            # key on wid (stable, unique) but keep the worker object —
            # after a fleet topology change wids no longer equal list
            # indices
            _, locs, gis = groups.setdefault((w.wid, mb), (w, [], []))
            locs.append(local)
            gis.append(int(gi))
        for li, (kind, _) in enumerate(eng.layers):
            r_st, s_st = D.split_block_state(kind, layer_states[li])
            for (wid, mb), (w, locs, gis) in groups.items():
                gis_np = np.asarray(gis)
                w.write_rows(eng._lkey(mb, li), np.asarray(locs),
                             jax.tree.map(lambda x: x[gis_np], r_st))
                if s_st:
                    mb_rows = np.asarray(locs) + w.lo
                    eng.s_states[mb][li] = jax.tree.map(
                        lambda c, n: c.at[mb_rows].set(n[gis_np]),
                        eng.s_states[mb][li], s_st)
        # lengths
        for gi, row in zip(sub_rows, rows):
            mb, local = divmod(int(row), self.mb_size)
            eng.mb_lengths[mb] = eng.mb_lengths[mb].at[local].set(
                int(np.asarray(sub["lengths"])[gi]))

    # ------------------------------------------------------------------ #
    # chunked prefill (prefill_chunk > 0, hetero): admission assigns a
    # slot and marks the request PREFILLING; each step every prefilling
    # sequence advances by one prompt chunk, executed INSIDE the decode
    # step wherever R-worker waits leave the S-worker idle, its KV
    # streamed to the owning R-worker layer by layer.  A sequence
    # transitions PREFILLING -> RUNNING the step its last chunk lands
    # (token 0 sampled from that chunk's last-valid logits) — decode for
    # the rest of the batch never stalls on a prompt.
    # ------------------------------------------------------------------ #
    def _place_chunked(self, reqs: List[Request]) -> None:
        rows = []
        for row, r, ids, eff in self._choose_rows(reqs):
            if self.prefix_cache:
                self._note_prefix(r, eff)
            if eff > 0:
                # map the cached prefix pages (refcount++, zero KV
                # movement) — chunking resumes at the uncached suffix
                self.engine.adopt_prefix(row, ids, eff)
            r.prefill_pos = eff
            rows.append(row)
        self._begin_chunked(reqs, rows)

    def _begin_chunked(self, reqs: List[Request], rows: List[int]) -> None:
        if self.obs is not None:
            self._obs_admit(reqs)
        for row, r in zip(rows, reqs):
            r.status = Status.PREFILLING
            r.slot = row
            r.start_step = self.step_idx
            self.slots[row] = r
        self.engine.begin_prefill_rows(rows)

    def _queue_prefill_chunks(self) -> None:
        """Queue one chunk per prefilling sequence (grouped per
        micro-batch) for the upcoming decode step.  With
        ``prefill_chunk=0`` (prefix-cache hits on an otherwise
        monolithic engine) the chunk spans the whole remaining suffix,
        pow2-padded so the jitted chunk callables retrace O(log) times,
        not per distinct suffix length."""
        per_mb: Dict[int, List[int]] = {}
        for row, r in enumerate(self.slots):
            if r is not None and r.status is Status.PREFILLING:
                per_mb.setdefault(row // self.mb_size, []).append(row)
        for mb, rows in per_mb.items():
            c = self.prefill_chunk or _pad_pow2(
                max(self.slots[row].feed_len - self.slots[row].prefill_pos
                    for row in rows), 8)
            toks = np.zeros((len(rows), c), np.int32)
            bases, counts, locs = [], [], []
            for i, row in enumerate(rows):
                r = self.slots[row]
                base = r.prefill_pos
                cnt = min(c, r.feed_len - base)
                toks[i, :cnt] = r.feed_tokens[base:base + cnt]
                locs.append(row % self.mb_size)
                bases.append(base)
                counts.append(cnt)
            self.engine.queue_prefill_chunk(mb, locs, toks, bases, counts)

    def _process_prefill_results(self) -> None:
        """Advance prefill progress from the chunks that landed in the
        decode step just executed; sequences whose last chunk arrived
        sample token 0 from its logits and join the decode batch."""
        for wk in self.engine.prefill_results:
            if wk.verify:
                continue      # spec-decode verify work: _spec_step's
            logits = wk.logits
            sampled = None
            for i, local in enumerate(wk.rows):
                row = wk.mb * self.mb_size + int(local)
                r = self.slots[row]
                if r is None or r.status is not Status.PREFILLING:
                    continue          # finished/replaced under our feet
                r.prefill_pos = int(wk.new_lens[i])
                if self.obs is not None:
                    r.mark("prefill_chunk", self.step_idx,
                           extra=r.prefill_pos)
                if r.prefill_pos < r.feed_len:
                    continue
                # the chunk's last-token logits ARE the first generation
                # step (same rule as the monolithic _place)
                if sampled is None:
                    # eligible = rows of THIS work item whose last
                    # chunk just landed (their logits row seeds token
                    # 0); everyone else's row is discarded
                    base = wk.mb * self.mb_size
                    elig = [None] * logits.shape[0]
                    for j, loc in enumerate(wk.rows):
                        rr = self.slots[base + int(loc)]
                        if rr is not None \
                                and rr.status is Status.PREFILLING \
                                and int(wk.new_lens[j]) >= rr.feed_len:
                            elig[int(loc)] = rr
                    sampled = self._sample_tokens(logits, elig)
                tok0 = int(sampled[int(local)])
                r.status = Status.RUNNING
                r.generated.append(tok0)
                self._last_tok[row] = tok0
                if self.obs is not None:
                    self._obs_first_token(r, row)
                reason = r.finish_reason_for(tok0)
                if reason is not None:
                    self._finish_row(row, r, reason)
                else:
                    self.engine.set_row_active(row, True)
                    if self.spec is not None:
                        # streamed straight to the R-workers — the
                        # drafter never saw this history
                        self._spec_dirty.add(row)
                    if self.prefix_cache:
                        # the written chain's pages are complete now —
                        # index them so later admissions can share
                        # (token 0 was just appended but never written
                        # to KV, hence the [:-1])
                        self.engine.register_prefix(
                            row, r.feed_tokens[:-1])

    # ------------------------------------------------------------------ #
    # speculative decoding: each serving step drafts up to k tokens per
    # RUNNING row on the S-resident drafter, scores all k+1 candidates
    # in ONE pipelined verify chunk (their KV appended on the R-workers
    # by the multi-token verify op), commits a token-exact prefix via
    # rejection sampling, and truncates the rejected tail's KV.  The
    # drafter itself never speculates into its own state: it drafts on
    # a throwaway copy and replays only committed tokens, so rejection
    # rolls back R-worker KV alone.
    # ------------------------------------------------------------------ #
    def _spec_rows(self) -> List[Tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.slots)
                if r is not None and r.status is Status.RUNNING]

    def _spec_sync_rows(self, live) -> None:
        """Re-feed dirty rows' WRITTEN history (feed_tokens[:-1], the
        same chain the R-workers hold) through the drafter so its KV
        agrees with the target's before drafting resumes."""
        rows = [row for row, _ in live if row in self._spec_dirty]
        if not rows:
            return
        lens = [self.slots[row].feed_len - 1 for row in rows]
        n_pad = _pad_pow2(len(rows))
        s_pad = _pad_pow2(max(lens), 8)
        toks = np.zeros((n_pad, s_pad), np.int32)
        plens = np.zeros((n_pad,), np.int32)
        for i, (row, ln) in enumerate(zip(rows, lens)):
            toks[i, :ln] = self.slots[row].feed_tokens[:ln]
            plens[i] = ln
        _, sub = self._spec_sync_fn(self._spec_params,
                                    tokens=jnp.asarray(toks),
                                    prompt_lens=jnp.asarray(plens))
        self._spec_state = M.scatter_rows(
            self._spec_state, sub, np.asarray(rows),
            np.arange(len(rows)))
        self._spec_dirty.difference_update(rows)

    def _spec_draft(self, live):
        """Greedy-draft tokens on a THROWAWAY copy of the drafter state
        (jax immutability makes the copy free): the real drafter only
        advances through the commit path, so rejection never has S-side
        KV to roll back.  Per-row draft length is capped so the
        committed chain can never exceed prompt + max_new_tokens —
        which submit() bounds by cache_len — hence verify appends
        never overflow paged capacity or wrap the dense ring."""
        k = self.spec.k
        k_row = {row: max(0, min(k, r.max_new_tokens
                                 - len(r.generated) - 1))
                 for row, r in live}
        drafts: Dict[int, List[int]] = {row: [] for row, _ in live}
        kmax = max(k_row.values())
        if kmax == 0:
            return drafts, k_row
        state = self._spec_state
        cur = np.array(self._last_tok, np.int32)
        for j in range(kmax):
            logits, state = self._spec_decode_fn(
                self._spec_params, state=state,
                tokens=jnp.asarray(cur[:, None]))
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            for row, _ in live:
                if j < k_row[row]:
                    drafts[row].append(int(nxt[row]))
            cur = nxt
        return drafts, k_row

    def _spec_queue_verify(self, live, drafts) -> None:
        """Queue one verify chunk per micro-batch with resident rows:
        candidates = [pending token c, draft_1..draft_kr], appended at
        the row's current KV length.  Chunk width is the FIXED k+1 so
        the fused verify callables trace once, not per draft length."""
        per_mb: Dict[int, List[int]] = {}
        for row, _r in live:
            per_mb.setdefault(row // self.mb_size, []).append(row)
        c = self.spec.k + 1
        for mb, rows in per_mb.items():
            toks = np.zeros((len(rows), c), np.int32)
            bases, counts, locs = [], [], []
            for i, row in enumerate(rows):
                cand = [int(self._last_tok[row])] + drafts[row]
                toks[i, :len(cand)] = cand
                locs.append(row % self.mb_size)
                bases.append(self.slots[row].feed_len - 1)
                counts.append(len(cand))
            self.engine.queue_prefill_chunk(mb, locs, toks, bases,
                                            counts, verify=True)

    def _spec_verify(self, live, drafts) -> List:
        """Run the queued verify (and any prefill) chunks in a
        chunk-only pipelined step under the step supervisor.  On a
        StepFault the healer re-prefills every live row from token
        history — discarding any orphaned candidate appends — and the
        verify work is re-queued and re-run TOKEN-EXACTLY: drafts are
        deterministic given the drafter state and the sampling RNG is
        untouched until commit."""
        attempt, t_first = 0, 0.0
        while True:
            if live:
                self._spec_queue_verify(live, drafts)
            try:
                self.engine.decode_step(None)
                if self.chaos is not None and live:
                    fs = self.chaos.fire("verify", step=self.step_idx)
                    if fs is not None:
                        raise StepFault(
                            "chaos: verify step aborted before commit",
                            transient=True, step_no=self.step_idx)
            except StepFault as fault:
                if attempt == 0:
                    t_first = time.monotonic()
                attempt += 1
                self._heal_step_fault(fault, attempt)
                continue
            if attempt:
                self._note_recovered(attempt, time.monotonic() - t_first)
            return [wk for wk in self.engine.prefill_results if wk.verify]

    def _spec_commit_drafter(self, feeds: Dict[int, List[int]]) -> None:
        """Advance the REAL drafter through each surviving row's
        committed tokens with one batched ragged prefill_chunk
        (chunk_pos -1 rows are untouched no-ops).  Fixed k+1 width —
        one trace."""
        c = self.spec.k + 1
        toks = np.zeros((self.batch, c), np.int32)
        pos = np.full((self.batch, c), -1, np.int32)
        for row, feed in feeds.items():
            base = int(np.asarray(self._spec_state["lengths"])[row])
            toks[row, :len(feed)] = feed
            pos[row, :len(feed)] = base + np.arange(len(feed))
        _, self._spec_state = self._spec_commit_fn(
            self._spec_params, state=self._spec_state,
            tokens=jnp.asarray(toks), chunk_pos=jnp.asarray(pos))

    def _spec_step(self) -> int:
        """One speculative serving step: sync -> draft -> verify ->
        accept/commit -> truncate.  Returns tokens committed batch-wide.
        Greedy rows commit by a deterministic argmax walk (bit-exact
        with non-speculative greedy decoding); sampled rows commit via
        rejection sampling that preserves the target token distribution
        exactly (tests/test_sampler.py's chi-squared check)."""
        live = self._spec_rows()
        if not live and not self.engine._prefill_inbox:
            return 0
        drafts: Dict[int, List[int]] = {}
        k_row: Dict[int, int] = {}
        if live:
            self._spec_sync_rows(live)
            drafts, k_row = self._spec_draft(live)
        obs = self.obs
        if obs is not None:
            for row, r in live:
                r.mark("draft", self.step_idx, extra=k_row[row])
                obs.spec_drafted.inc(k_row[row])
        vworks = self._spec_verify(live, drafts)
        lg_of: Dict[int, np.ndarray] = {}
        for wk in vworks:
            for i, local in enumerate(wk.rows):
                row = wk.mb * self.mb_size + int(local)
                cnt = len(drafts.get(row, ())) + 1
                lg_of[row] = np.asarray(wk.logits[int(local), :cnt])
        t_now = time.perf_counter() if obs is not None else 0.0
        emitted = 0
        trunc_rows: List[int] = []
        trunc_lens: List[int] = []
        finish: List[Tuple[int, Request, str]] = []
        feeds: Dict[int, List[int]] = {}
        for row, r in live:
            lv = lg_of[row]                    # [k_row+1, V]
            d = drafts[row]
            base = r.feed_len - 1              # KV length before verify
            if r.temperature > 0.0:
                self.rng, sub = jax.random.split(self.rng)
            else:
                sub = self.rng                 # greedy walk draws nothing
            toks, acc = spec_accept(lv, d, sub,
                                    temperature=r.temperature,
                                    top_k=r.top_k, top_p=r.top_p)
            self.spec_stats["drafted_tokens"] += len(d)
            self.spec_stats["accepted_tokens"] += acc
            if obs is not None:
                r.mark("verify", self.step_idx, extra=len(d) + 1)
                r.mark("accept", self.step_idx, extra=acc)
                obs.spec_accepted.inc(acc)
            c0 = int(self._last_tok[row])
            m, reason, walked = 0, None, []
            for t in toks:
                t = int(t)
                r.generated.append(t)
                walked.append(t)
                m += 1
                emitted += 1
                if obs is not None:
                    r.mark("token", self.step_idx, t_now)
                    obs.generated.inc()
                reason = r.finish_reason_for(t)
                if reason is not None:
                    break                      # stop token outranks cap
            if obs is not None:
                prev = self._tok_t[row]
                if prev > 0.0:
                    obs.inter_token.observe(t_now - prev)
                self._tok_t[row] = t_now
            # the committed chain's KV = feed_tokens[:-1] in both the
            # live and early-finish cases: verify appended k_row+1
            # candidates, positions base..base+m-1 hold [c0, accepted
            # drafts] and the rest must disappear
            trunc_rows.append(row)
            trunc_lens.append(base + m)
            if reason is not None:
                finish.append((row, r, reason))
            else:
                self._last_tok[row] = walked[-1]
                feeds[row] = [c0] + walked[:-1]
        if trunc_rows:
            # BEFORE retiring finished rows: tier parking exports the
            # written chain, so the rejected tail must already be gone
            self.engine.truncate_rows(trunc_rows, trunc_lens)
        for row, r, reason in finish:
            self._finish_row(row, r, reason)
        if feeds:
            self._spec_commit_drafter(feeds)
        self.spec_stats["steps"] += 1
        return emitted

    # ------------------------------------------------------------------ #
    def _replay_rows(self, rows) -> int:
        """Failure recovery: recompute lost R-state exactly by re-running
        prefill on prompt + generated-so-far for the live sequences among
        ``rows`` (this engine owns the token history — the dead worker's
        KV is just a deterministic function of it).  The last sampled
        token stays in ``_last_tok`` and is NOT re-fed: it has not been
        appended to any KV yet.  A half-prefilled sequence (chunked
        prefill in flight) replays exactly its streamed prefix —
        ``prefill_pos`` tokens — and resumes chunking from there."""
        live = [(int(r), self.slots[int(r)]) for r in rows
                if self.slots[int(r)] is not None]
        live = [(r, req) for r, req in live
                if req.status is not Status.PREFILLING
                or req.prefill_pos > 0]       # nothing streamed yet
        if not live or self.backend != "hetero":
            return 0
        lens = [req.prefill_pos if req.status is Status.PREFILLING
                else req.feed_len - 1
                for _, req in live]
        n_pad = _pad_pow2(len(live))
        s_pad = _pad_pow2(max(lens), 8)
        toks = np.zeros((n_pad, s_pad), np.int32)
        plens = np.zeros((n_pad,), np.int32)
        for i, ((row, req), ln) in enumerate(zip(live, lens)):
            # the written chain: feed minus the last sampled token (it
            # sits in _last_tok, not yet appended to any KV); a chunked
            # prefill in flight replays exactly its streamed prefix
            toks[i, :ln] = req.feed_tokens[:ln]
            plens[i] = ln
        _, sub = self._prefill_fn(n_pad)(self.params,
                                         tokens=jnp.asarray(toks),
                                         prompt_lens=jnp.asarray(plens))
        self._hetero_scatter(np.asarray([r for r, _ in live]), sub,
                             np.arange(len(live)))
        return len(live)

    def _recost_admission(self, weight_frac: float) -> None:
        """Topology changed: the surviving fleet chews R-Part work at
        ``weight_frac`` of the planned rate, so scale the Algorithm 1
        peak bound accordingly (paged page budgets re-cost themselves —
        ``_paged_pool_min`` reads the live allocators)."""
        if self.load_ctl is not None and self._w_lim0 is not None:
            self.load_ctl.w_lim = self._w_lim0 * max(0.0, weight_frac)

    # ------------------------------------------------------------------ #
    # self-healing: the step supervisor.  decode_step aborts with a typed
    # StepFault (dead / hung / suspected-lost worker, transient I/O or
    # pool hiccup) after fencing the completion sink; this layer owns
    # the token history, so it can always rebuild a consistent KV state
    # and retry the SAME step with the SAME tokens (sampling RNG is only
    # consumed after decode_step returns) — recovery is token-exact.
    # ------------------------------------------------------------------ #
    def _rids_of_mb(self, mb: int) -> List[int]:
        """Request ids resident in micro-batch ``mb`` — wired into the
        pipelined engine so its timeout messages can name the affected
        requests, not just worker/layer coordinates."""
        lo = int(mb) * self.mb_size
        return [r.rid for r in self.slots[lo:lo + self.mb_size]
                if r is not None]

    def _decode_supervised(self, toks) -> jnp.ndarray:
        """Run the pipelined decode step under the supervisor: catch
        StepFault, heal (backoff-retry transients, fail over dead/hung
        workers, re-prefill every live row), and retry until the step
        lands or the retry budget is spent.  Non-StepFault exceptions
        propagate untouched — they are bugs, not faults."""
        split = [toks[m * self.mb_size:(m + 1) * self.mb_size]
                 for m in range(self.num_mb)]
        attempt, t_first = 0, 0.0
        while True:
            try:
                parts = self.engine.decode_step(split)
            except StepFault as fault:
                if attempt == 0:
                    t_first = time.monotonic()
                attempt += 1
                self._heal_step_fault(fault, attempt)
                continue
            if attempt:
                self._note_recovered(attempt, time.monotonic() - t_first)
            return jnp.concatenate(parts, axis=0)

    def _heal_step_fault(self, fault: StepFault, attempt: int) -> None:
        """One recovery round for an aborted decode step.  Re-raises
        when the fault is not healable (deterministic worker error, no
        survivor to adopt rows, retry budget exhausted)."""
        self.faults += 1
        implicated = tuple(sorted(set(fault.dead_wids)
                                  | set(fault.hung_wids)))
        self.fault_events.append({
            "step": self.step_idx, "attempt": attempt,
            "kind": type(fault).__name__, "implicated": list(implicated),
            "lost": list(fault.lost_wids),
            "transient": bool(fault.transient), "msg": str(fault)})
        if self.obs is not None:
            self.obs.faults.inc()
            for r in self.slots:
                if r is not None:
                    r.mark("fault", self.step_idx)
        if self.fleet is not None:
            self.fleet.telemetry.record_event(
                self.step_idx, "fault", fault_kind=type(fault).__name__,
                attempt=attempt, implicated=list(implicated),
                transient=bool(fault.transient))
        # a deterministic worker-side error (no dead/hung worker to
        # remove, not marked transient) would fail identically on
        # retry — surface it like the unsupervised engine did
        if fault.wid is not None and not fault.transient \
                and not implicated:
            raise fault
        if attempt > self.max_step_retries:
            raise fault
        # suspicion is not conviction: a worker flagged hung may merely
        # be stalled on one slow item (host jitter, worker-side JIT
        # compile).  Grant a grace window — one that a chaos/real hang
        # outlasts but a straggler does not — and spare any worker that
        # finishes its item or shows a fresh heartbeat.  A spared
        # worker costs only the step retry, not a failover.
        to_remove = []
        grace = max(self.engine.suspect_after_s, 0.05)
        for wid in implicated:
            w = next((w for w in self.engine.workers if w.wid == wid),
                     None)
            if w is None:
                continue                    # already failed over
            if wid in fault.hung_wids and w.is_alive():
                deadline = time.monotonic() + grace
                spared = False
                while time.monotonic() < deadline:
                    if not w.processing or (time.monotonic()
                                            - w.heartbeat) <= grace:
                        spared = True
                        break
                    time.sleep(0.01)
                if spared:
                    continue
            to_remove.append(wid)
        # survivors may still be chewing stale queued items of the
        # aborted step; their posts are fenced off, but their KV
        # appends are not — wait for quiescence before exporting or
        # overwriting any state
        self._quiesce_workers(skip=to_remove)
        for wid in to_remove:
            widx = next((i for i, w in enumerate(self.engine.workers)
                         if w.wid == wid), None)
            if widx is None:
                continue
            self.engine.workers[widx].kill()
            if len(self.engine.workers) <= 1:
                raise fault      # no survivor to adopt its rows
            if self.fleet is not None:
                self.fleet.handle_failure(
                    widx, reprefill=self._replay_rows,
                    on_topology=self._recost_admission)
            else:
                self.engine.remove_worker(widx)
        if not to_remove:
            # transient (dropped completion, pool/tier hiccup, spared
            # straggler): short escalating backoff before the retry
            time.sleep(min(0.5,
                           self.retry_backoff_s * (2 ** (attempt - 1))))
        self._resync_after_fault()

    def _quiesce_workers(self, skip=(), timeout_s: float = 5.0) -> None:
        """Wait (bounded) until live workers have drained their input
        queues and stepped off any in-flight item.  Implicated workers
        are skipped — a hung one would pin the wait for its full sleep."""
        deadline = time.monotonic() + timeout_s
        for w in self.engine.workers:
            if w.wid in skip or not w.is_alive():
                continue
            while ((not w.inq.empty()
                    or getattr(w, "processing", False))
                   and time.monotonic() < deadline):
                time.sleep(0.001)

    def _resync_after_fault(self) -> None:
        """Rebuild a cross-layer-consistent KV state after an aborted
        step: the abort left some layers with this step's append and
        some without, so re-prefill EVERY live row from token history
        (orphaned partial appends are overwritten, lengths reset), then
        re-arm chunked prefill from each sequence's streamed position."""
        rows = [r for r, req in enumerate(self.slots) if req is not None]
        if rows:
            self._replay_rows(rows)
        if self.spec is not None:
            # defensive: the drafter state was not touched by the fault
            # (it lives on the S-worker), but replay is cheap relative
            # to a recovery and guarantees draft/verify agreement on
            # the row histories after any partial-append cleanup
            self._spec_dirty.update(rows)
        fresh = [r for r, req in enumerate(self.slots)
                 if req is not None and req.status is Status.PREFILLING
                 and req.prefill_pos == 0]
        if fresh:
            self.engine.begin_prefill_rows(fresh)
        if self._uses_chunks:
            # the aborted step consumed the queued chunks without
            # applying their progress — requeue from prefill_pos
            self.engine._prefill_inbox.clear()
            self._queue_prefill_chunks()

    def _note_recovered(self, attempts: int, mttr_s: float) -> None:
        self.recoveries += 1
        self.fault_events.append({
            "step": self.step_idx, "kind": "recovered",
            "attempts": attempts, "mttr_s": mttr_s})
        if self.obs is not None:
            self.obs.recovered.inc()
            self.obs.mttr.observe(mttr_s)
            for r in self.slots:
                if r is not None:
                    r.mark("recovered", self.step_idx)
        if self.fleet is not None:
            self.fleet.telemetry.record_event(
                self.step_idx, "recovered", attempts=attempts,
                mttr_s=mttr_s)

    def step(self) -> StepRecord:
        with S.step_span(S.STEP, step_num=self.step_idx):
            return self._step()

    def _step(self) -> StepRecord:
        pc = time.perf_counter
        fleet_wall = prefill_wall = 0.0
        if self.fleet is not None:
            t0 = pc()
            self.fleet.pre_step(reprefill=self._replay_rows,
                                on_topology=self._recost_admission)
            fleet_wall += pc() - t0
        if self.backend == "hetero" and (self.prefix_cache
                                         or self.obs is not None):
            topo = tuple(self.engine.slices)
            if topo != self._topo_seen:
                self._topo_seen = topo
                if self.prefix_cache:
                    # migration/recovery rebuilt allocators: re-index
                    # live rows' prompts before this step's admission
                    # probes
                    self._reregister_prefixes()
                if self.obs is not None:
                    for r in self.slots:
                        if r is not None:
                            r.mark("migrated", self.step_idx)
                            self.obs.migrated.inc()
        admitted = 0
        t0 = pc()
        with S.span(S.STEP_ADMIT):
            n = self._admit_count()
            if self.preempt_after and self.paged_kv:
                # admission pressure: queued work, free slots, but the
                # page budget said no — after preempt_after such steps,
                # park the least-finished row so its pages (tier-
                # restorable) make room; the victim requeues and
                # resumes token-exactly
                if n == 0 and self.queue and self._free_slots():
                    self._stall_steps += 1
                    if self._stall_steps >= self.preempt_after:
                        self._auto_preempt()
                        self._stall_steps = 0
                else:
                    self._stall_steps = 0
            if n > 0:
                reqs = [self.queue.popleft() for _ in range(n)]
                self._place(reqs)
                admitted = n
            if self._uses_chunks:
                self._queue_prefill_chunks()
        prefill_wall += pc() - t0

        t0 = pc()
        obs = self.obs
        if self.spec is not None:
            # speculative decoding replaces decode+sample wholesale:
            # draft on the S-resident drafter, score candidates in one
            # chunk-only pipelined step, commit via rejection sampling.
            # The verify chunk's S-time IS decode work here, so the
            # spec-off branch's chunk_s re-attribution is skipped
            # (queued prefill chunks ride the same step and smear into
            # decode_wall — acceptable at bench granularity).
            tokens_emitted = self._spec_step()
            decode_wall = pc() - t0
        else:
            toks = jnp.asarray(self._last_tok[:, None])
            if self.backend == "hetero":
                logits = self._decode_supervised(toks)
            else:
                # keep lengths frozen for inactive rows (avoid drift)
                logits = self.engine.decode_step(toks)
            decode_wall = pc() - t0
            if self.backend == "hetero":
                # chunk work executed inside the pipelined step —
                # S-side chunk callables plus event-loop waits that
                # served only chunk work — is prefill time, not decode
                chunk_s = self.engine.last_step_stats.get(
                    "prefill_s", 0.0)
                decode_wall -= min(chunk_s, decode_wall)
                prefill_wall += chunk_s
            with S.span(S.STEP_SAMPLE):
                new_tok = self._sample_tokens(
                    logits, [r if r is not None
                             and r.status is Status.RUNNING else None
                             for r in self.slots])

            t_now = pc() if obs is not None else 0.0
            tokens_emitted = 0
            with S.span(S.STEP_EMIT):
                for i, r in enumerate(self.slots):
                    if r is None or r.status is not Status.RUNNING:
                        continue    # PREFILLING rows own no decode token
                    tok = int(new_tok[i])
                    r.generated.append(tok)
                    self._last_tok[i] = tok
                    tokens_emitted += 1
                    if obs is not None:
                        r.mark("token", self.step_idx, t_now)
                        obs.generated.inc()
                        prev = self._tok_t[i]
                        if prev > 0.0:
                            obs.inter_token.observe(t_now - prev)
                        self._tok_t[i] = t_now
                    reason = r.finish_reason_for(tok)
                    if reason is not None:
                        self._finish_row(i, r, reason)
        if self._uses_chunks:
            # AFTER the token loop: a sequence whose last chunk landed
            # this step gets token 0 from the chunk logits and decodes
            # its first real token NEXT step — this step's batch logits
            # for its row predate the transition
            t0 = pc()
            with S.span(S.STEP_PREFILL_RESULTS):
                self._process_prefill_results()
            prefill_wall += pc() - t0
        if self.fleet is not None:
            t0 = pc()
            self.fleet.post_step(self.step_idx)
            fleet_wall += pc() - t0
        if obs is not None and obs.drift is not None:
            obs.drift.observe_step(
                wall_s=decode_wall, tokens=tokens_emitted,
                step_stats=self.engine.step_stats,
                num_workers=len(self.engine.workers))
        rec = StepRecord(self.step_idx, prefill_wall, decode_wall,
                         fleet_wall,
                         sum(r is not None for r in self.slots),
                         self.resident_len(), admitted)
        self.records.append(rec)
        self.step_idx += 1
        return rec

    def paged_resident_bytes(self) -> float:
        """Current page-backed KV bytes on the R-workers (paged_kv only)."""
        return self.engine.paged_resident_bytes() if self.paged_kv else 0.0

    def hotpath_stats(self) -> Dict[str, float]:
        """Cumulative decode hot-path breakdown (dispatch / collect /
        S-dispatch / R-wait seconds and step count) from the pipelined
        engine; empty for the colocated backend.  Keys follow the
        repro.obs.schema convention; the pre-schema spellings
        (``steps``, ``ooo_advances``) still resolve via the compat
        shim."""
        return schema.normalize(
            dict(getattr(self.engine, "step_stats", {}) or {}))

    def prefix_cache_stats(self) -> Dict[str, float]:
        """Admission-level hit counters plus allocator-level sharing
        state (pages shared by >1 row, refcount-zero cached pages).
        Schema-conformant keys with legacy-spelling compat (``hits`` ->
        ``hits_count`` ...)."""
        out: Dict[str, float] = dict(self.prefix_stats)
        if self.backend == "hetero":
            out.update(self.engine.prefix_cache_stats())
        denom = max(1, out.get("prompt_tokens", 0))
        out["token_hit_rate"] = out.get("cached_tokens", 0) / denom
        return schema.normalize(out)

    def tiering_stats(self) -> Dict[str, float]:
        """Host-tier traffic counters (swap-outs, restores, simulated
        stream seconds) plus engine-side preemptions; empty when
        tiering is off.  Schema-conformant keys with legacy-spelling
        compat (``restored`` -> ``restore_count`` ...)."""
        if self.kv_tier is None:
            return {}
        out: Dict[str, float] = dict(self.kv_tier.stats)
        out["swapped_pages"] = self.kv_tier.swapped_pages()
        out["host_bytes"] = self.kv_tier.nbytes()
        out["preemptions"] = self.preemptions
        return schema.normalize(out)

    # -- unified observability surface --------------------------------- #
    def metrics(self) -> Dict[str, float]:
        """One flat snapshot of everything the engine can measure:
        registry metrics (TTFT / queue-wait / inter-token histograms
        with p50/p90/p99, lifecycle counters) plus every legacy stats
        surface under a namespace prefix (``hotpath_``, ``prefix_``,
        ``tier_``, ``fleet_``, ``drift_``).  All keys follow
        repro.obs.schema; works with observability off (the registry
        part is simply absent)."""
        out: Dict[str, float] = {}
        if self.obs is not None:
            out.update(self.obs.registry.snapshot())
            if self.obs.drift is not None:
                out.update(self.obs.drift.report().as_metrics())
        out["steps_count"] = float(self.step_idx)
        out["queue_depth_count"] = float(len(self.queue))
        out["active_count"] = float(
            sum(r is not None for r in self.slots))
        out["resident_tokens"] = float(self.resident_len())
        out["preemptions_count"] = float(self.preemptions)
        out["fault_count"] = float(self.faults)
        out["recovered_count"] = float(self.recoveries)
        for k, v in self.hotpath_stats().items():
            out[f"hotpath_{k}"] = float(v)
        if self.prefix_cache:
            for k, v in self.prefix_cache_stats().items():
                out[f"prefix_{k}"] = float(v)
        if self.kv_tier is not None:
            for k, v in self.tiering_stats().items():
                out[f"tier_{k}"] = float(v)
        if self.fleet is not None:
            for k, v in schema.normalize(
                    self.fleet.telemetry.summary()).items():
                out[f"fleet_{k}"] = float(0.0 if v is None else v)
        return schema.StatsDict(out)

    def drift_report(self):
        """The perfmodel drift monitor's measured-vs-predicted
        residuals (repro.obs.drift.DriftReport); requires observability
        with drift enabled on the hetero backend."""
        if self._obs_obj is None or self._obs_obj.drift is None:
            raise RuntimeError(
                "no drift monitor — construct a hetero engine with "
                "observability=True (or ObsConfig(drift=True))")
        return self._obs_obj.drift.report()

    def request_timeline(self, rid: int) -> List[Tuple]:
        """The lifecycle event list of a finished/resident/queued
        request (empty unless observability was on while it ran)."""
        for r in self.finished:
            if r.rid == rid:
                return list(r.events)
        for r in list(self.slots) + list(self.queue):
            if r is not None and r.rid == rid:
                return list(r.events)
        raise KeyError(f"unknown request id {rid}")

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Serve until the queue and slots drain, or ``max_steps`` MORE
        steps have run.  The budget is relative to the current step —
        a second run() on the same engine gets the full allowance again
        (it used to compare against the absolute step counter, so rerun
        budgets silently shrank toward zero)."""
        end_step = self.step_idx + max_steps
        while (self.queue or any(r is not None for r in self.slots)) \
                and self.step_idx < end_step:
            self.step()
        return self.finished

    def close(self) -> None:
        if self.backend == "hetero":
            self.engine.close()
