"""The FastDecode heterogeneous runtime (§4.1, Fig. 4–5).

One **S-worker** (the accelerator: owns all weights, computes S-Part for a
large batch) drives ``num_r_workers`` **R-workers** (own the per-sequence
state — KV caches / recurrent states — for a contiguous slice of the
batch, compute the parameter-free R-Part near that state).  Per layer and
token step, only activation vectors cross the boundary.

Two (or more) micro-batches are kept in flight (the basic two-stage
token-level pipeline of Fig. 5): while the R-workers chew on micro-batch
A's layer-l attention, the S-worker advances micro-batch B.  The
interleaving falls out of the dispatch order, not timers, so it is
correct regardless of relative speeds (bubbles appear exactly when the
paper says they do; benchmarks measure them).

The decode hot path is **event-driven**: every R-worker posts finished
work to one shared :class:`CompletionSink`, and the S-worker advances
whichever micro-batch completes first (``schedule="ooo"``) instead of
blocking per-worker in issue order.  Per layer transition the S-side
runs ONE fused, jitted ``s_advance(l) -> s_pre(l+1)`` callable whose
outputs are already the per-worker ``r_in`` shards (slice boundaries are
baked into the trace), and workers scatter their ``r_out`` into a
preallocated host buffer instead of the S-worker concatenating device
arrays — see docs/ARCHITECTURE.md "Hot path".  The pre-fusion FIFO loop
survives as :meth:`HeteroPipelineEngine.decode_step_legacy` for A/B
benchmarking (benchmarks/bench_hotpath.py).

On this CPU-only container the R-workers are host threads with their own
jitted R-Part; on a real deployment they are processes on remote CPU
nodes (the payload protocol is already activation-only and
pytree-serializable).
"""
from __future__ import annotations

import queue
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.lockwitness import make_lock
from repro.obs import spans as S

def _quiet_donation_jit(f, donate_argnums):
    """jax.jit with donated dead inputs, suppressing the one expected
    compile-time warning.  Donation is best-effort: where no output
    shape matches a donated input (e.g. r_out -> shards) XLA warns once
    per compile and falls back to a copy — expected, not a bug.  The
    suppression is scoped to each wrapped callable's FIRST invocation
    (when compilation happens) so other code's donation warnings stay
    visible.  Caveat: warnings filters are process-global, so a warning
    raised on ANOTHER thread during that one compile window is also
    muted — acceptable here because the R-worker jits never donate."""
    jitted = jax.jit(f, donate_argnums=donate_argnums)
    state = {"first": True}

    def wrapped(*args):
        if state["first"]:
            state["first"] = False
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return jitted(*args)
        return jitted(*args)

    return wrapped

from repro.core import decompose as D
from repro.core.config import ModelConfig
from repro.models import model as M


# ---------------------------------------------------------------------------
# step faults — typed aborts the serving supervisor can heal
# ---------------------------------------------------------------------------
class StepFault(RuntimeError):
    """A decode step aborted mid-flight.

    Raised from the collect loop after the sink has been fenced (the
    epoch bump makes every in-flight completion of the aborted step
    stale), so the engine is quiescent but its per-layer state is
    **inconsistent across layers** — some layers appended this step's
    KV, some did not.  The serving layer's supervisor heals that by
    re-prefilling every live row from token history and retrying the
    step with the same tokens (sampling RNG is consumed only after a
    step returns, so the retry is token-exact).

    ``dead_wids``/``hung_wids`` name workers that must be failed over;
    ``lost_wids`` name workers suspected of a dropped completion
    (transient — retry without removal); ``transient`` marks the fault
    safe to retry as-is."""

    def __init__(self, msg: str, *, dead_wids: Sequence[int] = (),
                 hung_wids: Sequence[int] = (),
                 lost_wids: Sequence[int] = (),
                 wid: Optional[int] = None,
                 transient: bool = False, step_no: int = -1):
        super().__init__(msg)
        self.dead_wids = tuple(dead_wids)
        self.hung_wids = tuple(hung_wids)
        self.lost_wids = tuple(lost_wids)
        self.wid = wid
        self.transient = bool(transient)
        self.step_no = int(step_no)


class CollectTimeout(StepFault):
    """The collect loop gave up waiting: a pending worker is dead, hung
    past the suspicion threshold, or completions went missing."""


class WorkerStepError(StepFault):
    """An R-worker posted an exception for this step (``__cause__``
    carries the original, with ``r_worker_context`` coordinates)."""


# ---------------------------------------------------------------------------
# params / state layout helpers
# ---------------------------------------------------------------------------
def per_layer_params(params, cfg: ModelConfig) -> List[Tuple[str, Any]]:
    """[(kind, layer_params)] in layer order, unstacked."""
    pattern = cfg.layer_pattern
    period = len(pattern)
    n_full = cfg.num_layers // period
    out = []
    for li in range(cfg.num_layers):
        per, slot = divmod(li, period)
        kind = pattern[slot]
        if per < n_full:
            p = jax.tree.map(lambda x: x[per], params["stack"][f"s{slot}"])
        else:
            p = params["rem"][li - n_full * period]
        out.append((kind, p))
    return out


def per_layer_state(state, cfg: ModelConfig) -> List[Any]:
    pattern = cfg.layer_pattern
    period = len(pattern)
    n_full = cfg.num_layers // period
    out = []
    for li in range(cfg.num_layers):
        per, slot = divmod(li, period)
        if per < n_full:
            st = jax.tree.map(lambda x: x[per], state["stack"][f"s{slot}"])
        else:
            st = state["rem"][li - n_full * period]
        out.append(st)
    return out


def batch_slice(tree, lo: int, hi: int):
    return jax.tree.map(lambda x: x[lo:hi], tree)


# r_in payload entries that are per-head constants, NOT per-sequence data —
# they go to every R-worker whole (see decompose.r_ssd)
_RIN_BROADCAST = ("A_log", "D")


def rin_slice(r_in: dict, lo: int, hi: int) -> dict:
    return {k: (v if k in _RIN_BROADCAST else v[lo:hi])
            for k, v in r_in.items()}


def batch_concat(trees: Sequence[Any]):
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *trees)


def shard_rin(r_in: dict, slices) -> tuple:
    """Per-worker ``r_in`` shards.  Called INSIDE the fused jitted
    S-part callables with ``slices`` baked in as trace-time constants,
    so the whole fan-out is part of one device dispatch instead of
    ``num_workers`` interpreter-level ``rin_slice`` calls."""
    return tuple(rin_slice(r_in, lo, hi) for lo, hi in slices)


def mask_rows(new, old, active):
    """Row-gated state update: rows with active=False keep their old
    value.  Used by the fused decode callables so a decode step's S-side
    state churn (conv windows) never touches rows that are mid-chunked-
    prefill or released — their state belongs to the prefill path."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)


class CompletionSink:
    """The single completion channel shared by all R-workers of one
    engine — the heart of the event-driven hot path.

    A worker finishing ``(mb, layer, phase)`` converts its ``r_out``
    shard to host arrays (on the worker thread, so transfers overlap
    across workers), scatters it into a preallocated per-(step-parity,
    micro-batch, layer, phase) host buffer at its row slice, and posts a
    tiny ``(wid, tag, err)`` token to one queue.  The S-worker pops
    tokens in COMPLETION order and advances whichever micro-batch is
    ready — no per-worker blocking order, no device-side concatenation
    (``gather`` turns the already-assembled buffer into one device
    array).  On accelerator hosts these buffers live in pinned host
    memory; on this CPU container they are plain numpy.

    Buffers are double-buffered on step parity so a straggler's write
    can never race the previous step's still-executing consumer.
    ``epoch`` fences topology changes (``apply_partition`` /
    ``remove_worker``): posts tagged with an older epoch — e.g. a
    delayed delivery finishing across a migration — are dropped before
    they touch a buffer.
    """

    def __init__(self, mb_size: int):
        self.mb_size = int(mb_size)
        self.q: "queue.Queue" = queue.Queue()
        self.epoch = 0
        self._lock = make_lock("CompletionSink._lock")
        self._bufs: Dict[Tuple, Dict[str, np.ndarray]] = {}

    def _buffer(self, key, host: Dict[str, np.ndarray], fresh: bool = False):
        # caller (post) holds self._lock
        buf = None if fresh else self._bufs.get(key)
        if buf is None:
            buf = {k: np.empty((self.mb_size,) + v.shape[1:], v.dtype)
                   for k, v in host.items()}
            self._bufs[key] = buf
        return buf

    def post(self, wid: int, tag, host: Dict[str, np.ndarray],
             lo: int, hi: int) -> None:
        epoch, parity, mb, li, phase = tag
        # epoch check and buffer write are one critical section with
        # fence(): otherwise a delayed post could pass the check, lose
        # the CPU across a topology change, and scatter old-partition
        # rows over a newer epoch's buffer.  Only the small memcpy is
        # under the lock — the expensive device->host conversion
        # happened on the worker thread before calling in, so the
        # serialized section is us-scale against ms-scale R-items
        # (a per-buffer lock would complicate the fence for ~nothing).
        with self._lock:
            if epoch != self.epoch:
                return                   # fenced-off straggler
            buf = self._buffer((parity, mb, li, phase), host)
            try:
                for k, v in host.items():
                    buf[k][lo:hi] = v
            except (KeyError, ValueError):
                # the payload layout under this key changed — e.g. a
                # prefill chunk of a different length reusing a virtual
                # micro-batch slot.  Reallocate and rewrite; keeping
                # this on the exception path leaves the steady-state
                # critical section at just the memcpy.
                buf = self._buffer((parity, mb, li, phase), host,
                                   fresh=True)
                for k, v in host.items():
                    buf[k][lo:hi] = v
        self.q.put((wid, tag, None))

    def post_error(self, wid: int, tag, err: BaseException) -> None:
        with self._lock:
            if tag[0] != self.epoch:
                return
        self.q.put((wid, tag, err))

    def gather(self, tag) -> Dict[str, jnp.ndarray]:
        """The fully-scattered r_out of ``tag`` as device arrays (one
        host->device copy per leaf; jnp.asarray copies, so the buffer is
        immediately reusable — double-buffering guards the async case)."""
        _, parity, mb, li, phase = tag
        buf = self._bufs[(parity, mb, li, phase)]
        return {k: jnp.asarray(v) for k, v in buf.items()}

    def fence(self) -> None:
        """Invalidate all in-flight work (topology change or aborted
        step): bump the epoch and drain already-posted completions so
        the next decode step never consumes a stale result.  The bump
        shares post()'s lock, so no straggler can pass the epoch check
        and then scatter across the fence."""
        with self._lock:
            self.epoch += 1
        while True:
            try:
                self.q.get_nowait()
            except queue.Empty:
                return


# ---------------------------------------------------------------------------
# R-worker
# ---------------------------------------------------------------------------
class RWorker(threading.Thread):
    """Owns the R-Part state of batch rows [lo, hi) for every layer.

    ``quantized=True`` stores self-attention KV as int8 + per-(token,head)
    scales (paper §5.2): ~4x less R-side memory traffic, attention still
    accumulated in fp32 (repro.serving.kv_cache.r_attention_int8).

    ``paged=True`` stores self-attention KV block-granular (PagedAttention
    style, repro.serving.paged_cache): per micro-batch one host-side
    ``PagedAllocator`` (block table shared by all attention layers — a
    sequence's layers always have equal lengths) plus one device page
    pool per layer.  NOTE ``num_pages`` sizes ONE pool, and a pool is
    replicated per (attention layer, micro-batch): total device pages
    = num_pages * n_attn_layers * num_microbatches — same convention as
    the dense slab, whose ``cache_len`` is also per layer per row.
    Admission allocates only ceil(len/page) pages per row, decode
    appends grow the table page-by-page, and released rows return their
    pages to the pool.  Composes with ``quantized`` (int8 page pools).
    DEC_XATTN blocks keep the dense slab (their state mixes self-KV with
    static cross-KV); windowed attention (cfg.window > 0) stays dense
    too (its rotated ring can't be expressed in derived positions).
    """

    def __init__(self, wid: int, cfg: ModelConfig, lo: int, hi: int,
                 kv_chunk: int = 1024, quantized: bool = False,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_pages_per_seq: Optional[int] = None,
                 prefix_cache: bool = False,
                 kv_tier: Any = None,
                 profile: Any = None, slowdown: float = 1.0,
                 sim_row_cost: float = 0.0,
                 sim_deliver_jitter: float = 0.0,
                 profile_timing: bool = False,
                 chaos: Any = None):
        super().__init__(daemon=True, name=f"r-worker-{wid}")
        self.wid, self.cfg, self.lo, self.hi = wid, cfg, lo, hi
        self.kv_chunk = kv_chunk
        self.quantized = quantized
        self.paged = paged
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.num_pages = num_pages
        # the engine-global host tier (paged_cache.HostTier) — parked
        # pages swap out to it under pressure; tiering implies the
        # prefix index (digest chains are the tier's key space)
        self.kv_tier = kv_tier
        self.prefix_cache = prefix_cache or kv_tier is not None
        self.profile = profile                   # fleet.WorkerProfile or None
        self.slowdown = max(1.0, float(slowdown))  # simulated skew (tests)
        self.sim_row_cost = max(0.0, float(sim_row_cost))  # s/row/call
        # simulated async-delivery jitter (seconds, uniform): the result
        # arrives late but the worker moves on — models a remote link.
        # This is what makes completion order diverge from issue order
        # (FIFO worker threads alone complete monotonically); see
        # docs/ARCHITECTURE.md "Hot path" for when FIFO vs OoO matters.
        self.sim_deliver_jitter = max(0.0, float(sim_deliver_jitter))
        # profile_timing=True adds an explicit block_until_ready before
        # the host conversion, separating kernel time from transfer time
        # in busy_time — keep it OFF in steady state (the host copy
        # already absorbs the sync; legacy outq replies need it ON for
        # busy_time to mean anything, since they never copy to host)
        self.profile_timing = bool(profile_timing)
        self._jitter_rng = np.random.default_rng(0xD15C0 + wid)
        self._cache_len = 0                      # set at first state load
        self.state: Dict[int, Any] = {}          # layer -> r_state slice
        self.paged_keys: set = set()             # layer keys stored paged
        self.allocators: Dict[int, Any] = {}     # micro-batch -> allocator
        self._first_paged: Dict[int, Any] = {}   # mb -> min paged key
        self._chunk_tables: Dict[int, Any] = {}  # mb -> sliced device table
        self._step_clones: Dict[Tuple, Any] = {}  # (mb, pass) -> CoW pairs
        self.inq: "queue.Queue" = queue.Queue()
        self.outq: "queue.Queue" = queue.Queue()  # legacy (FIFO) replies
        self._jit_cache: Dict[Tuple[str, int], Any] = {}
        self.busy_time = 0.0
        # the paged decode kernel's walk, counted from host lengths: grid
        # blocks it copies and computes, and blocks in its grid (only
        # this thread writes them; the engine reads them between steps)
        self.paged_blocks_run = 0
        self.paged_blocks_grid = 0
        self._walk: Dict[int, Tuple[int, int]] = {}   # mb -> (run, grid)
        self._killed = False
        # chaos.FaultPlan (or None): fault-injection hooks in _run_one
        # and the paged allocator; a single `is None` test when off
        self.chaos = chaos
        # liveness telemetry for the collect loop's suspicion check:
        # `heartbeat` advances on every inbox wake and item boundary,
        # `processing` is True while _run_one runs — a stale heartbeat
        # with processing=True reads as "hung mid-item", processing=
        # False with an empty inbox but owed completions as "message
        # lost in flight"
        self.heartbeat = time.monotonic()
        self.processing = False

    # -- paged storage helpers ----------------------------------------------
    def _pageable(self, st) -> bool:
        # Windowed attention keeps the dense slab: its cache is a rotated
        # ring of the last `window` tokens, which the paged layout's
        # derived (contiguous-from-0) positions cannot represent — and
        # paging a bounded window buys nothing anyway.  A migration wire
        # payload from a quantized worker carries k_q instead of k.
        return (self.paged and self.cfg.window == 0 and isinstance(st, dict)
                and ("k" in st or "k_q" in st) and "pos" in st
                and "xk" not in st)

    def _alloc(self, mb: int):
        from repro.serving import paged_cache as PC
        if mb not in self.allocators:
            rows = self.hi - self.lo
            mp = self.max_pages_per_seq or -(-self._cache_len // self.page_size)
            num = self.num_pages or rows * mp
            alloc = PC.PagedAllocator(
                rows, num, self.page_size, mp,
                prefix_cache=self.prefix_cache, tier=self.kv_tier,
                chaos=self.chaos)
            # swap-out reads this micro-batch's layer pools at directive
            # time (pools are immutable jnp arrays, so the captured bytes
            # cannot be raced by a later functional update)
            alloc.pool_reader = lambda mb=mb: {
                lk % self.cfg.num_layers: self.state[lk]
                for lk in self.paged_keys
                if lk // self.cfg.num_layers == mb}
            self.allocators[mb] = alloc
        return self.allocators[mb]

    def _to_pages(self, layer: int, rows: np.ndarray, r_state_rows):
        from repro.serving import paged_cache as PC
        mb = layer // self.cfg.num_layers
        alloc = self._alloc(mb)
        if layer not in self.paged_keys:
            ref = r_state_rows["k"] if "k" in r_state_rows \
                else r_state_rows["k_q"]
            hkv, dh = ref.shape[2:]
            dtype = ref.dtype if "k" in r_state_rows else jnp.float32
            self.state[layer] = PC.init_page_pool(
                alloc.num_pages, self.page_size, hkv, dh,
                dtype=dtype, quantized=self.quantized)
            self.paged_keys.add(layer)
            self._first_paged[mb] = None         # recompute lazily
        self.state[layer] = PC.dense_rows_to_pages(
            self.state[layer], alloc, rows, r_state_rows)

    def release_rows(self, mb: int, rows) -> None:
        """Return finished rows' pages to the pool (continuous batching)."""
        alloc = self.allocators.get(mb)
        if alloc is not None:
            for r in rows:
                alloc.release(int(r))

    def paged_resident_bytes(self) -> float:
        """Bytes of KV actually occupying pool pages (all layers):
        row-referenced pages PLUS refcount-zero cached prefix pages —
        the latter still hold live KV until the LRU evicts them, so
        they are resident memory, merely reclaimable on demand."""
        from repro.serving import paged_cache as PC
        total = 0.0
        for layer in self.paged_keys:
            alloc = self.allocators[layer // self.cfg.num_layers]
            total += ((alloc.used_pages() + alloc.cached_pages()
                       + alloc.parked_pages())
                      * self.page_size
                      * PC.page_pool_token_bytes(self.state[layer]))
        return total

    # -- state loading ------------------------------------------------------
    def _coerce_storage(self, st):
        """(De)quantize an attention payload to this worker's storage
        format.  Wire payloads from a quantized worker carry int8+scales
        (k_q/...); a quantized destination keeps them verbatim (no
        re-quantization error), an fp destination dequantizes."""
        if not isinstance(st, dict):
            return st
        if self.quantized and "k" in st:
            from repro.serving.kv_cache import quantize_attn_state
            return quantize_attn_state(st)
        if not self.quantized and "k_q" in st:
            from repro.serving.kv_cache import dequantize_attn_state
            return dequantize_attn_state(st)
        return st

    def load_state(self, layer: int, r_state_slice) -> None:
        if self._pageable(r_state_slice):
            if "k_q" in r_state_slice and not self.quantized:
                from repro.serving.kv_cache import dequantize_attn_state
                r_state_slice = dequantize_attn_state(r_state_slice)
            ref = r_state_slice["k"] if "k" in r_state_slice \
                else r_state_slice["k_q"]
            self._cache_len = ref.shape[1]
            # an existing pool is reused across reloads: stale pages past
            # a row's re-admitted length are unreachable (derived
            # positions + lengths mask), so no zero-fill is needed
            self._to_pages(layer, np.arange(ref.shape[0]), r_state_slice)
            return
        r_state_slice = self._coerce_storage(r_state_slice)
        self.state[layer] = jax.tree.map(jnp.asarray, r_state_slice)

    def write_rows(self, layer: int, rows: np.ndarray, r_state_rows) -> None:
        """Continuous batching: replace finished rows with fresh prefixes."""
        if layer in self.paged_keys and self._pageable(r_state_rows):
            self._to_pages(layer, rows, r_state_rows)
            return
        if self.quantized and "k" in r_state_rows:
            from repro.serving.kv_cache import quantize_attn_state
            r_state_rows = quantize_attn_state(r_state_rows)
        self.state[layer] = jax.tree.map(
            lambda c, n: c.at[rows].set(n), self.state[layer], r_state_rows)

    # -- migration wire format (fleet live migration / KV snapshots) --------
    def export_rows(self, layer: int, local_rows: np.ndarray):
        """``local_rows``' r_state as host (numpy) arrays in the *dense
        wire format*: exactly what a dense worker stores per row —
        {k, v, pos} (or int8 {k_q, k_s, v_q, v_s, pos} from a quantized
        worker), recurrent {h}, etc.  Paged rows are gathered back into
        contiguous ``[row, cache_len, ...]`` slabs with derived
        positions, so the payload is storage-independent: any worker can
        re-install it via ``load_state`` whatever its own backend."""
        local_rows = np.asarray(local_rows)
        if layer in self.paged_keys:
            return self._pages_to_dense(layer, local_rows)
        return jax.tree.map(lambda x: np.asarray(x)[local_rows],
                            self.state[layer])

    def _pages_to_dense(self, layer: int, rows: np.ndarray):
        alloc = self.allocators[layer // self.cfg.num_layers]
        pool = self.state[layer]
        page, cap = self.page_size, self._cache_len
        host = {k: np.asarray(v) for k, v in pool.items()}
        out = {k: np.zeros((len(rows), cap) + v.shape[2:], v.dtype)
               for k, v in host.items()}
        pos = np.full((len(rows), cap), -1, np.int32)
        for i, row in enumerate(rows):
            row = int(row)
            if not alloc.active[row]:
                continue
            mapped = int((alloc.tables[row] >= 0).sum())
            # a degraded (pool-exhausted) row exports its stored prefix
            length = min(int(alloc.lengths[row]), mapped * page, cap)
            if length <= 0:
                continue
            n_pg = -(-length // page)
            ids = alloc.tables[row, :n_pg]
            for k, v in host.items():
                out[k][i, :length] = v[ids].reshape(
                    n_pg * page, *v.shape[2:])[:length]
            pos[i, :length] = np.arange(length)
        out["pos"] = pos
        return out

    def reassign(self, lo: int, hi: int) -> None:
        """Adopt a new row slice: drop ALL row-indexed storage (state
        slabs, page pools, allocators).  The caller (engine live
        migration) re-installs every layer's rows via ``load_state``
        right after; must only run between decode steps.  Parked pages
        are flushed to the host tier first (their pools are about to be
        dropped) so park/restore survives the topology change."""
        for alloc in self.allocators.values():
            alloc.swap_out_all_parked()
        self.lo, self.hi = int(lo), int(hi)
        self.state.clear()
        self.paged_keys.clear()
        self.allocators.clear()
        self._first_paged.clear()
        self._chunk_tables.clear()
        self._step_clones.clear()

    def kill(self) -> None:
        """Simulate an abrupt worker crash (tests/benchmarks): the thread
        exits without draining its queue.  ``is_alive()`` turning False
        is what the fleet health check detects."""
        self._killed = True
        self.inq.put(None)

    def _fn(self, kind: str, phase: int, chunk: bool = False):
        key = (kind, phase, chunk)
        if key not in self._jit_cache:
            from repro.core.config import ATTN
            if chunk:
                if self.quantized and kind == ATTN:
                    from repro.serving.kv_cache import r_attention_int8_chunk
                    f = partial(r_attention_int8_chunk,
                                window=self.cfg.window,
                                softcap=self.cfg.attn_logit_softcap,
                                kv_chunk=self.kv_chunk)
                else:
                    f = partial(D.r_dispatch_chunk, kind, phase,
                                cfg=self.cfg, kv_chunk=self.kv_chunk)
            elif self.quantized and kind == ATTN:
                from repro.serving.kv_cache import r_attention_int8
                f = partial(r_attention_int8, window=self.cfg.window,
                            softcap=self.cfg.attn_logit_softcap)
            else:
                f = partial(D.r_dispatch, kind, phase, cfg=self.cfg,
                            kv_chunk=self.kv_chunk)
            self._jit_cache[key] = jax.jit(
                lambda r_in, r_state: f(r_in, r_state))
        return self._jit_cache[key]

    def _paged_fn(self):
        if "paged" not in self._jit_cache:
            from repro.serving import paged_cache as PC
            f = partial(PC.r_attention_paged_tables, window=self.cfg.window,
                        softcap=self.cfg.attn_logit_softcap)
            self._jit_cache["paged"] = jax.jit(
                lambda r_in, pool, tables: f(r_in, pool, tables))
        return self._jit_cache["paged"]

    def _step_paged(self, layer: int, r_in):
        """One paged decode append+attend: grow active rows' tables for
        the incoming token, then run the jitted paged R-Part.

        All of a micro-batch's attention layers share one allocator and
        identical lengths, so the (host-synced) table grow runs only on
        the micro-batch's FIRST paged layer each step; the rest reuse
        the cached device table.  Rows the engine marked decode-inactive
        (``r_in["active"]`` False: released slots, rows mid-chunked-
        prefill) are excluded from the grow AND the length bump — their
        allocator bookkeeping belongs to the prefill path."""
        from repro.kernels.paged_attention import blocks_walked
        from repro.serving import paged_cache as PC
        mb = layer // self.cfg.num_layers
        alloc = self.allocators[mb]
        with S.span(S.R_GROW):
            if layer == self._first_paged_key(mb):
                act = r_in.get("active")
                lens = np.asarray(r_in["lengths"])
                alloc.ensure_lengths(lens + 1,
                                     mask=None if act is None
                                     else np.asarray(act))
                pool = self.state[layer]
                self._walk[mb] = (blocks_walked(lens, 1, pool["k"],
                                                alloc.max_pages)
                                  if "k" in pool else (0, 0))
                # CoW clones computed once on the shared allocator;
                # every paged layer of this step applies them to its
                # OWN pool below (the block table already points at
                # the fresh pages)
                self._step_clones[(mb, "decode")] = alloc.take_clones()
            clones = self._step_clones.get((mb, "decode"))
            if clones:
                self.state[layer] = PC.clone_pool_pages(self.state[layer],
                                                        clones)
            tables = alloc.tables_device()
        run, grid = self._walk[mb]
        self.paged_blocks_run += run
        self.paged_blocks_grid += grid
        r_out, new_pool = self._paged_fn()(r_in, self.state[layer], tables)
        return r_out, new_pool

    def _paged_chunk_fn(self):
        if "paged_chunk" not in self._jit_cache:
            from repro.serving import paged_cache as PC
            f = partial(PC.r_attention_paged_chunk, window=self.cfg.window,
                        softcap=self.cfg.attn_logit_softcap,
                        kv_chunk=self.kv_chunk)
            self._jit_cache["paged_chunk"] = jax.jit(
                lambda r_in, pool, tables: f(r_in, pool, tables))
        return self._jit_cache["paged_chunk"]

    def _step_paged_chunk(self, layer: int, r_in):
        """One chunked-prefill append+attend on paged storage: grow the
        shared block tables for the chunk's rows on the micro-batch's
        first paged layer (a row starting at offset 0 is re-admitted
        fresh), then scatter+attend via the jitted paged chunk op.

        The chunk op's gathered attention view is bounded to the pow2-
        rounded USED page prefix (a row's pages are a contiguous table
        prefix, so columns past the longest row are all unmapped):
        chunk attention then costs O(max live length), not O(configured
        capacity), at the price of log2(max_pages) traces."""
        from repro.serving import paged_cache as PC
        mb = layer // self.cfg.num_layers
        alloc = self.allocators[mb]
        with S.span(S.R_GROW):
            if layer == self._first_paged_key(mb):
                alloc.append_chunk(np.asarray(r_in["lengths"]),
                                   np.asarray(r_in["valid"]).sum(axis=1))
                self._step_clones[(mb, "chunk")] = alloc.take_clones()
                # the prefix bound is invariant until the next table
                # mutation — scan once per chunk, not once per layer
                used = int((alloc.tables >= 0).sum(axis=1).max())
                k = 1
                while k < used:
                    k *= 2
                self._chunk_tables[mb] = alloc.tables_device()[
                    :, :min(k, alloc.max_pages)]
            clones = self._step_clones.get((mb, "chunk"))
            if clones:
                self.state[layer] = PC.clone_pool_pages(self.state[layer],
                                                        clones)
        return self._paged_chunk_fn()(r_in, self.state[layer],
                                      self._chunk_tables[mb])

    def _paged_verify_fn(self):
        if "paged_verify" not in self._jit_cache:
            from repro.serving import paged_cache as PC
            f = partial(PC.r_attention_paged_verify,
                        window=self.cfg.window,
                        softcap=self.cfg.attn_logit_softcap,
                        kv_chunk=self.kv_chunk)
            self._jit_cache["paged_verify"] = jax.jit(
                lambda r_in, pool, tables: f(r_in, pool, tables))
        return self._jit_cache["paged_verify"]

    def _step_paged_verify(self, layer: int, r_in):
        """Speculative-decode verify append+attend on paged storage:
        grow the shared block tables for the k+1 candidate tokens on
        the micro-batch's first paged layer, then scatter+attend via
        the multi-token verify kernel.

        Allocator/table bookkeeping is keyed SEPARATELY from the
        prefill-chunk path ((mb, "verify") clones, ("v", mb) table
        snapshot): one decode step may legally carry BOTH a prefill
        chunk and a verify work for the same micro-batch — they touch
        disjoint rows, but each needs its own post-append table
        snapshot."""
        from repro.serving import paged_cache as PC
        mb = layer // self.cfg.num_layers
        alloc = self.allocators[mb]
        with S.span(S.R_GROW):
            if layer == self._first_paged_key(mb):
                alloc.append_chunk(np.asarray(r_in["lengths"]),
                                   np.asarray(r_in["valid"]).sum(axis=1))
                self._step_clones[(mb, "verify")] = alloc.take_clones()
                used = int((alloc.tables >= 0).sum(axis=1).max())
                k = 1
                while k < used:
                    k *= 2
                self._chunk_tables[("v", mb)] = alloc.tables_device()[
                    :, :min(k, alloc.max_pages)]
            clones = self._step_clones.get((mb, "verify"))
            if clones:
                self.state[layer] = PC.clone_pool_pages(self.state[layer],
                                                        clones)
        r_in = {k: v for k, v in r_in.items() if k != "verify"}
        return self._paged_verify_fn()(r_in, self.state[layer],
                                       self._chunk_tables[("v", mb)])

    def _first_paged_key(self, mb: int) -> int:
        if self._first_paged.get(mb) is None:
            self._first_paged[mb] = min(
                k for k in self.paged_keys
                if k // self.cfg.num_layers == mb)
        return self._first_paged[mb]

    def run(self) -> None:
        while True:
            if self._killed:
                return
            # bounded wait, not a bare get(): the idle heartbeat tick is
            # what lets the collect loop tell "alive but idle" from
            # "hung mid-item" without ever interrupting real work
            try:
                items = [self.inq.get(timeout=0.25)]
            except queue.Empty:
                self.heartbeat = time.monotonic()
                continue
            # batched-inbox drain: one wake services everything already
            # queued (work for several layers backs up behind a
            # straggler; draining them in one pass avoids a
            # get/process/sleep syscall cycle per item)
            while True:
                try:
                    items.append(self.inq.get_nowait())
                except queue.Empty:
                    break
            for item in items:
                if item is None or self._killed:
                    return
                self.heartbeat = time.monotonic()
                self.processing = True
                try:
                    self._run_one(item)
                finally:
                    self.processing = False
                    self.heartbeat = time.monotonic()

    def _run_one(self, item) -> None:
        tag, layer, kind, phase, r_in, sink = item
        drop = dup = False
        if self.chaos is not None:
            spec = self.chaos.fire("r_step", wid=self.wid, layer=layer,
                                   phase=phase)
            if spec is not None:
                if spec.kind == "crash":
                    # abrupt death mid-item: no completion, no error
                    # post — the thread just exits and is_alive() goes
                    # False, which is what failover must detect
                    self._killed = True
                    return
                if spec.kind == "error":
                    from repro.chaos.plan import ChaosComputeError
                    e: Exception = ChaosComputeError(
                        "injected R-step compute fault")
                    e.r_worker_context = (self.wid, layer, kind, phase)
                    if sink is not None:
                        sink.post_error(self.wid, tag, e)
                    else:
                        self.outq.put((tag, e))
                    return
                if spec.kind == "hang":
                    # stall with processing=True and a stale heartbeat;
                    # if the supervisor fails over meanwhile, the
                    # eventual post lands in a fenced epoch and is
                    # dropped — a short hang just completes late
                    time.sleep(spec.hang_s)
            spec = self.chaos.fire("completion", wid=self.wid, layer=layer,
                                   phase=phase)
            if spec is not None:
                drop = spec.kind == "drop"
                dup = spec.kind == "dup"
        try:
            t0 = time.perf_counter()
            # a chunked-prefill payload is recognized by its validity
            # mask — same inbox, same tags, different (multi-token) op.
            # A verify payload (speculative decode) additionally carries
            # the "verify" marker: dense/int8 storage runs it through
            # the very same chunk ops (bit-identical math), only paged
            # storage routes to the multi-token verify kernel.
            is_chunk = isinstance(r_in, dict) and "valid" in r_in
            is_verify = is_chunk and "verify" in r_in
            # tags end in (micro-batch, layer, phase), the args of the
            # engine's matching repro.pipe.dispatch span
            t_mb, t_li, t_ph = tag[-3:]
            with S.span(S.R_KERNEL, mb=t_mb, layer=t_li, phase=t_ph):
                if layer in self.paged_keys:
                    step = (self._step_paged_verify if is_verify
                            else self._step_paged_chunk if is_chunk
                            else self._step_paged)
                    r_out, new_state = step(layer, r_in)
                else:
                    r_out, new_state = self._fn(
                        kind, phase, chunk=is_chunk)(r_in,
                                                     self.state[layer])
            if self.profile_timing or sink is None:
                # explicit sync for precise timing; the sink path's host
                # conversion below absorbs it in steady state
                jax.block_until_ready(r_out)
            self.state[layer] = new_state
            host = None
            if sink is not None:
                # host conversion happens HERE, on the worker thread:
                # transfers overlap across workers and the S-worker
                # never pays for them
                with S.span(S.R_TO_HOST):
                    host = {k: np.asarray(v) for k, v in r_out.items()}
            dt = time.perf_counter() - t0
            if self.slowdown > 1.0:
                # simulated heterogeneity: a worker with 1/slowdown
                # the bandwidth takes slowdown * dt for the same rows
                time.sleep(dt * (self.slowdown - 1.0))
                dt *= self.slowdown
            if self.sim_row_cost > 0.0:
                # deterministic bandwidth-bound service time: streams
                # its rows' KV at sim_row_cost seconds per row
                extra = self.sim_row_cost * (self.hi - self.lo)
                time.sleep(extra)
                dt += extra
            self.busy_time += dt
            if sink is None:                     # legacy FIFO reply
                self.outq.put((tag, r_out))
            elif drop:
                # injected delivery fault: the KV append above is DONE
                # (state advanced), only the completion message is lost
                # — the supervisor's retry replays the step from token
                # history, so the orphaned append is overwritten
                pass
            elif dup:
                # duplicated delivery: the buffer scatter is idempotent,
                # the collect loop must tolerate the second token
                with S.span(S.R_POST):
                    sink.post(self.wid, tag, host, self.lo, self.hi)
                    sink.post(self.wid, tag, host, self.lo, self.hi)
            elif self.sim_deliver_jitter > 0.0:
                # async delivery over a jittery link: the result lands
                # late, the worker moves on to its next inbox item
                delay = float(self._jitter_rng.uniform(
                    0.0, self.sim_deliver_jitter))
                t = threading.Timer(delay, sink.post,
                                    args=(self.wid, tag, host,
                                          self.lo, self.hi))
                t.daemon = True
                t.start()
            else:
                with S.span(S.R_POST):
                    sink.post(self.wid, tag, host, self.lo, self.hi)
        except Exception as e:  # surface to the S-worker, don't deadlock
            # ship the ORIGINAL exception — traceback intact for the
            # S-side `raise ... from` — plus the failing computation's
            # coordinates (worker, layer key, kind, phase)
            e.r_worker_context = (self.wid, layer, kind, phase)
            if sink is not None:
                sink.post_error(self.wid, tag, e)
            else:
                self.outq.put((tag, e))

    def stop(self) -> None:
        self.inq.put(None)


# ---------------------------------------------------------------------------
# the pipelined engine
# ---------------------------------------------------------------------------
@dataclass
class _MbState:
    h: Any = None
    carry: Any = None
    lengths: Optional[jnp.ndarray] = None
    done: bool = False


@dataclass
class _PrefillChunk:
    """One queued chunk of prompt prefill for micro-batch ``mb``.

    Full-micro-batch arrays (rows not being prefilled carry valid=False
    everywhere: they write nothing, their compute is discarded) so the
    chunk rides the exact same per-layer fused-callable + CompletionSink
    tag machinery as a decode micro-batch — it IS a decode step with a
    sequence dimension.  ``vmb`` is the virtual micro-batch id routing
    its completions (>= num_mb, assigned per decode_step)."""
    mb: int
    tokens: Any                  # [mb_size, C] int32
    base: Any                    # [mb_size] int32 — per-row KV offset
    valid: Any                   # [mb_size, C] bool
    rows: Any                    # np[int] local rows being prefilled
    new_lens: Any                # np[int] base+count per entry of rows
    logits: Any = None           # [mb_size, vocab] once the last layer lands
    vmb: int = -1
    # speculative-decode verify work: same chunk machinery, but the final
    # callable returns ALL positions' logits ([mb_size, C, vocab]) and the
    # R-side paged op routes to the multi-token verify kernel
    verify: bool = False


class HeteroPipelineEngine:
    """S-worker + R-workers, ``num_microbatches`` in flight (Fig. 5b)."""

    def __init__(self, params, cfg: ModelConfig, *, batch: int,
                 cache_len: int, num_r_workers: int = 2,
                 num_microbatches: int = 2, kv_chunk: int = 1024,
                 quantized_kv: bool = False, paged_kv: bool = False,
                 page_size: int = 16, pages_per_worker: Optional[int] = None,
                 prefix_cache: bool = False,
                 kv_tier: Any = None,
                 fleet: Any = None, schedule: str = "ooo",
                 collect_timeout_s: float = 600.0,
                 profile_timing: bool = False,
                 chaos: Any = None,
                 suspect_after_s: float = 120.0,
                 suspect_strikes: int = 2):
        if num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be >= 1, got {num_microbatches}")
        if schedule not in ("ooo", "fifo"):
            raise ValueError(
                f"schedule must be 'ooo' (advance whichever micro-batch "
                f"completes first) or 'fifo' (advance in issue order), "
                f"got {schedule!r}")
        if collect_timeout_s <= 0:
            raise ValueError(
                f"collect_timeout_s must be > 0, got {collect_timeout_s}")
        if batch < 1 or cache_len < 1:
            raise ValueError(
                f"batch ({batch}) and cache_len ({cache_len}) must be >= 1")
        if batch % num_microbatches != 0:
            raise ValueError(
                f"batch ({batch}) must be divisible by num_microbatches "
                f"({num_microbatches}) — every micro-batch decodes the same "
                f"number of rows; round batch up to "
                f"{-(-batch // num_microbatches) * num_microbatches} or "
                f"change num_microbatches")
        self.params, self.cfg = params, cfg
        self.batch = batch
        self.mb_size = batch // num_microbatches
        self.num_mb = num_microbatches
        self.cache_len = cache_len
        self.paged_kv = paged_kv
        self.page_size = page_size
        # KV lifecycle tiering: the engine-global host tier every
        # worker/micro-batch allocator swaps to; implies the prefix
        # index (the tier is keyed by its digest chains)
        self.kv_tier = kv_tier if paged_kv else None
        self.prefix_cache = (prefix_cache or self.kv_tier is not None) \
            and paged_kv
        self.layers = per_layer_params(params, cfg)
        self.num_layers = cfg.num_layers
        self.fleet = fleet
        self.schedule = schedule
        self.collect_timeout_s = float(collect_timeout_s)
        # fault injection + suspicion-based stall detection.  The
        # collect loop polls in short slices instead of one fatal
        # blocking get: a pending worker that is dead, or hung past
        # `suspect_after_s` (heartbeat stale while processing), or
        # idle-with-empty-inbox for `suspect_strikes` consecutive polls
        # (completion lost in flight), aborts the step with a typed
        # StepFault the serving supervisor can heal; collect_timeout_s
        # remains the absolute backstop.  suspect_after_s must exceed
        # worst-case single-item service time (JIT compiles included)
        # and any simulated delivery jitter, or healthy-but-slow
        # workers get failed over spuriously — recovery stays correct,
        # just wasteful.
        self.chaos = chaos
        self.suspect_after_s = float(suspect_after_s)
        self.suspect_strikes = max(1, int(suspect_strikes))
        # serving layer hook: mb -> in-flight request ids, used to put
        # rids into stall messages so operators can correlate timelines
        self.rids_of: Optional[Any] = None
        # global batch rows whose migration wire payload failed its
        # checksum on the last apply_partition (installed from `lost`
        # instead; the serving layer re-prefills them)
        self.corrupt_rows: List[int] = []
        # pages_per_worker sizes ONE pool = one (attn layer, micro-batch)
        # of one worker — the same per-layer-per-row convention as
        # cache_len (see RWorker docstring for the total footprint)
        max_pages = -(-cache_len // page_size)
        self._worker_kwargs = dict(
            kv_chunk=kv_chunk, quantized=quantized_kv, paged=paged_kv,
            page_size=page_size, num_pages=pages_per_worker,
            max_pages_per_seq=max_pages, prefix_cache=self.prefix_cache,
            kv_tier=self.kv_tier, profile_timing=profile_timing,
            chaos=chaos)
        if fleet is not None:
            # the fleet owns worker construction: profiles -> planned
            # (possibly uneven) partition -> RWorker instances
            self.workers, self.slices = fleet.spawn_workers(
                cfg, self.mb_size, self._worker_kwargs)
        else:
            if num_r_workers < 1:
                raise ValueError(
                    f"num_r_workers must be >= 1, got {num_r_workers}")
            if num_r_workers > self.mb_size:
                raise ValueError(
                    f"num_r_workers ({num_r_workers}) exceeds the "
                    f"micro-batch size ({self.mb_size} = batch "
                    f"{batch} / {num_microbatches} micro-batches) — every "
                    f"R-worker needs at least one row; lower num_r_workers "
                    f"or raise batch")
            # contiguous batch slices per worker WITHIN a micro-batch
            bounds = np.linspace(0, self.mb_size,
                                 num_r_workers + 1).astype(int)
            self.slices = [(int(bounds[i]), int(bounds[i + 1]))
                           for i in range(num_r_workers)]
            self.workers = [RWorker(w, cfg, lo, hi,
                                    **self._worker_kwargs)
                            for w, (lo, hi) in enumerate(self.slices)]
        for w in self.workers:
            w.start()
        if fleet is not None:
            fleet.attach(self)
        # S-side per-layer state (small convs), per micro-batch
        self.s_states: List[List[Any]] = [
            [None] * self.num_layers for _ in range(self.num_mb)]
        self.mb_lengths = [jnp.zeros((self.mb_size,), jnp.int32)
                           for _ in range(self.num_mb)]
        # per-row decode participation: inactive rows (released slots,
        # rows mid-chunked-prefill) get no KV append, no recurrent-state
        # update, no length bump — their logits are discarded upstream
        self.mb_active = [jnp.ones((self.mb_size,), bool)
                          for _ in range(self.num_mb)]
        self._jit_pre: Dict[int, Any] = {}               # legacy path
        self._jit_adv: Dict[Tuple[int, int], Any] = {}   # legacy path
        self._jit_prefill = None
        self._embed = jax.jit(lambda p, t: p["embed"][t])
        self._logits = jax.jit(partial(M._logits, cfg=cfg))
        # event-driven hot path: one completion channel for the whole
        # fleet, fused layer-transition callables keyed by the worker
        # partition (a topology change re-traces with the new slice
        # boundaries baked in)
        self._sink = CompletionSink(self.mb_size)
        self._parity = 0
        self._jit_start_cache: Dict[Tuple, Any] = {}
        self._jit_step_cache: Dict[Tuple, Any] = {}
        # chunked prefill: queued chunk work (executed inside the next
        # decode_step, interleaved on the completion sink) + its fused
        # S-side callables, keyed by (chunk len, partition)
        self._prefill_inbox: deque = deque()
        self.prefill_results: List[_PrefillChunk] = []
        self._jit_chunk_start: Dict[Tuple, Any] = {}
        self._jit_chunk_step: Dict[Tuple, Any] = {}
        # most-recent partitions whose traces we keep (an oscillating
        # rebalancer reuses A<->B without retracing; older topologies
        # are evicted so executables don't accumulate over a long serve)
        self._topo_lru: List[Tuple] = []
        self._set_topo()
        self.step_stats: Dict[str, float] = {}
        self.last_step_stats: Dict[str, float] = {}
        self._step_no = 0

    # -- state loading ------------------------------------------------------
    def load_prefill(self, mb: int, tokens, prompt_lens, enc_feats=None):
        """Run prefill for micro-batch ``mb`` on the S-worker and ship each
        layer's R-state slice to its R-worker (done once per admission —
        the steady state never moves KV again)."""
        if self._jit_prefill is None:
            self._jit_prefill = jax.jit(
                partial(M.prefill, cfg=self.cfg, cache_len=self.cache_len))
        _, state = self._jit_prefill(self.params, tokens=tokens,
                                     prompt_lens=prompt_lens,
                                     enc_feats=enc_feats)
        layer_states = per_layer_state(state, self.cfg)
        for li, (kind, _) in enumerate(self.layers):
            r_st, s_st = D.split_block_state(kind, layer_states[li])
            for w in self.workers:
                w.load_state(self._lkey(mb, li), batch_slice(r_st, w.lo, w.hi))
            self.s_states[mb][li] = s_st
        self.mb_lengths[mb] = prompt_lens.astype(jnp.int32)
        self.mb_active[mb] = jnp.ones((self.mb_size,), bool)

    def _lkey(self, mb: int, layer: int) -> int:
        return mb * self.num_layers + layer

    # -- jitted S-side pieces -----------------------------------------------
    def _pre(self, li: int):
        if li not in self._jit_pre:
            kind, p = self.layers[li]
            cfg = self.cfg

            def f(p, h, s_state, lengths):
                ctx = M.Ctx(cfg, "decode", lengths[:, None], lengths, None, 0)
                return D.s_pre_stateful(kind, p, h, s_state, ctx)

            self._jit_pre[li] = jax.jit(f)
        return self._jit_pre[li]

    def _adv(self, li: int, phase: int):
        key = (li, phase)
        if key not in self._jit_adv:
            kind, p = self.layers[li]
            cfg = self.cfg

            def f(p, carry, r_out, lengths):
                ctx = M.Ctx(cfg, "decode", lengths[:, None], lengths, None, 0)
                return D.s_advance(kind, phase, p, carry, r_out, ctx)

            self._jit_adv[key] = jax.jit(f)
        return self._jit_adv[key]

    # -- fused event-driven S-side callables ---------------------------------
    _TOPO_KEEP = 4          # partitions whose compiled traces we retain

    def _topo(self) -> Tuple:
        return self._topo_cur

    def _set_topo(self) -> None:
        """Recompute the partition key and its trace-cache LRU — called
        only when the topology actually changes (construction,
        apply_partition), keeping the per-advance _step_fn lookup free
        of tuple building and list bookkeeping."""
        topo = tuple((int(lo), int(hi)) for lo, hi in self.slices)
        self._topo_cur = topo
        if topo in self._topo_lru:
            self._topo_lru.remove(topo)
        self._topo_lru.append(topo)
        while len(self._topo_lru) > self._TOPO_KEEP:
            dead = self._topo_lru.pop(0)
            for cache in (self._jit_start_cache, self._jit_step_cache,
                          self._jit_chunk_start, self._jit_chunk_step):
                for k in [k for k in cache if k[-1] == dead]:
                    del cache[k]

    def _start_fn(self, li: int):
        """embed -> s_pre(0), emitting per-worker r_in shards, one
        dispatch.  Only ever traced for layer 0 — every later layer is
        entered through a fused transition (:meth:`_step_fn`).

        ``active`` [mb_size] bool rides into every r_in shard (gating
        R-side appends/updates) and gates the S-side state writes, so
        rows mid-chunked-prefill or released stay untouched."""
        key = (li, self._topo())
        f = self._jit_start_cache.get(key)
        if f is None:
            kind, _ = self.layers[li]
            cfg, slices = self.cfg, self._topo()

            def start(params, p, tokens, s_state, lengths, active):
                h = params["embed"][tokens]
                ctx = M.Ctx(cfg, "decode", lengths[:, None], lengths, None, 0)
                po, new_s = D.s_pre_stateful(kind, p, h, s_state, ctx)
                new_s = mask_rows(new_s, s_state, active)
                r_in = dict(po.r_in)
                r_in["active"] = active
                return po.carry, shard_rin(r_in, slices), new_s

            f = _quiet_donation_jit(start, (3,))
            self._jit_start_cache[key] = f
        return f

    def _step_fn(self, li: int, phase: int):
        """The fused layer-transition callable for ``(li, phase)`` plus
        its static shape: ``"phase"`` (same block continues — DEC_XATTN),
        ``"fused"`` (s_advance(li) -> s_pre(li+1) in ONE jitted dispatch,
        r_in already sharded per worker), or ``"final"`` (s_advance of
        the last layer fused with the logits head).  Inputs that are
        dead after the call (carry, r_out, consumed s_state) are donated
        so XLA can reuse their buffers."""
        key = (li, phase, self._topo())
        ent = self._jit_step_cache.get(key)
        if ent is None:
            kind, _ = self.layers[li]
            cfg, slices = self.cfg, self._topo()
            more = phase + 1 < D.num_phases(kind)
            last = li + 1 >= self.num_layers
            if more:
                def f(p, carry, r_out, lengths, active):
                    ctx = M.Ctx(cfg, "decode", lengths[:, None], lengths,
                                None, 0)
                    po = D.s_advance(kind, phase, p, carry, r_out, ctx)
                    r_in = dict(po.r_in)
                    r_in["active"] = active
                    return po.carry, shard_rin(r_in, slices)

                ent = (_quiet_donation_jit(f, (1, 2)), "phase")
            elif last:
                def f(params, p, carry, r_out, lengths, active):
                    ctx = M.Ctx(cfg, "decode", lengths[:, None], lengths,
                                None, 0)
                    h = D.s_advance(kind, phase, p, carry, r_out, ctx)
                    return M._logits(params, h=h, cfg=cfg)[:, 0]

                ent = (_quiet_donation_jit(f, (2, 3)), "final")
            else:
                kind2, _ = self.layers[li + 1]

                def f(p, p2, carry, r_out, s_state2, lengths, active):
                    ctx = M.Ctx(cfg, "decode", lengths[:, None], lengths,
                                None, 0)
                    h = D.s_advance(kind, phase, p, carry, r_out, ctx)
                    po, new_s2 = D.s_pre_stateful(kind2, p2, h, s_state2,
                                                  ctx)
                    new_s2 = mask_rows(new_s2, s_state2, active)
                    r_in = dict(po.r_in)
                    r_in["active"] = active
                    return po.carry, shard_rin(r_in, slices), new_s2

                ent = (_quiet_donation_jit(f, (2, 3, 4)), "fused")
            self._jit_step_cache[key] = ent
        return ent

    # -- fused chunked-prefill S-side callables ------------------------------
    def _chunk_ctx(self, cfg, base, c):
        qpos = base[:, None] + jnp.arange(c)[None, :]
        return M.Ctx(cfg, "chunk", qpos, base, None, 0)

    def _chunk_start_fn(self, c: int):
        """embed -> s_pre_chunk(0) for a C-token prompt chunk — the
        chunk-work twin of :meth:`_start_fn` (same shard fan-out, same
        donation discipline), keyed by chunk length and partition."""
        key = (c, self._topo())
        f = self._jit_chunk_start.get(key)
        if f is None:
            kind, _ = self.layers[0]
            cfg, slices = self.cfg, self._topo()

            def start(params, p, tokens, s_state, base, valid):
                h = params["embed"][tokens]
                ctx = self._chunk_ctx(cfg, base, tokens.shape[1])
                po, new_s = D.s_pre_chunk_stateful(kind, p, h, s_state,
                                                   ctx, valid)
                return po.carry, shard_rin(po.r_in, slices), new_s

            f = _quiet_donation_jit(start, (3,))
            self._jit_chunk_start[key] = f
        return f

    def _chunk_step_fn(self, li: int, phase: int, c: int,
                       verify: bool = False):
        """Fused chunk layer transition, mirroring :meth:`_step_fn`'s
        "phase"/"fused"/"final" shapes.  "final" gathers each row's
        LAST VALID chunk position and returns its logits [mb_size, V]
        (rows with no valid tokens return garbage the caller ignores).
        With ``verify`` (speculative-decode scoring) the final instead
        returns EVERY position's logits [mb_size, C, V] — the accept
        walk needs the target distribution at each candidate offset.
        S-side conv freezing is row-gated inside s_pre_chunk_stateful,
        so no extra masking is needed here."""
        key = (li, phase, c, verify, self._topo())
        ent = self._jit_chunk_step.get(key)
        if ent is None:
            kind, _ = self.layers[li]
            cfg, slices = self.cfg, self._topo()
            more = phase + 1 < D.num_phases(kind)
            last = li + 1 >= self.num_layers
            if more:
                def f(p, carry, r_out, base, valid):
                    ctx = self._chunk_ctx(cfg, base, c)
                    po = D.s_advance_chunk(kind, phase, p, carry, r_out, ctx)
                    r_in = dict(po.r_in)
                    r_in["valid"] = valid
                    return po.carry, shard_rin(r_in, slices)

                ent = (_quiet_donation_jit(f, (1, 2)), "phase")
            elif last and verify:
                def f(params, p, carry, r_out, base, valid):
                    ctx = self._chunk_ctx(cfg, base, c)
                    h = D.s_advance_chunk(kind, phase, p, carry, r_out, ctx)
                    return M._logits(params, h=h, cfg=cfg)

                ent = (_quiet_donation_jit(f, (2, 3)), "final")
            elif last:
                def f(params, p, carry, r_out, base, valid):
                    ctx = self._chunk_ctx(cfg, base, c)
                    h = D.s_advance_chunk(kind, phase, p, carry, r_out, ctx)
                    cnt = valid.sum(axis=1)
                    idx = jnp.clip(cnt - 1, 0, h.shape[1] - 1)
                    hsel = h[jnp.arange(h.shape[0]), idx][:, None]
                    return M._logits(params, h=hsel, cfg=cfg)[:, 0]

                ent = (_quiet_donation_jit(f, (2, 3)), "final")
            else:
                kind2, _ = self.layers[li + 1]

                def f(p, p2, carry, r_out, s_state2, base, valid):
                    ctx = self._chunk_ctx(cfg, base, c)
                    h = D.s_advance_chunk(kind, phase, p, carry, r_out, ctx)
                    po, new_s2 = D.s_pre_chunk_stateful(kind2, p2, h,
                                                        s_state2, ctx, valid)
                    return po.carry, shard_rin(po.r_in, slices), new_s2

                ent = (_quiet_donation_jit(f, (2, 3, 4)), "fused")
            self._jit_chunk_step[key] = ent
        return ent

    # -- chunked-prefill work queue ------------------------------------------
    def queue_prefill_chunk(self, mb: int, rows, tokens, bases, counts,
                            verify: bool = False) -> _PrefillChunk:
        """Queue one chunk of prompt prefill for local ``rows`` of
        micro-batch ``mb``: ``tokens`` [n, C] right-padded, ``bases``
        [n] per-row KV offsets (tokens already prefilled), ``counts``
        [n] valid tokens this chunk (<= C; the tail chunk of a prompt
        is shorter).  The chunk executes INSIDE the next decode_step —
        pipelined through the same per-layer tags as the decode
        micro-batches, its KV streamed to the owning R-workers layer by
        layer — and the work item (with per-row last-valid logits)
        appears in ``self.prefill_results`` after that step."""
        rows = np.asarray(rows, np.int64)
        tokens = np.asarray(tokens, np.int32)
        n, c = tokens.shape
        if n != len(rows):
            raise ValueError(f"{len(rows)} rows vs {n} token rows")
        tok = np.zeros((self.mb_size, c), np.int32)
        val = np.zeros((self.mb_size, c), bool)
        base = np.asarray(self.mb_lengths[mb], np.int32).copy()
        for i, r in enumerate(rows):
            r = int(r)
            tok[r] = tokens[i]
            base[r] = int(bases[i])
            val[r, :int(counts[i])] = True
        work = _PrefillChunk(
            mb=int(mb), tokens=jnp.asarray(tok), base=jnp.asarray(base),
            valid=jnp.asarray(val), rows=rows,
            new_lens=np.asarray(bases, np.int64)
            + np.asarray(counts, np.int64), verify=bool(verify))
        self._prefill_inbox.append(work)
        return work

    def set_row_active(self, row: int, flag: bool) -> None:
        """Gate a global batch row's decode participation (False while
        the row is mid-chunked-prefill or its slot is released)."""
        mb, local = divmod(int(row), self.mb_size)
        self.mb_active[mb] = self.mb_active[mb].at[local].set(bool(flag))

    def begin_prefill_rows(self, rows) -> None:
        """Prepare global batch rows for incremental (chunked) prefill:
        mark them decode-inactive, zero their lengths, and zero the
        recurrent (RGLRU/SSD) R-/S-side state rows so chunk 0 continues
        from h0 = 0.  Attention rows need no reset — chunk appends are
        write-then-attend and a previous occupant's stale entries are
        masked by position.  Must be called between decode steps."""
        from repro.core.config import RGLRU, SSD
        by_mb: Dict[int, List[int]] = {}
        for row in rows:
            mb, local = divmod(int(row), self.mb_size)
            by_mb.setdefault(mb, []).append(local)
            self.mb_active[mb] = self.mb_active[mb].at[local].set(False)
        for mb, local_rows in by_mb.items():
            locs = np.asarray(sorted(local_rows))
            lens = np.array(self.mb_lengths[mb])
            lens[locs] = 0
            self.mb_lengths[mb] = jnp.asarray(lens, jnp.int32)
            for li, (kind, _) in enumerate(self.layers):
                if kind not in (RGLRU, SSD):
                    continue
                st = M._block_state(self.cfg, kind, len(locs),
                                    self.cache_len)
                r_st, s_st = D.split_block_state(kind, st)
                for w in self.workers:
                    sel = np.asarray([i for i, l in enumerate(locs)
                                      if w.lo <= l < w.hi])
                    if len(sel):
                        w.write_rows(
                            self._lkey(mb, li), locs[sel] - w.lo,
                            jax.tree.map(lambda x: x[sel], r_st))
                if s_st:
                    self.s_states[mb][li] = jax.tree.map(
                        lambda cur, z: cur.at[locs].set(z),
                        self.s_states[mb][li], s_st)

    # -- the pipelined decode step -------------------------------------------
    # -- stall detection ------------------------------------------------------
    def _pending_desc(self, pending, works) -> str:
        """Human-readable outstanding-work list for stall messages,
        including the in-flight request ids per micro-batch (via the
        serving layer's ``rids_of`` hook) so operators can correlate
        a stall with request timelines."""
        parts = []
        for (mb, li, ph), ws in sorted(pending.items()):
            d = (f"micro-batch {mb} layer {li} ({self.layers[li][0]}) "
                 f"phase {ph} from worker(s) {sorted(ws)}")
            real_mb = mb if mb < self.num_mb else works[mb - self.num_mb].mb
            if self.rids_of is not None:
                try:
                    rids = list(self.rids_of(real_mb))
                except Exception:
                    rids = []
                if rids:
                    d += f" [in-flight rids: {rids}]"
            parts.append(d)
        return "; ".join(parts)

    def _check_stall(self, pending, works, strikes, waited, step_no) -> None:
        """Classify the workers still owing completions after an empty
        poll window; abort the step with a typed CollectTimeout when one
        is dead, hung past the suspicion threshold, or struck out as
        idle-with-completions-owed (lost message)."""
        owing: set = set()
        for ws in pending.values():
            owing |= ws
        by_wid = {w.wid: w for w in self.workers}
        now = time.monotonic()
        dead: List[int] = []
        hung: List[int] = []
        lost: List[int] = []
        for wid in sorted(owing):
            w = by_wid.get(wid)
            if w is None or not w.is_alive():
                dead.append(wid)
            elif w.processing and now - w.heartbeat > self.suspect_after_s:
                hung.append(wid)
            elif not w.processing and w.inq.empty():
                lost.append(wid)
        if not dead and not hung:
            for wid in lost:
                strikes[wid] = strikes.get(wid, 0) + 1
            lost = [wid for wid in lost
                    if strikes[wid] >= self.suspect_strikes]
            if not lost and waited <= self.collect_timeout_s:
                return
        raise CollectTimeout(
            f"decode step timed out after {waited:.1f}s waiting for "
            f"R-worker results — "
            + (f"dead worker(s) {dead}; " if dead else "")
            + (f"hung worker(s) {hung} (heartbeat stale > "
               f"{self.suspect_after_s:.1f}s); " if hung else "")
            + (f"worker(s) {lost} idle with completions owed "
               f"(message lost in flight?); " if lost else "")
            + f"outstanding: {self._pending_desc(pending, works) or 'none'}",
            dead_wids=dead, hung_wids=hung, lost_wids=lost,
            transient=not dead and not hung, step_no=step_no) from None

    def decode_step(self, tokens_per_mb: Optional[Sequence[jnp.ndarray]]):
        """One new token for every sequence of every micro-batch —
        event-driven: advance whichever micro-batch's R-results land
        first (``schedule="ooo"``) or in issue order (``"fifo"``).

        tokens_per_mb: list of [mb_size, 1] int32, or None to run a
        CHUNK-ONLY step (speculative-decode verify: the queued verify/
        prefill works execute through the same sink machinery, no decode
        micro-batches are started, and no decode length bump happens).
        Returns list of logits [mb_size, vocab] (list of None when
        chunk-only).
        """
        with S.span(S.PIPE_STEP):
            return self._decode_step(tokens_per_mb)

    def _decode_step(self, tokens_per_mb):
        run_decode = tokens_per_mb is not None
        if run_decode:
            assert len(tokens_per_mb) == self.num_mb
        pc = time.perf_counter
        stats = {"dispatch_s": 0.0, "collect_s": 0.0, "s_dispatch_s": 0.0,
                 "r_wait_s": 0.0, "ooo_advances": 0.0, "prefill_s": 0.0,
                 "dup_completion_count": 0.0}
        t_step0 = pc()
        step_no = self._step_no
        self._step_no += 1
        sink = self._sink
        self._parity ^= 1
        parity, epoch = self._parity, sink.epoch
        pending: Dict[Tuple[int, int, int], set] = {}
        issue_seq: Dict[Tuple[int, int, int], int] = {}
        fifo: deque = deque()
        ready: set = set()
        carries: List[Any] = [None] * self.num_mb
        logits_out: List[Any] = [None] * self.num_mb
        emit_at: List[float] = [0.0] * self.num_mb
        # queued prefill chunks ride this step as virtual micro-batches
        # num_mb+i: same tags, same sink, same event loop — their layer
        # advances interleave with decode advances wherever R-worker
        # completions leave the S-worker free
        works: List[_PrefillChunk] = []
        while self._prefill_inbox:
            wk = self._prefill_inbox.popleft()
            wk.vmb = self.num_mb + len(works)
            works.append(wk)
        self.prefill_results = []
        chunk_carries: Dict[int, Any] = {}
        active = (self.num_mb if run_decode else 0) + len(works)
        walk0 = self._paged_walk()

        def dispatch(mb: int, li: int, phase: int, shards) -> None:
            with S.span(S.PIPE_DISPATCH, mb=mb, layer=li, phase=phase):
                t0 = pc()
                tag = (epoch, parity, mb, li, phase)
                pending[(mb, li, phase)] = {w.wid for w in self.workers}
                issue_seq[(mb, li, phase)] = len(issue_seq)
                if self.schedule == "fifo" and mb < self.num_mb:
                    # chunk work is exempt from FIFO pinning: it has no
                    # emission-order contract, it fills bubbles
                    fifo.append((mb, li, phase))
                kind, _ = self.layers[li]
                real_mb = (mb if mb < self.num_mb
                           else works[mb - self.num_mb].mb)
                if mb >= self.num_mb and works[mb - self.num_mb].verify:
                    # mark verify shards so the R-worker routes them to
                    # the multi-token verify op (key presence, like
                    # "valid")
                    shards = tuple(dict(s, verify=True) for s in shards)
                lkey = self._lkey(real_mb, li)
                for w, shard in zip(self.workers, shards):
                    w.inq.put((tag, lkey, kind, phase, shard, sink))
                stats["dispatch_s"] += pc() - t0

        def advance(mb: int, li: int, phase: int) -> None:
            nonlocal active
            # an advance is out-of-order when an earlier-issued tag is
            # still outstanding — the FIFO schedule would have stalled
            # here (the bench's inversion counter)
            me = issue_seq[(mb, li, phase)]
            if any(issue_seq[t] < me for t in pending):
                stats["ooo_advances"] += 1.0
            with S.span(S.PIPE_GATHER):
                t0 = pc()
                r_out = sink.gather((epoch, parity, mb, li, phase))
                stats["collect_s"] += pc() - t0
            with S.span(S.PIPE_ADVANCE):
                t1 = pc()
                fn, mode = self._step_fn(li, phase)
                p = self.layers[li][1]
                if mode == "phase":
                    carry, shards = fn(p, carries[mb], r_out,
                                       self.mb_lengths[mb],
                                       self.mb_active[mb])
                    carries[mb] = carry
                    nxt = (li, phase + 1)
                elif mode == "fused":
                    carry, shards, new_s = fn(
                        p, self.layers[li + 1][1], carries[mb], r_out,
                        self.s_states[mb][li + 1], self.mb_lengths[mb],
                        self.mb_active[mb])
                    carries[mb] = carry
                    self.s_states[mb][li + 1] = new_s
                    nxt = (li + 1, 0)
                else:
                    logits_out[mb] = fn(self.params, p, carries[mb], r_out,
                                        self.mb_lengths[mb],
                                        self.mb_active[mb])
                    nxt = None
                stats["s_dispatch_s"] += pc() - t1
            if nxt is not None:
                dispatch(mb, *nxt, shards)
            else:
                # when this micro-batch's token becomes emittable — the
                # streaming-latency metric the OoO schedule improves
                # (FIFO holds a ready micro-batch behind the head)
                emit_at[mb] = pc() - t_step0
                active -= 1

        def advance_chunk(vmb: int, li: int, phase: int) -> None:
            nonlocal active
            wk = works[vmb - self.num_mb]
            # a chunk advance is a FREE RIDE (billed to prefill) only if
            # nothing else was already waiting for the S-worker when it
            # started — chunk compute that makes a completed decode
            # micro-batch queue behind it is decode latency, and leaving
            # it out of prefill_s keeps the serving layer's decode_wall
            # honest about oversized-chunk interference
            free_ride = (sink.q.empty()
                         or all(lg is not None for lg in logits_out))
            t0 = pc()
            with S.span(S.PIPE_GATHER):
                r_out = sink.gather((epoch, parity, vmb, li, phase))
            with S.span(S.PIPE_ADVANCE):
                fn, mode = self._chunk_step_fn(li, phase,
                                               wk.tokens.shape[1],
                                               verify=wk.verify)
                p = self.layers[li][1]
                if mode == "phase":
                    carry, shards = fn(p, chunk_carries[vmb], r_out,
                                       wk.base, wk.valid)
                    chunk_carries[vmb] = carry
                    nxt = (li, phase + 1)
                elif mode == "fused":
                    carry, shards, new_s = fn(
                        p, self.layers[li + 1][1], chunk_carries[vmb],
                        r_out, self.s_states[wk.mb][li + 1], wk.base,
                        wk.valid)
                    chunk_carries[vmb] = carry
                    self.s_states[wk.mb][li + 1] = new_s
                    nxt = (li + 1, 0)
                else:
                    wk.logits = fn(self.params, p, chunk_carries[vmb], r_out,
                                   wk.base, wk.valid)
                    nxt = None
                if free_ride:
                    stats["prefill_s"] += pc() - t0
            if nxt is not None:
                dispatch(vmb, *nxt, shards)
            else:
                active -= 1

        for mb in range(self.num_mb if run_decode else 0):
            with S.span(S.PIPE_START):
                t0 = pc()
                carry, shards, new_s = self._start_fn(0)(
                    self.params, self.layers[0][1], tokens_per_mb[mb],
                    self.s_states[mb][0], self.mb_lengths[mb],
                    self.mb_active[mb])
                carries[mb] = carry
                self.s_states[mb][0] = new_s
                stats["s_dispatch_s"] += pc() - t0
            dispatch(mb, 0, 0, shards)

        for wk in works:
            with S.span(S.PIPE_START):
                t0 = pc()
                carry, shards, new_s = self._chunk_start_fn(
                    wk.tokens.shape[1])(
                    self.params, self.layers[0][1], wk.tokens,
                    self.s_states[wk.mb][0], wk.base, wk.valid)
                chunk_carries[wk.vmb] = carry
                self.s_states[wk.mb][0] = new_s
                stats["prefill_s"] += pc() - t0
            dispatch(wk.vmb, 0, 0, shards)

        # suspicion-based stall detection: poll the sink in short slices
        # (instead of one fatal blocking get) and classify the workers
        # still owing completions on every empty window — dead / hung /
        # idle-with-empty-inbox.  `strikes` counts consecutive empty
        # windows per suspected-idle worker so a completion that is
        # merely in flight between the post and our get is never
        # mistaken for a lost message.
        strikes: Dict[int, int] = {}
        poll_s = min(max(self.suspect_after_s, 0.05),
                     self.collect_timeout_s)
        last_progress = pc()
        try:
            while active:
                with S.span(S.PIPE_R_WAIT):
                    t0 = pc()
                    try:
                        got = sink.q.get(timeout=poll_s)
                    except queue.Empty:
                        got = None
                    t1 = pc()
                wait = t1 - t0
                stats["r_wait_s"] += wait
                if got is None:
                    self._check_stall(pending, works, strikes,
                                      pc() - last_progress, step_no)
                    continue
                last_progress = t1
                wid, tag, err = got
                if works and all(lg is not None for lg in logits_out):
                    # every decode micro-batch has already emitted: this
                    # wait served ONLY chunk work — bill it to prefill
                    # so the serving layer's decode_wall split is honest
                    stats["prefill_s"] += wait
                t_epoch, t_parity, mb, li, phase = tag
                if t_epoch != epoch or t_parity != parity:
                    continue  # fenced-off straggler from an older step
                kind = self.layers[li][0]
                if err is not None:
                    ctx = getattr(err, "r_worker_context", None)
                    raise WorkerStepError(
                        f"R-worker {wid} failed on micro-batch {mb}, "
                        f"layer {li} ({kind}), phase {phase}"
                        + (f" [worker context: wid={ctx[0]} lkey={ctx[1]} "
                           f"kind={ctx[2]} phase={ctx[3]}]" if ctx else ""),
                        wid=wid,
                        transient=bool(getattr(err, "transient", False)),
                        step_no=step_no,
                    ) from err
                outstanding = pending.get((mb, li, phase))
                if outstanding is None or wid not in outstanding:
                    if (mb, li, phase) in issue_seq:
                        # duplicated delivery of a tag this step DID
                        # dispatch: the buffer scatter is idempotent
                        # (same rows, same bytes), so tolerate and count
                        stats["dup_completion_count"] += 1.0
                        continue
                    raise RuntimeError(
                        f"R-worker {wid} posted an unexpected completion "
                        f"for micro-batch {mb}, layer {li} ({kind}), "
                        f"phase {phase} — outstanding work: "
                        f"{sorted(pending) or 'none'}")
                outstanding.discard(wid)
                strikes.pop(wid, None)
                if outstanding:
                    continue
                del pending[(mb, li, phase)]
                if mb >= self.num_mb:
                    advance_chunk(mb, li, phase)
                elif self.schedule == "fifo":
                    ready.add((mb, li, phase))
                    while fifo and fifo[0] in ready:
                        nxt = fifo.popleft()
                        ready.discard(nxt)
                        advance(*nxt)
                else:
                    advance(mb, li, phase)
        except Exception:
            # never let the next step consume this step's leftovers
            sink.fence()
            raise

        outs = []
        for mb in range(self.num_mb):
            outs.append(logits_out[mb])
            # inactive rows (released / mid-prefill) did not append a
            # token; their lengths are owned by the prefill path.  A
            # chunk-only (verify) step bumps nothing: candidate-token
            # lengths are applied from the works loop below.
            if run_decode:
                self.mb_lengths[mb] = (self.mb_lengths[mb]
                                       + self.mb_active[mb]
                                       .astype(jnp.int32))
        for wk in works:
            # apply chunk progress AFTER the event loop: mb_lengths is
            # an input of every in-flight fused callable, so it must
            # stay frozen while the step is advancing.  Host-side numpy
            # on purpose — a jnp scatter would compile per distinct row
            # count (~100ms stalls sprinkled over the serve)
            if len(wk.rows):
                lens = np.array(self.mb_lengths[wk.mb])
                lens[wk.rows] = wk.new_lens
                self.mb_lengths[wk.mb] = jnp.asarray(lens, jnp.int32)
            self.prefill_results.append(wk)
        stats["step_s"] = pc() - t_step0
        stats["emit_mean_s"] = sum(emit_at) / self.num_mb
        walk1 = self._paged_walk()
        stats["paged_blocks_run_count"] = float(walk1[0] - walk0[0])
        stats["paged_blocks_grid_count"] = float(walk1[1] - walk0[1])
        self.last_step_stats = stats
        for k, v in stats.items():
            self.step_stats[k] = self.step_stats.get(k, 0.0) + v
        self.step_stats["steps"] = self.step_stats.get("steps", 0.0) + 1.0
        return outs

    # -- the pre-fusion FIFO decode step (A/B baseline) ----------------------
    def _dispatch(self, mb: int, li: int, phase: int, r_in) -> None:
        kind, _ = self.layers[li]
        for w in self.workers:
            w.inq.put(((mb, li, phase), self._lkey(mb, li), kind, phase,
                       rin_slice(r_in, w.lo, w.hi), None))

    def decode_step_legacy(self, tokens_per_mb: Sequence[jnp.ndarray]):
        """The pre-fusion hot path: strict FIFO collection, separate
        ``_pre``/``_adv`` dispatches, interpreter-level ``rin_slice``
        fan-out and device-side ``batch_concat`` fan-in.  Kept as the
        A/B baseline for benchmarks/bench_hotpath.py and as a second
        correctness oracle — numerics are identical to
        :meth:`decode_step` up to float association."""
        assert len(tokens_per_mb) == self.num_mb
        pc = time.perf_counter
        stats = {"dispatch_s": 0.0, "collect_s": 0.0, "s_dispatch_s": 0.0,
                 "r_wait_s": 0.0}
        t_step0 = pc()
        mbs = [_MbState() for _ in range(self.num_mb)]
        order: List[Tuple[int, int, int]] = []

        def timed_dispatch(mb: int, li: int, phase: int, r_in) -> None:
            t0 = pc()
            self._dispatch(mb, li, phase, r_in)
            stats["dispatch_s"] += pc() - t0

        def timed_collect(mb: int, li: int, phase: int):
            kind, _ = self.layers[li]
            parts = []
            for w in self.workers:
                t0 = pc()
                try:
                    tag, r_out = w.outq.get(timeout=self.collect_timeout_s)
                except queue.Empty:
                    rids = []
                    if self.rids_of is not None:
                        try:
                            rids = list(self.rids_of(mb))
                        except Exception:
                            rids = []
                    raise CollectTimeout(
                        f"timed out after {self.collect_timeout_s:.0f}s "
                        f"waiting for R-worker {w.wid} on micro-batch {mb}, "
                        f"layer {li} ({kind}), phase {phase}"
                        + (f" [in-flight rids: {rids}]" if rids else ""),
                        dead_wids=[w.wid] if not w.is_alive() else [],
                        hung_wids=[w.wid] if w.is_alive() else [],
                    ) from None
                stats["r_wait_s"] += pc() - t0
                if isinstance(r_out, Exception):
                    raise WorkerStepError(
                        f"R-worker {w.wid} failed on micro-batch {mb}, "
                        f"layer {li} ({kind}), phase {phase}",
                        wid=w.wid,
                        transient=bool(getattr(r_out, "transient", False)),
                    ) from r_out
                if tag != (mb, li, phase):
                    raise RuntimeError(
                        f"R-worker {w.wid} returned a result for "
                        f"(micro-batch, layer, phase) {tag}, expected "
                        f"({mb}, {li}, {phase}) ({kind})")
                parts.append(r_out)
            t0 = pc()
            out = batch_concat(parts)
            stats["collect_s"] += pc() - t0
            return out

        def start_layer(mb: int, li: int) -> None:
            st = mbs[mb]
            kind, p = self.layers[li]
            t0 = pc()
            po, new_s = self._pre(li)(p, st.h, self.s_states[mb][li],
                                      self.mb_lengths[mb])
            stats["s_dispatch_s"] += pc() - t0
            self.s_states[mb][li] = new_s
            st.carry = po.carry
            timed_dispatch(mb, li, 0, po.r_in)
            order.append((mb, li, 0))

        for mb in range(self.num_mb):
            t0 = pc()
            mbs[mb].h = self._embed(self.params, tokens_per_mb[mb])
            stats["s_dispatch_s"] += pc() - t0
            start_layer(mb, 0)

        qi = 0
        while qi < len(order):
            mb, li, phase = order[qi]
            qi += 1
            kind, p = self.layers[li]
            r_out = timed_collect(mb, li, phase)
            t0 = pc()
            res = self._adv(li, phase)(p, mbs[mb].carry, r_out,
                                       self.mb_lengths[mb])
            stats["s_dispatch_s"] += pc() - t0
            if isinstance(res, tuple) and len(res) == 2 and res[1] is not None \
                    and isinstance(res[1], dict):
                # next phase of the same block (DEC_XATTN)
                mbs[mb].carry = res[0]
                timed_dispatch(mb, li, phase + 1, res[1])
                order.append((mb, li, phase + 1))
            else:
                h = res[0] if isinstance(res, tuple) else res
                mbs[mb].h = h
                if li + 1 < self.num_layers:
                    start_layer(mb, li + 1)
                else:
                    mbs[mb].done = True

        outs = []
        for mb in range(self.num_mb):
            t0 = pc()
            logits = self._logits(self.params, h=mbs[mb].h)[:, 0]
            stats["s_dispatch_s"] += pc() - t0
            outs.append(logits)
            self.mb_lengths[mb] = self.mb_lengths[mb] + 1
        stats["step_s"] = pc() - t_step0
        self.last_step_stats = stats
        for k, v in stats.items():
            self.step_stats[k] = self.step_stats.get(k, 0.0) + v
        self.step_stats["steps"] = self.step_stats.get("steps", 0.0) + 1.0
        return outs

    def _paged_walk(self) -> Tuple[int, int]:
        """The R-workers' paged decode walk so far: (grid blocks run,
        grid blocks), read while no R-Part work is in flight."""
        return (sum(w.paged_blocks_run for w in self.workers),
                sum(w.paged_blocks_grid for w in self.workers))

    def reset_step_stats(self) -> None:
        self.step_stats = {}
        self.last_step_stats = {}

    # -- bookkeeping ----------------------------------------------------------
    def worker_busy_times(self) -> List[float]:
        return [w.busy_time for w in self.workers]

    def worker_for(self, row: int):
        """Map a global batch row to (worker, micro-batch, local row
        within the worker's slice) — the one invariant that keeps state
        scatter, page release and admission accounting consistent."""
        mb, local = divmod(int(row), self.mb_size)
        for w in self.workers:
            if w.lo <= local < w.hi:
                return w, mb, local - w.lo
        raise IndexError(row)

    def release_row(self, row: int) -> None:
        """Continuous batching: a finished sequence frees its KV pages on
        the owning R-worker (dense slabs are simply overwritten at the
        next admission and need no release)."""
        if not self.paged_kv:
            return
        w, mb, local = self.worker_for(row)
        w.release_rows(mb, [local])

    def truncate_rows(self, rows, new_lens) -> None:
        """Roll global batch rows back to ``new_lens`` tokens — the
        speculative-decode rejection path: a verify step appended k+1
        candidate tokens, the sampler committed a prefix, and the
        rejected tail must disappear before the next step reads.

        Paged storage releases the pages backing only-rejected positions
        (``PagedAllocator.truncate``: refcount ladder, partition
        invariant preserved); dense storage just lowers ``mb_lengths``
        — stale ring entries past the new length sit outside every
        chunk-path read mask and are overwritten by the next verify
        step's write region (which starts at the new length).  Must run
        between decode steps."""
        by_mb: Dict[int, List[Tuple[int, int]]] = {}
        for row, nl in zip(rows, new_lens):
            mb, local = divmod(int(row), self.mb_size)
            by_mb.setdefault(mb, []).append((local, int(nl)))
            if self.paged_kv:
                w, _, wlocal = self.worker_for(int(row))
                alloc = w.allocators.get(mb)
                if alloc is not None:
                    alloc.truncate(wlocal, int(nl))
        for mb, pairs in by_mb.items():
            lens = np.array(self.mb_lengths[mb])
            for local, nl in pairs:
                lens[local] = nl
            self.mb_lengths[mb] = jnp.asarray(lens, jnp.int32)

    def paged_resident_bytes(self) -> float:
        """KV bytes currently backed by allocated pages across R-workers
        (the dense path's equivalent is batch*cache_len regardless of
        occupancy)."""
        return sum(w.paged_resident_bytes() for w in self.workers)

    # -- shared-prefix KV reuse ----------------------------------------------
    def _row_allocator(self, row: int):
        w, mb, local = self.worker_for(row)
        return w.allocators.get(mb), local

    def probe_prefix(self, row: int, prompt_tokens,
                     restore: bool = False):
        """Longest cached prefix of ``prompt_tokens`` in the allocator
        that owns global batch row ``row`` — a cached prefix is only
        adoptable by rows of the same (worker, micro-batch) pool.
        Returns (page_ids, cached_token_count).

        With ``restore=True`` (tiering) index misses consult the host
        tier; restored page bytes are applied to the owning worker's
        layer pools right here, before returning — this runs on the
        engine thread between decode steps (the ``write_rows`` safety
        pattern), so nothing can read a restored page before its KV
        lands."""
        w, mb, local = self.worker_for(row)
        alloc = w.allocators.get(mb)
        if alloc is None or alloc.prefix is None:
            return [], 0
        lkeys = [k for k in w.paged_keys
                 if k // self.num_layers == mb]
        ids, cached = alloc.probe_prefix(
            prompt_tokens, restore=restore and bool(lkeys))
        restores = alloc.take_restores()
        if restores:
            from repro.serving import paged_cache as PC
            for lk in lkeys:
                w.state[lk] = PC.restore_pool_pages(
                    w.state[lk], restores, lk % self.num_layers)
        return ids, cached

    def park_row(self, row: int, tokens) -> bool:
        """Park-on-finish/preempt: index global batch row ``row``'s
        written chain (``tokens``) and keep its pages whole-sequence
        parked (host-tier-swappable) instead of LRU-cached — the
        tiering replacement for :meth:`release_row`.  Falls back to a
        plain release (inside the allocator) when the row is frozen,
        clamped, or the backend has no prefix index."""
        if not self.paged_kv:
            return False
        w, mb, local = self.worker_for(row)
        alloc = w.allocators.get(mb)
        if alloc is None:
            return False
        return alloc.park_row(local, tokens)

    def adopt_prefix(self, row: int, page_ids, length: int) -> None:
        """Map a probed prefix into ``row``'s block table (refcount++;
        no KV moves) so only positions >= ``length`` need prefilling."""
        alloc, local = self._row_allocator(row)
        alloc.adopt_prefix(local, page_ids, length)

    def register_prefix(self, row: int, prompt_tokens) -> int:
        """Index ``row``'s pages under its prompt's block-hash chain so
        later admissions can share them."""
        alloc, local = self._row_allocator(row)
        if alloc is None or alloc.prefix is None:
            return 0
        return alloc.register_prefix(local, prompt_tokens)

    def prefix_cache_stats(self) -> Dict[str, int]:
        """Aggregate allocator-level sharing counters (pages shared by
        >1 row, refcount-zero cached pages, free pages)."""
        out = {"shared_pages": 0, "cached_pages": 0, "free_pages": 0,
               "parked_pages": 0}
        for w in self.workers:
            for a in w.allocators.values():
                out["shared_pages"] += a.shared_pages()
                out["cached_pages"] += a.cached_pages()
                out["free_pages"] += a.free_pages()
                out["parked_pages"] += a.parked_pages()
        if self.kv_tier is not None:
            out["swapped_pages"] = self.kv_tier.swapped_pages()
        return out

    # -- fleet: live migration + failure recovery ---------------------------
    def zero_r_state(self) -> List[Any]:
        """Fresh (empty) full-micro-batch R-state, one entry per layer —
        the recovery filler for rows that cannot be restored (the serving
        layer then re-prefills the live ones).  Emitted in the fleet's
        wire format: int8+scales when the workers are quantized, so it
        concatenates cleanly with surviving workers' exports."""
        state = M.init_decode_state(self.cfg, self.mb_size, self.cache_len)
        layer_states = per_layer_state(state, self.cfg)
        out = []
        for li, (kind, _) in enumerate(self.layers):
            r_st = D.split_block_state(kind, layer_states[li])[0]
            if self._worker_kwargs.get("quantized") \
                    and isinstance(r_st, dict) and "k" in r_st:
                from repro.serving.kv_cache import quantize_attn_state
                r_st = quantize_attn_state(r_st)
            out.append(r_st)
        return out

    def _assemble_rows(self, lkey: int, lo: int, hi: int, old_spans,
                       exports: Dict[int, Any], lost):
        """Stitch wire-format rows [lo, hi) of one layer key from the
        exporting old owners, falling back to the ``lost`` payload for
        rows no surviving worker held (failure recovery)."""
        pieces = []
        cur = lo
        while cur < hi:
            src = next(((s_lo, s_hi, exports[wid])
                        for wid, s_lo, s_hi in old_spans
                        if s_lo <= cur < s_hi and wid in exports), None)
            if src is not None:
                s_lo, s_hi, wire = src
                take = min(hi, s_hi)
                pieces.append(jax.tree.map(
                    lambda x: x[cur - s_lo:take - s_lo], wire))
            else:
                nxt = [s_lo for _, s_lo, _ in old_spans if s_lo > cur]
                take = min(hi, min(nxt) if nxt else hi)
                if lost is None or lkey not in lost:
                    raise RuntimeError(
                        f"rows [{cur}, {take}) of layer key {lkey} have no "
                        f"surviving owner and no lost-rows payload — pass "
                        f"a KV snapshot or zero_r_state() filler")
                pieces.append(jax.tree.map(lambda x: x[cur:take],
                                           lost[lkey]))
            cur = take
        if len(pieces) == 1:
            return pieces[0]
        return jax.tree.map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], 0),
            *pieces)

    def apply_partition(self, new_slices, workers=None, lost=None) -> int:
        """Live-migrate R-state onto a new contiguous partition of the
        micro-batch rows (the fleet's rebalance/recovery primitive).

        ``new_slices``: one (lo, hi) per entry of ``workers`` (defaults
        to the current worker list), in order, covering [0, mb_size).
        Workers whose slice is unchanged are untouched; the rest export
        their rows in the dense wire format, adopt the new slice, and
        re-install — in-flight micro-batch state (KV slabs, page tables,
        recurrent states) survives the move.  Rows owned by a vanished
        worker are taken from ``lost`` ({lkey: full-micro-batch wire
        tree}, e.g. a KV snapshot).  A worker assigned zero rows is
        stopped and dropped (mirrors the constructor validation).

        Must be called between decode steps.  Returns the number of
        (row, micro-batch) assignments that changed owner."""
        # fence the completion channel FIRST: any in-flight tag from
        # before the topology change (e.g. a delayed delivery, or
        # leftovers of an aborted step) carries the old epoch and is
        # dropped instead of being mistaken for new-partition work.  The
        # fused S-side callables are keyed on the slice tuple, so the
        # new partition re-traces with its own boundaries baked in.
        self._sink.fence()
        workers = list(self.workers) if workers is None else list(workers)
        new_slices = [(int(lo), int(hi)) for lo, hi in new_slices]
        if len(workers) != len(new_slices):
            raise ValueError(f"{len(workers)} workers vs "
                             f"{len(new_slices)} slices")
        dropped = [w for w, (lo, hi) in zip(workers, new_slices) if hi <= lo]
        pairs = [(w, s) for w, s in zip(workers, new_slices) if s[1] > s[0]]
        workers = [w for w, _ in pairs]
        new_slices = [s for _, s in pairs]
        cur = 0
        for lo, hi in new_slices:
            if lo != cur:
                raise ValueError(
                    f"partition {new_slices} is not a contiguous cover of "
                    f"[0, {self.mb_size})")
            cur = hi
        if cur != self.mb_size:
            raise ValueError(
                f"partition {new_slices} covers [0, {cur}), micro-batch "
                f"has {self.mb_size} rows")

        old_owner = {}
        for w in workers:
            for r in range(w.lo, w.hi):
                old_owner[r] = id(w)
        moved = sum(1 for w, (lo, hi) in zip(workers, new_slices)
                    for r in range(lo, hi) if old_owner.get(r) != id(w))

        changed = [w for w, s in zip(workers, new_slices)
                   if (w.lo, w.hi) != s]
        changed_ids = {id(w) for w in changed}
        # a worker dropped to zero rows is still alive and must export
        # its rows before it goes
        sources = changed + dropped
        old_spans = [(id(w), w.lo, w.hi) for w in sources]
        lkeys = sorted({k for w in workers + dropped for k in w.state}
                       | (set(lost) if lost else set()))
        exports: Dict[int, Dict[int, Any]] = {lk: {} for lk in lkeys}
        # checksummed KV transport: digest each wire payload at export
        # time, verify before install.  In-process this guards against
        # injected (chaos "wire_corrupt") and accidental mutation; on a
        # real deployment the digest rides the serialized payload.
        from repro.chaos.checksum import tree_digest
        sums: Dict[Tuple[int, int], bytes] = {}
        for w in sources:
            for lk in lkeys:
                if lk in w.state:
                    exports[lk][id(w)] = wire = w.export_rows(
                        lk, np.arange(w.hi - w.lo))
                    sums[(lk, id(w))] = tree_digest(wire)
        if self.chaos is not None:
            for w in sources:
                for lk in lkeys:
                    if id(w) in exports[lk] and self.chaos.fire(
                            "wire_corrupt", wid=w.wid, lkey=lk,
                            where="migration"):
                        exports[lk][id(w)] = self.chaos.corrupt_tree(
                            exports[lk][id(w)])
        # verification: a corrupted export is DROPPED, its rows fall
        # back to `lost` (zeros synthesized if the caller gave none) and
        # are reported in self.corrupt_rows for the serving layer to
        # re-prefill — detected degradation, never silent garbage
        self.corrupt_rows = []
        span_of = {wid_: (s_lo, s_hi) for wid_, s_lo, s_hi in old_spans}
        corrupt_lkeys = set()
        for (lk, wid_), d0 in sums.items():
            if tree_digest(exports[lk][wid_]) != d0:
                del exports[lk][wid_]
                corrupt_lkeys.add(lk)
                s_lo, s_hi = span_of[wid_]
                mb = lk // self.num_layers
                self.corrupt_rows.extend(
                    mb * self.mb_size + r for r in range(s_lo, s_hi))
        self.corrupt_rows = sorted(set(self.corrupt_rows))
        if corrupt_lkeys:
            zeros = None
            lost = dict(lost) if lost else {}
            for lk in corrupt_lkeys:
                if lk not in lost:
                    if zeros is None:
                        zeros = self.zero_r_state()
                    lost[lk] = zeros[lk % self.num_layers]
        for w, s in zip(workers, new_slices):
            if id(w) in changed_ids:
                w.reassign(*s)
        for w in dropped:
            # a gracefully dropped worker's parked pages cross to the
            # engine-global tier before its pools die (a KILLED worker
            # gets no such flush — only already-swapped entries survive)
            for alloc in w.allocators.values():
                alloc.swap_out_all_parked()
            w.stop()
        for lk in lkeys:
            for w, (lo, hi) in zip(workers, new_slices):
                if id(w) not in changed_ids:
                    continue
                w.load_state(lk, self._assemble_rows(
                    lk, lo, hi, old_spans, exports[lk], lost))
        self.workers = workers
        self.slices = new_slices
        self._set_topo()
        return moved * self.num_mb

    def remove_worker(self, widx: int, new_slices=None, lost=None):
        """Failure path: drop worker ``widx``, repartition the survivors
        (even split unless the fleet planner supplies ``new_slices``),
        and refill its rows from ``lost`` wire payloads (KV snapshot) or
        fresh zero state (the serving layer re-prefills live rows).
        Returns the removed worker."""
        if len(self.workers) <= 1:
            raise RuntimeError(
                "cannot remove the last R-worker — no survivor can adopt "
                "its rows")
        dead = self.workers[widx]
        survivors = self.workers[:widx] + self.workers[widx + 1:]
        if new_slices is None:
            bounds = np.linspace(0, self.mb_size,
                                 len(survivors) + 1).astype(int)
            new_slices = [(int(bounds[i]), int(bounds[i + 1]))
                          for i in range(len(survivors))]
        if lost is None:
            zeros = self.zero_r_state()
            keys = {k for w in self.workers for k in w.state}
            lost = {lk: zeros[lk % self.num_layers] for lk in keys}
        dead.kill()
        self.apply_partition(new_slices, workers=survivors, lost=lost)
        return dead

    def close(self) -> None:
        for w in self.workers:
            w.stop()
        stuck = []
        for w in self.workers:
            w.join(timeout=5)
            if w.is_alive():
                stuck.append(w.wid)
        if stuck:
            # a hung worker survived the join — warn (not raise: close()
            # runs in teardown paths, including after deliberate kills)
            # with the ids so the leak is attributable.  The threads are
            # daemons, so process exit is not blocked.
            warnings.warn(
                f"HeteroPipelineEngine.close(): R-worker(s) {stuck} did "
                f"not exit within 5s of stop() — thread(s) leaked (hung "
                f"mid-item?)", RuntimeWarning, stacklevel=2)


# ---------------------------------------------------------------------------
# single-device colocated reference (the "vanilla" baseline of Fig. 9/11)
# ---------------------------------------------------------------------------
class ColocatedEngine:
    """R-Part and S-Part both on the S-device — the paper's vanilla
    baseline.  Also the correctness oracle for the pipelined engine."""

    def __init__(self, params, cfg: ModelConfig, *, batch: int,
                 cache_len: int):
        if batch < 1 or cache_len < 1:
            raise ValueError(
                f"batch ({batch}) and cache_len ({cache_len}) must be >= 1")
        self.params, self.cfg = params, cfg
        self.cache_len = cache_len
        self._prefill = jax.jit(partial(M.prefill, cfg=cfg,
                                        cache_len=cache_len))
        self._step = jax.jit(partial(M.decode_step, cfg=cfg))
        self.state = None

    def load_prefill(self, tokens, prompt_lens, enc_feats=None):
        _, self.state = self._prefill(self.params, tokens=tokens,
                                      prompt_lens=prompt_lens,
                                      enc_feats=enc_feats)

    def decode_step(self, tokens):
        logits, self.state = self._step(self.params, state=self.state,
                                        tokens=tokens)
        return logits
