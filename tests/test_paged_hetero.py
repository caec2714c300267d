"""Paged R-worker KV end-to-end: the paged pipeline must match the dense
pipeline and the colocated oracle to fp tolerance on ragged batches, the
paged kernel must match its jnp reference, and the serving engine must
return every page when sequences finish."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.core.hetero import ColocatedEngine, HeteroPipelineEngine
from repro.kernels import ops
from repro.kernels import ref as KR
from repro.models import model as M
from repro.obs import schema

B, S, GEN = 4, 12, 5
RAGGED = (5, 12, 3, 9)


def _engines_logits(params, cfg, tokens, plens, gen, **hetero_kw):
    eng = HeteroPipelineEngine(params, cfg, batch=B, cache_len=S + gen,
                               num_r_workers=2, num_microbatches=2,
                               kv_chunk=8, **hetero_kw)
    h = B // 2
    eng.load_prefill(0, tokens[:h, :S], plens[:h])
    eng.load_prefill(1, tokens[h:, :S], plens[h:])
    logs = []
    try:
        for t in range(gen):
            tok = tokens[:, S + t:S + t + 1]
            logs.append(jnp.concatenate(eng.decode_step([tok[:h], tok[h:]]),
                                        0))
    finally:
        eng.close()
    return jnp.stack(logs)


@pytest.mark.parametrize("page", [3, 4, 16])
def test_paged_matches_dense_and_colocated_ragged(page, rng, key):
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + GEN)))
    plens = jnp.asarray(RAGGED, jnp.int32)

    ref = ColocatedEngine(params, cfg, batch=B, cache_len=S + GEN)
    ref.load_prefill(tokens[:, :S], plens)
    ref_logits = jnp.stack([ref.decode_step(tokens[:, S + t:S + t + 1])
                            for t in range(GEN)])

    dense = _engines_logits(params, cfg, tokens, plens, GEN)
    paged = _engines_logits(params, cfg, tokens, plens, GEN,
                            paged_kv=True, page_size=page)
    assert float(jnp.abs(paged - dense).max()) < 2e-4
    assert float(jnp.abs(paged - ref_logits).max()) < 2e-4


def test_paged_walk_counters_match_closed_form(rng, key):
    """The decode step counts the paged kernel's walk from host lengths:
    blocks run = sum over calls and rows of ceil((len + 1) / (ppb *
    page)), blocks in the grid = rows x ceil(MP / ppb) per call.  Row 1
    crosses a block boundary during the steps."""
    from repro.kernels.paged_attention import choose_pages_per_block
    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    page, gen = 4, 5
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + gen)))
    plens = np.asarray(RAGGED, np.int32)
    eng = HeteroPipelineEngine(params, cfg, batch=B, cache_len=S + gen,
                               num_r_workers=2, num_microbatches=2,
                               kv_chunk=8, paged_kv=True, page_size=page)
    h = B // 2
    try:
        eng.load_prefill(0, tokens[:h, :S], jnp.asarray(plens[:h]))
        eng.load_prefill(1, tokens[h:, :S], jnp.asarray(plens[h:]))
        w = eng.workers[0]
        layers = len({k % cfg.num_layers for k in w.paged_keys})
        pool = w.state[min(w.paged_keys)]["k"]
        mp = -(-(S + gen) // page)
        ppb = choose_pages_per_block(page, cfg.num_kv_heads, cfg.head_dim,
                                     pool.dtype.itemsize, mp)
        assert 1 < -(-mp // ppb)              # the grid has several blocks
        for t in range(gen):
            tok = tokens[:, S + t:S + t + 1]
            eng.decode_step([tok[:h], tok[h:]])
        stats = schema.normalize(eng.step_stats)
    finally:
        eng.close()
    lens = plens[None, :] + np.arange(gen)[:, None]          # [step, row]
    run = layers * int((-(-(lens + 1) // (ppb * page))).sum())
    grid = layers * gen * B * -(-mp // ppb)
    assert stats["paged_blocks_run_count"] == run
    assert stats["paged_blocks_grid_count"] == grid
    assert run < grid


# NOTE: the former test_paged_int8_matches_dense_int8 (§5.2 composition:
# int8 page pools == int8 dense slabs) is subsumed by the consolidated
# serving matrix — tests/test_equiv_matrix.py runs the "int8" and
# "paged-int8" storages against the same colocated oracle, so a paged
# int8 divergence from dense int8 fails there token-exactly.


def test_paged_windowed_arch_falls_back_to_dense(rng, key):
    """Windowed attention stores a rotated ring the paged layout can't
    represent — paged_kv must fall back to the dense slab and stay
    exactly equivalent (the silent-corruption case a contiguous-prefix
    conversion would hit)."""
    cfg = tiny_cfg("recurrentgemma-2b")
    assert cfg.window > 0
    params = M.init_params(key, cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 3)))
    plens = jnp.asarray(RAGGED, jnp.int32)
    dense = _engines_logits(params, cfg, tokens, plens, 3)
    paged = _engines_logits(params, cfg, tokens, plens, 3,
                            paged_kv=True, page_size=4)
    assert float(jnp.abs(paged - dense).max()) < 1e-5
    # and really dense underneath: no paged layers were created
    eng = HeteroPipelineEngine(params, cfg, batch=B, cache_len=S + 3,
                               num_r_workers=1, paged_kv=True, page_size=4)
    try:
        eng.load_prefill(0, tokens[:2, :S], plens[:2])
        eng.load_prefill(1, tokens[2:, :S], plens[2:])
        assert all(not w.paged_keys for w in eng.workers)
    finally:
        eng.close()


def test_paged_noop_for_non_attention_arch(rng, key):
    """paged_kv on an arch whose R-state is not a KV slab (whisper's
    DEC_XATTN keeps the dense slab) must stay equivalent."""
    cfg = tiny_cfg("whisper-medium")
    params = M.init_params(key, cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 2)))
    enc = jnp.asarray(rng.standard_normal(
        (B, cfg.encoder_seq, cfg.encoder_d_model)), jnp.float32)
    plens = jnp.full((B,), S, jnp.int32)

    outs = []
    for paged in (False, True):
        eng = HeteroPipelineEngine(params, cfg, batch=B, cache_len=S + 2,
                                   num_r_workers=2, num_microbatches=2,
                                   kv_chunk=8, paged_kv=paged)
        h = B // 2
        eng.load_prefill(0, tokens[:h, :S], plens[:h], enc_feats=enc[:h])
        eng.load_prefill(1, tokens[h:, :S], plens[h:], enc_feats=enc[h:])
        try:
            tok = tokens[:, S:S + 1]
            outs.append(jnp.concatenate(
                eng.decode_step([tok[:h], tok[h:]]), 0))
        finally:
            eng.close()
    assert float(jnp.abs(outs[0] - outs[1]).max()) < 1e-5


# ---------------------------------------------------------------------------
# kernel-level: Pallas paged flash-decode vs jnp reference
# ---------------------------------------------------------------------------
def _random_tables(rng, b, mp, page, lengths, num_pages):
    tables = np.full((b, mp), -1, np.int32)
    perm = list(rng.permutation(num_pages))
    for row in range(b):
        for k in range(-(-int(lengths[row] + 1) // page)):
            tables[row, k] = perm.pop()
    return jnp.asarray(tables)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (5, 0.0), (0, 30.0)])
def test_paged_kernel_matches_ref(window, softcap, rng):
    b, hkv, g, dh, page, mp = 3, 2, 3, 8, 4, 5
    num_pages = b * mp
    lengths = jnp.asarray([0, 7, 13], jnp.int32)
    pk = jnp.asarray(rng.standard_normal((num_pages, page, hkv, dh)),
                     jnp.float32)
    pv = jnp.asarray(rng.standard_normal((num_pages, page, hkv, dh)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hkv * g, dh)), jnp.float32)
    tables = _random_tables(rng, b, mp, page, np.asarray(lengths), num_pages)

    o_ref = KR.paged_decode_attention_ref(q, pk, pv, tables, lengths,
                                          window=window, softcap=softcap)
    o_pal = ops.paged_decode_attention(q, pk, pv, tables, lengths,
                                       window=window, softcap=softcap,
                                       use_kernel="pallas", interpret=True)
    np.testing.assert_allclose(o_pal, o_ref, atol=2e-6)


def test_paged_kernel_unmapped_row_is_zero(rng):
    """A fully released row (all-unmapped table) must output zeros, not
    stale pool data."""
    b, hkv, g, dh, page, mp = 2, 1, 2, 8, 4, 3
    pk = jnp.asarray(rng.standard_normal((6, page, hkv, dh)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((6, page, hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hkv * g, dh)), jnp.float32)
    tables = jnp.asarray([[0, 1, -1], [-1, -1, -1]], jnp.int32)
    lengths = jnp.asarray([6, 99], jnp.int32)
    for use in ("ref", "pallas"):
        o = ops.paged_decode_attention(q, pk, pv, tables, lengths,
                                       use_kernel=use, interpret=True)
        assert float(jnp.abs(o[1]).max()) == 0.0
        assert float(jnp.abs(o[0]).max()) > 0.0


def test_allocator_freezes_degraded_row():
    """A row whose decode-time grow hit pool exhaustion must never regrow
    (a later regrow would map freed pages over positions whose writes
    were dropped, exposing another sequence's stale KV)."""
    from repro.serving.paged_cache import PagedAllocator
    a = PagedAllocator(rows=2, num_pages=2, page=4, max_pages_per_seq=4)
    a.admit(0, 4)
    a.admit(1, 4)                            # pool now empty
    a.ensure_lengths(np.asarray([5, 4]))     # row 0 grow fails -> frozen
    assert bool(a.frozen[0])
    before = a.tables[0].copy()
    a.release(1)                             # a page becomes free
    a.ensure_lengths(np.asarray([8, 0]))     # must NOT regrow row 0
    assert np.array_equal(before, a.tables[0])
    a.admit(0, 6)                            # re-admission unfreezes
    assert not bool(a.frozen[0]) and int((a.tables[0] >= 0).sum()) == 2


# ---------------------------------------------------------------------------
# serving: admission allocates by prompt length, completion frees
# ---------------------------------------------------------------------------
def test_serving_paged_allocates_and_frees(rng, key):
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Request

    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    eng = ServingEngine(params, cfg, batch=4, cache_len=48,
                        backend="hetero", paged_kv=True, page_size=4,
                        num_r_workers=2)
    try:
        for i in range(6):
            plen = int(rng.integers(3, 14))
            prompt = np.asarray(rng.integers(1, cfg.vocab_size, (plen,)),
                                np.int32)
            eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=5))
        peak = 0.0
        while (eng.queue or any(r is not None for r in eng.slots)) \
                and eng.step_idx < 200:
            eng.step()
            peak = max(peak, eng.paged_resident_bytes())
        assert len(eng.finished) == 6
        assert peak > 0.0
        # every page returned once the pool drained
        assert eng.paged_resident_bytes() == 0.0
        # resident pages never exceeded what the ragged lengths need:
        # far below the dense slab's batch*cache_len footprint
        from repro.serving.kv_cache import kv_bytes_per_seq
        dense = 4 * kv_bytes_per_seq(cfg, 48)
        assert peak < 0.75 * dense
    finally:
        eng.close()


def test_serving_paged_ooo_skew_frees_all_pages(rng, key):
    """Continuous batching on the event-driven loop with skewed, jittery
    workers: completions arrive out of issue order across micro-batches,
    yet the page accounting must stay exact — every page returned when
    the pool drains, every request finished."""
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Request

    cfg = tiny_cfg("granite-3-8b")
    params = M.init_params(key, cfg)
    eng = ServingEngine(params, cfg, batch=4, cache_len=48,
                        backend="hetero", paged_kv=True, page_size=4,
                        num_r_workers=2, schedule="ooo")
    for i, w in enumerate(eng.engine.workers):
        w.slowdown = 1.0 + i            # worker 1 twice as slow
        w.sim_deliver_jitter = 1e-3
    try:
        for i in range(5):
            plen = int(rng.integers(3, 14))
            prompt = np.asarray(rng.integers(1, cfg.vocab_size, (plen,)),
                                np.int32)
            eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=4))
        eng.run(max_steps=200)
        assert len(eng.finished) == 5
        assert eng.paged_resident_bytes() == 0.0
        stats = eng.hotpath_stats()
        assert stats.get("steps", 0) > 0 and stats.get("r_wait_s", 0) > 0
    finally:
        eng.close()
