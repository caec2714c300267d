"""Paged flash-decode Pallas TPU kernel: R-Part attention over a
block-granular (PagedAttention-style) KV pool.

Instead of one dense ``[B, S, Hkv, Dh]`` slab per micro-batch, the
KV-cache lives in a shared page pool ``[P, page, Hkv, Dh]`` and every
sequence owns an ordered list of page ids — its *block table* row.  The
paper's R-workers are admission-limited by KV memory (§4.3 eq. 9), so
allocating by page instead of by worst-case ``cache_len`` is what lets a
worker hold sequences proportional to their *actual* token count.

Block-table layout / protocol (shared with ``repro.serving.paged_cache``):

    pages_k/v  [P, page, Hkv, Dh]   the pool (one per layer per worker)
    tables     [B, MP] int32        k-th entry = page id backing absolute
                                    positions [k*page, (k+1)*page); -1 if
                                    unmapped
    lengths    [B] int32            position of THIS step's new token

Pages are allocated as a contiguous prefix (slot k mapped => slots < k
mapped) and tokens are appended in order, so a slot's absolute positions
are *derived* — ``k*page + j`` — and need not be stored: the valid mask
``pos <= lengths[b]`` over mapped pages is exactly the written token set.
A fully unmapped row (freed slot still being stepped by the engine)
yields an all-masked score row and a zero output, never a stale read.

Grid: (batch, MP).  The page-list dimension is innermost and sequential;
the block table and lengths ride in scalar-prefetch SMEM so each step's
K/V DMA source address is ``tables[b, i]`` — the gather never
materializes a contiguous copy of the sequence (the jnp reference in
kernels/ref.py does exactly that gather, and is the oracle).  Online
softmax state lives in VMEM scratch, shared with decode_attention.py.

TPU tiling: a page is taken with ALL its kv heads, viewing the pool as
``[P, page*Hkv, Dh]`` (a free reshape), so a block spans whole trailing
dimensions whatever ``page`` and ``Hkv`` are; the query block holds all
heads and a head-match mask pairs query and kv heads, as described in
decode_attention.py.  Row ``j`` of a page block is token ``j // Hkv``
of kv head ``j % Hkv``.

Shared-prefix aliasing: the kernel makes NO exclusivity assumption about
page ids — two rows' tables may legally point at the same page (the
ref-counted prefix cache of ``serving/paged_cache.py`` does exactly
that), since pages are only ever READ here and each row's valid mask is
derived from its own table slots and length.  Writes happen host-ordered
in the allocator's step path (``write_token_paged`` /
``r_attention_paged_chunk``), which copy-on-write-clones a shared page
before any row writes into it — so an aliased page is immutable for as
long as it is aliased, and no new kernel work is needed for reuse.

Speculative-decode verify scores T queries per sequence against the same
pages in ONE pool sweep: the T query tokens are stacked as rows
``[T*Hq, Dh]`` (row ``i`` is token ``i // Hq``, head ``i % Hq``), so every
page is DMA'd once per row for all T candidates — the per-token cost is
the KV-bandwidth pass, and verifying k+1 positions amortizes it
(k+1)-fold.  Query t of row b sits at absolute position
``lengths[b] + t`` (lengths = token count before the verify step).
Decode is the T == 1 case of the same kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (F32, attend_block,
                                            causal_window_mask, head_match,
                                            init_scratch, write_output)


def _kernel(tbl_ref,            # SMEM [B, MP] int32 block table
            len_ref,            # SMEM [B] int32 base positions
            q_ref,              # [1, T*Hq, Dh]
            k_ref, v_ref,       # [1, page*Hkv, Dh]  (page tables[b, i])
            o_ref,              # [1, T*Hq, Dh]
            m_s, l_s, acc,      # VMEM scratch: [T*Hq,1], [T*Hq,1], [T*Hq,Dh]
            *, scale: float, window: int, sink: int, softcap: float,
            page: int, hq: int, hkv: int, blocks: int):
    bi = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        init_scratch(m_s, l_s, acc)

    q = q_ref[0].astype(F32) * scale                     # [T*Hq, Dh]
    k = k_ref[0].astype(F32)                             # [page*Hkv, Dh]
    v = v_ref[0].astype(F32)
    shape = (q.shape[0], k.shape[0])
    qpos = len_ref[bi] + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // hq
    # absolute positions of this page's slots are derived, not stored
    kpos = sb * page + jax.lax.broadcasted_iota(jnp.int32, shape, 1) // hkv
    valid = ((tbl_ref[bi, sb] >= 0) & head_match(*shape, hq, hkv)
             & causal_window_mask(qpos, kpos, window=window, sink=sink))
    attend_block(q, k, v, valid, m_s, l_s, acc, softcap=softcap)

    @pl.when(sb == blocks - 1)
    def _done():
        write_output(o_ref, m_s, l_s, acc)


def paged_verify_attention(q, pages_k, pages_v, tables, lengths, *,
                           window: int = 0, sink: int = 0,
                           softcap: float = 0.0, interpret: bool = False):
    """q [B,T,Hq,Dh]; pages_k/v [P,page,Hkv,Dh]; tables [B,MP] int32
    (-1 = unmapped); lengths [B] int32 base positions (query t attends
    positions <= lengths[b] + t).  Returns o [B,T,Hq,Dh] in q.dtype."""
    b, t, hq, dh = q.shape
    n_pages, page, hkv, _ = pages_k.shape
    mp = tables.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    rows = t * hq
    qr = q.reshape(b, rows, dh)
    kr = pages_k.reshape(n_pages, page * hkv, dh)
    vr = pages_v.reshape(n_pages, page * hkv, dh)

    # unmapped (-1) entries are masked out in the kernel; the index map
    # clamps them so the DMA source stays in-pool
    page_spec = pl.BlockSpec(
        (1, page * hkv, dh),
        lambda bi, si, tbl, ln: (jnp.maximum(tbl[bi, si], 0), 0, 0))
    q_spec = pl.BlockSpec((1, rows, dh), lambda bi, si, tbl, ln: (bi, 0, 0))

    kern = functools.partial(
        _kernel, scale=1.0 / math.sqrt(dh), window=window, sink=sink,
        softcap=softcap, page=page, hq=hq, hkv=hkv, blocks=mp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[q_spec, page_spec, page_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((rows, 1), F32),
                        pltpu.VMEM((rows, 1), F32),
                        pltpu.VMEM((rows, dh), F32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dh), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, t, hq, dh)


def paged_decode_attention(q, pages_k, pages_v, tables, lengths, *,
                           window: int = 0, sink: int = 0,
                           softcap: float = 0.0, interpret: bool = False):
    """q [B,Hq,Dh]; pages_k/v [P,page,Hkv,Dh]; tables [B,MP] int32
    (-1 = unmapped); lengths [B] int32.  Returns o [B,Hq,Dh] in q.dtype."""
    return paged_verify_attention(
        q[:, None], pages_k, pages_v, tables, lengths, window=window,
        sink=sink, softcap=softcap, interpret=interpret)[:, 0]
