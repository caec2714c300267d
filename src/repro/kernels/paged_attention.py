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

Grid: (batch, ceil(MP / pages_per_block)).  A grid step takes a block
of ``pages_per_block`` table slots (``ppb * page`` tokens); the block
dimension is innermost and sequential, and online-softmax state lives in
VMEM scratch across it, shared with decode_attention.py.  The block
table and lengths ride in scalar-prefetch SMEM.  The pools stay in HBM
(``pl.ANY``): each step issues one async copy per slot, from
``pool[tables[b, j]]`` into a double-buffered VMEM block ``[2,
ppb*page*Hkv, Dh]`` for K and one for V, and starts the next block's
copies before it computes the current one — the gather never
materializes a contiguous copy of the sequence (the jnp reference in
kernels/ref.py does exactly that gather, and is the oracle).  A row's
walk stops at its length: a block that starts past ``lengths[b] + T - 1``
issues no copy and runs no compute, and a slot past the length or
unmapped is not copied — its V is zeroed so that stale VMEM never
reaches the output, and its scores are masked.  ``pages_per_block`` is
taken from the shapes (``choose_pages_per_block``): the largest power of
two whose K block fits ``KV_BLOCK_BYTES``, so a grid step moves enough
bytes to pay for its fixed cost at any head count.

TPU tiling: a page is taken with ALL its kv heads, viewing the pool as
``[P, page*Hkv, Dh]`` (a free reshape), so one page is one contiguous
copy of whole trailing dimensions whatever ``page`` and ``Hkv`` are;
the query block holds all heads and a head-match mask pairs query and
kv heads, as described in decode_attention.py.  Row ``j`` of a block is
token ``j // Hkv`` of the block (slot ``j // (page*Hkv)``) of kv head
``j % Hkv``.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (F32, attend_block,
                                            causal_window_mask, head_match,
                                            init_scratch, write_output)

# VMEM bytes of one K (or V) block; double-buffered K and V hold 4x this
KV_BLOCK_BYTES = 256 << 10


def choose_pages_per_block(page: int, hkv: int, dh: int, itemsize: int,
                           max_pages: int) -> int:
    """Table slots a grid step gathers: the largest power of two whose K
    block holds at most ``KV_BLOCK_BYTES`` and no more than ``max_pages``
    slots (at least 1)."""
    per_page = page * hkv * dh * itemsize
    ppb = 1
    while 2 * ppb * per_page <= KV_BLOCK_BYTES and 2 * ppb <= max_pages:
        ppb *= 2
    return ppb


def blocks_walked(lengths, t: int, pages_k, max_pages: int) -> tuple:
    """(grid blocks that copy and compute, grid blocks in the grid) of one
    kernel call over the pool ``pages_k`` [P,page,Hkv,Dh] (read for its
    shape and dtype only) and rows at base positions ``lengths`` with
    ``t`` queries each: a row needs the blocks holding positions
    ``0 .. lengths[b] + t - 1``."""
    _, page, hkv, dh = pages_k.shape
    ppb = choose_pages_per_block(page, hkv, dh, pages_k.dtype.itemsize,
                                 max_pages)
    blocks = -(-max_pages // ppb)
    need = -(-(np.asarray(lengths, np.int64) + t) // (ppb * page))
    return int(np.minimum(need, blocks).sum()), int(len(need) * blocks)


def _kernel(tbl_ref,            # SMEM [B, blocks*ppb] int32 block table
            len_ref,            # SMEM [B] int32 base positions
            q_ref,              # [1, T*Hq, Dh]
            k_hbm, v_hbm,       # [P, page*Hkv, Dh] pools, left in HBM
            o_ref,              # [1, T*Hq, Dh]
            kbuf, vbuf,         # VMEM [2, ppb*page*Hkv, Dh] double buffers
            sems,               # DMA semaphores [2 (k, v), 2 (buffer)]
            m_s, l_s, acc,      # VMEM scratch: [T*Hq,1], [T*Hq,1], [T*Hq,Dh]
            *, scale: float, window: int, sink: int, softcap: float,
            page: int, hq: int, hkv: int, t: int, ppb: int, blocks: int):
    bi = pl.program_id(0)
    sb = pl.program_id(1)
    prow = page * hkv                    # VMEM rows of one page
    tok = ppb * page                     # tokens of one block
    last = len_ref[bi] + (t - 1)         # the last position a query reads
    n_blk = jnp.minimum(last // tok + 1, blocks)

    def slot(blk, j):
        """Page id of slot j of block blk, and whether it is copied."""
        idx = blk * ppb + j
        pid = tbl_ref[bi, idx]
        return pid, (idx * page <= last) & (pid >= 0)

    def copies(buf, j, pid):
        dst = pl.ds(j * prow, prow)
        return (pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[buf, dst],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[buf, dst],
                                      sems.at[1, buf]))

    def fetch_slot(blk, buf, j):
        pid, ok = slot(blk, j)

        @pl.when(ok)
        def _start():
            for c in copies(buf, j, pid):
                c.start()

        @pl.when(jnp.logical_not(ok))
        def _zero():
            vbuf[buf, pl.ds(j * prow, prow), :] = jnp.zeros(
                (prow, vbuf.shape[-1]), vbuf.dtype)

    def wait_slot(blk, buf, j):
        pid, ok = slot(blk, j)

        @pl.when(ok)
        def _wait():
            for c in copies(buf, j, pid):
                c.wait()

    def fetch(blk, buf):
        for j in range(ppb):
            fetch_slot(blk, buf, j)

    @pl.when(sb == 0)
    def _init():
        init_scratch(m_s, l_s, acc)
        fetch(0, 0)

    @pl.when(sb < n_blk)
    def _block():
        buf = sb % 2

        @pl.when(sb + 1 < n_blk)
        def _prefetch():
            fetch(sb + 1, 1 - buf)

        for j in range(ppb):
            wait_slot(sb, buf, j)
        q = q_ref[0].astype(F32) * scale                 # [T*Hq, Dh]
        k = kbuf[buf].astype(F32)                        # [ppb*page*Hkv, Dh]
        v = vbuf[buf].astype(F32)
        shape = (q.shape[0], k.shape[0])
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
        qpos = len_ref[bi] + iota(shape, 0) // hq
        # absolute positions of the block's slots are derived, not stored
        kpos = sb * tok + iota(shape, 1) // hkv
        # the page id behind each column: unmapped slots are masked
        col_slot = iota((1, shape[1]), 1) // prow
        ids = jnp.full((1, shape[1]), -1, jnp.int32)
        for j in range(ppb):
            ids = jnp.where(col_slot == j, tbl_ref[bi, sb * ppb + j], ids)
        valid = ((ids >= 0) & head_match(*shape, hq, hkv)
                 & causal_window_mask(qpos, kpos, window=window, sink=sink))
        attend_block(q, k, v, valid, m_s, l_s, acc, softcap=softcap)

    @pl.when(sb == blocks - 1)
    def _done():
        write_output(o_ref, m_s, l_s, acc)


def paged_verify_attention(q, pages_k, pages_v, tables, lengths, *,
                           window: int = 0, sink: int = 0,
                           softcap: float = 0.0,
                           pages_per_block: int | None = None,
                           interpret=False):
    """q [B,T,Hq,Dh]; pages_k/v [P,page,Hkv,Dh]; tables [B,MP] int32
    (-1 = unmapped); lengths [B] int32 base positions (query t attends
    positions <= lengths[b] + t).  Returns o [B,T,Hq,Dh] in q.dtype.
    ``pages_per_block`` defaults to ``choose_pages_per_block`` of the
    shapes; tests set it to walk several blocks at tiny sizes."""
    b, t, hq, dh = q.shape
    n_pages, page, hkv, _ = pages_k.shape
    mp = tables.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    ppb = pages_per_block or choose_pages_per_block(
        page, hkv, dh, pages_k.dtype.itemsize, mp)
    blocks = -(-mp // ppb)
    tables = tables.astype(jnp.int32)
    if blocks * ppb != mp:       # the last block's missing slots: unmapped
        tables = jnp.pad(tables, ((0, 0), (0, blocks * ppb - mp)),
                         constant_values=-1)
    rows = t * hq
    qr = q.reshape(b, rows, dh)
    kr = pages_k.reshape(n_pages, page * hkv, dh)
    vr = pages_v.reshape(n_pages, page * hkv, dh)

    q_spec = pl.BlockSpec((1, rows, dh), lambda bi, si, tbl, ln: (bi, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    kern = functools.partial(
        _kernel, scale=1.0 / math.sqrt(dh), window=window, sink=sink,
        softcap=softcap, page=page, hq=hq, hkv=hkv, t=t, ppb=ppb,
        blocks=blocks)
    kv_block = (2, ppb * page * hkv, dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, blocks),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM(kv_block, pages_k.dtype),
                        pltpu.VMEM(kv_block, pages_v.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((rows, 1), F32),
                        pltpu.VMEM((rows, 1), F32),
                        pltpu.VMEM((rows, dh), F32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dh), q.dtype),
        interpret=interpret,
    )(tables, lengths.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, t, hq, dh)


def paged_decode_attention(q, pages_k, pages_v, tables, lengths, *,
                           window: int = 0, sink: int = 0,
                           softcap: float = 0.0, interpret: bool = False):
    """q [B,Hq,Dh]; pages_k/v [P,page,Hkv,Dh]; tables [B,MP] int32
    (-1 = unmapped); lengths [B] int32.  Returns o [B,Hq,Dh] in q.dtype."""
    return paged_verify_attention(
        q[:, None], pages_k, pages_v, tables, lengths, window=window,
        sink=sink, softcap=softcap, interpret=interpret)[:, 0]
