"""Flash-decode Pallas TPU kernel for R-Part attention (one new token per
sequence against a long KV-cache).

TPU adaptation of the paper's §5.1 mixed-precision CPU attention: the
KV-cache is stored in bf16 (int8 variant in quant_kv.py), streamed
HBM->VMEM in sequence tiles of ``block_s`` cache rows, converted and
accumulated in fp32 — the same store-low/compute-high policy with
VMEM/MXU in place of AVX registers.

Grid: (batch, seq_blocks).  The seq dimension is innermost (sequential
on TPU), so the online-softmax running max / denominator / accumulator
live in VMEM scratch across grid steps and the output is written on the
last step — the canonical flash-decoding reduction.  ``lengths`` rides
in scalar-prefetch SMEM.

Layout (TPU tiling): a block's last two dimensions must be multiples of
the (sublane, lane) tile or span the whole array.  The cache layout
``[B, S, Hkv, Dh]`` puts the KV heads in the sublane dimension, so a
block of ONE head would be a sublane slice of width 1.  Instead every
block carries ALL kv heads: the cache is viewed (a free reshape) as
``[B, S*Hkv, Dh]`` and a block is about ``block_s`` rows of ``Dh``
lanes (``block_s // Hkv`` tokens, so VMEM use is the same at any head
count), row ``j`` holding token ``j // Hkv`` of kv head ``j % Hkv``.
The query block is all ``Hq`` heads ``[Hq, Dh]`` (query head ``i``
reads kv head ``i // G``), so one MXU product scores every (query head,
cache row) pair and a head-match mask keeps the ``1/Hkv`` of them that
GQA pairs: each product does ``2*Hq*Dh`` flops per cache row, ``Hkv``
times the useful ``2*G*Dh``.  Whether the kernel
stays bound by the KV bytes streamed at a given width is not measured.
Per-token data (positions, int8 scales) are lane vectors
``[B, 1, S*Hkv]``, repeated per head to match the rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
F32 = jnp.float32


# ---------------------------------------------------------------------------
# shared online-softmax pieces (used by the paged kernels too)
# ---------------------------------------------------------------------------
def init_scratch(m_s, l_s, acc):
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)


def causal_window_mask(qpos, kpos, *, window: int, sink: int):
    """Valid (query, key) pairs by absolute position: causal, plus the
    sliding window (with attention sinks) when ``window > 0``."""
    valid = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        in_win = kpos > qpos - window
        if sink > 0:
            in_win |= kpos < sink
        valid &= in_win
    return valid


def attend_block(q, k, v, valid, m_s, l_s, acc, *, softcap: float,
                 k_scale=None, v_scale=None):
    """One flash step: q [R, Dh] (pre-scaled fp32) against a block of
    cache rows k/v [C, Dh] fp32; ``valid`` [R, C]; optional per-row int8
    dequantization scales as lane vectors [1, C] (applied to the scores
    and to the probabilities, which equals scaling k and v)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)          # [R, C]
    if k_scale is not None:
        s = s * k_scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
    m_s[...] = m_new


def write_output(o_ref, m_s, l_s, acc):
    """Normalize; a row that saw no valid key outputs zeros."""
    out = acc[...] / jnp.maximum(l_s[...], 1e-30)
    out = jnp.where(m_s[...] > NEG_INF / 2, out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def head_match(rows: int, cols: int, hq: int, hkv: int):
    """[rows, cols] mask: query row ``i`` (head ``i % hq``) may see cache
    row ``j`` (kv head ``j % hkv``) under grouped-query attention."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return (r % hq) // (hq // hkv) == c % hkv


# ---------------------------------------------------------------------------
# dense-cache kernel (fp storage, or int8 storage with per-row scales)
# ---------------------------------------------------------------------------
def _kernel(len_ref,            # SMEM [B] int32: position of the new token
            q_ref,              # [1, Hq, Dh]
            k_ref, v_ref,       # [1, Sblk*Hkv, Dh]
            pos_ref,            # [1, 1, Sblk*Hkv] int32 (-1 = invalid)
            *rest,              # ([1,1,Sblk*Hkv] k/v scales,) o_ref, scratch
            scale: float, window: int, sink: int, softcap: float,
            hkv: int, blocks: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc = rest
    else:
        o_ref, m_s, l_s, acc = rest
    bi = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        init_scratch(m_s, l_s, acc)

    q = q_ref[0].astype(F32) * scale                     # [Hq, Dh]
    k = k_ref[0].astype(F32)                             # [C, Dh]
    v = v_ref[0].astype(F32)
    hq, cols = q.shape[0], k.shape[0]
    valid = head_match(hq, cols, hq, hkv) & causal_window_mask(
        len_ref[bi], pos_ref[0], window=window, sink=sink)
    attend_block(q, k, v, valid, m_s, l_s, acc, softcap=softcap,
                 k_scale=ks_ref[0] if quantized else None,
                 v_scale=vs_ref[0] if quantized else None)

    @pl.when(sb == blocks - 1)
    def _done():
        write_output(o_ref, m_s, l_s, acc)


def decode_attention(q, k, v, pos, lengths, k_scale=None, v_scale=None, *,
                     window: int = 0, sink: int = 0, softcap: float = 0.0,
                     block_s: int = 2048, interpret: bool = False):
    """q [B,Hq,Dh]; k,v [B,S,Hkv,Dh] (bf16/f32, or int8 with k_scale,
    v_scale [B,S,Hkv]); pos [B,S] int32; lengths [B] int32.  Returns
    o [B,Hq,Dh] in q.dtype.  ``block_s`` is the number of cache rows
    (token x kv head) per block, so VMEM use does not grow with Hkv."""
    b, hq, dh = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    quantized = k_scale is not None
    # tokens per block: a whole number of 128-lane tiles of rows (the
    # lane vectors' tiling), or one block spanning the whole cache
    unit = 128 // math.gcd(128, hkv)
    tok = max(unit, block_s // hkv // unit * unit)
    if tok >= s_len:
        tok = s_len
    blocks = -(-s_len // tok)
    pad = blocks * tok - s_len

    def rows(x, fill=0):          # [B, S, Hkv, ...] -> [B, S*Hkv, ...]
        if pad:
            widths = ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)
            x = jnp.pad(x, widths, constant_values=fill)
        return x.reshape(b, (s_len + pad) * hkv, *x.shape[3:])

    def lanes(x, fill=0):         # [B, S, Hkv] -> [B, 1, S*Hkv]
        return rows(x, fill)[:, None]

    pos_rows = jnp.broadcast_to(pos.astype(jnp.int32)[:, :, None],
                                (b, s_len, hkv))
    args = [q, rows(k), rows(v), lanes(pos_rows, -1)]
    cblk = tok * hkv
    blk3 = pl.BlockSpec((1, cblk, dh), lambda bi, si, ln: (bi, si, 0))
    lane = pl.BlockSpec((1, 1, cblk), lambda bi, si, ln: (bi, 0, si))
    in_specs = [pl.BlockSpec((1, hq, dh), lambda bi, si, ln: (bi, 0, 0)),
                blk3, blk3, lane]
    if quantized:
        args += [lanes(k_scale.astype(F32)), lanes(v_scale.astype(F32))]
        in_specs += [lane, lane]

    kern = functools.partial(
        _kernel, scale=1.0 / math.sqrt(dh), window=window, sink=sink,
        softcap=softcap, hkv=hkv, blocks=blocks, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, dh), lambda bi, si, ln: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hq, 1), F32),
                        pltpu.VMEM((hq, 1), F32),
                        pltpu.VMEM((hq, dh), F32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), *args)

