"""Jit'd dispatch wrappers for the R-Part attention kernels.

``use_kernel='auto'`` picks the Pallas kernel on TPU and the jnp reference
on other backends (the reference lowers to better XLA/CPU code and keeps
the multi-pod dry-run free of per-backend custom calls).
``use_kernel='pallas'`` forces the kernel; off the TPU it runs only with
``interpret=True``, which the CPU tests pass to validate the kernels —
it is never on by default.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import decode_attention as _da
from repro.kernels import paged_attention as _pa
from repro.kernels import quant_kv as _qk
from repro.kernels import ref as _ref


def _use_pallas(use_kernel: str) -> bool:
    return use_kernel == "pallas" or (
        use_kernel == "auto" and jax.default_backend() == "tpu")


@partial(jax.jit, static_argnames=("window", "sink", "softcap", "block_s",
                                   "use_kernel", "interpret"))
def decode_attention(q, k, v, pos, lengths, *, window: int = 0, sink: int = 0,
                     softcap: float = 0.0, block_s: int = 2048,
                     use_kernel: str = "auto", interpret: bool = False):
    """Batched decode attention.  q [B,Hq,Dh]; k,v [B,S,Hkv,Dh];
    pos [B,S] int32; lengths [B] int32 -> [B,Hq,Dh]."""
    if _use_pallas(use_kernel):
        return _da.decode_attention(q, k, v, pos, lengths, window=window,
                                    sink=sink, softcap=softcap,
                                    block_s=block_s,
                                    interpret=interpret)
    return _ref.decode_attention_ref(q, k, v, pos, lengths, window=window,
                                     sink=sink, softcap=softcap)


@partial(jax.jit, static_argnames=("window", "sink", "softcap", "block_s",
                                   "use_kernel", "interpret"))
def decode_attention_int8(q, k_q, k_scale, v_q, v_scale, pos, lengths, *,
                          window: int = 0, sink: int = 0, softcap: float = 0.0,
                          block_s: int = 2048, use_kernel: str = "auto",
                          interpret: bool = False):
    if _use_pallas(use_kernel):
        return _da.decode_attention(
            q, k_q, v_q, pos, lengths, k_scale, v_scale, window=window,
            sink=sink, softcap=softcap, block_s=block_s,
            interpret=interpret)
    return _ref.decode_attention_int8_ref(
        q, k_q, k_scale, v_q, v_scale, pos, lengths, window=window,
        sink=sink, softcap=softcap)


@partial(jax.jit, static_argnames=("window", "sink", "softcap",
                                   "use_kernel", "interpret"))
def paged_decode_attention(q, pages_k, pages_v, tables, lengths, *,
                           window: int = 0, sink: int = 0,
                           softcap: float = 0.0, use_kernel: str = "auto",
                           interpret: bool = False):
    """Block-table decode attention.  q [B,Hq,Dh]; pages_k/v
    [P,page,Hkv,Dh]; tables [B,MP] int32; lengths [B] -> [B,Hq,Dh]."""
    if _use_pallas(use_kernel):
        return _pa.paged_decode_attention(
            q, pages_k, pages_v, tables, lengths, window=window, sink=sink,
            softcap=softcap, interpret=interpret)
    return _ref.paged_decode_attention_ref(
        q, pages_k, pages_v, tables, lengths, window=window, sink=sink,
        softcap=softcap)


@partial(jax.jit, static_argnames=("window", "sink", "softcap", "block_s",
                                   "use_kernel", "interpret"))
def paged_decode_attention_int8(q, pk_q, pk_s, pv_q, pv_s, tables, lengths,
                                *, window: int = 0, sink: int = 0,
                                softcap: float = 0.0, block_s: int = 2048,
                                use_kernel: str = "auto",
                                interpret: bool = False):
    """Int8 pools compose the paged gather with the dense int8 kernel: the
    pages are gathered into a per-sequence slab (with derived positions)
    and the existing quant_kv flash-decode consumes it.  On CPU the whole
    chain stays the jnp reference."""
    if _use_pallas(use_kernel):
        k_q, pos = _ref.paged_gather(pk_q, tables)
        k_s, _ = _ref.paged_gather(pk_s, tables)
        v_q, _ = _ref.paged_gather(pv_q, tables)
        v_s, _ = _ref.paged_gather(pv_s, tables)
        return _da.decode_attention(
            q, k_q, v_q, pos, lengths, k_s, v_s, window=window, sink=sink,
            softcap=softcap, block_s=block_s, interpret=interpret)
    return _ref.paged_decode_attention_int8_ref(
        q, pk_q, pk_s, pv_q, pv_s, tables, lengths, window=window,
        sink=sink, softcap=softcap)


# ---------------------------------------------------------------------------
# speculative-decode verify: T candidate tokens scored per row in one KV
# sweep.  The paged fp path has a dedicated Pallas kernel (the multi-token
# generalization of paged_decode_attention); the dense and int8 paths run
# the flash reference on both backends — multi-query flash lowers to clean
# XLA and the KV-bandwidth win comes from the single sweep, not the kernel.
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("window", "sink", "softcap", "kv_chunk",
                                   "use_kernel", "interpret"))
def verify_attention(q, k, v, pos, lengths, *, window: int = 0, sink: int = 0,
                     softcap: float = 0.0, kv_chunk: int = 1024,
                     use_kernel: str = "auto", interpret: bool = False):
    """Dense multi-token verify.  q [B,T,Hq,Dh]; k,v [B,S,Hkv,Dh];
    pos [B,S] int32; lengths [B] int32 base -> [B,T,Hq,Dh]."""
    del use_kernel, interpret
    return _ref.verify_attention_ref(q, k, v, pos, lengths, window=window,
                                     sink=sink, softcap=softcap,
                                     kv_chunk=kv_chunk)


@partial(jax.jit, static_argnames=("window", "sink", "softcap", "kv_chunk",
                                   "use_kernel", "interpret"))
def verify_attention_int8(q, k_q, k_scale, v_q, v_scale, pos, lengths, *,
                          window: int = 0, sink: int = 0, softcap: float = 0.0,
                          kv_chunk: int = 1024, use_kernel: str = "auto",
                          interpret: bool = False):
    del use_kernel, interpret
    return _ref.verify_attention_int8_ref(
        q, k_q, k_scale, v_q, v_scale, pos, lengths, window=window,
        sink=sink, softcap=softcap, kv_chunk=kv_chunk)


@partial(jax.jit, static_argnames=("window", "sink", "softcap", "kv_chunk",
                                   "use_kernel", "interpret"))
def paged_verify_attention(q, pages_k, pages_v, tables, lengths, *,
                           window: int = 0, sink: int = 0,
                           softcap: float = 0.0, kv_chunk: int = 1024,
                           use_kernel: str = "auto", interpret: bool = False):
    """Block-table multi-token verify.  q [B,T,Hq,Dh]; pages_k/v
    [P,page,Hkv,Dh]; tables [B,MP] int32; lengths [B] base -> [B,T,Hq,Dh]."""
    if _use_pallas(use_kernel):
        return _pa.paged_verify_attention(
            q, pages_k, pages_v, tables, lengths, window=window, sink=sink,
            softcap=softcap, interpret=interpret)
    return _ref.paged_verify_attention_ref(
        q, pages_k, pages_v, tables, lengths, window=window, sink=sink,
        softcap=softcap, kv_chunk=kv_chunk)


@partial(jax.jit, static_argnames=("window", "sink", "softcap", "kv_chunk",
                                   "use_kernel", "interpret"))
def paged_verify_attention_int8(q, pk_q, pk_s, pv_q, pv_s, tables, lengths,
                                *, window: int = 0, sink: int = 0,
                                softcap: float = 0.0, kv_chunk: int = 1024,
                                use_kernel: str = "auto",
                                interpret: bool = False):
    """Int8 pools gather into a per-sequence slab (as the decode int8 path
    does) and run the dense int8 verify reference over it."""
    del use_kernel, interpret
    return _ref.paged_verify_attention_int8_ref(
        q, pk_q, pk_s, pv_q, pv_s, tables, lengths, window=window,
        sink=sink, softcap=softcap, kv_chunk=kv_chunk)


quantize_kv = _qk.quantize_kv
dequantize_kv = _qk.dequantize_kv
