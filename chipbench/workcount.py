"""Operations and bytes that the served algorithm needs, from shapes.

These count the work the computation requires, not what an
implementation happens to do: padded pages, masked heads and repeated
reads are not counted, so a kernel that wastes work reads a lower share
of its roofline, and one that stops wasting it reads a higher one.
"""
from __future__ import annotations

from typing import Iterable

from spec import Spec

BF16 = 2


def paged_attn_decode(spec: Spec, contexts: Iterable[int]):
    """(flops, bytes) of one layer's decode attention over rows whose
    caches hold ``contexts`` tokens (the new token included): each
    context token is read once as K and V (2 * Hkv * Dh bf16 values),
    each row reads q and writes its output (Hq * Dh bf16 each), and
    scores plus the weighted sum take 4 * Hq * Dh flops per token."""
    hq, hkv, dh = spec.heads, spec.kv_heads, spec.head_dim
    tokens = rows = 0
    for c in contexts:
        tokens += int(c)
        rows += 1
    flops = 4 * hq * dh * tokens
    nbytes = tokens * 2 * hkv * dh * BF16 + rows * 2 * hq * dh * BF16
    return flops, nbytes


def weight_bytes(spec: Spec) -> int:
    """Bytes of weights a decode step must read once: every layer's
    matrices and the LM head (tied or not, V x d)."""
    return (spec.layers * spec.layer_params() + spec.head_params()) * BF16


def decode_flops_per_token(spec: Spec, context: int) -> int:
    """Model flops of one decode token at ``context`` cached tokens:
    2 per weight of every layer and of the LM head, plus attention."""
    dense = 2 * (spec.layers * spec.layer_params() + spec.head_params())
    attn = spec.layers * 4 * spec.heads * spec.head_dim * int(context)
    return dense + attn
