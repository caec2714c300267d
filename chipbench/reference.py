"""Plain float32 reference of the served decoder, and its fp8 control.

The published description, written out in ``jax.numpy`` with nothing of
the program imported: pre-norm blocks, RMSNorm ``x * g / rms(x)``,
grouped-query attention with RoPE (and, for Qwen3, RMSNorm of q and k
over ``head_dim`` before RoPE), a SwiGLU feed-forward, a final RMSNorm
and the LM head (tied to the embedding where the configuration says so).
One departure, shared with the program and noted in the configuration
file: RoPE rotates the pairs ``(2i, 2i+1)``.

It runs once the measured window has closed and the program's state is
freed, layer by layer (``layer_by_layer``): each layer's weights are
made anew from the seed (``weights.layer_weights``), applied to every
sequence, and dropped.
Matrix products run in float32 at ``precision="highest"``; attention
runs over blocks of queries so that no full score matrix is held.

``mode="fp8"`` is the control: every weight product quantizes its
activations per row and its weights per output column to float8_e4m3fn
and multiplies the rounded values, which is what an fp8 serving path
would compute.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from spec import Spec

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
BUCKET = 512           # sequences are padded to a multiple of this
ROWS = 256             # logit rows are read in multiples of this
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (2i, 2i+1) by t * theta^(-2i/D)."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None, None] * inv[None, None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v):
    """Causal attention, q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq, D]."""
    t, hq, d = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)          # query head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, hq, d) / jnp.sqrt(F32(d))
    kpos = jnp.arange(t)

    def block(args):
        qi, j = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST)
        qpos = j * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    return out.reshape(t, hq, d)


def _block(spec: Spec, mode: str, w, h):
    t = h.shape[0]
    hq, hkv, hd = spec.heads, spec.kv_heads, spec.head_dim
    x = _rms(h, w["ln1"], spec.norm_eps)
    q = _mm(x, w["wq"], mode).reshape(t, hq, hd)
    k = _mm(x, w["wk"], mode).reshape(t, hkv, hd)
    v = _mm(x, w["wv"], mode).reshape(t, hkv, hd)
    if spec.qk_norm:
        q = _rms(q, w["q_norm"], spec.norm_eps)
        k = _rms(k, w["k_norm"], spec.norm_eps)
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    o = _attention(q, k, v).reshape(t, hq * hd)
    h = h + _mm(o, w["wo"], mode)
    x = _rms(h, w["ln2"], spec.norm_eps)
    gate = _mm(x, w["ffn_w_gate"], mode)
    up = _mm(x, w["ffn_w_up"], mode)
    return h + _mm(jax.nn.silu(gate) * up, w["ffn_w_down"], mode)


_block_jit = jax.jit(_block, static_argnums=(0, 1))


def _head_logits(spec: Spec, mode: str, hw, h):
    """Final norm and LM head at the rows of ``h`` [n, d] -> [n, V]."""
    x = _rms(h, hw["final_norm"], spec.norm_eps)
    if spec.tied:
        return _mm(x, hw["embed"].astype(F32).T, mode)
    return _mm(x, hw["lm_head"].astype(F32), mode)


@jax.jit
def _served_gap(logits, tokens):
    """Per row: the reference's best logit minus that of ``tokens``."""
    at = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - at


_head_jit = jax.jit(_head_logits, static_argnums=(0, 1))


def layer_by_layer(seqs: Sequence[np.ndarray], modes: Sequence[str],
                   layers: int, embed: Callable, layer_weights: Callable,
                   block: Callable) -> Dict[str, List]:
    """Last-layer hidden states [T_pad, d] of every sequence, per mode.

    Sequences are padded to a multiple of ``BUCKET``; ``embed(tokens)``
    gives their first hidden states.  Each layer's weights,
    ``layer_weights(li)``, are made once, applied by ``block(li, mode,
    w, h)`` to every sequence in every mode, and dropped, so no more
    than one layer's weights are held."""
    padded = []
    for s in seqs:
        n = -(-len(s) // BUCKET) * BUCKET
        buf = np.zeros((n,), np.int32)
        buf[:len(s)] = s
        padded.append(jnp.asarray(buf))
    hs = {m: [embed(p) for p in padded] for m in modes}
    for li in range(layers):
        lw = layer_weights(li)
        for m in modes:
            hs[m] = [block(li, m, lw, h) for h in hs[m]]
        del lw
    return hs


def hidden_states(spec: Spec, seed: int, seqs: Sequence[np.ndarray],
                  modes: Sequence[str] = ("f32",)) -> Dict[str, List]:
    """Last-layer hidden states [T_pad, d] of every sequence, per mode."""
    return layer_by_layer(
        seqs, modes, spec.layers, lambda p: W.embed_rows(spec, seed, p),
        lambda li: W.layer_weights(spec, seed, li),
        lambda li, m, w, h: _block_jit(spec, m, w, h))


def served_gaps(seqs: Sequence[np.ndarray], served_from: Sequence[int],
                hs: Dict[str, List], head: Callable, control: bool):
    """The gaps ``gaps`` returns, from the last hidden states ``hs`` of
    every mode (``"f32"``, and ``"fp8"`` with ``control``) and
    ``head(mode, h)``, which maps hidden-state rows to logits."""
    served, ctrl = [], []
    for i, s in enumerate(seqs):
        pos = np.arange(served_from[i] - 1, len(s) - 1)
        n = len(pos)
        # pad the rows read to a multiple of ROWS so runs share programs
        pos = np.concatenate([pos, np.full(-n % ROWS, pos[-1])])
        rows = jnp.asarray(pos)
        toks = jnp.asarray(np.asarray(s, np.int32)[pos + 1])
        ref = head("f32", hs["f32"][i][rows])
        served.append(np.asarray(_served_gap(ref, toks))[:n])
        if control:
            c = head("fp8", hs["fp8"][i][rows])
            ctrl.append(np.asarray(_served_gap(
                ref, jnp.argmax(c, axis=1).astype(jnp.int32)))[:n])
        del ref
    return (served, ctrl) if control else served


def gaps(spec: Spec, seed: int, seqs: Sequence[np.ndarray],
         served_from: Sequence[int], control: bool = False):
    """Teacher-forced comparison of served tokens.

    ``seqs[i]`` is prompt + served tokens, of which the served ones start
    at ``served_from[i]``; the logits at position ``p - 1`` predict the
    token at ``p``.  Returns, per sequence, the reference's best logit
    minus the served token's (f32 numpy, one per served token); with
    ``control``, also the same gap of the token the fp8 control puts
    first at each of those positions."""
    modes = ("f32", "fp8") if control else ("f32",)
    hs = hidden_states(spec, seed, seqs, modes)
    hw = W.head_weights(spec, seed)
    return served_gaps(seqs, served_from, hs,
                       lambda m, x: _head_jit(spec, m, hw, x), control)
