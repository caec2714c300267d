"""Seeded random weights for a configuration, made on the device.

The benchmark makes the weights, not the program: ``program_params``
builds the whole tree the serving engine takes, in one jitted call, in
the types it serves (bf16 matrices, f32 norm gains); ``layer_weights``,
``embedding`` and ``lm_head`` rebuild single pieces of the same tree for
the reference, leaf for leaf equal, so the reference never reads an
array the program held.

Every leaf is ``normal(fold_in(key, ...)) * scale``: layer ``l``'s leaf
``j`` uses ``fold_in(fold_in(root, l), j)``, so a leaf does not depend
on how many leaves or layers are made with it (``vmap`` over the layer
index gives the same values as one layer at a time).  Norm gains are
stored as the program stores them, the offset ``s`` of ``x * (1 + s)``;
the reference applies the published form ``x * g`` with ``g = 1 + s``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from spec import Spec

F32 = jnp.float32
# stable leaf ids: a leaf's key is fold_in(layer key, index in this tuple)
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "ffn_w_gate", "ffn_w_up", "ffn_w_down")
GAIN_SCALE = 0.1
_EMBED, _FINAL_NORM, _LM_HEAD = 1 << 20, (1 << 20) + 1, (1 << 20) + 2


def root_key(seed: int):
    """A key from a seed of up to 64 bits (seeds may exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed & 0xFFFFFFFF)


def layer_shapes(spec: Spec):
    """name -> (shape, scale, dtype) of one layer's leaves."""
    d, hd, f = spec.d_model, spec.head_dim, spec.d_ff
    bf = jnp.dtype(spec.dtype)
    out_scale = 0.02 / math.sqrt(2.0 * spec.layers)
    shapes = {
        "ln1": ((d,), GAIN_SCALE, F32),
        "wq": ((d, spec.heads * hd), 0.02, bf),
        "wk": ((d, spec.kv_heads * hd), 0.02, bf),
        "wv": ((d, spec.kv_heads * hd), 0.02, bf),
        "wo": ((spec.heads * hd, d), out_scale, bf),
        "ln2": ((d,), GAIN_SCALE, F32),
        "ffn_w_gate": ((d, f), 0.02, bf),
        "ffn_w_up": ((d, f), 0.02, bf),
        "ffn_w_down": ((f, d), out_scale, bf),
    }
    if spec.qk_norm:
        shapes["q_norm"] = ((hd,), GAIN_SCALE, F32)
        shapes["k_norm"] = ((hd,), GAIN_SCALE, F32)
    return shapes


def _leaf(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _layer(spec: Spec, root, li):
    k = jax.random.fold_in(root, li)
    shapes = layer_shapes(spec)
    return {name: _leaf(jax.random.fold_in(k, j), *shapes[name])
            for j, name in enumerate(LAYER_LEAVES) if name in shapes}


def _embedding(spec: Spec, root):
    return _leaf(jax.random.fold_in(root, _EMBED),
                 (spec.vocab, spec.d_model), 0.02, jnp.dtype(spec.dtype))


def _final_norm(spec: Spec, root):
    return _leaf(jax.random.fold_in(root, _FINAL_NORM), (spec.d_model,),
                 GAIN_SCALE, F32)


def _lm_head(spec: Spec, root):
    return _leaf(jax.random.fold_in(root, _LM_HEAD),
                 (spec.d_model, spec.vocab), 0.02, jnp.dtype(spec.dtype))


@partial(jax.jit, static_argnums=0)
def _program_params(spec: Spec, root):
    stack = jax.vmap(lambda li: _layer(spec, root, li))(
        jnp.arange(spec.layers, dtype=jnp.uint32))
    params = {"embed": _embedding(spec, root),
              "final_norm": _final_norm(spec, root),
              "stack": {"s0": stack}, "rem": []}
    if not spec.tied:
        params["lm_head"] = _lm_head(spec, root)
    return params


def program_params(spec: Spec, seed: int):
    """The serving engine's parameter tree (stacked layers, one pattern
    slot of full attention), made on the default device in one call."""
    return _program_params(spec, root_key(seed))


@partial(jax.jit, static_argnums=0)
def _layer_f32(spec: Spec, root, li):
    w = _layer(spec, root, li)
    return {k: v.astype(F32) for k, v in w.items()}


def layer_weights(spec: Spec, seed: int, li: int):
    """Layer ``li``'s leaves as served (bf16 / f32 gains), upcast to f32."""
    return _layer_f32(spec, root_key(seed), jnp.uint32(li))


@partial(jax.jit, static_argnums=0)
def _head_f32(spec: Spec, root):
    out = {"final_norm": _final_norm(spec, root)}
    if spec.tied:
        out["embed"] = _embedding(spec, root)       # bf16, [V, d]
    else:
        out["lm_head"] = _lm_head(spec, root)       # bf16, [d, V]
    return out


def head_weights(spec: Spec, seed: int):
    return _head_f32(spec, root_key(seed))


@partial(jax.jit, static_argnums=0)
def _embed_rows(spec: Spec, root, tokens):
    return _embedding(spec, root)[tokens].astype(F32)


def embed_rows(spec: Spec, seed: int, tokens):
    """Embedding rows of ``tokens`` (any int array), upcast to f32."""
    return _embed_rows(spec, root_key(seed), tokens)
