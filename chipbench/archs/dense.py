"""The dense family: pre-norm decoder blocks of grouped-query attention
(RoPE; RMSNorm of q and k where the model has it) and a SwiGLU
feed-forward, as ``qwen3-8b``.  A configuration that names no
``harness_family`` is dense.

Its sizes are ``spec.Spec``, its weights ``weights.py``, its float32
reference and fp8 control ``reference.py``, its engine
``system.build_engine``.  Other families import the pieces they share
from those modules: ``weights.root_key`` and ``weights._leaf``;
``reference._rms``, ``_mm`` and ``_q8`` (the fp8 control),
``_attention``, ``layer_by_layer`` (the reference's loop, one layer's
weights at a time) and ``served_gaps``.
"""
from __future__ import annotations

import dataclasses

import reference
import weights
from spec import Spec, from_dict

spec = from_dict
program_params = weights.program_params
gaps = reference.gaps


def model_config(sizes: Spec):
    """The program's ModelConfig: the registered architecture with every
    size taken from the configuration file."""
    from repro.core.config import get_arch
    return dataclasses.replace(
        get_arch(sizes.program_arch), num_layers=sizes.layers,
        d_model=sizes.d_model, num_heads=sizes.heads,
        num_kv_heads=sizes.kv_heads, head_dim=sizes.head_dim,
        d_ff=sizes.d_ff, vocab_size=sizes.vocab,
        rope_theta=sizes.rope_theta, norm_eps=sizes.norm_eps,
        tie_embeddings=sizes.tied, qk_norm=sizes.qk_norm,
        dtype=sizes.dtype)
