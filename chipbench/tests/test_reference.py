"""The float32 reference against the program's own full forward pass, at
a tiny size on the CPU, and the seeded weights leaf for leaf."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as R
import weights as W
from tiny import SPECS

SEED = 2**31 + 77


def _program_cfg(spec):
    from repro.core.config import get_arch
    return dataclasses.replace(
        get_arch(spec.program_arch), num_layers=spec.layers,
        d_model=spec.d_model, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=spec.d_ff,
        vocab_size=spec.vocab, norm_eps=spec.norm_eps,
        rope_theta=spec.rope_theta, tie_embeddings=spec.tied,
        qk_norm=spec.qk_norm, dtype=spec.dtype)


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_reference_matches_program_forward(arch):
    from repro.models import model as M
    spec = SPECS[arch]
    params = W.program_params(spec, SEED)
    toks = np.random.default_rng(0).integers(0, spec.vocab, 700)
    toks = toks.astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lg, _ = M.train_forward(params, _program_cfg(spec),
                                jnp.asarray(toks[None]))
    hs = R.hidden_states(spec, SEED, [toks])["f32"][0][:len(toks)]
    ref = R._head_logits(spec, "f32", W.head_weights(spec, SEED), hs)
    err = float(jnp.max(jnp.abs(lg[0] - ref)))
    assert err < 1e-5 * float(jnp.max(jnp.abs(ref))), err


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_layer_weights_equal_program_stack(arch):
    spec = SPECS[arch]
    stack = W.program_params(spec, SEED)["stack"]["s0"]
    for li in range(spec.layers):
        lw = W.layer_weights(spec, SEED, li)
        for k, v in lw.items():
            np.testing.assert_array_equal(
                np.asarray(stack[k][li], np.float32), np.asarray(v))


def test_gaps_zero_on_reference_tokens_and_positive_elsewhere():
    spec = SPECS["qwen"]
    prompt = np.arange(1, 40, dtype=np.int32)
    hs = R.hidden_states(spec, SEED, [prompt])["f32"][0]
    lg = R._head_logits(spec, "f32", W.head_weights(spec, SEED), hs)
    best = int(jnp.argmax(lg[len(prompt) - 1]))
    worst = int(jnp.argmin(lg[len(prompt) - 1]))
    for tok, zero in ((best, True), (worst, False)):
        seq = np.concatenate([prompt, [tok]]).astype(np.int32)
        g = R.gaps(spec, SEED, [seq], [len(prompt)])[0]
        assert (g[0] == 0.0) == zero


def test_seeds_beyond_32_bits_differ():
    spec = SPECS["granite"]
    a = W.layer_weights(spec, 5, 0)["wq"]
    b = W.layer_weights(spec, 5 + 2**32, 0)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
