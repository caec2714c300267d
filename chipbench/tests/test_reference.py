"""The float32 reference against the program's own full forward pass, at
a tiny size on the CPU, and the seeded weights leaf for leaf; the
weights, the program's configuration and the gaps are reached through
the dense family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
import reference as R
import weights as W
from tiny import SPECS

SEED = 2**31 + 77
DENSE = family.load("dense")


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_reference_matches_program_forward(arch):
    from repro.models import model as M
    spec = SPECS[arch]
    params = DENSE.program_params(spec, SEED)
    toks = np.random.default_rng(0).integers(0, spec.vocab, 700)
    toks = toks.astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lg, _ = M.train_forward(params, DENSE.model_config(spec),
                                jnp.asarray(toks[None]))
    hs = R.hidden_states(spec, SEED, [toks])["f32"][0][:len(toks)]
    ref = R._head_logits(spec, "f32", W.head_weights(spec, SEED), hs)
    err = float(jnp.max(jnp.abs(lg[0] - ref)))
    assert err < 1e-5 * float(jnp.max(jnp.abs(ref))), err


@pytest.mark.parametrize("arch", sorted(SPECS))
def test_layer_weights_equal_program_stack(arch):
    spec = SPECS[arch]
    stack = DENSE.program_params(spec, SEED)["stack"]["s0"]
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
        g = DENSE.gaps(spec, SEED, [seq], [len(prompt)])[0]
        assert (g[0] == 0.0) == zero


def test_seeds_beyond_32_bits_differ():
    spec = SPECS["granite"]
    a = W.layer_weights(spec, 5, 0)["wq"]
    b = W.layer_weights(spec, 5 + 2**32, 0)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
