"""Hypothesis-driven property tests over the attention stack — the
system's central invariant chain:  Pallas kernel == chunked flash == naive
softmax attention, under random shapes, GQA ratios, masks and windows."""
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st

from repro.kernels import ops
from repro.kernels import ref as KR
from repro.models import layers as L


@settings(max_examples=25, deadline=None)
@given(
    b=st.integers(1, 3),
    sq=st.integers(1, 20),
    sk=st.integers(1, 40),
    hkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    dh=st.sampled_from([4, 8, 16]),
    window=st.sampled_from([0, 3, 7]),
    seed=st.integers(0, 2 ** 16),
)
def test_flash_equals_naive_random(b, sq, sk, hkv, g, dh, window, seed):
    rng = np.random.default_rng(seed)
    hq = hkv * g
    q = jnp.asarray(rng.standard_normal((b, sq, hq, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, hkv, dh)), jnp.float32)
    off = int(rng.integers(0, 5))
    qpos = jnp.broadcast_to(jnp.arange(off, off + sq), (b, sq))
    kpos = jnp.broadcast_to(jnp.arange(sk), (b, sk))
    # random invalid slots
    mask = rng.random((b, sk)) < 0.15
    kpos = jnp.where(jnp.asarray(mask), -1, kpos)
    o1 = L.flash_attention(q, k, v, qpos, kpos, causal=True, window=window,
                           q_chunk=int(rng.integers(1, sq + 1)),
                           kv_chunk=int(rng.integers(1, sk + 1)))
    o2 = L.naive_attention(q, k, v, qpos, kpos, causal=True, window=window)
    np.testing.assert_allclose(o1, o2, atol=5e-5)


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 3),
    # up to 300 tokens: a block holds at least 128 cache rows, so longer
    # caches are needed to span several blocks
    s=st.integers(2, 300),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 4]),
    dh=st.sampled_from([8, 16]),
    block_s=st.sampled_from([8, 16, 64]),
    seed=st.integers(0, 2 ** 16),
)
def test_pallas_kernel_equals_oracle_random(b, s, hkv, g, dh, block_s, seed):
    rng = np.random.default_rng(seed)
    hq = hkv * g
    q = jnp.asarray(rng.standard_normal((b, hq, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32)
    lengths = jnp.asarray(rng.integers(0, s, b), jnp.int32)
    o1 = ops.decode_attention(q, k, v, pos, lengths, use_kernel="pallas",
                              interpret=True, block_s=block_s)
    o2 = KR.decode_attention_ref(q, k, v, pos, lengths)
    np.testing.assert_allclose(o1, o2, atol=5e-5)


@settings(max_examples=15, deadline=None)
@given(s=st.integers(1, 50), w=st.integers(1, 12), seed=st.integers(0, 999))
def test_window_never_attends_outside(s, w, seed):
    """Property: with window w (no sinks), output equals attention over
    ONLY the last w valid positions."""
    rng = np.random.default_rng(seed)
    b, hkv, dh = 1, 1, 8
    q = jnp.asarray(rng.standard_normal((b, 1, hkv, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    qp = jnp.asarray([[s - 1]])
    kp = jnp.broadcast_to(jnp.arange(s), (b, s))
    o_win = L.naive_attention(q, k, v, qp, kp, causal=True, window=w)
    lo = max(0, s - w)
    o_trunc = L.naive_attention(q, k[:, lo:], v[:, lo:], qp, kp[:, lo:],
                                causal=True, window=0)
    np.testing.assert_allclose(o_win, o_trunc, atol=3e-5)
