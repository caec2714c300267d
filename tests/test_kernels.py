"""Pallas kernel validation (interpret mode on CPU) against the pure-jnp
oracle, swept over shapes, dtypes, GQA ratios, and masking features —
as required for every kernel in kernels/."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R


def _mk(rng, *shape, d=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), d)


SHAPES = [
    # B, S, Hq, Hkv, D, block_s (cache rows; a block holds a whole number
    # of 128-row tiles, so S > 128 / Hkv tokens spans several blocks)
    (2, 137, 4, 2, 16, 16),
    (3, 300, 8, 8, 32, 128),
    (2, 300, 4, 1, 128, 32),
    (1, 17, 2, 2, 64, 32),
]
FEATS = [dict(), dict(window=20), dict(window=20, sink=3), dict(softcap=8.0)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kw", FEATS)
def test_decode_attention_kernel_vs_oracle(shape, kw, rng):
    B, S, Hq, Hkv, D, bs = shape
    q, k, v = _mk(rng, B, Hq, D), _mk(rng, B, S, Hkv, D), _mk(rng, B, S, Hkv, D)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    pos = pos.at[0, S // 2:].set(-1)
    lengths = jnp.asarray(rng.integers(1, S, B), jnp.int32)
    o1 = ops.decode_attention(q, k, v, pos, lengths, use_kernel="pallas",
                              interpret=True, block_s=bs, **kw)
    o2 = R.decode_attention_ref(q, k, v, pos, lengths, **kw)
    np.testing.assert_allclose(o1, o2, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_dtypes(dtype, rng):
    """Mixed precision: low-precision storage, fp32 accumulation (§5.1)."""
    B, S, Hq, Hkv, D = 2, 100, 8, 4, 64
    q = _mk(rng, B, Hq, D, d=dtype)
    k, v = _mk(rng, B, S, Hkv, D, d=dtype), _mk(rng, B, S, Hkv, D, d=dtype)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    lengths = jnp.asarray([50, 99], jnp.int32)
    o1 = ops.decode_attention(q, k, v, pos, lengths, use_kernel="pallas",
                              interpret=True, block_s=32)
    o2 = R.decode_attention_ref(q, k, v, pos, lengths)
    assert o1.dtype == dtype
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-2)


def test_int8_kernel_vs_oracle(rng):
    B, S, Hq, Hkv, D = 2, 100, 8, 4, 64
    q = _mk(rng, B, Hq, D)
    k, v = _mk(rng, B, S, Hkv, D), _mk(rng, B, S, Hkv, D)
    kq, ks = ops.quantize_kv(k)
    vq, vs = ops.quantize_kv(v)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    lengths = jnp.asarray([50, 99], jnp.int32)
    o1 = ops.decode_attention_int8(q, kq, ks, vq, vs, pos, lengths,
                                   use_kernel="pallas", interpret=True,
                                   block_s=32)
    o2 = R.decode_attention_int8_ref(q, kq, ks, vq, vs, pos, lengths)
    np.testing.assert_allclose(o1, o2, atol=3e-5)


def test_int8_quantization_error_bounded(rng):
    """§5.2: int8-KV attention must stay close to the fp32 result."""
    B, S, Hq, Hkv, D = 2, 64, 4, 4, 32
    q = _mk(rng, B, Hq, D)
    k, v = _mk(rng, B, S, Hkv, D), _mk(rng, B, S, Hkv, D)
    kq, ks = ops.quantize_kv(k)
    vq, vs = ops.quantize_kv(v)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    lengths = jnp.full((B,), S - 1, jnp.int32)
    o_q = R.decode_attention_int8_ref(q, kq, ks, vq, vs, pos, lengths)
    o_f = R.decode_attention_ref(q, k, v, pos, lengths)
    # symmetric per-vector int8: relative error ~1/127
    assert float(jnp.abs(o_q - o_f).max()) < 0.05


def test_quantize_roundtrip(rng):
    x = _mk(rng, 4, 7, 16)
    q, s = ops.quantize_kv(x)
    x2 = ops.dequantize_kv(q, s)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(x2, x, atol=float(jnp.abs(x).max()) / 100)


# ---------------------------------------------------------------------------
# speculative-decode verify kernel: T queries per row in one KV sweep
# ---------------------------------------------------------------------------
def _verify_tables(rng, b, mp, page, lengths, t, num_pages):
    """Contiguous-prefix tables covering lengths[b] + t tokens per row."""
    tables = np.full((b, mp), -1, np.int32)
    perm = list(rng.permutation(num_pages))
    for row in range(b):
        for k in range(-(-(int(lengths[row]) + t) // page)):
            tables[row, k] = perm.pop()
    return jnp.asarray(tables)


@pytest.mark.parametrize("t", [1, 3, 5])
@pytest.mark.parametrize("kw", FEATS)
def test_paged_verify_kernel_vs_oracle(t, kw, rng):
    b, hkv, g, dh, page, mp = 3, 2, 3, 8, 4, 8
    num_pages = b * mp
    lengths = jnp.asarray([0, 7, 13], jnp.int32)
    pk = _mk(rng, num_pages, page, hkv, dh)
    pv = _mk(rng, num_pages, page, hkv, dh)
    q = _mk(rng, b, t, hkv * g, dh)
    tables = _verify_tables(rng, b, mp, page, np.asarray(lengths), t,
                            num_pages)
    o1 = ops.paged_verify_attention(q, pk, pv, tables, lengths,
                                    use_kernel="pallas", interpret=True, **kw)
    o2 = R.paged_verify_attention_ref(q, pk, pv, tables, lengths, **kw)
    np.testing.assert_allclose(o1, o2, atol=3e-5)


def test_paged_verify_kernel_unmapped_row_is_zero(rng):
    b, t, hkv, g, dh, page, mp = 2, 3, 1, 2, 8, 4, 3
    pk = _mk(rng, 6, page, hkv, dh)
    pv = _mk(rng, 6, page, hkv, dh)
    q = _mk(rng, b, t, hkv * g, dh)
    tables = jnp.asarray([[0, 1, -1], [-1, -1, -1]], jnp.int32)
    lengths = jnp.asarray([4, 99], jnp.int32)
    for use in ("ref", "pallas"):
        o = ops.paged_verify_attention(q, pk, pv, tables, lengths,
                                       use_kernel=use, interpret=True)
        assert float(jnp.abs(o[1]).max()) == 0.0
        assert float(jnp.abs(o[0]).max()) > 0.0


# ---------------------------------------------------------------------------
# the paged kernel's walk in multi-page blocks: the interpreter's fresh
# memory is NaN, so a slot that is read without having been copied fails
# ---------------------------------------------------------------------------
def _walk_case(rng, t, ppb, page, mp, num_pages):
    """Rows at the block edges, a fully unmapped row, a short row, and a
    row (a degraded one, whose table stopped growing) whose last needed
    block holds a mapped slot and then an unmapped one inside the
    length."""
    tok = ppb * page
    lengths = np.asarray([tok - 1, tok, tok + 1, 13, 0,
                          2 * tok + page + 1 - t], np.int32)
    tables = np.full((len(lengths), mp), -1, np.int32)
    perm = list(rng.permutation(num_pages))
    for row, n in enumerate(lengths):
        need = -(-(int(n) + t) // page)
        if row == 3:
            need = 0
        elif row == 5:
            assert need == 2 * ppb + 2
            need -= 1
        for k in range(min(need, mp)):
            tables[row, k] = perm.pop()
    return jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("t", [1, 3, 5])
@pytest.mark.parametrize("kw", FEATS)
@pytest.mark.parametrize("ppb", [2, 4])
def test_paged_kernel_multi_page_blocks_vs_oracle(t, kw, ppb, rng):
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import paged_attention as PA
    hkv, g, dh, page = 2, 2, 8, 4
    mp = 3 * ppb + 1                         # MP not a multiple of ppb
    num_pages = 6 * mp
    pk = _mk(rng, num_pages, page, hkv, dh)
    pv = _mk(rng, num_pages, page, hkv, dh)
    tables, lengths = _walk_case(rng, t, ppb, page, mp, num_pages)
    q = _mk(rng, len(lengths), t, hkv * g, dh)
    o = PA.paged_verify_attention(q, pk, pv, tables, lengths,
                                  pages_per_block=ppb,
                                  interpret=pltpu.InterpretParams(), **kw)
    o_ref = R.paged_verify_attention_ref(q, pk, pv, tables, lengths, **kw)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, o_ref, atol=3e-5)
    assert float(jnp.abs(o[3]).max()) == 0.0


@pytest.mark.parametrize("shape,ppb", [
    ((16, 8, 128, 2, 576), 8),       # qwen3-8b decode cell: 256 KiB blocks
    ((16, 32, 128, 2, 64), 2),       # llama-7b, MHA
    ((16, 8, 128, 2, 4), 4),         # a short table caps the block
    ((256, 8, 128, 2, 64), 1),       # a page above the budget: one a step
])
def test_pages_per_block_from_shapes(shape, ppb):
    from repro.kernels import paged_attention as PA
    assert PA.choose_pages_per_block(*shape) == ppb


def test_verify_refs_match_per_position_decode(rng):
    """Row-by-row oracle: position t of the verify output equals a decode
    call with lengths + t, for dense fp and int8 storage."""
    b, s, t, hq, hkv, dh = 2, 24, 3, 4, 2, 16
    q = _mk(rng, b, t, hq, dh)
    k, v = _mk(rng, b, s, hkv, dh), _mk(rng, b, s, hkv, dh)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32)
    lengths = jnp.asarray([9, 17], jnp.int32)
    o = ops.verify_attention(q, k, v, pos, lengths)
    kq, ks = ops.quantize_kv(k)
    vq, vs = ops.quantize_kv(v)
    o8 = ops.verify_attention_int8(q, kq, ks, vq, vs, pos, lengths)
    for j in range(t):
        d = R.decode_attention_ref(q[:, j], k, v, pos, lengths + j)
        np.testing.assert_allclose(o[:, j], d, atol=3e-6)
        d8 = R.decode_attention_int8_ref(q[:, j], kq, ks, vq, vs, pos,
                                         lengths + j)
        np.testing.assert_allclose(o8[:, j], d8, atol=3e-6)


def test_kernel_matches_model_decode_attention(rng, key):
    """kernel == layers.flash_attention == what the model executes."""
    from repro.models import layers as L
    B, S, Hq, Hkv, D = 2, 40, 4, 2, 32
    q, k, v = _mk(rng, B, Hq, D), _mk(rng, B, S, Hkv, D), _mk(rng, B, S, Hkv, D)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    lengths = jnp.asarray([20, 39], jnp.int32)
    o_kernel = ops.decode_attention(q, k, v, pos, lengths,
                                    use_kernel="pallas", interpret=True,
                                    block_s=16)
    o_model = L.flash_attention(q[:, None], k, v, lengths[:, None], pos,
                                causal=True, kv_chunk=64)[:, 0]
    np.testing.assert_allclose(o_kernel, o_model, atol=3e-5)
