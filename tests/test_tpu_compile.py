"""Ahead-of-time compile guard for the Pallas kernels, compiled by the TPU
compiler for a described v5e chip — no chip needed.  Interpret mode
cannot see the TPU's tiling rules or its VMEM limit; this does.  Each
test asserts the kernel really lowered to a Mosaic custom call and that
its VMEM use, as the compiler reports it, stays within a fixed budget.

Widths: granite-3-8b (32 query / 8 kv heads) and llama-7b (32 / 32, MHA),
head_dim 128, bf16, page 16.  The MHA width guards against blocks whose
VMEM use grows with the number of kv heads.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file."""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, DH, PAGE, S = 8, 128, 16, 1024
MP = S // PAGE
POOL = B * MP
BF16, I32 = jnp.bfloat16, jnp.int32
WIDTHS = {"granite-3-8b": (32, 8), "llama-7b": (32, 32)}
# VMEM the compiler may give one kernel; the blocks are sized to a fixed
# number of cache rows, so this holds at any head count
VMEM_BUDGET = 4 << 20
VMEM_SPACE = "1"          # memory_space id of VMEM in the TPU backend config


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one — keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def kernel_vmem_bytes(text: str) -> int:
    """VMEM the compiler allotted the Mosaic kernel(s) in ``text``, read
    from each tpu_custom_call's ``used_scoped_memory_configs``."""
    total, calls = 0, 0
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'"used_scoped_memory_configs":(\[[^\]]*\])', line)
        assert m, "no scoped-memory report on the kernel call"
        calls += 1
        total += sum(int(c["size"]) for c in json.loads(m.group(1))
                     if c["memory_space"] == VMEM_SPACE)
    assert calls, "no tpu_custom_call in the compiled program"
    return total


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    vmem = kernel_vmem_bytes(compiled.as_text())
    assert 0 < vmem <= VMEM_BUDGET, vmem
    return compiled


@pytest.mark.parametrize("width", WIDTHS)
def test_paged_decode_compiles_for_v5e(one_chip, width):
    hq, hkv = WIDTHS[width]
    c = _compile(
        one_chip,
        lambda q, k, v, t, n: ops.paged_decode_attention(
            q, k, v, t, n, use_kernel="pallas"),
        ((B, hq, DH), BF16), ((POOL, PAGE, hkv, DH), BF16),
        ((POOL, PAGE, hkv, DH), BF16), ((B, MP), I32), ((B,), I32))
    # the all-heads page view is a free reshape: no pool-sized temporary
    assert c.memory_analysis().temp_size_in_bytes < POOL * PAGE * hkv * DH


@pytest.mark.parametrize("width", WIDTHS)
def test_paged_verify_compiles_for_v5e(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(
        one_chip,
        lambda q, k, v, t, n: ops.paged_verify_attention(
            q, k, v, t, n, use_kernel="pallas"),
        ((B, 5, hq, DH), BF16), ((POOL, PAGE, hkv, DH), BF16),
        ((POOL, PAGE, hkv, DH), BF16), ((B, MP), I32), ((B,), I32))


@pytest.mark.parametrize("width", WIDTHS)
def test_int8_decode_compiles_for_v5e(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(
        one_chip,
        lambda q, kq, ks, vq, vs, p, n: ops.decode_attention_int8(
            q, kq, ks, vq, vs, p, n, use_kernel="pallas"),
        ((B, hq, DH), BF16), ((B, S, hkv, DH), jnp.int8),
        ((B, S, hkv), jnp.float32), ((B, S, hkv, DH), jnp.int8),
        ((B, S, hkv), jnp.float32), ((B, S), I32), ((B,), I32))


@pytest.mark.parametrize("width", WIDTHS)
def test_paged_int8_decode_compiles_for_v5e(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(
        one_chip,
        lambda q, kq, ks, vq, vs, t, n: ops.paged_decode_attention_int8(
            q, kq, ks, vq, vs, t, n, use_kernel="pallas"),
        ((B, hq, DH), BF16), ((POOL, PAGE, hkv, DH), jnp.int8),
        ((POOL, PAGE, hkv), jnp.float32), ((POOL, PAGE, hkv, DH), jnp.int8),
        ((POOL, PAGE, hkv), jnp.float32), ((B, MP), I32), ((B,), I32))


@pytest.mark.parametrize("width", WIDTHS)
def test_dense_decode_compiles_for_v5e(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(
        one_chip,
        lambda q, k, v, p, n: ops.decode_attention(
            q, k, v, p, n, use_kernel="pallas"),
        ((B, hq, DH), BF16), ((B, S, hkv, DH), BF16),
        ((B, S, hkv, DH), BF16), ((B, S), I32), ((B,), I32))


def test_paged_decode_compiles_for_v5e_at_the_decode_cell(one_chip):
    """The decode cell's R-Part call: 2 rows of 576 page slots (cache
    9,216 tokens) over a pool of 1,152 pages, qwen3-8b widths (32/8
    heads): the multi-page blocks and their double buffers fit."""
    rows, mp = 2, 9216 // PAGE
    pool = ((rows * mp, PAGE, 8, DH), BF16)
    _compile(
        one_chip,
        lambda q, k, v, t, n: ops.paged_decode_attention(
            q, k, v, t, n, use_kernel="pallas"),
        ((rows, 32, DH), BF16), pool, pool, ((rows, mp), I32),
        ((rows,), I32))
