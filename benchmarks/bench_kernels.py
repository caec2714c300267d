"""Kernel-level bench: the R-Part attention reference path's achieved
memory bandwidth on this host (the quantity the paper's CPU R-worker is
bound by), the int8 traffic reduction (§5.2), and the Pallas kernels'
interpret-mode validation timing (correctness gate; real perf is on TPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timeit
from repro.kernels import ops, ref


def run(print_fn=print):
    out = {}
    B, S, Hq, Hkv, D = 8, 2048, 8, 8, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    lengths = jnp.full((B,), S - 1, jnp.int32)

    fn = jax.jit(lambda: ref.decode_attention_ref(q, k, v, pos, lengths))
    t = timeit(fn, warmup=1, iters=3)
    bytes_moved = B * S * 2 * Hkv * D * 4
    print_fn(csv_row("rpart_ref_fp32", t * 1e6,
                     f"{bytes_moved/t/1e9:.1f}GB/s_achieved"))
    out["fp32_bw"] = bytes_moved / t

    kq, ks = ops.quantize_kv(k)
    vq, vs = ops.quantize_kv(v)
    fn8 = jax.jit(lambda: ref.decode_attention_int8_ref(
        q, kq, ks, vq, vs, pos, lengths))
    t8 = timeit(fn8, warmup=1, iters=3)
    bytes8 = B * S * 2 * Hkv * (D * 1 + 4)
    print_fn(csv_row("rpart_ref_int8", t8 * 1e6,
                     f"traffic={bytes8/bytes_moved:.2f}x_of_fp32"
                     f" (paper §5.2: ~0.25x -> ~4x fewer CPUs)"))

    # pallas interpret-mode correctness timing (not a perf number on CPU)
    tk = timeit(lambda: ops.decode_attention(
        q[:2], k[:2, :256], v[:2, :256], pos[:2, :256],
        jnp.full((2,), 255, jnp.int32), use_kernel="pallas", interpret=True,
        block_s=128),
        warmup=1, iters=2)
    err = float(jnp.abs(
        ops.decode_attention(q[:2], k[:2, :256], v[:2, :256], pos[:2, :256],
                             jnp.full((2,), 255, jnp.int32),
                             use_kernel="pallas", interpret=True, block_s=128)
        - ref.decode_attention_ref(q[:2], k[:2, :256], v[:2, :256],
                                   pos[:2, :256],
                                   jnp.full((2,), 255, jnp.int32))).max())
    print_fn(csv_row("pallas_interpret_validation", tk * 1e6,
                     f"max_err={err:.1e}"))
    out["kernel_err"] = err
    return out


if __name__ == "__main__":
    run()
