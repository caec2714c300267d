"""The kernel roofline's reader finds the program's paged decode kernel
by its name: the kernel is compiled for a described v5e (no chip needed)
inside a jitted step, as the R-workers call it, and a custom call of the
compiled program has to match the reader's test.  A rename in the
program fails here instead of leaving the metric silent."""
import os

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import family
import run

ROWS, PAGE, PAGES_PER_ROW = 4, 16, 16


def test_reader_finds_the_compiled_paged_kernel():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from repro.kernels import ops
    sp = family.load_config("qwen3-8b")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    pool = (ROWS * PAGES_PER_ROW, PAGE, sp.kv_heads, sp.head_dim)
    shapes = [((ROWS, sp.heads, sp.head_dim), jnp.bfloat16),
              (pool, jnp.bfloat16), (pool, jnp.bfloat16),
              ((ROWS, PAGES_PER_ROW), jnp.int32), ((ROWS,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]

    def step(q, k, v, tables, lengths):
        return ops.paged_decode_attention(q, k, v, tables, lengths,
                                          use_kernel="pallas") * 2

    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    reader = run.load_reader("paged_attn_roofline.decode")
    found = [ln for ln in text.splitlines() if reader.is_kernel(ln, "")]
    assert len(found) == 1, found
