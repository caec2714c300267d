"""Numeric validation of the explicit shard_map flash-decoding schedule on
a real (host-device) mesh, vs the GSPMD-lowered reference path."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.config import get_arch
from repro.distributed import sharding as SH
from repro.distributed.api import use_rules
from repro.models import model as M

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_arch("granite-3-8b").reduced(layers=2, d_model=64, vocab=128)
params = M.init_params(jax.random.PRNGKey(0), cfg)
B, S = 4, 16
rng = np.random.default_rng(0)
tokens = jnp.asarray(rng.integers(0, 128, (B, S)))
plens = jnp.full((B,), S, jnp.int32)
_, state = M.prefill(params, cfg, tokens, plens, cache_len=S + 4,
                     q_chunk=8, kv_chunk=8)
tok = jnp.asarray(rng.integers(0, 128, (B, 1)))

outs = {}
for strat in ("fastdecode", "fastdecode_sm"):
    rules = SH.make_rules(strat, "decode")
    def fn(params, state, tokens):
        with use_rules(mesh, rules):
            return M.decode_step(params, cfg, state, tokens)
    logits, _ = jax.jit(fn)(params, state, tok)
    outs[strat] = np.asarray(logits)
err = np.abs(outs["fastdecode"] - outs["fastdecode_sm"]).max()
print("MAXERR", err)
assert err < 2e-4, err
print("COLLECTIVES_EQUIV_OK")
"""


@pytest.mark.slow
def test_explicit_schedule_matches_gspmd():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert "COLLECTIVES_EQUIV_OK" in p.stdout, p.stdout + p.stderr
