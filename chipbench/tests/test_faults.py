"""A whole run at a tiny size on the CPU, with the harness's look for a
chip skipped: sound, it reads correct; with the served path broken
underneath in each way a serving cell can break, it reads not correct.
So does the control: the reference in fp8, whose first tokens take the
served tokens' place in the check."""
import json
import os

import jax.numpy as jnp
import pytest

import run
from tiny import DECODE, SPECS

SEED = 2**31 + 11
MIX = dict(DECODE, engine=dict(DECODE["engine"], num_r_workers=1),
           check=dict(DECODE["check"], requests=4))


def _run(control=False):
    bench = run.load_benchmark()
    cell = "qwen3-8b.longctx-decode"
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    metrics = [m for m in bench["end_to_end"]
               if cell in m.get("workloads", [cell])]
    return run.execute(SPECS["qwen"], MIX, SEED, 1.0, False, metrics, peaks,
                       control=control, say=lambda m: None, cache=False)


def test_sound_run_is_correct():
    result, checks, _ = _run()
    assert result["correct"], checks
    assert checks["tokens_compared"]["value"] >= MIX["check"]["min_tokens"]
    assert set(result["metrics"]) == {"itl_p95_ms", "out_tok_per_s",
                                      "setup_s"}


def test_control_is_not_correct():
    result, checks, info = _run(control=True)
    assert not result["correct"], checks
    assert checks["max_logit_gap"]["value"] > MIX["check"]["max_gap"]
    # the same run's served tokens meet the limit
    assert info["program_max_logit_gap"] <= MIX["check"]["max_gap"]


def _altered_token(orig):
    def sample(logits, rng, *a, **kw):
        return (orig(logits, rng, *a, **kw) + 1) % logits.shape[-1]
    return sample


def _kv_append_dropped(orig):
    def write(pool, *a, **kw):
        return pool
    return write


def _half_batch_left_out(orig):
    def attend(r_in, pool, tables, **kw):
        out, pool = orig(r_in, pool, tables, **kw)
        o = out["o"]
        half = o.shape[0] // 2
        return {"o": o.at[half:].set(jnp.zeros_like(o[half:]))}, pool
    return attend


FAULTS = {
    "token_altered": ("repro.serving.engine", "sample", _altered_token),
    "state_unchanged": ("repro.serving.paged_cache", "write_token_paged",
                        _kv_append_dropped),
    "half_batch_left_out": ("repro.serving.paged_cache",
                            "r_attention_paged_tables",
                            _half_batch_left_out),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_served_path_is_not_correct(fault, monkeypatch):
    import importlib
    mod_name, attr, wrap = FAULTS[fault]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    result, checks, _ = _run()
    assert not result["correct"], checks
    assert checks["max_logit_gap"]["value"] > MIX["check"]["max_gap"]
