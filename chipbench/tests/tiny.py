"""Tiny configurations and mixes for the CPU tests of the harness."""
from spec import Spec

SPECS = {
    "qwen": Spec(name="tiny-qwen3", layers=2, d_model=64, heads=4,
                 kv_heads=2, head_dim=16, d_ff=128, vocab=97,
                 rope_theta=1e6, norm_eps=1e-6, tied=False, qk_norm=True,
                 dtype="float32", program_arch="qwen3-8b"),
    "granite": Spec(name="tiny-granite", layers=2, d_model=64, heads=4,
                    kv_heads=2, head_dim=16, d_ff=128, vocab=97,
                    rope_theta=1e4, norm_eps=1e-5, tied=True, qk_norm=False,
                    dtype="float32", program_arch="granite-3-8b"),
}

DECODE = {
    "kind": "closed_batch", "rows": 4,
    "context_tokens": {"min": 24, "max": 40}, "max_new_tokens": 200,
    "warm_decode_steps": 1,
    "engine": {"batch": 4, "num_microbatches": 2, "num_r_workers": 2,
               "prefill_chunk": 8, "page_size": 4, "cache_len": 240},
    "check": {"requests": 2, "min_tokens": 4, "max_gap": 0.01},
}
