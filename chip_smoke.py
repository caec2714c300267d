"""Chip smoke run: serve granite-3-8b at its published widths on one TPU.

    python chip_smoke.py

Drives the system's main path through its normal entry points: a
``ServingEngine(backend="hetero", paged_kv=True)`` built from random
weights (from a fixed seed) serves 8 greedy requests with 64-512 token prompts and
16 new tokens each; the colocated engine then serves the same requests.
Widths are the published ones (d_model 4096, 32/8 heads, head_dim 128,
d_ff 12800, vocab 49155, bf16); only the depth is cut, to fit the hetero
engine's weights (held twice: stacked and per layer) plus KV in one
chip's memory.

It fails (non-zero exit) unless every request finishes on both engines,
the hetero engine records no fault, the paged attention dispatch reaches
the Pallas kernel, and every greedy token of either engine is, in a
teacher-forced full-forward reference over its own prompt and tokens,
the argmax or a bf16 near-tie of it.  Where the engines' tokens differ,
the reference top-2 margin there is printed.  Times and memory printed
on the way are
informational, not a benchmark.  The last line of standard output is
``{"ok": true, "device": {...}}`` on success and nothing on failure.

Without a TPU (or outside a checkout of this repository) it exits
non-zero before serving anything.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

BATCH, CACHE_LEN, MAX_NEW = 8, 1024, 16
# depth kept of granite-3-8b's 40 layers, reckoned from bytes: one layer
# is 0.40 GB in bf16 and so are the tied embeddings; the hetero engine
# holds every layer twice (stacked and per-layer slices), so 12 layers
# take 2 * 12 * 0.40 + 0.40 = 10.0 GB, leaving room on a 16 GB v5e chip
# for the KV cache (0.4 GB) and the prefill temporaries (1.6 GB)
LAYERS = 12
SEED = 0              # random weights and prompts
PROMPT_LENS = (64, 128, 192, 256, 320, 384, 448, 512)
# a token counts as a bf16 near-tie of the reference argmax when their
# logits differ by at most this fraction of the argmax's: 2^-5 is 4 to 8
# units in the last place of bf16 (8 significant bits), the order of the
# rounding noise between two bf16 evaluations of the same network
NEAR_TIE_REL = 2.0 ** -5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> int:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def make_requests(cfg):
    import numpy as np
    from repro.serving.request import Request
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    return [Request(rid=i, prompt=p, max_new_tokens=MAX_NEW)
            for i, p in enumerate(prompts)]


def serve(params, cfg, backend: str, **kw):
    """Serve the smoke requests on a fresh engine; returns (tokens by rid,
    metrics, info dict).  The engine is closed and dropped before
    returning so its device buffers can be freed."""
    import jax
    from repro.serving.engine import ServingEngine
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    eng = ServingEngine(params, cfg, batch=BATCH, cache_len=CACHE_LEN,
                        backend=backend, **kw)
    try:
        build_s = time.perf_counter() - t0
        for r in make_requests(cfg):
            eng.submit(r)
        t1 = time.perf_counter()
        eng.step()                  # admission + prefill + first decode
        first_s = time.perf_counter() - t1
        t2 = time.perf_counter()
        eng.run(max_steps=4 * MAX_NEW)
        rest_s = time.perf_counter() - t2
        tokens = {r.rid: list(r.generated) for r in eng.finished}
        metrics = dict(eng.metrics())
        steps = eng.step_idx
        unfinished = len(eng.queue) + sum(s is not None for s in eng.slots)
    finally:
        eng.close()
    del eng
    gc.collect()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)
    info = dict(build_s=build_s, first_step_s=first_s, rest_s=rest_s,
                steps=steps, tokens=sum(map(len, tokens.values())),
                unfinished=unfinished, peak_bytes_in_use=peak)
    log(f"{backend}: " + ", ".join(f"{k}={v}" for k, v in info.items())
        + " (informational, not a benchmark)")
    return tokens, metrics, info


def check_dispatch(cfg) -> bool:
    """use_kernel='auto' on this backend lowers the paged decode attention
    at the served shapes to the Pallas kernel (a Mosaic custom call)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    page, mb_rows = 16, BATCH // 2
    pages = mb_rows * (CACHE_LEN // page)
    sd = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.dtype)
    text = jax.jit(ops.paged_decode_attention).lower(
        sd((mb_rows, cfg.num_heads, cfg.head_dim), dt),
        sd((pages, page, cfg.num_kv_heads, cfg.head_dim), dt),
        sd((pages, page, cfg.num_kv_heads, cfg.head_dim), dt),
        sd((mb_rows, CACHE_LEN // page), jnp.int32),
        sd((mb_rows,), jnp.int32)).compile().as_text()
    return "tpu_custom_call" in text


def compare(params, cfg, ref_toks, het_toks) -> bool:
    """Teacher-forced check of every generated token of both engines.

    One full forward over prompt + an engine's generated tokens gives the
    reference logits at each generated position; a token passes if its
    logit is within the bf16 near-tie bound of the reference argmax
    there.  Where the two engines first differ the reference top-2
    margin is printed as well."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    forward = jax.jit(
        lambda p, t, at: M.train_forward(p, cfg, t)[0][0, at])
    ok = True
    for req in make_requests(cfg):
        n = len(req.prompt)
        # the logits at position n - 1 + i predict generated token i
        at = jnp.arange(n - 1, n - 1 + MAX_NEW, dtype=jnp.int32)
        logits = {}
        for name, toks in (("colocated", ref_toks), ("hetero", het_toks)):
            gen = np.asarray(toks[req.rid], np.int32)
            # right-pad to one length (one compile); the forward is
            # causal, so the padding cannot change the positions read
            padded = np.zeros((1, max(PROMPT_LENS) + MAX_NEW), np.int32)
            padded[0, :n + len(gen)] = np.concatenate([req.prompt, gen])
            lg = np.asarray(forward(params, padded, at), np.float32)
            logits[name] = lg
            if not np.isfinite(lg).all():
                log(f"request {req.rid}: {name} reference logits not finite")
                ok = False
                continue
            best = lg.argmax(axis=-1)
            rows = np.arange(len(gen))
            gap = lg[rows, best] - lg[rows, gen]
            bound = NEAR_TIE_REL * np.abs(lg[rows, best])
            worst = int(np.argmax(gap - bound))
            passed = bool((gap <= bound).all())
            log(f"request {req.rid}: {name} {int((gen == best).sum())}/"
                f"{len(gen)} tokens are the reference argmax; nearest its "
                f"bound: token {worst}, gap to the argmax "
                f"{float(gap[worst]):.6g}, near-tie bound "
                f"{float(bound[worst]):.6g} -> "
                + ("all within bf16 near-ties" if passed
                   else "NOT a near-tie"))
            ok &= passed
        a, b = ref_toks[req.rid], het_toks[req.rid]
        t = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if t is None:
            log(f"request {req.rid}: engines agree on all {len(a)} tokens")
        else:
            top2 = np.sort(logits["hetero"][t])[-2:]
            log(f"request {req.rid}: engines first differ at token {t} "
                f"(colocated {a[t]}, hetero {b[t]}); reference top-2 "
                f"margin there {float(top2[1] - top2[0]):.6g}")
    return ok


def main() -> int:

    if not os.path.isdir(os.path.join(SRC, "repro")):
        return fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (platform {dev.platform!r})")
    log(f"device_kind={dev.device_kind} count={len(devices)}")
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    from repro.core.config import get_arch
    from repro.models import model as M
    cfg = dataclasses.replace(get_arch("granite-3-8b"),
                              num_layers=LAYERS)
    t0 = time.perf_counter()
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    leaves = jax.tree.leaves(params)
    total = sum(x.nbytes for x in leaves)
    layer_bytes = sum(x.nbytes for x in jax.tree.leaves(params["stack"]))
    log(f"granite-3-8b: {cfg.num_layers} of 40 layers, d_model "
        f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}; weights {total} bytes ({layer_bytes} in layers; the "
        f"hetero engine holds the layers twice: {total + layer_bytes} "
        f"bytes); set-up {time.perf_counter() - t0:.3f}s")

    if not check_dispatch(cfg):
        return fail("paged decode attention did not lower to the Pallas "
                    "kernel on this backend")
    log("paged decode attention dispatch: Pallas kernel (tpu_custom_call)")

    het, het_m, het_i = serve(params, cfg, "hetero",
                              paged_kv=True, num_r_workers=2)
    ref, _, ref_i = serve(params, cfg, "colocated")

    problems = []
    if het_m.get("fault_count", -1) != 0 or het_m.get("recovered_count",
                                                      -1) != 0:
        problems.append(f"hetero faults: fault_count="
                        f"{het_m.get('fault_count')} recovered_count="
                        f"{het_m.get('recovered_count')}")
    for name, toks, info in (("hetero", het, het_i),
                             ("colocated", ref, ref_i)):
        done = [len(toks.get(i, ())) == MAX_NEW
                for i in range(len(PROMPT_LENS))]
        if not all(done) or info["unfinished"]:
            problems.append(f"{name}: {sum(done)}/{len(done)} requests "
                            f"finished with {MAX_NEW} tokens")
        bad = [t for ts in toks.values() for t in ts
               if not 0 <= t < cfg.vocab_size]
        if bad:
            problems.append(f"{name}: out-of-vocabulary tokens {bad[:4]}")
    if problems:
        return fail("; ".join(problems))
    if not compare(params, cfg, ref, het):
        return fail("hetero and colocated greedy tokens differ beyond a "
                    "bf16 near-tie")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
