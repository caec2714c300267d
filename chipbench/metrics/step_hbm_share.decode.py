"""Model step: the bytes a decode step must read (every weight once, and
each row's cached K and V in every layer) per second over the window,
over the chip's HBM bandwidth, in percent."""
import workcount


def read(run):
    if run.window_s <= 0 or not run.steps:
        return None
    w = workcount.weight_bytes(run.spec)
    total = 0
    for contexts in run.steps:
        if not contexts:
            continue
        _, kv = workcount.paged_attn_decode(run.spec, contexts)
        total += w + run.spec.layers * kv
    return 100.0 * total / run.window_s / run.peaks["hbm_bytes_per_s"]
