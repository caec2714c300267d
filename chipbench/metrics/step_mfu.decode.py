"""Model step: model flops of every token decoded in the window (2 per
weight of each layer and of the LM head, plus attention at each row's
actual context) per second, over the chip's bf16 peak, in percent."""
import workcount


def read(run):
    if run.window_s <= 0 or not run.steps:
        return None
    flops = sum(workcount.decode_flops_per_token(run.spec, c)
                for contexts in run.steps for c in contexts)
    return 100.0 * flops / run.window_s / run.peaks["bf16_flops_per_s"]
