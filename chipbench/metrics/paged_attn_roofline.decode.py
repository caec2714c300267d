"""Kernels: the paged decode attention kernel's share of its roofline.

The least time the chip could take for the window's decode attention
(the larger of its flops over the bf16 peak and its bytes over the HBM
bandwidth, counted by ``workcount.paged_attn_decode`` for every layer of
every step at each row's actual context) over the kernel's device time
in the trace.  The kernel is found by its op name."""
import workcount

# the Pallas paged decode kernel's custom call, as the trace names it:
# "%paged_decode_attention.N = ... custom-call(...), custom_call_target=
# "tpu_custom_call" ..."
KERNEL = "paged_decode_attention"


def is_kernel(name: str, program: str) -> bool:
    return KERNEL in name and "tpu_custom_call" in name


def read(run):
    if run.trace is None or not run.steps:
        return None
    t = run.trace.op_seconds(is_kernel)
    if t <= 0:
        return None
    flops = nbytes = 0
    for contexts in run.steps:
        f, b = workcount.paged_attn_decode(run.spec, contexts)
        flops += run.spec.layers * f
        nbytes += run.spec.layers * b
    bound = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
