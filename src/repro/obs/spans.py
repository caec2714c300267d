"""Named host spans of the serving hot path, on the profiler's clock.

Every boundary of the decode hot path is a ``jax.profiler``
annotation with one of the fixed names below.  Under
``jax.profiler.trace(dir, create_perfetto_trace=True)`` (or
``start_trace``/``stop_trace``) they land in the same profile as the
device ops, on one clock, so a device idle gap can be laid against the
host work that was running; with no profile being taken an annotation
costs about a microsecond.

Engine thread (``ServingEngine.step`` and the hetero ``decode_step``):

- ``repro.step`` — one serving step (a step annotation, ``step_num``);
- ``repro.step.admit`` — admission and queuing of prefill chunks;
- ``repro.step.sample`` — sampling the step's logits (its host sync);
- ``repro.step.emit`` — appending tokens, finishing rows;
- ``repro.step.prefill_results`` — chunks whose prompt completed;
- ``repro.pipe.step`` — ``HeteroPipelineEngine.decode_step``;
- ``repro.pipe.start`` — layer-0 S callables (decode and chunk);
- ``repro.pipe.r_wait`` — waiting on the completion queue
  (``hotpath_stats`` ``r_wait_s``);
- ``repro.pipe.gather`` — assembling a layer's R results
  (``collect_s``);
- ``repro.pipe.advance`` — the fused S callable of a layer transition
  (with ``repro.pipe.start`` of decode rows: ``s_dispatch_s``);
- ``repro.pipe.dispatch`` — enqueuing R work (``dispatch_s``), args
  ``mb``, ``layer``, ``phase``.

R-worker threads:

- ``repro.r.kernel`` — the R-Part call, args ``mb``, ``layer``,
  ``phase`` (the same as its ``repro.pipe.dispatch`` within a step);
- ``repro.r.grow`` — paged block-table growth (nested in the kernel
  span: the host sync on lengths, allocator growth, CoW clones, the
  table upload);
- ``repro.r.to_host`` — copying ``r_out`` to the host (waits for the
  kernel);
- ``repro.r.post`` — delivering the result to the completion sink.

Any thread: ``repro.gc`` — a pause of the garbage collector, from a
``gc.callbacks`` hook registered once per process on import.
"""
from __future__ import annotations

import gc

from jax.profiler import StepTraceAnnotation, TraceAnnotation

span = TraceAnnotation
step_span = StepTraceAnnotation

STEP = "repro.step"
STEP_ADMIT = "repro.step.admit"
STEP_SAMPLE = "repro.step.sample"
STEP_EMIT = "repro.step.emit"
STEP_PREFILL_RESULTS = "repro.step.prefill_results"
PIPE_STEP = "repro.pipe.step"
PIPE_START = "repro.pipe.start"
PIPE_R_WAIT = "repro.pipe.r_wait"
PIPE_GATHER = "repro.pipe.gather"
PIPE_ADVANCE = "repro.pipe.advance"
PIPE_DISPATCH = "repro.pipe.dispatch"
R_KERNEL = "repro.r.kernel"
R_GROW = "repro.r.grow"
R_TO_HOST = "repro.r.to_host"
R_POST = "repro.r.post"
GC = "repro.gc"

NAMES = (STEP, STEP_ADMIT, STEP_SAMPLE, STEP_EMIT, STEP_PREFILL_RESULTS,
         PIPE_STEP, PIPE_START, PIPE_R_WAIT, PIPE_GATHER, PIPE_ADVANCE,
         PIPE_DISPATCH, R_KERNEL, R_GROW, R_TO_HOST, R_POST, GC)

# the open collector span; collections never nest (the collector does
# not re-enter itself), so one slot serves every thread
_gc_open = []


def _on_gc(phase: str, info) -> None:
    if phase == "start":
        a = TraceAnnotation(GC)
        a.__enter__()
        _gc_open.append(a)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
