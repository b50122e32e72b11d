"""Host spans of the serving engine, written into the JAX profiler's trace.

Each phase of :meth:`repro.serving.MPICEngine.step` runs under a
``jax.profiler.TraceAnnotation`` named ``mpic.<phase>``, on the thread that
calls ``step()``.  The profiler buffers the spans in memory and writes them
beside the device's operations, on the same clock, when the trace stops; so
any capture (``jax.profiler.trace(dir)``, TensorBoard) shows what the host
was doing while the device was idle.  Nothing is recorded while no profiler
runs: a span then costs about half a microsecond, and its arguments are
never formatted.

Spans (nesting by indentation)::

    mpic.step                  one engine step (the profiler's step marker)
      mpic.admit  req=<id>     one admitted request, link through first token
        mpic.link.gather       library entries of the prompt's media
        mpic.link.pack         each segment's page, offset and delta arrays
        mpic.link.upload       each entry's K/V and those arrays to the device
        mpic.link.write        the dispatch of one pool scatter per segment
        mpic.prefill.pack      selected-token inputs, buckets, page table
        mpic.prefill.dispatch  the selective prefill's dispatch
        mpic.prefill.fetch     first-token logits back to the host
        mpic.sample            the first token
      mpic.decode              one decode step of every running slot
        mpic.decode.operands   host-built operands, page growth
        mpic.decode.dispatch   the decode program's dispatch
        mpic.decode.fetch      logits back to the host
        mpic.decode.sample     next tokens, finished requests
"""
from __future__ import annotations

import jax

PREFIX = "mpic."
STEP = PREFIX + "step"


# ``span(name, **args)``: a host span ``name`` (one of the names above)
# with ``args`` as its stats, which the profiler formats only while it
# records.  The class itself, not a wrapper: a wrapper call would cost
# another 0.2 us per span.
span = jax.profiler.TraceAnnotation


def step_span(step_num: int) -> jax.profiler.StepTraceAnnotation:
    """The step marker around one engine step, numbered ``step_num``."""
    return jax.profiler.StepTraceAnnotation(STEP, step_num=step_num)
