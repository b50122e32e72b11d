"""Model FLOPs of every token computed in the traced window (recomputed
prompt tokens and decode tokens; reused image tokens and set-up excluded)
over the traced window's length times the chip's bf16 peak."""
from bench.readers import step_flops


def read(run):
    t = run.trace
    if t is None or run.peak is None or t.window_s <= 0:
        return None
    f = sum(step_flops(run, s) for s in t.steps)
    if f <= 0:
        return None
    return 100.0 * f / (t.window_s * run.peak["flops_bf16"])
