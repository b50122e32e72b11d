"""Paged decode attention kernel's share of its roofline: per layer call,
max(flops / peak, bytes / HBM bandwidth) from the live slots' lengths,
summed over the traced decode steps, over the kernel's traced device time."""
from bench.readers import DECODE_PROGRAM, kernel_roofline

def read(run):
    if run.trace is None:
        return None
    conf = run.conf
    calls = [run.arch.paged_attn_call(conf, s.decode_ctx)
             for s in run.trace.steps if s.decode_ctx
             for _ in range(conf["num_hidden_layers"])]
    return kernel_roofline(run, DECODE_PROGRAM, calls)
