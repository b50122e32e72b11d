"""Paged selective prefill attention kernel's share of its roofline: per
layer call, max(flops / peak, bytes / HBM bandwidth) from the recomputed
positions and prompt length, over the kernel's traced device time."""
from bench.readers import PREFILL_PROGRAM, kernel_roofline, prefill_is_mpic

def read(run):
    if run.trace is None:
        return None
    conf = run.conf
    recs = [r for s in run.trace.steps for r in s.prefills]
    if not recs or not all(prefill_is_mpic(r) for r in recs):
        return None
    calls = [run.arch.selective_attn_call(conf, r.selected, r.total)
             for r in recs for _ in range(conf["num_hidden_layers"])]
    return kernel_roofline(run, PREFILL_PROGRAM, calls)
