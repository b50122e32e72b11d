"""Model FLOPs of the recomputed prompt tokens over the device time of the
selective prefill programs that computed them, times the bf16 peak: the
whole prefill step's share of the chip, beside its attention kernel's."""
from bench import trace as tr
from bench.readers import PREFILL_PROGRAM


def read(run):
    t = run.trace
    if t is None or run.peak is None:
        return None
    w, conf = run.arch, run.conf
    recs = [r for s in t.steps for r in s.prefills]
    secs = sum(tr.module_times_s(t.mods, PREFILL_PROGRAM))
    if not recs or secs <= 0:
        return None
    f = sum(w.token_flops(conf, p + 1, head=False)
            for r in recs for p in r.selected)
    f += len(recs) * 2 * w.head_params(conf)
    return 100.0 * f / (secs * run.peak["flops_bf16"])
