"""Median admission wall time spent waiting on library loads
(``Request.load_blocked_s``), over requests due in the window."""
from bench.stats import pct


def read(run):
    v = pct([r.req.load_blocked_s * 1e3 for r in run.window
             if r.req.t_first_token > 0 and r.spec.images], 50)
    return v
