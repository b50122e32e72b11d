"""Median time from due time to admission (``Request.t_admitted``, stamped
when the scheduler pops the request)."""
from bench.stats import pct


def read(run):
    v = pct([r.req.t_admitted - r.due for r in run.window
             if r.req.t_admitted > 0], 50)
    return None if v is None else v * 1e3
