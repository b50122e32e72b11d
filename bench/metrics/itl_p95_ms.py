"""95th percentile of every gap between consecutive output tokens of every
request, for tokens emitted in the window (host clock)."""
from bench.readers import itl_gaps_s
from bench.stats import pct


def read(run):
    v = pct(itl_gaps_s(run), 95)
    return None if v is None else v * 1e3
