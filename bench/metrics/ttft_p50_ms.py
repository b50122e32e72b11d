"""Median time from a request's due time to its first token, over every
request due in the window (host clock)."""
from bench.readers import ttfts_s
from bench.stats import pct


def read(run):
    v = pct(ttfts_s(run), 50)
    return None if v is None else v * 1e3
