"""Median admission-to-first-token time (``t_first_token - t_admitted``,
host stamps around the synchronous link and selective prefill), over
requests whose first token came in the window.  One reader for both
``prefill_ms_p50.latency`` (below the knee) and ``prefill_ms_p50.sat``
(above it)."""
from bench.readers import prefill_ms
from bench.stats import pct


def read(run):
    return pct(prefill_ms(run), 50)
