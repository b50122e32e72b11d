"""99th percentile of how late the harness submitted a request after its
due time: a starved generator shows here, not as a fast server."""
from bench.stats import pct


def read(run):
    v = pct([r.submitted - r.due for r in run.window], 99)
    return None if v is None else v * 1e3
