"""Prompt tokens the prefill recomputed (``prefill_stats['n_recomputed']``)
over all prompt tokens, for requests due in the window: a count that shows
whether the traffic used reuse."""


def read(run):
    done = [r for r in run.window if r.req.prefill_stats]
    tot = sum(r.total for r in done)
    if tot == 0:
        return None
    return 100.0 * sum(r.req.prefill_stats["n_recomputed"] for r in done) / tot
