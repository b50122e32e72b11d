"""Shared arithmetic of the metric readers in ``bench/metrics``."""
from __future__ import annotations

from typing import List, Optional

from bench import flops, trace as tr

DECODE_PROGRAM = "paged_decode"       # jit of MPICEngine._paged_decode_fn
PREFILL_PROGRAM = "jit__step_fn"      # jit of PagedPrefiller._step_fn


def ttfts_s(run) -> List[float]:
    return [r.ttft for r in run.window if r.ttft is not None]


def itl_gaps_s(run) -> List[float]:
    """Every gap between consecutive output tokens whose later token was
    emitted inside the window, over all requests."""
    out = []
    for r in run.recs:
        st = r.stamps
        out += [b - a for a, b in zip(st, st[1:]) if run.in_window(b)]
    return out


def tokens_in_window(run) -> int:
    return sum(1 for r in run.recs for t in r.stamps if run.in_window(t))


def prefill_ms(run) -> List[float]:
    """Admission to first token of every request whose first token came
    in the window (above the knee, requests due in the window are admitted
    only after it)."""
    return [(r.req.t_first_token - r.req.t_admitted) * 1e3
            for r in run.recs if r.stamps and run.in_window(r.stamps[0])]


def prefill_is_mpic(rec) -> bool:
    """The program recomputed exactly the positions the mpic rule names
    (no library miss forced a whole image to be recomputed)."""
    return rec.req.prefill_stats.get("n_recomputed") == len(rec.selected)


def step_flops(run, step) -> float:
    """Model operations of every token a step computed: decode tokens with
    the LM head, recomputed prefill tokens, and one head row per prefill
    (counted by the cell's architecture file)."""
    w, conf = run.arch, run.conf
    f = sum(w.token_flops(conf, c, head=True) for c in step.decode_ctx)
    for rec in step.prefills:
        f += sum(w.token_flops(conf, p + 1, head=False)
                 for p in rec.selected)
        f += 2 * w.head_params(conf)
    return f


def kernel_roofline(run, program: str, calls) -> Optional[float]:
    """Percent of the roofline: summed least times of ``calls`` (one
    (flops, bytes) per layer call) over the traced device time of the
    Pallas kernel calls inside ``program``."""
    t = run.trace
    if t is None or run.peak is None:
        return None
    secs, n = tr.kernel_time_s(t.ops, t.mods, program)
    ideal = sum(flops.roofline_s(f, b, run.peak) for f, b in calls)
    if n == 0 or secs <= 0 or ideal <= 0:
        return None
    return 100.0 * ideal / secs
