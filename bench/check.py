"""Whether what the timed path served is correct.

After the window, a sample of the finished requests, drawn from the seed
and holding the longest, is run through the plain reference of the
configuration's architecture file (``bench/reference.py`` for the dense
block) over each prompt and its served tokens.  The
number compared is the widest gap by which a served token's logit lies
below the reference's best logit at that step; every token is greedy.
The limit is per cell, in ``bench/limits/<cell>.json``, with the readings
it was set from.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from bench import spec
from bench.reference import Item, served_gaps
from bench.weights import image_embeds


def item_of(rec) -> Item:
    s = rec.spec
    segs = []
    if s.system_len:
        segs.append(("text", s.tokens[:s.system_len]))
    segs += [("image", i) for i in s.images]
    segs.append(("text", s.tokens[s.system_len:]))
    return Item(segments=segs, served=np.asarray(rec.req.output_tokens))


def pick(finished: List, seed: int, target: int, cap: int = 24) -> List:
    """The longest-output and the longest-prompt request, then others drawn
    from the seed until ``target`` served tokens are in the sample."""
    if not finished:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    out_len = [len(r.req.output_tokens) for r in finished]
    first = [int(np.argmax(out_len)),
             int(np.argmax([r.total for r in finished]))]
    order = list(dict.fromkeys(first + list(rng.permutation(len(finished)))))
    chosen, n = [], 0
    for i in order:
        if n >= target or len(chosen) >= cap:
            break
        chosen.append(finished[i])
        n += out_len[i]
    return chosen


def reference_for(cell, params, seed: int, k: int, lowp: bool = False):
    """The ``Reference`` of the cell's architecture file over ``params``."""
    ref = spec.arch(cell)
    conf = cell.config
    n_img, width = conf["assumed"]["image_tokens"], conf["hidden_size"]
    return ref.Reference(params, ref.Arch.from_config(conf),
                         lambda idx: image_embeds(seed, idx, n_img, width),
                         k=k, lowp=lowp)


def verdict(cell, gaps: List[np.ndarray], who: str = "") -> Dict:
    """The compared numbers of one set of gaps beside the cell's limits,
    and whether they hold."""
    lim = cell.limits
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    widest = float(g.max()) if g.size else float("inf")
    gap_lim = float(lim["max_logit_gap"]["limit"])
    min_tok = int(lim["min_tokens_compared"])
    numbers = {
        "max_logit_gap": {"value": widest, "limit": gap_lim},
        "tokens_compared": {"value": int(g.size), "limit": min_tok},
    }
    correct = bool(np.isfinite(widest) and widest <= gap_lim
                   and g.size >= min_tok)
    for name, d in numbers.items():
        rel = "<=" if name == "max_logit_gap" else ">="
        print(f"check {who}{name} {d['value']} {rel} limit {d['limit']}",
              file=sys.stderr, flush=True)
    return {"correct": correct, "numbers": numbers,
            "off_argmax": int((g > 0).sum())}


def compare(cell, params, finished: List, seed: int,
            control: bool = False) -> Dict:
    """The verdict on what the program served; with ``control``, also
    (under ``"control"``) the verdict on the reference computed in float8
    put in the program's place: at each position of the same prompts and
    served tokens, the token that the float8 reference puts first."""
    sample = pick(finished, seed, int(cell.traffic["check"]["served_tokens"]))
    k = int(cell.traffic["mpic_k"])
    ref = reference_for(cell, params, seed, k)
    low = reference_for(cell, params, seed, k, lowp=True) \
        if control else None
    prog, ctrl = [], []
    for r in sample:
        item = item_of(r)
        lg = ref.logits(item)
        prog.append(served_gaps(lg, item.served))
        if low is not None:
            ctrl.append(served_gaps(lg, np.argmax(low.logits(item), -1)))
    out = {}
    if low is not None:
        out["control"] = verdict(cell, ctrl, "control.")
    out.update(verdict(cell, prog), requests=len(sample))
    return out
