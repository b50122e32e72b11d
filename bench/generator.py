"""The one traffic generator: a traffic file's parameters -> a schedule.

A mix is data: an arrival process (Poisson, or gamma with a coefficient of
variation for bursts) at a fixed mean rate, and one or more request
classes, each a shared system prompt, images drawn from a Zipf-popular
library, a question or whole prompt and an output length, with weights.

Sizes (prompt and output lengths, image popularity ranks, classes) and the
arrival times come from the traffic file's own ``sizes_seed``, so every
run seed serves the same set of sizes on the same arrival times: a
replayed trace, and the same work for every seed.  The run seed draws
which size set arrives at which time, which library image holds which
popularity rank, and every token and embedding value.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request of the schedule, before it becomes a ``Request``."""
    index: int
    due_s: float               # arrival, seconds from the traffic's start
    system_len: int
    images: List[int]          # library image indices, in prompt order
    text_len: int              # question (or whole prompt) tokens
    max_new: int
    tokens: np.ndarray         # int32 ids: system then question

    def prompt_len(self, image_tokens: int) -> int:
        return self.system_len + len(self.images) * image_tokens + self.text_len


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(w) for w in words]))


def _draw_len(rng, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "uniform":
        return rng.integers(d["lo"], d["hi"] + 1, size=n)
    if d["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(d["median"]), d["sigma"], size=n))
        return np.clip(np.rint(x), d["lo"], d["hi"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {d['dist']!r}")


def zipf_probs(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** s
    return p / p.sum()


CLASS_KEYS = ("system_tokens", "images_per_request", "question_tokens",
              "prompt_tokens", "output_tokens")


def classes(traffic: dict) -> List[dict]:
    """The request classes of a mix: each entry of ``classes`` (with its
    ``weight``) over the file's top-level keys, or the top level alone."""
    base = {k: traffic[k] for k in CLASS_KEYS if k in traffic}
    return [dict(base, **c) for c in traffic.get("classes", [])] or [base]


def _text_dist(c: dict) -> dict:
    return c["prompt_tokens"] if "prompt_tokens" in c else c["question_tokens"]


def max_lengths(traffic: dict, image_tokens: int):
    """(longest prompt, longest output) any run of this mix can send."""
    prompt = max(c["system_tokens"] + c["images_per_request"] * image_tokens
                 + _text_dist(c)["hi"] for c in classes(traffic))
    return prompt, max(c["output_tokens"]["hi"] for c in classes(traffic))


def _gaps(arr: dict, rng, n: int) -> np.ndarray:
    """Inter-arrival gaps: Poisson, or gamma with the coefficient of
    variation ``cv`` (bursts for cv > 1), at the mean rate ``rate_per_s``."""
    rate = float(arr["rate_per_s"])
    if arr["process"] == "poisson":
        return rng.exponential(1.0 / rate, size=n)
    if arr["process"] == "gamma":
        shape = 1.0 / float(arr["cv"]) ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), size=n)
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def _sizes(c: dict, traffic: dict, rng, n: int):
    """(text lengths, output lengths, popularity ranks) of n requests of
    class ``c``."""
    text = _draw_len(rng, _text_dist(c), n)
    out = _draw_len(rng, c["output_tokens"], n)
    n_img = c["images_per_request"]
    ranks = np.zeros((n, n_img), np.int64)
    if n_img:
        p = zipf_probs(traffic["library_images"], traffic["zipf_exponent"])
        for i in range(n):
            ranks[i] = rng.choice(len(p), size=n_img, replace=False, p=p)
    return text, out, ranks


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    """Requests due from ``-lead_s`` to ``seconds + max_wait_s`` (window
    starts at 0), in due order."""
    arr = traffic["arrivals"]
    rate, lead = float(arr["rate_per_s"]), float(arr.get("lead_s", 0.0))
    span = lead + seconds + float(traffic["max_wait_s"])
    n = int(math.ceil(rate * span)) + 1
    sizes = _rng(traffic["sizes_seed"], int(seconds * 1000))
    gaps = _gaps(arr, sizes, n)
    cls = classes(traffic)
    drawn = [_sizes(c, traffic, sizes, n) for c in cls]
    if len(cls) > 1:
        w = np.asarray([float(c["weight"]) for c in cls])
        which = sizes.choice(len(cls), size=n, p=w / w.sum())
    else:
        which = np.zeros(n, np.int64)

    run = _rng(seed, 1)
    order = run.permutation(n)          # which size set arrives when
    n_lib = traffic.get("library_images", 0)
    rank_to_image = (run.permutation(n_lib) if n_lib
                     else np.zeros(0, np.int64))
    due = np.cumsum(gaps) - gaps[0] - lead
    specs = []
    sys_tokens = run.integers(0, vocab, size=max(c["system_tokens"]
                                                 for c in cls))
    for i in range(n):
        j = order[i]
        c = int(which[j])
        text, out, ranks = drawn[c]
        sys_len = int(cls[c]["system_tokens"])
        q = run.integers(0, vocab, size=int(text[j]))
        specs.append(Spec(
            index=i, due_s=float(due[i]), system_len=sys_len,
            images=[int(rank_to_image[r]) for r in ranks[j]],
            text_len=int(text[j]), max_new=int(out[j]),
            tokens=np.concatenate([sys_tokens[:sys_len], q]).astype(np.int32)))
    return specs


def upload_order(traffic: dict, seed: int) -> np.ndarray:
    """The order library images are uploaded in (drawn from the run seed)."""
    return _rng(seed, 2).permutation(traffic["library_images"])
