"""TTFT on a varying-length mpic-k stream: paged+bucketed vs dense prefill.

The seed prefill path builds a throwaway dense ``(L, kv_len, H, D)``
blended cache per request, runs an *unjitted* selective prefill whose
shapes differ per prompt, then scatters the result into the page pool and
discards the dense copy.  The paged prefill path links reused segments
straight into the request's reserved pages and runs ONE shape-bucketed,
donated jit — so a stream of mixed-length prompts hits a warm compile
cache and performs zero host round-trips between link and first token.

Measured on the REAL engine: submit a stream of mpic-k requests whose
prompt lengths vary inside one shape bucket, admit them one at a time
(decode disabled by ``max_new_tokens=1``), and time each admission's TTFT.
The first pass over each (selection, page) bucket pair is warm-up (jit
compile); steady-state is the claim.  Emits ``BENCH_prefill.json`` and
asserts the paged+bucketed steady-state TTFT beats the seed dense path by
>= 1.3x (full runs; smoke only checks both paths still work).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import build_bench_model, emit, scaled, smoke
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.serving import EngineConfig, MPICEngine, Request

MAX_SEQ_LEN = scaled(1024, 256)
MEDIA_LEN = scaled(48, 16)
# text-run lengths cycle so consecutive prompts differ but stay in one
# selection bucket (sel = text + first-k media tokens)
TEXT_LENS = scaled((24, 31, 27, 36, 22, 33), (10, 14, 12, 15, 9, 13))
WARMUP_REQS = scaled(6, 3)
TIMED_REQS = scaled(24, 6)
MPIC_K = 8
OUT_PATH = os.environ.get(
    "MPIC_BENCH_OUT_PREFILL",
    "BENCH_prefill.smoke.json" if smoke() else "BENCH_prefill.json")


def _prompt(cfg, i):
    r = np.random.default_rng(i)
    t = TEXT_LENS[i % len(TEXT_LENS)]
    return Prompt([
        text_segment(r.integers(8, 200, t)),
        media_segment("A", image_embeds("A", MEDIA_LEN, cfg.d_model)),
        text_segment(r.integers(8, 200, t // 2)),
    ], user_id="u1")


def drive(cfg, model, params, *, paged_prefill: bool) -> dict:
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=MAX_SEQ_LEN, decode_slots=2,
                                  paged=True, paged_prefill=paged_prefill))
    eng.upload("u1", "A", image_embeds("A", MEDIA_LEN, cfg.d_model))
    ttfts = []
    for i in range(WARMUP_REQS + TIMED_REQS):
        req = eng.submit(Request(prompt=_prompt(cfg, i), max_new_tokens=1,
                                 policy="mpic",
                                 policy_kwargs={"k": MPIC_K}))
        t0 = time.perf_counter()
        while not req.done:
            eng.step()
        ttfts.append(time.perf_counter() - t0)
    steady = ttfts[WARMUP_REQS:]
    row = {
        "label": "paged_bucketed" if paged_prefill else "dense_seed_path",
        "ttft_ms": round(float(np.mean(steady)) * 1e3, 3),
        "p90_ttft_ms": round(float(np.percentile(steady, 90)) * 1e3, 3),
        "warmup_ttft_ms": round(float(np.mean(ttfts[:WARMUP_REQS])) * 1e3, 3),
        "requests": TIMED_REQS,
        "distinct_prompt_lens": len(set(TEXT_LENS)),
        "mpic_k": MPIC_K,
    }
    if paged_prefill:
        row["prefill_traces"] = eng.prefill_trace_count
    return row


def main():
    cfg, model, params = build_bench_model()
    rows = [drive(cfg, model, params, paged_prefill=False),
            drive(cfg, model, params, paged_prefill=True)]
    dense, paged = rows
    paged["speedup_vs_dense"] = round(
        dense["ttft_ms"] / max(paged["ttft_ms"], 1e-9), 2)
    # compile-cache proof: all same-bucket prompt lengths share one trace
    # (a second trace can appear only if the media+text mix crosses a
    # selection-bucket boundary — the stream above is sized not to)
    assert paged["prefill_traces"] <= 2, \
        f"bucketed prefill retraced {paged['prefill_traces']}x"
    if not smoke():
        assert paged["ttft_ms"] * 1.3 <= dense["ttft_ms"], \
            "paged+bucketed prefill must be >=1.3x faster than the dense path"
    with open(OUT_PATH, "w") as f:
        json.dump({"bench": "prefill_paged", "rows": rows}, f, indent=2)
    print(f"[fig_prefill_paged] wrote {OUT_PATH}")
    emit(rows, "prefill_paged")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
