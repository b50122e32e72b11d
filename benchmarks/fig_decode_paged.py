"""Steady-state decode throughput: paged+donated vs dense non-donated.

The dense baseline is the seed engine's decode loop: every step runs
``attend`` over the full ``max_seq_len`` KV region per slot and, because the
decode jit is not donated, re-materializes the whole ``(L, B, max_seq_len,
…)`` batch cache.  The paged path decodes through the paged-attention
kernel over a page table bucketed to the *live* maximum length and donates
the pool buffers, so per-step work scales with ``cur_len`` and no
full-cache copy happens.

Measured on the REAL engine: admit ``decode_slots`` requests, let every
prefill finish, then time pure decode steps (all slots advancing one token
per step).  Emits ``BENCH_decode.json`` next to the repo root so the decode
perf trajectory is tracked from this PR onward; asserts the paged
steady-state step is strictly faster than the dense baseline.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import build_bench_model, emit, scaled, smoke
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.serving import EngineConfig, MPICEngine, Request, State

MAX_SEQ_LEN = scaled(2048, 256)
DECODE_SLOTS = 4
MEDIA_LEN = 16
PROMPT_TXT = 8
WARMUP_STEPS = scaled(8, 2)
TIMED_STEPS = scaled(48, 6)
# smoke runs must not overwrite the tracked perf-trajectory artifact with
# CI-runner noise
OUT_PATH = os.environ.get(
    "MPIC_BENCH_OUT",
    "BENCH_decode.smoke.json" if smoke() else "BENCH_decode.json")


def _prompt(cfg, i):
    r = np.random.default_rng(i)
    return Prompt([
        text_segment(r.integers(8, 200, PROMPT_TXT)),
        media_segment("A", image_embeds("A", MEDIA_LEN, cfg.d_model)),
        text_segment(r.integers(8, 200, PROMPT_TXT)),
    ], user_id="u1")


def drive(cfg, model, params, *, paged: bool, pool_dtype: str = "") -> dict:
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=MAX_SEQ_LEN,
                                  decode_slots=DECODE_SLOTS,
                                  max_prefills_per_step=DECODE_SLOTS,
                                  paged=paged, donate_decode=paged,
                                  pool_dtype=pool_dtype))
    eng.upload("u1", "A", image_embeds("A", MEDIA_LEN, cfg.d_model))
    total_new = WARMUP_STEPS + TIMED_STEPS + 4
    for i in range(DECODE_SLOTS):
        eng.submit(Request(prompt=_prompt(cfg, i), max_new_tokens=total_new,
                           policy="mpic", policy_kwargs={"k": 4}))
    # admit everything; a few steps until all slots are decoding
    while any(r is None or r.state is not State.RUNNING
              for r in eng.running):
        eng.step()
    for _ in range(WARMUP_STEPS):           # jit + page-bucket warm-up
        eng.step()
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        eng.step()
    wall = time.perf_counter() - t0
    assert all(r is not None and r.state is State.RUNNING
               for r in eng.running), "steady state lost during timing"
    step_ms = wall / TIMED_STEPS * 1e3
    toks_per_s = DECODE_SLOTS * TIMED_STEPS / wall
    label = "dense_nondonated" if not paged else (
        "paged_int8_donated" if pool_dtype == "int8" else "paged_donated")
    row = {
        "label": label,
        "ttft_ms": 0.0,
        "decode_step_ms": round(step_ms, 3),
        "decode_tokens_per_s": round(toks_per_s, 1),
        "max_seq_len": MAX_SEQ_LEN,
        "decode_slots": DECODE_SLOTS,
        "timed_steps": TIMED_STEPS,
    }
    if paged:
        live_tokens = max(r.cur_len for r in eng.running if r is not None)
        row["live_tokens_per_slot"] = live_tokens
        row["pages_in_use"] = eng.pool.cfg.num_pages - eng.pool.free_pages
        row["pool_dtype"] = pool_dtype or cfg.compute_dtype
    return row


def main():
    cfg, model, params = build_bench_model()
    rows = [drive(cfg, model, params, paged=False),
            drive(cfg, model, params, paged=True),
            drive(cfg, model, params, paged=True, pool_dtype="int8")]
    dense, paged, int8 = rows
    paged["speedup_vs_dense"] = round(
        dense["decode_step_ms"] / max(paged["decode_step_ms"], 1e-9), 2)
    # int8 pool: same prompts, same steps → same page occupancy as the fp
    # pool leg; the dequant-in-kernel step must stay within 10% of it
    assert int8["pages_in_use"] == paged["pages_in_use"], \
        "int8 leg must time at equal page occupancy"
    int8["step_vs_fp_pool"] = round(
        int8["decode_step_ms"] / max(paged["decode_step_ms"], 1e-9), 2)
    # the acceptance claim: lengths-bounded, donated paged decode beats the
    # dense non-donated full-region decode in steady state, and the int8
    # pool's in-kernel dequant costs at most 10% per step on top of it.
    # Smoke mode only checks that all paths still run — 6 steps at seq 256
    # on a shared CI runner is noise, not a measurement.
    if not smoke():
        assert paged["decode_step_ms"] < dense["decode_step_ms"], \
            "paged decode step must be faster than the dense baseline"
        assert int8["step_vs_fp_pool"] <= 1.10, (
            f"int8 dequant-in-kernel decode step is "
            f"{int8['step_vs_fp_pool']}x the fp pool step (budget: 1.10x)")
    with open(OUT_PATH, "w") as f:
        json.dump({"bench": "decode_paged", "rows": rows}, f, indent=2)
    print(f"[fig_decode_paged] wrote {OUT_PATH}")
    emit(rows, "decode_paged")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
