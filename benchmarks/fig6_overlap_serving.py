"""Paper Fig. 6, end to end on the REAL serving engine.

``fig6_parallel_transfer.py`` demonstrates load/compute overlap analytically
(``plan_transfers``) and for a bare ParallelLoader.  This benchmark drives
the full ``MPICEngine`` admission path instead: a stream of mixed hit/miss
requests (two library-hit media + one never-uploaded media that must be
recomputed) served twice —

  * **sequential** (``pipelined=False``): the seed engine's admission — each
    request's media fetched in parallel across loader workers, gathered to
    completion *before* its policy compute starts — per request
    ``T ≈ load_wall + compute``;
  * **pipelined**  (``pipelined=True``): the scheduler issues the next
    requests' fetches while the current request's recompute runs and the
    linker gathers per media id at link time — ``T ≈ max(load, compute)``.

Media loads carry simulated paper-scale latency (≈1 GB over the Fig. 6 disk
bandwidth ⇒ ~0.3 s per entry, ``SimulatedLatencyLibrary``) while compute is
the real CPU prefill, so the reported overlap is measured wall-clock, not a
model.  The acceptance check: pipelined per-request prefill wall-time is
strictly below that request's measured load + compute sum, and ``report()``
exposes the measured overlap ratio.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import build_bench_model, emit, scaled, smoke
from repro.cache import SimulatedLatencyLibrary, TIER_HBM
from repro.cache.library import TIER_BW, TIER_DISK
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.serving import EngineConfig, MPICEngine, Request

MEDIA_LEN = scaled(24, 12)
N_REQUESTS = scaled(4, 2)
# one paper-scale image KV (~1 GB) over the Fig. 6 disk bandwidth
LOAD_DELAY_S = scaled(float((1 << 30) / TIER_BW[TIER_DISK]), 0.05)


def _prompt(cfg, i):
    r = np.random.default_rng(i)
    return Prompt([
        text_segment(r.integers(8, 200, 8)),
        media_segment("A", image_embeds("A", MEDIA_LEN, cfg.d_model)),
        text_segment(r.integers(8, 200, 6)),
        media_segment("B", image_embeds("B", MEDIA_LEN, cfg.d_model)),
        # never uploaded → Fig. 6 "m misses": recomputed by the policy
        media_segment(f"MISS{i}",
                      image_embeds(f"MISS{i}", MEDIA_LEN, cfg.d_model)),
    ], user_id="u1")


def drive(cfg, model, params, *, pipelined: bool):
    lib = SimulatedLatencyLibrary(
        tier_latency_s={TIER_HBM: LOAD_DELAY_S, TIER_DISK: LOAD_DELAY_S})
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=256, decode_slots=2,
                                  prefetch_depth=3, pipelined=pipelined),
                     static_library=lib)
    for mid in ("A", "B"):
        eng.upload("u1", mid, image_embeds(mid, MEDIA_LEN, cfg.d_model))

    # jit/trace warm-up so walls measure steady-state serving
    eng.submit(Request(prompt=_prompt(cfg, 999), max_new_tokens=1,
                       policy="mpic", policy_kwargs={"k": 8}))
    eng.run()
    eng.finished.clear()

    reqs = [eng.submit(Request(prompt=_prompt(cfg, i), max_new_tokens=4,
                               policy="mpic", policy_kwargs={"k": 8}))
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    rep = eng.report()
    sched = rep["scheduler"]

    load_s = sum(r.load_s for r in reqs)
    compute_s = sum(r.compute_s for r in reqs)
    prefill_wall = sum(r.prefill_wall_s for r in reqs)
    return {
        "label": "pipelined" if pipelined else "sequential",
        "ttft_ms": rep["mean_ttft_s"] * 1e3,
        "wall_ms": round(wall * 1e3, 1),
        "prefill_wall_ms": round(prefill_wall * 1e3, 1),
        "load_ms": round(load_s * 1e3, 1),
        "compute_ms": round(compute_s * 1e3, 1),
        "seq_estimate_ms": round((load_s + compute_s) * 1e3, 1),
        "overlap_ratio": round(sched["mean_load_overlap_ratio"], 3),
        "overlap_below_sequential": bool(
            all(r.prefill_wall_s < r.load_s + r.compute_s
                for r in reqs[1:])) if pipelined else None,
    }


def main():
    cfg, model, params = build_bench_model()
    rows = [drive(cfg, model, params, pipelined=False),
            drive(cfg, model, params, pipelined=True)]
    seq, par = rows
    par["speedup"] = round(seq["wall_ms"] / max(par["wall_ms"], 1e-9), 2)
    # the Fig. 6 claim on the real engine: overlap pushes admission toward
    # max(load, compute) — strictly below the sequential sum.  At smoke
    # scale (50 ms loads) the margin is runner noise, so only check that
    # both modes ran.
    if not smoke():
        assert par["prefill_wall_ms"] < par["seq_estimate_ms"], \
            "pipelined prefill wall must beat sequential load+compute"
    emit(rows, "fig6_serving")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
