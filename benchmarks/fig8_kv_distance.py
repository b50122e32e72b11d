"""Paper Fig. 8 / Insight 3 — KV deviation of reused vs recomputed cache.

The paper computes an image's KV at two prompt positions and ranks tokens
by K distance: leading tokens deviate most.  We report BOTH:
  * ``raw``     — no position compensation (the paper's setting on vLLM);
  * ``relinked`` — after MPIC's exact RoPE relocation (ours), isolating the
    *cross-attention* deviation that selective recompute must repair.
The paper's claim (leading tokens deviate most) should hold in both; the
relinked residual is strictly smaller — the linker removes the position
component exactly.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import build_bench_model, emit
from repro.core import Prompt, media_segment, precompute_media_kv, text_segment
from repro.data import image_embeds
from repro.models.layers import rope_relink

MEDIA_LEN = 32


def main():
    cfg, model, params = build_bench_model()
    rng = np.random.default_rng(0)
    emb = image_embeds("probe", MEDIA_LEN, cfg.d_model)
    question = rng.integers(8, 200, 24)

    # K of the image computed standalone (canonical, what the library holds)
    k0, _ = precompute_media_kv(model, params, jnp.asarray(emb))

    # K of the image computed in-context AFTER the question (offset 24)
    prompt = Prompt([text_segment(question), media_segment("probe", emb)])
    toks = jnp.asarray(prompt.flat_tokens()[None])
    mask = jnp.asarray(prompt.media_mask()[None])
    me = jnp.asarray(prompt.flat_media_embeds(cfg.d_model)[None])
    cache = model.make_cache(1, prompt.total_len + 1)
    _, cache = model.prefill(params, toks, cache, media_embeds=me,
                             media_mask=mask)
    off = prompt.media_segments()[0][0]
    k_ctx = np.asarray(cache["k"][:, 0, off:off + MEDIA_LEN], np.float32)

    # raw distance (no relink) vs relinked distance
    d_raw = np.abs(k_ctx - np.asarray(k0, np.float32)).sum(axis=(0, 2, 3))
    k_rel = np.asarray(rope_relink(
        jnp.asarray(k0), jnp.full((MEDIA_LEN,), off, jnp.int32),
        cfg.rope_theta), np.float32)
    d_rel = np.abs(k_ctx - k_rel).sum(axis=(0, 2, 3))

    lead = MEDIA_LEN // 4
    rows = []
    for label, d in (("raw", d_raw), ("relinked", d_rel)):
        rows.append({
            "label": label, "ttft_ms": 0.0,
            "lead25_mean_dist": round(float(d[:lead].mean()), 4),
            "rest_mean_dist": round(float(d[lead:].mean()), 4),
            "lead_ratio": round(float(d[:lead].mean() /
                                      max(d[lead:].mean(), 1e-9)), 3),
            "total": round(float(d.sum()), 2),
        })
    # invariant: relink strictly reduces total deviation
    assert rows[1]["total"] < rows[0]["total"]
    emit(rows, "fig8")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
