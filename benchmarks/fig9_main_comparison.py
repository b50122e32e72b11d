"""Paper Fig. 9 — the headline table: TTFT + quality for all four CC
algorithms on 2 model variants × 2 datasets (MMDU-like, Sparkles-like).

Claims validated: MPIC-k dominates CacheBlend on both axes, beats
full-reuse quality at similar TTFT (single- vs two-step), and cuts TTFT
substantially vs prefix caching on multi-image prompts.
"""
from __future__ import annotations

import tempfile

from benchmarks.common import (
    build_bench_model,
    emit,
    evaluate,
    make_prefix_store,
    populate_library,
    scaled,
)
from repro.data import make_dialogues

MEDIA_LEN = scaled(64, 16)
N_IMAGES = scaled(3, 2)
N_SAMPLES = scaled(3, 1)
MODELS = scaled((("llava-vicuna", 0), ("llava-mistral", 1)),
                (("llava-vicuna", 0),))
STYLES = scaled(("mmdu", "sparkles"), ("mmdu",))


def main():
    rows = []
    with tempfile.TemporaryDirectory() as td:
        # two model variants stand in for vicuna-7B / mistral-7B backbones
        for model_name, seed in MODELS:
            cfg, model, params = build_bench_model(seed=seed)
            for style in STYLES:
                dialogues = make_dialogues(
                    n=N_SAMPLES, n_images=N_IMAGES, d_model=cfg.d_model,
                    media_len=MEDIA_LEN, style=style, seed=7)
                lib = populate_library(model, params, dialogues, MEDIA_LEN,
                                       td + f"/{model_name}-{style}")
                ps = make_prefix_store(model, params)
                for policy, kw in (
                        ("prefix_caching", {}),
                        ("full_reuse", {}),
                        ("cacheblend", {"r": 0.15}),
                        ("mpic", {"k": 8})):
                    r = evaluate(policy, model, params, dialogues, lib,
                                 prefix_store=ps, **kw)
                    r["model"] = model_name
                    r["dataset"] = style
                    rows.append(r)
    emit(rows, "fig9")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
