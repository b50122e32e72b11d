"""Paper Fig. 10 — sensitivity to the number of images.

Claims: MPIC's TTFT stays below prefix caching at every image count and
its quality does NOT degrade as images accumulate (unlike full reuse).
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


def main(n_images_list=None, n_samples=None):
    n_images_list = n_images_list or scaled((1, 2, 3, 4, 6), (1, 2))
    n_samples = n_samples or scaled(2, 1)
    import jax
    cfg, model, params = build_bench_model()
    rows = []
    with tempfile.TemporaryDirectory() as td:
        ps = make_prefix_store(model, params)
        for n in n_images_list:
            # every image count brings fresh shapes; drop stale compiled
            # programs so the CPU JIT dylib pool doesn't exhaust
            jax.clear_caches()
            dialogues = make_dialogues(
                n=n_samples, n_images=n, d_model=cfg.d_model,
                media_len=MEDIA_LEN, style="mmdu", seed=300 + n)
            lib = populate_library(model, params, dialogues, MEDIA_LEN,
                                   td + f"/{n}")
            for policy, kw in (("prefix_caching", {}), ("mpic", {"k": 8}),
                               ("full_reuse", {})):
                r = evaluate(policy, model, params, dialogues, lib,
                             prefix_store=ps, **kw)
                r["n_images"] = n
                rows.append(r)
    emit(rows, "fig10")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
