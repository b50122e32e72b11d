"""Paper Fig. 3 — prefix caching vs full reuse as #images grows.

Claims: (a) prefix-caching TTFT grows ~quadratically with image count,
full-reuse TTFT grows slowly; (b) full-reuse quality collapses as images
multiply; (c) at 1 image full reuse can be SLOWER (two-step overhead).
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

MEDIA_LEN = scaled(96, 24)


def main(n_images_list=None, n_samples=None):
    n_images_list = n_images_list or scaled((1, 2, 4, 6), (1, 2))
    n_samples = n_samples or scaled(3, 1)
    cfg, model, params = build_bench_model()
    rows = []
    with tempfile.TemporaryDirectory() as td:
        for n in n_images_list:
            dialogues = make_dialogues(
                n=n_samples, n_images=n, d_model=cfg.d_model,
                media_len=MEDIA_LEN, style="mmdu", seed=100 + n)
            lib = populate_library(model, params, dialogues, MEDIA_LEN, td)
            ps = make_prefix_store(model, params)
            for policy, kw in (("prefix_caching", {}), ("full_reuse", {})):
                r = evaluate(policy, model, params, dialogues, lib,
                             prefix_store=ps, **kw)
                r["n_images"] = n
                rows.append(r)
    emit(rows, "fig3")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
