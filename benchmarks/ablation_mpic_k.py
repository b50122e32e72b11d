"""Ablation — MPIC-k sweep (the paper's MPIC-8/16/32/... variants).

The k knob trades recompute cost for quality: k=0 is full reuse, k=length
is prefix-caching-grade quality on media tokens.  The paper reports MPIC-32
as the sweet spot at 576-token images; at our 48-token smoke images the
same *shape* should appear scaled down: quality (KL) improves monotonically
with k while recompute grows linearly.
"""
from __future__ import annotations

import tempfile


from benchmarks.common import (
    build_bench_model,
    emit,
    evaluate,
    populate_library,
    scaled,
)
from repro.data import make_dialogues

MEDIA_LEN = scaled(48, 16)


def main(ks=None, n_samples=None):
    ks = ks or scaled((0, 4, 8, 16, 32, 48), (0, 8, MEDIA_LEN))
    n_samples = n_samples or scaled(3, 1)
    cfg, model, params = build_bench_model()
    dialogues = make_dialogues(n=n_samples, n_images=2, d_model=cfg.d_model,
                               media_len=MEDIA_LEN, style="mmdu", seed=11)
    rows = []
    with tempfile.TemporaryDirectory() as td:
        lib = populate_library(model, params, dialogues, MEDIA_LEN, td)
        for k in ks:
            name = "full_reuse" if k == 0 else "mpic"
            kw = {} if k == 0 else {"k": k}
            r = evaluate(name, model, params, dialogues, lib, **kw)
            r["k"] = k
            rows.append(r)
    # monotonicity check (allow small noise): quality at k=max beats k=0
    assert rows[-1]["kl"] <= rows[0]["kl"] + 1e-6, \
        f"quality did not improve with k: {rows[0]['kl']} -> {rows[-1]['kl']}"
    emit(rows, "ablation_mpic_k")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
