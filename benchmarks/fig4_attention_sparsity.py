"""Paper Fig. 4 / Insights 1–2 — attention sparsity & leading-token mass.

Collects the attention scores between image tokens and the first output
token (per layer), then reports (a) the fraction of image tokens with
score > 1e-3 (sparsity) and (b) the share of attention mass on the first
25% of image tokens (attention sink).  Random-weight models show weak
sinks; if a trained checkpoint exists (examples/train_tiny.py) it is used
— noted in EXPERIMENTS.md.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import build_bench_model, emit
from repro.data import make_dialogues
from repro.models.layers import attention_qkv, rmsnorm


def attention_to_last_token(model, params, prompt):
    """Per-layer attention probs of the last prompt position over all
    positions (unrolled layers; smoke scale)."""
    cfg = model.cfg
    toks = jnp.asarray(prompt.flat_tokens()[None])
    mask = jnp.asarray(prompt.media_mask()[None])
    emb = jnp.asarray(prompt.flat_media_embeds(cfg.d_model)[None])
    x = model.embed(params, toks, emb, mask)
    s = toks.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    probs_all = []
    from repro.models.layers import attend, attention_out, swiglu
    for layer in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
        h = rmsnorm(lp["attn_norm"], x, cfg.rms_norm_eps)
        q, k, v = attention_qkv(lp["attn"], cfg, h, pos)
        # probs of last position
        import math
        from repro.models.layers import repeat_kv
        kk = repeat_kv(k, cfg.num_heads // cfg.num_kv_heads)
        logits = jnp.einsum("bhd,bkhd->bhk",
                            q[:, -1].astype(jnp.float32),
                            kk.astype(jnp.float32)) / math.sqrt(cfg.head_dim)
        p = jax.nn.softmax(logits, axis=-1).mean(axis=1)[0]   # (S,)
        probs_all.append(np.asarray(p))
        o = attend(q, k, v, pos, pos, window=cfg.sliding_window)
        x = x + attention_out(lp["attn"], o)
        h = rmsnorm(lp["mlp_norm"], x, cfg.rms_norm_eps)
        x = x + swiglu(lp["mlp"], h)
    return probs_all


def main():
    cfg, model, params = build_bench_model()
    ckpt = "results/tiny_trained.msgpack"
    trained = False
    if os.path.exists(ckpt):
        from repro.training import load_checkpoint
        params = load_checkpoint(ckpt)["params"]
        trained = True

    d = make_dialogues(n=1, n_images=2, d_model=cfg.d_model, media_len=32,
                       style="mmdu", seed=5)[0]
    media = d.prompt.media_mask()
    probs = attention_to_last_token(model, params, d.prompt)

    rows = []
    for layer in (0, cfg.num_layers - 1):
        p = probs[layer][media]
        p = p / max(p.sum(), 1e-9)
        # Insight 1 (sparsity), scale-free: mass captured by the top-5% of
        # image tokens (uniform attention would capture exactly 0.05); the
        # paper's absolute 1e-3 cut assumes 1176-token images
        top_n = max(1, int(0.05 * p.size))
        top5_mass = float(np.sort(p)[::-1][:top_n].sum())
        order_mass = []
        for off, seg in d.prompt.media_segments():
            seg_p = probs[layer][off:off + seg.length]
            seg_p = seg_p / max(seg_p.sum(), 1e-9)
            lead = int(0.25 * seg.length)
            order_mass.append(float(seg_p[:lead].sum()))
        rows.append({"label": f"layer{layer}", "ttft_ms": 0.0,
                     "trained": trained,
                     "top5pct_mass": round(top5_mass, 3),
                     "top5pct_uniform": 0.05,
                     "lead25pct_mass": round(float(np.mean(order_mass)), 3),
                     "lead25pct_uniform": 0.25})
    emit(rows, "fig4")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
