"""Unified transformer stack for all assigned architecture families.

Design notes
------------
* **Scan over layers.**  Layer params are stacked on a leading axis and the
  stack runs under `jax.lax.scan`, so HLO size (and compile time for the
  80-layer dry-runs) is O(1) in depth.
* **One attention path.**  Train, plain prefill, MPIC selective prefill and
  decode all use :func:`repro.models.layers.attend`, which masks by
  *original token position*.  The cache carries a ``pos`` array (the
  original position of each slot; INVALID_POS = empty), so
  position-independent blending is native, not a special case.
* **Cache pytree** (``make_cache``):
    k, v       (L, B, S, Hkv, Dh)   attention KV (absent for pure SSM)
    pos        (B, S) int32          original position per slot
    ssm_h      (L, B, nH, ds, hd)    SSD state (ssm / hybrid)
    ssm_conv   (L, B, W-1, di)       conv tail (ssm / hybrid)
    cross_k/v  (L, B, Senc, H, Dh)   whisper cross-attention KV (the
                                     MPIC-cacheable artifact for audio)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.cache.pagequant import quant_scatter
from repro.kernels.paged_attn.ops import paged_attention_call
from repro.kernels.selective_attn.ops import selective_attention_paged_call
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    INVALID_POS,
    _dtype,
    attend,
    banded_attend,
    attention_out,
    attention_qkv,
    dense_init,
    gelu_mlp,
    init_attention,
    init_gelu_mlp,
    init_layernorm,
    init_rmsnorm,
    init_swiglu,
    layernorm,
    rmsnorm,
    swiglu,
)
from repro.launch.pspec import shard


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg) -> dict:
    dt = _dtype(cfg.param_dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    if cfg.arch_type == "ssm":
        return {"norm": init_rmsnorm(d, dt), "ssm": ssm_mod.init_ssm(ks[0], cfg)}
    p = {
        "attn_norm": init_rmsnorm(d, dt),
        "attn": init_attention(ks[0], cfg),
        "mlp_norm": init_rmsnorm(d, dt),
    }
    if cfg.arch_type == "moe":
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    elif cfg.arch_type == "audio":
        p["mlp"] = init_gelu_mlp(ks[1], d, cfg.d_ff, dt)
        p["attn_norm"] = init_layernorm(d, dt)
        p["mlp_norm"] = init_layernorm(d, dt)
        p["cross_norm"] = init_layernorm(d, dt)
        p["cross_attn"] = init_attention(ks[2], cfg)
    else:
        p["mlp"] = init_swiglu(ks[1], d, cfg.d_ff, dt)
    if cfg.hybrid:
        p["ssm"] = ssm_mod.init_ssm(ks[3], cfg)
        p["attn_mix_norm"] = init_rmsnorm(d, dt)
        p["ssm_mix_norm"] = init_rmsnorm(d, dt)
    return p


def _init_encoder_layer(key, cfg) -> dict:
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    return {
        "attn_norm": init_layernorm(cfg.d_model, dt),
        "attn": init_attention(ks[0], cfg),
        "mlp_norm": init_layernorm(cfg.d_model, dt),
        "mlp": init_gelu_mlp(ks[1], cfg.d_model, cfg.d_ff, dt),
    }


def init_params(key, cfg) -> dict:
    dt = _dtype(cfg.param_dtype)
    k_emb, k_layers, k_head, k_enc, k_pos = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params = {
        "embed": dense_init(k_emb, (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys),
        "final_norm": (init_layernorm(cfg.d_model, dt) if cfg.arch_type == "audio"
                       else init_rmsnorm(cfg.d_model, dt)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt,
                                       scale=0.02)
    if cfg.learned_pos_emb:
        params["pos_embed"] = dense_init(
            k_pos, (cfg.max_position_embeddings, cfg.d_model), dt, scale=0.02)
    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers + 1)
        params["enc_layers"] = jax.vmap(
            lambda k: _init_encoder_layer(k, cfg))(enc_keys[:-1])
        params["enc_norm"] = init_layernorm(cfg.d_model, dt)
        params["enc_pos_embed"] = dense_init(
            enc_keys[-1], (cfg.encoder_seq, cfg.d_model), dt, scale=0.02)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def make_cache(cfg, batch: int, kv_len: int, dtype=None) -> dict:
    """Empty cache (all slots invalid) for serve prefill/decode."""
    dt = dtype or _dtype(cfg.compute_dtype)
    L = cfg.num_layers
    cache: dict = {}
    if not cfg.attn_free:
        cache["k"] = jnp.zeros((L, batch, kv_len, cfg.num_kv_heads, cfg.head_dim), dt)
        cache["v"] = jnp.zeros((L, batch, kv_len, cfg.num_kv_heads, cfg.head_dim), dt)
        cache["pos"] = jnp.full((batch, kv_len), INVALID_POS, jnp.int32)
    if cfg.arch_type in ("ssm", "hybrid") or cfg.hybrid:
        cache["ssm_h"] = jnp.zeros(
            (L, batch, cfg.ssm_num_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32)
        cache["ssm_conv"] = jnp.zeros(
            (L, batch, cfg.ssm_conv_width - 1, cfg.ssm_inner), dt)
    if cfg.is_encoder_decoder:
        cache["cross_k"] = jnp.zeros(
            (L, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dt)
        cache["cross_v"] = jnp.zeros(
            (L, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dt)
    return cache


def _scatter_rows(buf: jnp.ndarray, vals: jnp.ndarray, idx: jnp.ndarray):
    """buf (B,S,...) <- vals (B,Sq,...) at idx (B,Sq)."""
    return jax.vmap(lambda b, v, i: b.at[i].set(v))(buf, vals, idx)


def _scan_or_loop(body, carry, xs, scan: bool):
    """lax.scan (production: O(1) HLO) or an unrolled Python loop (cost
    compiles: makes per-layer FLOPs visible to XLA cost analysis)."""
    if scan:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        xi = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def embed_tokens(params: dict, cfg, tokens: jnp.ndarray,
                 media_embeds: Optional[jnp.ndarray] = None,
                 media_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = params["embed"][tokens]
    if media_embeds is not None:
        # modality-frontend carve-out: precomputed patch/frame embeddings
        x = jnp.where(media_mask[..., None], media_embeds.astype(x.dtype), x)
    return shard(x, "batch", "seq", None)


def _logits(params: dict, cfg, x: jnp.ndarray) -> jnp.ndarray:
    norm = layernorm if cfg.arch_type == "audio" else rmsnorm
    x = norm(params["final_norm"], x, cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
    return shard(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _attn_block(lp: dict, cfg, x, q_pos, k_full, v_full, kv_pos, *,
                bidirectional=False, window=0):
    """Shared attention sub-block: returns attention output (B,Sq,D)."""
    q, _, _ = attention_qkv(lp["attn"], cfg, x, q_pos)
    q = shard(q, "batch", "seq", "heads", None)
    o = attend(q, k_full, v_full, q_pos, kv_pos,
               window=window, bidirectional=bidirectional)
    return attention_out(lp["attn"], o)


def _mlp_block(lp: dict, cfg, x, aux):
    if cfg.arch_type == "moe":
        out, a = moe_mod.moe_ffn(lp["moe"], cfg, x)
        return out, aux + a
    if cfg.arch_type == "audio":
        return gelu_mlp(lp["mlp"], x), aux
    return swiglu(lp["mlp"], x), aux


def _decoder_layer(lp: dict, cfg, x, positions, layer_cache, write_idx,
                   *, window: int, mode: str,
                   ssm_mask=None, ssm_tail_start=None, contiguous=False):
    """One decoder layer in cache mode (prefill / selective / decode).

    layer_cache: dict of this layer's slices; returns (x_out, new_layer_cache, aux).
    mode: "contiguous" (plain prefill/decode for ssm-bearing archs OK) or
          "selective" (MPIC — attention archs only).
    """
    aux = jnp.zeros((), jnp.float32)
    norm = layernorm if cfg.arch_type == "audio" else rmsnorm
    new_cache = {}

    if cfg.arch_type == "ssm":
        h = rmsnorm(lp["norm"], x, cfg.rms_norm_eps)
        if x.shape[1] == 1:
            out, st = ssm_mod.ssm_decode(
                lp["ssm"], cfg, h,
                {"h": layer_cache["ssm_h"], "conv": layer_cache["ssm_conv"]})
        else:
            out, st = ssm_mod.ssm_forward(lp["ssm"], cfg, h,
                                          dt_mask=ssm_mask,
                                          tail_start=ssm_tail_start)
        new_cache["ssm_h"], new_cache["ssm_conv"] = st["h"], st["conv"]
        return x + out, new_cache, aux

    # -- attention sub-block ------------------------------------------------
    h = norm(lp["attn_norm"], x, cfg.rms_norm_eps)
    q, k_new, v_new = attention_qkv(lp["attn"], cfg, h, positions)
    s_q = q.shape[1]
    if contiguous and s_q == layer_cache["k"].shape[1]:
        # contiguous full prefill: the cache IS the fresh K/V — a direct
        # write avoids the scatter, which the SPMD partitioner lowers to a
        # full-cache all-gather (80 GiB/step on hymba; §Perf iteration)
        k_full = k_new.astype(layer_cache["k"].dtype)
        v_full = v_new.astype(layer_cache["v"].dtype)
        kv_pos = positions
    else:
        k_full = _scatter_rows(layer_cache["k"],
                               k_new.astype(layer_cache["k"].dtype), write_idx)
        v_full = _scatter_rows(layer_cache["v"],
                               v_new.astype(layer_cache["v"].dtype), write_idx)
        kv_pos = _scatter_rows(layer_cache["pos"], positions, write_idx)
    if (contiguous and window and s_q == k_full.shape[1]
            and s_q % window == 0 and s_q >= 2 * window):
        # contiguous prefill with a sliding window: banded attention over
        # the fresh K/V (the cache holds exactly these tokens)
        o = banded_attend(q, k_new, v_new, positions, window)
    else:
        o = attend(q, k_full, v_full, positions, kv_pos, window=window)
    attn_out = attention_out(lp["attn"], o)
    new_cache["k"], new_cache["v"] = k_full, v_full

    if cfg.hybrid:
        hs = rmsnorm(lp["attn_norm"], x, cfg.rms_norm_eps)
        if x.shape[1] == 1:
            s_out, st = ssm_mod.ssm_decode(
                lp["ssm"], cfg, hs,
                {"h": layer_cache["ssm_h"], "conv": layer_cache["ssm_conv"]})
        else:
            s_out, st = ssm_mod.ssm_forward(lp["ssm"], cfg, hs,
                                            dt_mask=ssm_mask,
                                            tail_start=ssm_tail_start)
        new_cache["ssm_h"], new_cache["ssm_conv"] = st["h"], st["conv"]
        attn_out = 0.5 * (rmsnorm(lp["attn_mix_norm"], attn_out, cfg.rms_norm_eps)
                          + rmsnorm(lp["ssm_mix_norm"], s_out, cfg.rms_norm_eps))
    x = x + attn_out

    # -- cross-attention (whisper) -------------------------------------------
    if cfg.is_encoder_decoder:
        h = norm(lp["cross_norm"], x, cfg.rms_norm_eps)
        qc = (h @ lp["cross_attn"]["wq"]).reshape(
            h.shape[0], h.shape[1], cfg.num_heads, cfg.head_dim)
        enc_pos = jnp.zeros(
            (h.shape[0], layer_cache["cross_k"].shape[1]), jnp.int32)
        xo = attend(qc, layer_cache["cross_k"], layer_cache["cross_v"],
                    jnp.zeros_like(positions), enc_pos, bidirectional=True)
        x = x + attention_out(lp["cross_attn"], xo)
        new_cache["cross_k"] = layer_cache["cross_k"]
        new_cache["cross_v"] = layer_cache["cross_v"]

    # -- FFN ------------------------------------------------------------------
    h = norm(lp["mlp_norm"], x, cfg.rms_norm_eps)
    ff, aux = _mlp_block(lp, cfg, h, aux)
    x = x + ff
    x = shard(x, "batch", "seq", None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# public forwards
# ---------------------------------------------------------------------------

def forward_with_cache(params: dict, cfg, embeds: jnp.ndarray,
                       positions: jnp.ndarray, cache: dict,
                       write_idx: jnp.ndarray, *, window: Optional[int] = None,
                       ssm_mask=None, ssm_tail_start=None, contiguous=False):
    """Run tokens (any subset, any positions) against a blended cache.

    embeds    (B, Sq, D)  input embeddings of the tokens to *recompute*
    positions (B, Sq)     their original positions in the full prompt
    cache                 see ``make_cache`` — may already contain reused
                          (linked) KV from the MPIC library
    write_idx (B, Sq)     cache slots these tokens' K/V are scattered into

    Returns (logits (B, Sq, V), new_cache, aux_loss).

    Decode is Sq == 1; plain prefill is positions == write_idx == arange and
    an empty cache; MPIC selective prefill is a partially-filled cache with
    positions = the selected tokens.  Pure-SSM / hybrid archs require
    contiguous tokens (prefix semantics) — enforced by callers per
    DESIGN.md §Arch-applicability.
    """
    w = cfg.sliding_window if window is None else window
    x = embeds
    aux0 = jnp.zeros((), jnp.float32)

    layer_cache_keys = [k for k in cache if k != "pos"]
    xs_cache = {k: cache[k] for k in layer_cache_keys}
    kv_pos = (_scatter_rows(cache["pos"], positions, write_idx)
              if "pos" in cache else None)

    def body(carry, xs):
        xc, aux = carry
        lp, lc = xs
        if kv_pos is not None:
            lc = dict(lc, pos=kv_pos)
        xc, new_lc, a = _decoder_layer(lp, cfg, xc, positions, lc, write_idx,
                                       window=w, mode="cache",
                                       ssm_mask=ssm_mask,
                                       ssm_tail_start=ssm_tail_start,
                                       contiguous=contiguous)
        return (xc, aux + a), new_lc

    (x, aux), new_layer_caches = _scan_or_loop(
        body, (x, aux0), (params["layers"], xs_cache), cfg.scan_layers)
    new_cache = dict(new_layer_caches)
    if kv_pos is not None:
        new_cache["pos"] = kv_pos
    logits = _logits(params, cfg, x)
    return logits, new_cache, aux


def decode_paged(params: dict, cfg, embeds: jnp.ndarray,
                 positions: jnp.ndarray, pool_k: jnp.ndarray,
                 pool_v: jnp.ndarray, page_table: jnp.ndarray,
                 lengths: jnp.ndarray, write_pages: jnp.ndarray,
                 write_offs: jnp.ndarray, k_scales=None, v_scales=None,
                 *, backend: str = "ref", interpret=None):
    """One decode step for ALL slots against the shared paged KV pool.

    embeds      (B, 1, D)       new-token embeddings
    positions   (B, 1)          absolute positions (= current cache length)
    pool_k/v    (L, P, ps, Hkv, Dh)  shared page pool (donated by callers)
    page_table  (B, mp) int32   pages owned per slot, scratch-padded; ``mp``
                                only needs to cover max(lengths) — work
                                scales with the live cache, not max_seq_len
    lengths     (B,) int32      valid tokens AFTER this step's write
    write_pages/write_offs (B,) pool coordinates of the new token per slot
    k_scales/v_scales (L, P, Hkv) fp32  int8-pool page scales — when given,
                                the pools are int8: the new token quantizes
                                on write (running page amax) and attention
                                dequantizes in-kernel

    Returns (logits (B, V), pool_k, pool_v) — plus the updated scale
    buffers when quantized.  Attention archs only (no SSM state, no cross
    KV) — gated by ``Model.supports_paged_decode``.  Padding slots point
    their write at a scratch page and carry ``lengths == 0``.  Sliding
    windows (``cfg.sliding_window``) mask inside the kernel exactly like
    the dense ``attend`` decode mask.
    """
    aux0 = jnp.zeros((), jnp.float32)
    quantized = k_scales is not None

    def body(carry, xs):
        xc, aux = carry
        if quantized:
            lp, pk, pv, ks, vs = xs
        else:
            lp, pk, pv = xs
            ks = vs = None
        h = rmsnorm(lp["attn_norm"], xc, cfg.rms_norm_eps)
        q, k_new, v_new = attention_qkv(lp["attn"], cfg, h, positions)
        # mesh-sharded serving: new-token K/V and the pool pages stay
        # kv-head-partitioned, so the write and the attention below are
        # shard-local (pspec identity when no policy is active)
        q = shard(q, "batch", "seq", "heads", None)
        k_new = shard(k_new, "batch", "seq", "kv_heads", None)
        v_new = shard(v_new, "batch", "seq", "kv_heads", None)
        if quantized:
            pk, pv, ks, vs = quant_scatter(
                pk[None], pv[None], ks[None], vs[None], write_pages,
                write_offs, k_new[:, 0][None], v_new[:, 0][None])
            pk, pv, ks, vs = pk[0], pv[0], ks[0], vs[0]
            ks = shard(ks, None, "kv_heads")
            vs = shard(vs, None, "kv_heads")
        else:
            pk = pk.at[write_pages, write_offs].set(
                k_new[:, 0].astype(pk.dtype))
            pv = pv.at[write_pages, write_offs].set(
                v_new[:, 0].astype(pv.dtype))
        pk = shard(pk, None, None, "kv_heads", None)
        pv = shard(pv, None, None, "kv_heads", None)
        o = paged_attention_call(q[:, 0], pk, pv, page_table, lengths,
                                 k_scale=ks, v_scale=vs,
                                 window=cfg.sliding_window,
                                 backend=backend, interpret=interpret)
        xc = xc + attention_out(lp["attn"], o[:, None])
        h = rmsnorm(lp["mlp_norm"], xc, cfg.rms_norm_eps)
        ff, aux = _mlp_block(lp, cfg, h, aux)
        xc = xc + ff
        ys = (pk, pv, ks, vs) if quantized else (pk, pv)
        return (xc, aux), ys

    if quantized:
        (x, _), (new_k, new_v, new_ks, new_vs) = _scan_or_loop(
            body, (embeds, aux0),
            (params["layers"], pool_k, pool_v, k_scales, v_scales),
            cfg.scan_layers)
        logits = _logits(params, cfg, x)
        return logits[:, -1, :], new_k, new_v, new_ks, new_vs
    (x, _), (new_k, new_v) = _scan_or_loop(
        body, (embeds, aux0), (params["layers"], pool_k, pool_v),
        cfg.scan_layers)
    logits = _logits(params, cfg, x)
    return logits[:, -1, :], new_k, new_v


def selective_prefill_paged(params: dict, cfg, embeds: jnp.ndarray,
                            sel_positions: jnp.ndarray, pool_k: jnp.ndarray,
                            pool_v: jnp.ndarray, page_table: jnp.ndarray,
                            lengths: jnp.ndarray, write_pages: jnp.ndarray,
                            write_offs: jnp.ndarray, k_scales=None,
                            v_scales=None, *, backend: str = "ref",
                            interpret=None):
    """MPIC selective-attention prefill straight against the paged KV pool.

    embeds       (B, Sq, D)      embeddings of the selected tokens (padded
                                 to the caller's shape bucket)
    sel_positions (B, Sq)        their original prompt positions
    pool_k/v     (L, P, ps, Hkv, Dh)  shared page pool (donated by callers)
    page_table   (B, mp) int32   pages owned per slot, scratch-padded; ``mp``
                                 only needs to cover ⌈lengths/ps⌉
    lengths      (B,) int32      valid kv slots (= prompt length); slot i
                                 holds original position i — the linker
                                 places reused segments at their offsets and
                                 this pass scatters the recomputed tokens
                                 into theirs, so no per-slot pos array is
                                 needed (contrast ``forward_with_cache``)
    write_pages/write_offs (B, Sq)  pool coordinates per selected token;
                                 padding rows point at the scratch page

    Per layer (mirroring ``decode_paged``): compute Q/K/V of the selected
    tokens, scatter K/V into their pages, then selective attention over the
    full paged region — the recomputed tokens become visible to each other
    inside this one pass (the paper's single-step property).  Returns
    (logits (B, Sq, V), pool_k, pool_v) — plus the updated scale buffers
    when ``k_scales``/``v_scales`` (L, P, Hkv) mark the pools int8.
    """
    aux0 = jnp.zeros((), jnp.float32)
    quantized = k_scales is not None
    b, sq = sel_positions.shape
    flat_pages = write_pages.reshape(-1)
    flat_offs = write_offs.reshape(-1)

    def body(carry, xs):
        xc, aux = carry
        if quantized:
            lp, pk, pv, ks, vs = xs
        else:
            lp, pk, pv = xs
            ks = vs = None
        h = rmsnorm(lp["attn_norm"], xc, cfg.rms_norm_eps)
        q, k_new, v_new = attention_qkv(lp["attn"], cfg, h, sel_positions)
        q = shard(q, "batch", "seq", "heads", None)
        k_new = shard(k_new, "batch", "seq", "kv_heads", None)
        v_new = shard(v_new, "batch", "seq", "kv_heads", None)
        if quantized:
            hkv, dh = k_new.shape[2], k_new.shape[3]
            pk, pv, ks, vs = quant_scatter(
                pk[None], pv[None], ks[None], vs[None], flat_pages,
                flat_offs, k_new.reshape(1, b * sq, hkv, dh),
                v_new.reshape(1, b * sq, hkv, dh))
            pk, pv, ks, vs = pk[0], pv[0], ks[0], vs[0]
            ks = shard(ks, None, "kv_heads")
            vs = shard(vs, None, "kv_heads")
        else:
            pk = pk.at[write_pages, write_offs].set(k_new.astype(pk.dtype))
            pv = pv.at[write_pages, write_offs].set(v_new.astype(pv.dtype))
        pk = shard(pk, None, None, "kv_heads", None)
        pv = shard(pv, None, None, "kv_heads", None)
        o = selective_attention_paged_call(
            q, pk, pv, page_table, sel_positions, lengths,
            k_scale=ks, v_scale=vs,
            window=cfg.sliding_window, backend=backend, interpret=interpret)
        xc = xc + attention_out(lp["attn"], o)
        h = rmsnorm(lp["mlp_norm"], xc, cfg.rms_norm_eps)
        ff, aux = _mlp_block(lp, cfg, h, aux)
        xc = xc + ff
        ys = (pk, pv, ks, vs) if quantized else (pk, pv)
        return (xc, aux), ys

    if quantized:
        (x, _), (new_k, new_v, new_ks, new_vs) = _scan_or_loop(
            body, (embeds, aux0),
            (params["layers"], pool_k, pool_v, k_scales, v_scales),
            cfg.scan_layers)
        return _logits(params, cfg, x), new_k, new_v, new_ks, new_vs
    (x, _), (new_k, new_v) = _scan_or_loop(
        body, (embeds, aux0), (params["layers"], pool_k, pool_v),
        cfg.scan_layers)
    return _logits(params, cfg, x), new_k, new_v


def forward_train(params: dict, cfg, tokens: jnp.ndarray,
                  media_embeds=None, media_mask=None, *,
                  audio_embeds=None):
    """Plain causal forward over a contiguous sequence (training path)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = embed_tokens(params, cfg, tokens, media_embeds, media_mask)
    if cfg.learned_pos_emb:
        x = x + params["pos_embed"][positions]
    aux0 = jnp.zeros((), jnp.float32)

    cross_kv = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, audio_embeds)
        cross_kv = compute_cross_kv(params, cfg, enc_out)

    def body(carry, xs):
        xc, aux = carry
        if cfg.is_encoder_decoder:
            lp, (ck, cv) = xs
            lc = {"k": None, "v": None, "cross_k": ck, "cross_v": cv}
        else:
            lp = xs
            lc = {}
        aux_inc = jnp.zeros((), jnp.float32)
        norm = layernorm if cfg.arch_type == "audio" else rmsnorm

        if cfg.arch_type == "ssm":
            h = rmsnorm(lp["norm"], xc, cfg.rms_norm_eps)
            out, _ = ssm_mod.ssm_forward(lp["ssm"], cfg, h)
            return (xc + out, aux), None

        h = norm(lp["attn_norm"], xc, cfg.rms_norm_eps)
        q, k, v = attention_qkv(lp["attn"], cfg, h, positions)
        q = shard(q, "batch", "seq", "heads", None)
        k = shard(k, "batch", "seq", "kv_heads", None)
        w_ = cfg.sliding_window
        if w_ and s % w_ == 0 and s >= 2 * w_:
            o = banded_attend(q, k, v, positions, w_)   # S×2w band only
        else:
            o = attend(q, k, v, positions, positions, window=w_)
        attn_out = attention_out(lp["attn"], o)
        if cfg.hybrid:
            s_out, _ = ssm_mod.ssm_forward(
                lp["ssm"], cfg, rmsnorm(lp["attn_norm"], xc, cfg.rms_norm_eps))
            attn_out = 0.5 * (
                rmsnorm(lp["attn_mix_norm"], attn_out, cfg.rms_norm_eps)
                + rmsnorm(lp["ssm_mix_norm"], s_out, cfg.rms_norm_eps))
        xc = xc + attn_out

        if cfg.is_encoder_decoder:
            h = norm(lp["cross_norm"], xc, cfg.rms_norm_eps)
            qc = (h @ lp["cross_attn"]["wq"]).reshape(
                b, s, cfg.num_heads, cfg.head_dim)
            enc_pos = jnp.zeros((b, lc["cross_k"].shape[1]), jnp.int32)
            xo = attend(qc, lc["cross_k"], lc["cross_v"],
                        jnp.zeros_like(positions), enc_pos, bidirectional=True)
            xc = xc + attention_out(lp["cross_attn"], xo)

        h = norm(lp["mlp_norm"], xc, cfg.rms_norm_eps)
        ff, aux_inc = _mlp_block(lp, cfg, h, aux_inc)
        xc = shard(xc + ff, "batch", "seq", None)
        return (xc, aux + aux_inc), None

    xs = (params["layers"], cross_kv) if cfg.is_encoder_decoder else params["layers"]
    (x, aux), _ = _scan_or_loop(body, (x, aux0), xs, cfg.scan_layers)
    return _logits(params, cfg, x), aux


# ---------------------------------------------------------------------------
# whisper encoder + cross KV (the audio-family MPIC artifact)
# ---------------------------------------------------------------------------

def encode(params: dict, cfg, audio_embeds: jnp.ndarray) -> jnp.ndarray:
    """Bidirectional encoder over precomputed frame embeddings (stub frontend)."""
    b, s, _ = audio_embeds.shape
    x = audio_embeds.astype(_dtype(cfg.compute_dtype))
    x = x + params["enc_pos_embed"][None, :s, :]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(x, lp):
        h = layernorm(lp["attn_norm"], x, cfg.rms_norm_eps)
        q, k, v = attention_qkv(lp["attn"], cfg, h, pos, rope=False)
        o = attend(q, k, v, pos, pos, bidirectional=True)
        x = x + attention_out(lp["attn"], o)
        h = layernorm(lp["mlp_norm"], x, cfg.rms_norm_eps)
        return x + gelu_mlp(lp["mlp"], h), None

    def body2(x, lp):
        return body(x, lp)

    x, _ = _scan_or_loop(body2, x, params["enc_layers"], cfg.scan_layers)
    return layernorm(params["enc_norm"], x, cfg.rms_norm_eps)


def compute_cross_kv(params: dict, cfg, enc_out: jnp.ndarray):
    """Per-decoder-layer cross K/V over encoder output.

    This is position-independent by construction (no decoder positions are
    baked in), so it is exactly what MPIC's library stores for audio
    segments.
    Returns (cross_k, cross_v), each (L, B, Senc, Hkv, Dh).
    """
    b, s, _ = enc_out.shape

    def per_layer(lp):
        k = (enc_out @ lp["cross_attn"]["wk"]).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim)
        v = (enc_out @ lp["cross_attn"]["wv"]).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim)
        return k, v

    return jax.vmap(per_layer)(params["layers"])
