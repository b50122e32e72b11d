"""Core neural-net primitives: init helpers, norms, RoPE, SwiGLU, attention.

Everything is functional: params are nested dicts of jnp arrays, apply
functions are pure.  All attention paths (train, plain prefill, MPIC
selective prefill, decode) funnel through :func:`attend`, which masks by
*original token position* — this is what makes position-independent cache
blending a first-class citizen rather than a bolted-on mode.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.launch.pspec import shard

# Sentinel position for cache slots that hold no token yet (masked out).
INVALID_POS = jnp.iinfo(jnp.int32).max


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Normal(0, scale²) weights drawn in ``dtype`` itself: a bf16 model
    never holds an fp32 copy of a weight, even transiently."""
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# RoPE — including the MPIC position-relink rotation
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate ``x`` (..., S, H, Dh) by per-token ``positions`` (..., S)."""
    freqs = rope_freqs(x.shape[-1], theta)          # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]             # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_relink(k: jnp.ndarray, delta: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Re-rotate cached keys by ``delta`` positions (MPIC linker).

    RoPE rotations compose: K(p + Δ) = R(Δ)·K(p).  ``delta`` broadcasts over
    (..., S) so a whole linked segment shifts with one elementwise pass —
    this is what makes the stored cache position-independent *exactly*
    (the residual reuse error is only missing cross-attention context).
    """
    return apply_rope(k, delta, theta)


# ---------------------------------------------------------------------------
# attention core — position-masked, cache-agnostic
# ---------------------------------------------------------------------------

def banded_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  positions: jnp.ndarray, window: int) -> jnp.ndarray:
    """Sliding-window attention computing ONLY the S×2w band.

    Contiguous sequences (train / plain prefill) with window w need each
    query to see at most the previous w keys, so the S×S score matrix is
    a waste: reshape into S/w query blocks, give block i the keys of
    blocks {i-1, i} (2w keys — pure reshape/concat, no gather), and mask
    by position as usual.  Halves attention FLOPs and HBM bytes at
    S = 4w (see EXPERIMENTS.md §Perf, qwen iteration 2).

    Requires S % w == 0 and S >= 2w (caller checks).
    """
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    k = shard(k, "batch", None, "heads", None)
    v = shard(v, "batch", None, "heads", None)
    nb = s // window

    def blk(x):                                    # (B,S,H,D) -> (B,nb,2w,H,D)
        xr = x.reshape(b, nb, window, hq, dh)
        prev = jnp.concatenate([jnp.zeros_like(xr[:, :1]), xr[:, :-1]], axis=1)
        return jnp.concatenate([prev, xr], axis=2)

    qr = q.reshape(b, nb, window, hq, dh)
    kb, vb = blk(k), blk(v)
    # the 2w band axis is the kv_seq axis: shard it when heads cannot shard
    kb = shard(kb, "batch", None, "kv_seq", "heads", None)
    vb = shard(vb, "batch", None, "kv_seq", "heads", None)
    qp = positions.reshape(b, nb, window)
    pp = jnp.concatenate(
        [jnp.full_like(qp[:, :1], INVALID_POS),
         positions.reshape(b, nb, window)[:, :-1]], axis=1)
    kp = jnp.concatenate([pp, qp], axis=2)          # (B, nb, 2w)

    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", qr, kb,
                        preferred_element_type=jnp.float32) * scale
    logits = shard(logits, "batch", None, "heads", None, "kv_seq")
    valid = kp[:, :, None, None, :] != INVALID_POS
    causal = kp[:, :, None, None, :] <= qp[:, :, :, None][:, :, None]
    near = kp[:, :, None, None, :] > qp[:, :, :, None][:, :, None] - window
    mask = shard(valid & causal & near,
                 "batch", None, None, None, "kv_seq")
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(vb.dtype)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, vb,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, dh).astype(q.dtype)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, Dh) -> (B, S, Hkv*n_rep, Dh) for GQA."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
           *, window: int = 0, bidirectional: bool = False) -> jnp.ndarray:
    """Attention masked by original token positions.

    q:      (B, Sq, Hq, Dh)      queries (selected / new tokens)
    k, v:   (B, Skv, Hkv, Dh)    blended cache (reused + recomputed)
    q_pos:  (B, Sq)  int32       original positions of the queries
    kv_pos: (B, Skv) int32       original positions of cache slots
                                 (INVALID_POS = empty slot, masked out)
    window: sliding-window size (0 = full causal)

    Covers train (q_pos == kv_pos == arange), plain prefill, MPIC selective
    prefill (Sq < Skv) and decode (Sq == 1) with a single code path.
    """
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    # context parallelism: when the heads axis cannot shard (e.g. 40 heads
    # on a 16-way model axis), the run's rules map "kv_seq" to a mesh axis
    # and the softmax/PV contractions partition flash-decoding-style —
    # WITHOUT this, the SPMD partitioner shards the *contraction* dim and
    # all-reduces the full S×S score matrix (observed: 1.9 TB/device on
    # qwen prefill_32k; see EXPERIMENTS.md §Perf)
    k = shard(k, "batch", "kv_seq", "heads", None)
    v = shard(v, "batch", "kv_seq", "heads", None)

    scale = 1.0 / math.sqrt(dh)
    # bf16 operands, fp32 accumulation (flash-attention numerics): avoids
    # materializing fp32 copies of Q/K — 'convert' was the top HBM writer
    # in the §Perf bytes profile
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = shard(logits, "batch", "heads", None, "kv_seq")

    valid = kv_pos[:, None, None, :] != INVALID_POS
    if bidirectional:
        mask = valid
    else:
        causal = kv_pos[:, None, None, :] <= q_pos[:, None, :, None]
        mask = jnp.logical_and(valid, causal)
        if window > 0:
            near = kv_pos[:, None, None, :] > q_pos[:, None, :, None] - window
            mask = jnp.logical_and(mask, near)

    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# attention module (QKV + RoPE + output proj)
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> dict:
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": dense_init(ks[0], (d, qd), dt),
        "wk": dense_init(ks[1], (d, kvd), dt),
        "wv": dense_init(ks[2], (d, kvd), dt),
        "wo": dense_init(ks[3], (qd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dt)
        p["bk"] = jnp.zeros((kvd,), dt)
        p["bv"] = jnp.zeros((kvd,), dt)
    return p


def attention_qkv(params: dict, cfg, x: jnp.ndarray, positions: jnp.ndarray,
                  *, rope: bool = True):
    """x (B,S,D), positions (B,S) -> q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh)."""
    b, s, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if rope and not cfg.learned_pos_emb:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_out(params: dict, o: jnp.ndarray) -> jnp.ndarray:
    b, s, h, dh = o.shape
    return o.reshape(b, s, h * dh) @ params["wo"]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, d_ff), dtype),
        "w_up": dense_init(ks[1], (d, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d), dtype),
    }


def swiglu(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]


def init_gelu_mlp(key, d: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "w_up": dense_init(ks[0], (d, d_ff), dtype),
        "w_down": dense_init(ks[1], (d_ff, d), dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "b_down": jnp.zeros((d,), dtype),
    }


def gelu_mlp(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.gelu(x @ params["w_up"] + params["b_up"])
    return h @ params["w_down"] + params["b_down"]
