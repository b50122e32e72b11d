"""Plain float32 reference of what the ``mpic`` policy is defined to compute.

Independent of the program: it imports nothing of ``repro`` and reads only
the benchmark's own weights (a dict of arrays keyed as the parameter tree
is) and the benchmark's own inputs.  The model is a pre-norm decoder with
RMSNorm, grouped-query attention with rotate-half RoPE, optional q/k/v
biases and a SwiGLU MLP (Mistral / Qwen2 as published).

``mpic`` semantics (paper section 5; ``core/select.py`` names the rule):

* each image's K/V is computed standalone, the image's tokens alone at
  positions ``0..n-1``, and its keys are moved to the image's place in the
  prompt by RoPE (here: rotated at the final position directly, which is
  the same rotation);
* every text token and the first ``k`` tokens of each image are
  recomputed in one pass that attends, causally by position, over the
  recomputed K/V and the other image tokens' standalone K/V;
* each output token is computed the same way, attending to all of that.

Everything is float32 with ``Precision.HIGHEST`` matmuls, one layer per
jitted call so that only one layer's weights are ever upcast.
``lowp=True`` is the control: every matmul operand and the K/V are rounded
to float8 (e4m3) with per-row absmax scales first.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)
NO_POS = 2 ** 30            # key slot that holds no token


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    qkv_bias: bool

    @staticmethod
    def from_config(conf: dict) -> "Arch":
        heads = conf["num_attention_heads"]
        return Arch(layers=conf["num_hidden_layers"],
                    d_model=conf["hidden_size"], heads=heads,
                    kv_heads=conf["num_key_value_heads"],
                    head_dim=conf.get("head_dim")
                    or conf["hidden_size"] // heads,
                    rope_theta=float(conf["rope_theta"]),
                    eps=float(conf["rms_norm_eps"]),
                    qkv_bias=bool(conf.get("qkv_bias", False)))


@dataclasses.dataclass
class Item:
    """One served request as the reference sees it."""
    segments: List[tuple]       # ("text", int32 ids) | ("image", index)
    served: np.ndarray          # the program's output tokens


def _f8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, lowp):
    if lowp:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_tables(positions: np.ndarray, head_dim: int, theta: float):
    """cos/sin (S, Dh/2) computed in float64 on the host."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    ang = positions.astype(np.float64)[:, None] * inv[None]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _layer_weights(layers, i):
    return {k: (jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
                .astype(jnp.float32) if not isinstance(v, dict)
                else _layer_weights(v, i))
            for k, v in layers.items()}


@functools.partial(jax.jit, static_argnames=("arch", "lowp"))
def _layer(layers, i, x, q_cos, q_sin, q_slot, q_pos, k_all, v_all, key_pos,
           *, arch: Arch, lowp: bool):
    """One decoder layer for the computed tokens ``x`` (n, D).

    Their keys/values are written at slots ``q_slot`` of ``k_all``/``v_all``
    (Tp, Hkv, Dh; post-RoPE keys), then each query attends to every slot
    whose ``key_pos`` is at most its own ``q_pos``.  Returns the new ``x``
    and this token set's (pre-RoPE K, post-RoPE K, V)."""
    w = _layer_weights(layers, i)
    a = arch
    n = x.shape[0]
    h = _rms(x, w["attn_norm"]["scale"], a.eps)
    q = _mm(h, w["attn"]["wq"], lowp)
    k = _mm(h, w["attn"]["wk"], lowp)
    v = _mm(h, w["attn"]["wv"], lowp)
    if a.qkv_bias:
        q, k, v = q + w["attn"]["bq"], k + w["attn"]["bk"], v + w["attn"]["bv"]
    q = q.reshape(n, a.heads, a.head_dim)
    k_pre = k.reshape(n, a.kv_heads, a.head_dim)
    v = v.reshape(n, a.kv_heads, a.head_dim)
    q = _rope(q, q_cos, q_sin)
    k = _rope(k_pre, q_cos, q_sin)
    if lowp:
        k, v = _f8(k, -1), _f8(v, -1)
    k_all = k_all.at[q_slot].set(k)
    v_all = v_all.at[q_slot].set(v)
    g = a.heads // a.kv_heads
    qg = q.reshape(n, a.kv_heads, g, a.head_dim)
    s = jnp.einsum("nhgd,thd->hgnt", qg, k_all, precision=HI)
    s = s / np.sqrt(a.head_dim)
    allowed = key_pos[None, :] <= q_pos[:, None]                  # (n, Tp)
    s = jnp.where(allowed[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgnt,thd->nhgd", p, v_all, precision=HI)
    x = x + _mm(o.reshape(n, a.heads * a.head_dim), w["attn"]["wo"], lowp)
    h = _rms(x, w["mlp_norm"]["scale"], a.eps)
    gate = _mm(h, w["mlp"]["w_gate"], lowp)
    up = _mm(h, w["mlp"]["w_up"], lowp)
    x = x + _mm(jax.nn.silu(gate) * up, w["mlp"]["w_down"], lowp)
    return x, k_pre, k, v


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _logits(x, rows, norm, head, *, eps: float, lowp: bool):
    h = _rms(x[rows], norm.astype(jnp.float32), eps)
    return _mm(h, head.astype(jnp.float32), lowp)


def _bucket(n: int) -> int:
    b = 64
    while b < n:
        b *= 2
    return b


class Reference:
    """The reference over the benchmark's weights ``params`` and images."""

    def __init__(self, params: Dict, arch: Arch, image_fn, k: int,
                 lowp: bool = False):
        self.p = params
        self.a = arch
        self.image_fn = image_fn        # index -> (n, D) embeddings
        self.k = k
        self.lowp = lowp
        self._images: Dict[int, tuple] = {}
        self._table = None              # host copy of the embedding

    def _run(self, x, positions, slots, key_pos, reuse_k, reuse_v,
             collect: bool):
        """All layers for the computed tokens ``x``; returns final x and,
        with ``collect``, each layer's (pre-RoPE K, V) of these tokens."""
        a = self.a
        cos, sin = rope_tables(positions, a.head_dim, a.rope_theta)
        q_slot = jnp.asarray(slots, jnp.int32)
        q_pos = jnp.asarray(positions, jnp.int32)
        kp = jnp.asarray(key_pos, jnp.int32)
        ks, vs = [], []
        for i in range(a.layers):
            x, k_pre, _, v = _layer(
                self.p["layers"], jnp.int32(i), x, cos, sin, q_slot, q_pos,
                reuse_k[i], reuse_v[i], kp, arch=a, lowp=self.lowp)
            if collect:
                ks.append(k_pre)
                vs.append(v)
        return x, ks, vs

    def image_kv(self, index: int):
        """Standalone pre-RoPE K and V of one image, (L, n, Hkv, Dh) each."""
        if index not in self._images:
            a = self.a
            x = jnp.asarray(self.image_fn(index)).astype(jnp.float32)
            n = x.shape[0]
            pos = np.arange(n)
            z = jnp.zeros((a.layers, n, a.kv_heads, a.head_dim), jnp.float32)
            _, ks, vs = self._run(x, pos, pos, pos, z, z, collect=True)
            self._images[index] = (jnp.stack(ks), jnp.stack(vs))
        return self._images[index]

    def _host_embed(self) -> np.ndarray:
        if self._table is None:
            self._table = np.asarray(self.p["embed"])
        return self._table

    def logits(self, item: Item) -> np.ndarray:
        """Reference logits (T, V) at each served token's step, in order.

        Inputs are assembled on the host and padded to buckets, so a run
        compiles a handful of shapes however its requests vary."""
        a = self.a
        table = self._host_embed()
        served = np.asarray(item.served, np.int32)
        rows_x, rows_pos = [], []         # computed tokens
        reuse = []                        # (offset, image index, length)
        off = 0
        for kind, val in item.segments:
            if kind == "text":
                rows_x.append(table[np.asarray(val)].astype(np.float32))
                rows_pos.append(off + np.arange(len(val)))
                off += len(val)
            else:
                emb = np.asarray(self.image_fn(val)).astype(np.float32)
                n = emb.shape[0]
                kk = min(self.k, n)
                rows_x.append(emb[:kk])
                rows_pos.append(off + np.arange(kk))
                reuse.append((off, val, n))
                off += n
        total = off
        rows_x.append(table[served[:-1]].astype(np.float32))
        rows_pos.append(total + np.arange(len(served) - 1))
        pos = np.concatenate(rows_pos)
        n_tok = total + len(served) - 1
        tp = _bucket(n_tok + 1)
        scratch = tp - 1
        # pad the computed set to a bucket: pads sit at position 0 and
        # write to the scratch slot, which no real query can see
        npad = _bucket(len(pos)) - len(pos)
        slots = np.concatenate([pos, np.full(npad, scratch)])
        qpos = np.concatenate([pos, np.zeros(npad, np.int64)])
        x = np.concatenate(rows_x + [np.zeros((npad, a.d_model), np.float32)])
        key_pos = np.full(tp, NO_POS, np.int64)
        key_pos[:n_tok] = np.arange(n_tok)
        reuse_k = jnp.zeros((a.layers, tp, a.kv_heads, a.head_dim),
                            jnp.float32)
        reuse_v = jnp.zeros_like(reuse_k)
        for o, idx, n in reuse:
            k_pre, v = self.image_kv(idx)
            cos, sin = rope_tables(o + np.arange(n), a.head_dim, a.rope_theta)
            reuse_k, reuse_v = _place(reuse_k, reuse_v, k_pre, v, cos, sin,
                                      jnp.int32(o), lowp=self.lowp)
        xo, _, _ = self._run(jnp.asarray(x), qpos, slots, key_pos, reuse_k,
                             reuse_v, collect=False)
        # rows that predict served tokens: the last prompt token, then
        # every served token but the last
        n_prompt_rows = len(pos) - (len(served) - 1)
        rows = np.arange(n_prompt_rows - 1, len(pos))
        m = len(rows)
        rows = np.concatenate([rows, np.zeros(_bucket(m) - m, np.int64)])
        head = self.p["lm_head"] if "lm_head" in self.p \
            else self.p["embed"].T
        out = _logits(xo, jnp.asarray(rows, jnp.int32),
                      self.p["final_norm"]["scale"], head,
                      eps=a.eps, lowp=self.lowp)
        return np.asarray(out)[:m]


@functools.partial(jax.jit, static_argnames=("lowp",), donate_argnums=(0, 1))
def _place(reuse_k, reuse_v, k_pre, v, cos, sin, off, *, lowp: bool):
    """Rotate one image's standalone keys to its place and write its K/V
    at token offset ``off`` of every layer."""
    k = jax.vmap(lambda kl: _rope(kl, cos, sin))(k_pre)
    if lowp:
        k, v = _f8(k, -1), _f8(v, -1)
    zero = jnp.int32(0)
    reuse_k = jax.lax.dynamic_update_slice(reuse_k, k, (zero, off, zero, zero))
    reuse_v = jax.lax.dynamic_update_slice(reuse_v, v, (zero, off, zero, zero))
    return reuse_k, reuse_v


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each token's logit lies."""
    t = np.asarray(tokens, np.int64)
    got = np.take_along_axis(ref_logits, t[:, None], axis=-1)[:, 0]
    return ref_logits.max(axis=-1) - got
