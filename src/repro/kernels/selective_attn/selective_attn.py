"""Pallas TPU kernel: selective-attention prefill (flash-style, blended KV).

The MPIC hot spot: queries are only the *selected* (recomputed) tokens,
keys/values span the full linked cache (reused segments + freshly scattered
dummy slots).  Masking is by original token position, so the kernel is
oblivious to where segments were linked — position independence lives in
the ``q_pos``/``kv_pos`` operands, not in the loop structure.

TPU mapping (DESIGN.md §3):
  dense:  grid = (B, Hq, Sq/BQ, Skv/BK);  paged: grid = (B, Sq/BQ, pages),
  one whole page (all kv heads) per step with the heads looped inside.
  The KV axis is the innermost (sequential) grid dim; online-softmax
  running stats (m, l, acc) live in VMEM scratch and survive across KV
  steps.  Every block's last two dims are whole or (8, 128)-aligned, as
  Mosaic requires: positions ride as (BQ, 1) columns / (1, BK) rows, pages
  keep the pool's (Hkv, Dh).  K is loaded as (BK, Dh) and contracted with
  dot_general — no transposes materialize in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INVALID_POS = jnp.iinfo(jnp.int32).max
NEG_INF = -1e30


def _sel_attn_kernel(q_pos_ref, kv_pos_ref,        # (1,BQ,1) / (1,1,BK)
                     q_ref, k_ref, v_ref,          # blocks
                     o_ref,                        # output block
                     m_ref, l_ref, acc_ref,        # VMEM scratch
                     *, window: int, n_kv_blocks: int, scale: float):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, Dh)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, Dh)
    v = v_ref[0, 0].astype(jnp.float32)            # (BK, Dh)
    qp = q_pos_ref[0]                              # (BQ, 1) column
    kp = kv_pos_ref[0]                             # (1, BK) row

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    valid = (kp != INVALID_POS) & (kp <= qp)
    if window > 0:
        valid &= kp > qp - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                            # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)                   # NEG_INF-NEG_INF guard

    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0, ...] = (acc_ref[...] /
                            jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _sel_attn_paged_kernel(page_table_ref, lengths_ref,   # scalar prefetch
                           q_pos_ref,                     # (1, BQ, 1)
                           q_ref, k_ref, v_ref,           # VMEM blocks
                           *rest, page_size: int, n_pages: int, window: int,
                           scale: float, group: int, quantized: bool):
    """One grid step = one query block of one sequence against one whole
    page, all heads.  Blocks keep the pool's last two dims
    (``(1, page_size, Hkv, Dh)``, as Mosaic requires); heads are a static
    loop over static strided loads of each kv head's ``(page_size, Dh)``
    slice.  On an int8 pool (``quantized``) the page's ``(1, 1, Hkv)``
    fp32 scale rows arrive beside it: the K scale folds into the logits
    scale, the V scale multiplies the page's accumulator contribution —
    dequantization never leaves the registers."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = q_pos_ref[0]                                  # (BQ, 1) column
    length = lengths_ref[b]
    # paged-prefill invariant: cache slot i holds original position i, so
    # the kv position of this page's slots is their token index
    tok = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                  # (1, page_size)
    valid = (tok < length) & (tok <= qp)
    if window > 0:
        valid &= tok > qp - window

    for kh in range(k_ref.shape[2]):
        k = k_ref[0, :, kh, :].astype(jnp.float32)     # (page_size, Dh)
        v = v_ref[0, :, kh, :].astype(jnp.float32)
        for h in range(kh * group, (kh + 1) * group):
            q = q_ref[0, h].astype(jnp.float32)        # (BQ, Dh)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if quantized:
                s = s * ks_ref[0, :, kh:kh + 1]
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[h]                          # (BQ, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            if quantized:
                pv = pv * vs_ref[0, :, kh:kh + 1]
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def selective_attention_paged_pallas(q, k_pool, v_pool, page_table, q_pos,
                                     lengths, k_scale=None, v_scale=None,
                                     *, window: int = 0,
                                     block_q: int = 128,
                                     interpret: bool = False):
    """q (B,Hq,Sq,Dh); pools (P,page_size,Hkv,Dh); page_table (B,n_pages).

    The KV axis of the grid walks the page table: each step's BlockSpec
    ``index_map`` reads the page index from the SMEM-prefetched table
    (same dynamic-DMA structure as ``paged_attn``), so keys stream out of
    the pool without ever materializing a contiguous copy.  Sq % block_q
    == 0 (ops.py pads; padding query rows produce garbage that callers
    discard).  ``k_scale``/``v_scale`` (P,Hkv) fp32 switch to the
    int8-pool kernel (dequant-in-register).
    """
    b, hq, sq, dh = q.shape
    p, page_size, hkv, _ = k_pool.shape
    n_pages = page_table.shape[1]
    assert sq % block_q == 0
    group = hq // hkv
    quantized = k_scale is not None

    kernel = functools.partial(
        _sel_attn_paged_kernel, page_size=page_size, n_pages=n_pages,
        window=window, scale=1.0 / (dh ** 0.5), group=group,
        quantized=quantized)

    page_spec = pl.BlockSpec((1, page_size, hkv, dh),
                             lambda b_, i, j, pt, ln: (pt[b_, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, block_q, 1),
                     lambda b_, i, j, pt, ln: (b_, i, 0)),          # q_pos
        pl.BlockSpec((1, hq, block_q, dh),
                     lambda b_, i, j, pt, ln: (b_, 0, i, 0)),       # q
        page_spec, page_spec,
    ]
    args = [page_table, lengths, q_pos.reshape(b, sq, 1), q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec(
            (1, 1, hkv), lambda b_, i, j, pt, ln: (pt[b_, j], 0, 0))] * 2
        args += [k_scale.reshape(p, 1, hkv), v_scale.reshape(p, 1, hkv)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, lengths
        grid=(b, sq // block_q, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, block_q, dh),
                               lambda b_, i, j, pt, ln: (b_, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((hq, block_q, 1), jnp.float32),   # l (running sum)
            pltpu.VMEM((hq, block_q, dh), jnp.float32),  # acc
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        interpret=interpret,
    )(*args)


def selective_attention_pallas(q, k, v, q_pos, kv_pos, *, window: int = 0,
                               block_q: int = 128, block_k: int = 128,
                               interpret: bool = False):
    """q (B,Hq,Sq,Dh), k/v (B,Hkv,Skv,Dh), q_pos (B,Sq), kv_pos (B,Skv).

    Sq % block_q == 0 and Skv % block_k == 0 (ops.py pads; padding KV slots
    carry INVALID_POS so they are masked; padding query rows produce zeros).
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert sq % block_q == 0 and skv % block_k == 0
    group = hq // hkv
    n_kv = skv // block_k
    grid = (b, hq, sq // block_q, n_kv)

    kernel = functools.partial(
        _sel_attn_kernel, window=window, n_kv_blocks=n_kv,
        scale=1.0 / (dh ** 0.5))

    # positions get a unit axis so every block's last two dims are legal
    # for Mosaic: q_pos a (BQ, 1) column, kv_pos a (1, BK) row
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b_, h, i, j: (b_, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h, i, j: (b_, 0, j)),
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running sum)
            pltpu.VMEM((block_q, dh), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(q_pos.reshape(b, sq, 1), kv_pos.reshape(b, 1, skv), q, k, v)
