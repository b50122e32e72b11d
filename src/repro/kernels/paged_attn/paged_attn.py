"""Pallas TPU kernel: paged decode attention (vLLM PagedAttention → TPU).

TPU adaptation: the page table and lengths ride in SMEM via
``PrefetchScalarGridSpec`` so each grid step's BlockSpec ``index_map`` can
*dynamically* pick the page to DMA into VMEM — a gather expressed through
the grid rather than CUDA warp-level pointer chasing.  The grid is
``(batch, page)`` and each step moves one whole page, all kv heads; online
softmax accumulates per (batch, kv-head) across the page axis in VMEM
scratch; the GQA query group (Hq/Hkv queries per kv head) rides the
sublane dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(page_table_ref, lengths_ref,    # scalar prefetch (SMEM)
                       q_ref, k_ref, v_ref,            # VMEM blocks
                       *rest, page_size: int, max_pages: int, scale: float,
                       window: int, quantized: bool):
    """One grid step = one whole page of one sequence, all kv heads.

    The page block is ``(1, page_size, Hkv, Dh)``: its last two dims are
    the pool's own, which is what Mosaic requires of a block.  Heads are a
    static loop; each reads its ``(page_size, Dh)`` slice with a static
    strided load.  On an int8 pool (``quantized``) two more blocks carry
    the page's fp32 scales, ``(1, 1, Hkv)``: the K scale folds into the
    logits scale and the V scale multiplies the page's contribution to the
    accumulator, so the pool is never materialized in fp."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    token_idx = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = token_idx < length                         # (1, page_size)
    if window > 0:
        # sliding window, decode semantics: the query sits at position
        # length-1 and sees keys with kv_pos > q_pos - window (matches the
        # dense ``attend`` masking)
        valid = jnp.logical_and(valid, token_idx > length - 1 - window)

    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)            # (G, Dh) query group
        k = k_ref[0, :, h, :].astype(jnp.float32)      # (page_size, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * ks_ref[0, :, h:h + 1]              # (1, 1) page scale
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[h]                              # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if quantized:
            pv = pv * vs_ref[0, :, h:h + 1]
        acc_ref[h] = acc_ref[h] * alpha + pv
        m_ref[h] = m_new

    @pl.when(j == max_pages - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                           k_scale=None, v_scale=None, *,
                           window: int = 0, interpret: bool = False):
    """q (B,Hq,Dh); pools (P,page_size,Hkv,Dh); page_table (B,max_pages).
    ``k_scale``/``v_scale`` (P,Hkv) fp32 switch to the int8-pool kernel
    (dequant-in-register; both or neither must be given)."""
    b, hq, dh = q.shape
    p, page_size, hkv, _ = k_pool.shape
    max_pages = page_table.shape[1]
    group = hq // hkv
    q_g = q.reshape(b, hkv, group, dh)
    quantized = k_scale is not None

    kernel = functools.partial(
        _paged_attn_kernel, page_size=page_size, max_pages=max_pages,
        scale=1.0 / (dh ** 0.5), window=window, quantized=quantized)

    page_spec = pl.BlockSpec((1, page_size, hkv, dh),
                             lambda b_, j, pt, ln: (pt[b_, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, hkv, group, dh), lambda b_, j, pt, ln: (b_, 0, 0, 0)),
        # the dynamic page gather: page index comes from the SMEM table
        page_spec, page_spec,
    ]
    args = [page_table, lengths, q_g, k_pool, v_pool]
    if quantized:
        # the page's scale row rides the same dynamic gather as the page;
        # the unit middle axis makes the block's last two dims the array's
        in_specs += [pl.BlockSpec((1, 1, hkv),
                                  lambda b_, j, pt, ln: (pt[b_, j], 0, 0))] * 2
        args += [k_scale.reshape(p, 1, hkv), v_scale.reshape(p, 1, hkv)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, lengths
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, group, dh),
                               lambda b_, j, pt, ln: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, dh), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dh), q.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(b, hq, dh)
