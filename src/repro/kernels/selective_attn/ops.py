"""jit'd public wrapper for the selective-attention kernel.

Accepts the model's (B, S, H, Dh) layout, pads sequences to block
multiples (padding KV slots get INVALID_POS so they are masked out;
padding query rows are discarded after the call), transposes to the
kernel's (B, H, S, Dh) layout, and dispatches to Pallas — interpret mode
off the TPU, compiled Mosaic on it (``interpret=None`` lets
:func:`repro.kernels.platform.pallas_interpret` decide).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attn.ops import head_shard_axis
from repro.kernels.platform import resolve_interpret
from repro.kernels.selective_attn.ref import (
    selective_attention_paged_ref,
    selective_attention_ref,
)
from repro.kernels.selective_attn.selective_attn import (
    INVALID_POS,
    selective_attention_paged_pallas,
    selective_attention_pallas,
)


def _pad_to(x, axis, mult, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# VMEM the paged prefill kernel's per-step blocks and scratch may take: the
# query and output blocks (double-buffered) plus the fp32 accumulator and
# the lane-padded running max/sum, all spanning every head.  Under the
# 16 MiB default scoped VMEM of a v5e core, with room for the page blocks.
_PREFILL_VMEM_BUDGET = 12 * 2 ** 20


def _prefill_block_q(sq: int, hq: int, dh: int, itemsize: int,
                     block_q: int) -> int:
    """Query rows per grid step: at most ``block_q``, at least 8, and few
    enough that one step's all-heads blocks fit the VMEM budget."""
    row_bytes = hq * (4 * dh * itemsize + 4 * dh + 2 * 128 * 4)
    max_rows = max(8, (_PREFILL_VMEM_BUDGET // row_bytes) // 8 * 8)
    return min(block_q, max(8, sq), max_rows)


def selective_attention_paged_call(q, k_pool, v_pool, page_table, q_pos,
                                   lengths, *, k_scale=None, v_scale=None,
                                   window: int = 0,
                                   block_q: int = 128, backend: str = "ref",
                                   interpret=None):
    """Paged selective-prefill attention — dispatch without jit.

    Accepts the model's (B, Sq, Hq, Dh) query layout and returns the same;
    K/V are read through ``page_table`` from the (P, page_size, Hkv, Dh)
    pool slices.  ``k_scale``/``v_scale`` (P, Hkv) fp32 select the int8
    pool path (dequant fused in the kernel/oracle).  Safe to trace inside
    scan/jit (the engine's donated prefill step traces it under
    ``lax.scan`` over layers).
    """
    b, sq, hq, dh = q.shape
    qt = jnp.moveaxis(q, 2, 1)
    if backend == "ref":
        out = selective_attention_paged_ref(
            qt, k_pool, v_pool, page_table, q_pos, lengths,
            k_scale, v_scale, window=window)
        return jnp.moveaxis(out, 1, 2)
    bq = _prefill_block_q(sq, hq, dh, q.dtype.itemsize, block_q)
    qt = _pad_to(qt, 2, bq)
    # padding query rows: q_pos 0 yields a garbage-but-finite row that the
    # caller slices off (their K/V never reach the pool)
    q_pos_p = _pad_to(q_pos, 1, bq, value=0)
    fn = functools.partial(selective_attention_paged_pallas, window=window,
                           block_q=bq, interpret=resolve_interpret(interpret))
    args = (qt, k_pool, v_pool, page_table, q_pos_p, lengths)
    mesh, ax = head_shard_axis(hq, k_pool.shape[2])
    in_specs = (P(None, ax, None, None), P(None, None, ax, None),
                P(None, None, ax, None), P(None, None), P(None, None),
                P(None))
    if k_scale is not None:
        args += (k_scale, v_scale)
        in_specs += (P(None, ax), P(None, ax))
    if mesh is not None:
        # mesh-sharded serving: the paged prefill kernel is embarrassingly
        # parallel across kv-head shards (see paged_attn.ops) — run it
        # per-device under shard_map instead of asking GSPMD to partition
        # the pallas call
        fn = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs,
            out_specs=P(None, ax, None, None), check_vma=False)
    out = fn(*args)
    return jnp.moveaxis(out[:, :, :sq, :], 1, 2)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_q", "interpret",
                                    "use_ref"))
def selective_attention_paged(q, k_pool, v_pool, page_table, q_pos, lengths,
                              *, k_scale=None, v_scale=None,
                              window: int = 0, block_q: int = 128,
                              interpret=None, use_ref: bool = False):
    """Standalone jit'd paged selective attention (kernel tests, ad-hoc)."""
    return selective_attention_paged_call(
        q, k_pool, v_pool, page_table, q_pos, lengths,
        k_scale=k_scale, v_scale=v_scale, window=window,
        block_q=block_q, backend="ref" if use_ref else "pallas",
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret", "use_ref"))
def selective_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret=None, use_ref: bool = False):
    """q (B,Sq,Hq,Dh), k/v (B,Skv,Hkv,Dh), q_pos (B,Sq), kv_pos (B,Skv).

    Returns (B, Sq, Hq, Dh).  ``interpret=None`` runs the kernel compiled
    on a TPU and in the Pallas interpreter elsewhere.
    """
    b, sq, hq, dh = q.shape
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if use_ref:
        out = selective_attention_ref(qt, kt, vt, q_pos, kv_pos, window=window)
        return jnp.moveaxis(out, 1, 2)

    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, kt.shape[2]))
    qt = _pad_to(qt, 2, bq)
    kt = _pad_to(kt, 2, bk)
    vt = _pad_to(vt, 2, bk)
    q_pos_p = _pad_to(q_pos, 1, bq, value=0)
    kv_pos_p = _pad_to(kv_pos, 1, bk, value=INVALID_POS)

    out = selective_attention_pallas(
        qt, kt, vt, q_pos_p, kv_pos_p, window=window,
        block_q=bq, block_k=bk, interpret=resolve_interpret(interpret))
    return jnp.moveaxis(out[:, :, :sq, :], 1, 2)
