"""Operations and bytes the algorithm needs, computed from shapes.

Counts what the work requires, not what a kernel happens to touch: a
paged-attention call reads the K/V of the tokens each slot holds, not the
bucketed page-table width.  One multiply-add is two operations.
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2


def layer_params(cfg) -> int:
    d, q, kv, ff = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    n = d * q + 2 * d * kv + q * d + 3 * d * ff
    if cfg.qkv_bias:
        n += q + 2 * kv
    return n


def head_params(cfg) -> int:
    return cfg.d_model * cfg.vocab_size


def attn_flops(cfg, ctx: int) -> int:
    """One query token over ``ctx`` keys, one layer: QK^T and PV."""
    return 4 * cfg.num_heads * cfg.head_dim * ctx


def token_flops(cfg, ctx: int, head: bool) -> int:
    """Model operations of one computed token that attends to ``ctx``
    positions, through every layer, with the LM head when its logits are
    used."""
    f = cfg.num_layers * (2 * layer_params(cfg) + attn_flops(cfg, ctx))
    if head:
        f += 2 * head_params(cfg)
    return f


def paged_attn_call(cfg, lengths: Iterable[int]):
    """(flops, bytes) of one layer's paged decode attention over the live
    slots' lengths: each slot's K and V, its query in and output out."""
    lengths = list(lengths)
    tok = sum(lengths)
    fl = attn_flops(cfg, tok)
    by = (2 * tok * cfg.num_kv_heads * cfg.head_dim * BF16
          + 2 * len(lengths) * cfg.num_heads * cfg.head_dim * BF16)
    return fl, by


def selective_attn_call(cfg, positions: Iterable[int], total: int):
    """(flops, bytes) of one layer's selective prefill attention: each
    recomputed token at position p attends causally to p + 1 keys; the
    prompt's K and V are read once, the queries in and outputs out."""
    positions = list(positions)
    fl = sum(attn_flops(cfg, p + 1) for p in positions)
    by = (2 * total * cfg.num_kv_heads * cfg.head_dim * BF16
          + 2 * len(positions) * cfg.num_heads * cfg.head_dim * BF16)
    return fl, by


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
