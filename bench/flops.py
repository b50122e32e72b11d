"""Operations and bytes the algorithm needs, computed from shapes.

Counts what the work requires, not what a kernel happens to touch: a
paged-attention call reads the K/V of the tokens each slot holds, not the
bucketed page-table width.  One multiply-add is two operations.

These are the work counts of the dense GQA + SwiGLU block, which
``bench/arch/dense_gqa.py`` exports (another block's architecture file
brings its own).  Every count reads the configuration file's published
keys, never the program's ``ModelConfig``, so a kernel's work is the same
whatever the program builds.  ``roofline_s`` is shared by all blocks.
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2


def _heads(conf: dict):
    """(query heads, KV heads, head width) as published."""
    heads = conf["num_attention_heads"]
    return (heads, conf["num_key_value_heads"],
            conf.get("head_dim") or conf["hidden_size"] // heads)


def layer_params(conf: dict) -> int:
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    heads, kv_heads, dh = _heads(conf)
    q, kv = heads * dh, kv_heads * dh
    n = d * q + 2 * d * kv + q * d + 3 * d * ff
    if conf.get("qkv_bias", False):
        n += q + 2 * kv
    return n


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def attn_flops(conf: dict, ctx: int) -> int:
    """One query token over ``ctx`` keys, one layer: QK^T and PV."""
    heads, _, dh = _heads(conf)
    return 4 * heads * dh * ctx


def token_flops(conf: dict, ctx: int, head: bool) -> int:
    """Model operations of one computed token that attends to ``ctx``
    positions, through every layer, with the LM head when its logits are
    used."""
    f = conf["num_hidden_layers"] * (2 * layer_params(conf)
                                     + attn_flops(conf, ctx))
    if head:
        f += 2 * head_params(conf)
    return f


def paged_attn_call(conf: dict, lengths: Iterable[int]):
    """(flops, bytes) of one layer's paged decode attention over the live
    slots' lengths: each slot's K and V, its query in and output out."""
    lengths = list(lengths)
    heads, kv_heads, dh = _heads(conf)
    tok = sum(lengths)
    fl = attn_flops(conf, tok)
    by = (2 * tok * kv_heads * dh * BF16
          + 2 * len(lengths) * heads * dh * BF16)
    return fl, by


def selective_attn_call(conf: dict, positions: Iterable[int], total: int):
    """(flops, bytes) of one layer's selective prefill attention: each
    recomputed token at position p attends causally to p + 1 keys; the
    prompt's K and V are read once, the queries in and outputs out."""
    positions = list(positions)
    heads, kv_heads, dh = _heads(conf)
    fl = sum(attn_flops(conf, p + 1) for p in positions)
    by = (2 * total * kv_heads * dh * BF16
          + 2 * len(positions) * heads * dh * BF16)
    return fl, by


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
