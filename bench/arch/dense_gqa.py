"""The dense GQA + SwiGLU block of both served configurations (Mistral-7B
and Qwen2-7B as published): the architecture file of a configuration that
names none under ``arch``.

An architecture file exports the plain reference, ``Arch.from_config(conf)``
and ``Reference(params, arch, image_fn, k=, lowp=)`` with
``.logits(item)``, importing nothing of ``repro``; the work counts
``head_params(conf)``, ``token_flops(conf, ctx, head)``,
``paged_attn_call(conf, lengths)`` and ``selective_attn_call(conf,
positions, total)``, read from the configuration file's published keys;
and, where the block needs them, ``PROGRAM`` (``ModelConfig`` fields that
the published keys do not set, such as ``arch_type``) and ``STD`` (leaf
name -> standard deviation of its seeded weights, for leaves the rules of
``bench/weights.py`` miss, such as a router's bias).
"""
from bench.flops import (head_params, paged_attn_call,  # noqa: F401
                         selective_attn_call, token_flops)
from bench.reference import Arch, Reference  # noqa: F401
