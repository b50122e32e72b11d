"""Config registry: ``get_config(arch_id)`` / ``get_smoke_config(arch_id)``."""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (
    INPUT_SHAPES,
    InputShape,
    ModelConfig,
    MPICConfig,
    reduced,
)

# arch-id -> module name
ARCH_REGISTRY = {
    "internvl2-76b": "internvl2_76b",
    "phi3-medium-14b": "phi3_medium_14b",
    "yi-9b": "yi_9b",
    "hymba-1.5b": "hymba_1_5b",
    "stablelm-1.6b": "stablelm_1_6b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "mamba2-130m": "mamba2_130m",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "whisper-small": "whisper_small",
    "qwen2.5-14b": "qwen2_5_14b",
    "llava-1.6-7b": "llava_mpic",
}

ASSIGNED_ARCHS = [a for a in ARCH_REGISTRY if a != "llava-1.6-7b"]


def _module(arch_id: str):
    if arch_id not in ARCH_REGISTRY:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {sorted(ARCH_REGISTRY)}")
    return importlib.import_module(f"repro.configs.{ARCH_REGISTRY[arch_id]}")


def get_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).SMOKE_CONFIG


def serving_config(arch_id: str, *, smoke: bool = False,
                   layers: int = 0) -> ModelConfig:
    """The config a server builds: the published one — or, with ``smoke``,
    the reduced CPU preset — cut to ``layers`` decoder layers when
    ``layers > 0``.  Only depth is ever cut; widths stay as published."""
    cfg = get_smoke_config(arch_id) if smoke else get_config(arch_id)
    return dataclasses.replace(cfg, num_layers=layers) if layers else cfg


__all__ = [
    "ARCH_REGISTRY",
    "ASSIGNED_ARCHS",
    "INPUT_SHAPES",
    "InputShape",
    "ModelConfig",
    "MPICConfig",
    "get_config",
    "get_smoke_config",
    "reduced",
    "serving_config",
]
