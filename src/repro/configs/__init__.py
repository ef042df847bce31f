"""Architecture registry.

``get_config(arch_id)`` returns the full (production) config; ``get_reduced``
returns the CPU smoke-test variant of the same family; ``get_chip_share``
returns one chip's share of the config's stated deployment, for the
families that have one.
"""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig  # noqa: F401
from repro.configs import (
    granite3_8b,
    granite_moe_1b,
    mamba2_2_7b,
    minitron_4b,
    nemotron_4_15b,
    phi3_5_moe,
    phi3_vision_4_2b,
    stablelm_12b,
    starcoder2_3b,
    whisper_large_v3,
    zamba2_2_7b,
)

_MODULES = {
    "stablelm-12b": stablelm_12b,
    "nemotron-4-15b": nemotron_4_15b,
    "mamba2-2.7b": mamba2_2_7b,
    "starcoder2-3b": starcoder2_3b,
    "whisper-large-v3": whisper_large_v3,
    "phi3.5-moe-42b-a6.6b": phi3_5_moe,
    "minitron-4b": minitron_4b,
    "zamba2-2.7b": zamba2_2_7b,
    "phi-3-vision-4.2b": phi3_vision_4_2b,
    "granite-moe-1b-a400m": granite_moe_1b,
    # the paper's own model (not part of the assigned pool of 10)
    "granite-3.2-8b": granite3_8b,
}

ASSIGNED_ARCHS: List[str] = [k for k in _MODULES if k != "granite-3.2-8b"]


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; choose from {sorted(_MODULES)}")
    return _MODULES[arch_id].CONFIG


def get_reduced(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; choose from {sorted(_MODULES)}")
    return _MODULES[arch_id].reduced()


def get_chip_share(arch_id: str) -> ModelConfig:
    mod = _MODULES.get(arch_id)
    if mod is None or not hasattr(mod, "chip_share"):
        have = sorted(k for k, m in _MODULES.items()
                      if hasattr(m, "chip_share"))
        raise KeyError(f"no one-chip share for {arch_id!r}; have {have}")
    return mod.chip_share()


def all_configs() -> Dict[str, ModelConfig]:
    return {k: m.CONFIG for k, m in _MODULES.items()}


def get_input_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]
