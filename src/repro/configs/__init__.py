"""Architecture config registry.

``get_config("zamba2-2.7b")`` returns the exact assigned config;
``get_config("zamba2-2.7b", reduced=True)`` returns the CPU smoke-test
variant of the same family.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig, SSMConfig,
                                ShapeConfig, SplitConfig, XLSTMConfig,
                                YaRNConfig, SHAPES, TRAIN_4K, PREFILL_32K,
                                DECODE_32K, LONG_500K)

_ARCH_MODULES: Dict[str, str] = {
    "zamba2-2.7b": "repro.configs.zamba2_2_7b",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "gemma2-9b": "repro.configs.gemma2_9b",
    "llama3-405b": "repro.configs.llama3_405b",
    "qwen2-vl-72b": "repro.configs.qwen2_vl_72b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "nemotron-4-15b": "repro.configs.nemotron_4_15b",
    "llama3.2-3b": "repro.configs.llama3_2_3b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}


def list_archs() -> List[str]:
    return list(_ARCH_MODULES)


def get_config(name: str, reduced: bool = False) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {list_archs()}")
    cfg = importlib.import_module(_ARCH_MODULES[name]).CONFIG
    return cfg.reduced() if reduced else cfg


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


__all__ = [
    "ArchConfig", "MLAConfig", "MoEConfig", "SSMConfig", "XLSTMConfig",
    "SplitConfig", "YaRNConfig",
    "ShapeConfig", "SHAPES", "TRAIN_4K", "PREFILL_32K", "DECODE_32K",
    "LONG_500K", "get_config", "get_shape", "list_archs",
]
