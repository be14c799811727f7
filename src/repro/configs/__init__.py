"""Config registry: one module per assigned architecture.

``get_config(name)`` returns the full-size config; ``get_smoke(name)`` a
reduced same-family variant for CPU smoke tests.
"""
from __future__ import annotations

import importlib

from repro.configs.base import FedConfig, InputShape, ModelConfig, INPUT_SHAPES  # noqa: F401

ARCH_IDS = [
    "llava_next_34b",
    "phi3_mini_3_8b",
    "jamba_1_5_large_398b",
    "minicpm3_4b",
    "qwen2_5_3b",
    "whisper_medium",
    "xlstm_125m",
    "deepseek_moe_16b",
    "granite_moe_3b_a800m",
    "qwen1_5_0_5b",
    "deepseek_v2_lite",
]

# dashed aliases matching the assignment table
ALIASES = {
    "llava-next-34b": "llava_next_34b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "minicpm3-4b": "minicpm3_4b",
    "qwen2.5-3b": "qwen2_5_3b",
    "whisper-medium": "whisper_medium",
    "xlstm-125m": "xlstm_125m",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def _module(name: str):
    name = ALIASES.get(name, name)
    assert name in ARCH_IDS, f"unknown arch {name!r}; known: {ARCH_IDS}"
    return importlib.import_module(f"repro.configs.{name}")


def get_config(name: str) -> ModelConfig:
    return _module(name).CONFIG


def get_smoke(name: str) -> ModelConfig:
    return _module(name).smoke_config()
