"""Architecture config registry.

Each assigned architecture lives in its own module exposing ``CONFIG`` (the
full published config) and ``smoke_config()`` (a reduced same-family config
for CPU smoke tests).  ``get(name)`` / ``list_archs()`` are the public API.
"""
from __future__ import annotations

import importlib

from repro.core.types import ArchConfig, SHAPES, ShapeConfig  # noqa: F401

_ARCH_MODULES = [
    "qwen3_14b",
    "minicpm_2b",
    "minicpm3_4b",
    "mistral_nemo_12b",
    "llava_next_34b",
    "zamba2_1p2b",
    "rwkv6_1p6b",
    "qwen3_moe_235b_a22b",
    "qwen3_moe_30b_a3b",
    "whisper_small",
    # paper CNNs
    "chaos_small",
    "chaos_medium",
    "chaos_large",
    # ImageNet-size image backbone
    "convnext_tiny",
    # transformer-scale CHAOS bench net (DESIGN.md §10)
    "lm_bench",
]

_ALIAS = {m.replace("_", "-"): m for m in _ARCH_MODULES}
_ALIAS.update({
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "zamba2-1.2b": "zamba2_1p2b",
    "rwkv6-1.6b": "rwkv6_1p6b",
})


def _module(name: str):
    key = name.replace("-", "_").replace(".", "p")
    if name in _ALIAS:
        key = _ALIAS[name]
    return importlib.import_module(f"repro.configs.{key}")


def get(name: str) -> ArchConfig:
    return _module(name).CONFIG


def smoke(name: str) -> ArchConfig:
    return _module(name).smoke_config()


def list_archs():
    return [m.replace("_", "-").replace("1p", "1.") for m in _ARCH_MODULES]


ASSIGNED = [
    "qwen3-14b",
    "minicpm-2b",
    "minicpm3-4b",
    "mistral-nemo-12b",
    "llava-next-34b",
    "zamba2-1.2b",
    "rwkv6-1.6b",
    "qwen3-moe-235b-a22b",
    "qwen3-moe-30b-a3b",
    "whisper-small",
]
