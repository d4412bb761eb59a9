"""Architecture registry: --arch <id> -> ModelConfig (+ reduced smoke)."""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig
from repro.configs.shapes import SHAPES, ShapeSpec, cell_applicable, all_cells

_MODULES = {
    "mixtral-8x7b": "mixtral_8x7b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "stablelm-12b": "stablelm_12b",
    "granite-8b": "granite_8b",
    "gemma3-12b": "gemma3_12b",
    "smollm-135m": "smollm_135m",
    "mamba2-780m": "mamba2_780m",
    "llava-next-34b": "llava_next_34b",
    "hymba-1.5b": "hymba_1_5b",
    "mellum2-12b": "mellum2_12b",
}

ARCHS = list(_MODULES)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {ARCHS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.SMOKE if smoke else mod.CONFIG
