"""Architecture registry: ``--arch <id>`` → ModelConfig (full + reduced smoke)."""
from __future__ import annotations

import importlib
from typing import Callable, Dict, List, Tuple

from repro.configs.base import ModelConfig, ShapeConfig, SHAPES, shape_applicable

_ARCH_MODULES: Dict[str, str] = {
    "llava-next-mistral-7b": "repro.configs.llava_next_mistral_7b",
    "zamba2-2.7b": "repro.configs.zamba2_2p7b",
    "mamba2-1.3b": "repro.configs.mamba2_1p3b",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "smollm-360m": "repro.configs.smollm_360m",
    "llama3.2-1b": "repro.configs.llama3p2_1b",
    "olmo-1b": "repro.configs.olmo_1b",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "grok-1-314b": "repro.configs.grok1_314b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "granite-4.0-h-small": "repro.configs.granite4h_small",
}

ARCH_IDS: Tuple[str, ...] = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def get_reduced(arch: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return importlib.import_module(_ARCH_MODULES[arch]).reduced()


def all_cells() -> List[Tuple[str, ShapeConfig, bool, str]]:
    """Every (arch, shape) cell with (runs?, skip_reason)."""
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            ok, why = shape_applicable(cfg, shape)
            cells.append((arch, shape, ok, why))
    return cells
