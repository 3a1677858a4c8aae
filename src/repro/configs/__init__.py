"""Assigned architecture configs.

``get_config(name)`` returns the exact published configuration;
``get_smoke_config(name)`` returns a reduced same-family variant for CPU
smoke tests (small width/depth/experts/vocab, identical code paths).
"""

from __future__ import annotations

import importlib

from repro.configs.base import (
    AttnConfig,
    MambaConfig,
    ModelConfig,
    MoEConfig,
    LayerSpec,
    ParallelConfig,
    ShapeSpec,
    SHAPES,
)

ARCH_IDS = [
    "deepseek_v2_236b",
    "deepseek_v2_lite",
    "dbrx_132b",
    "jamba_1_5_large_398b",
    "musicgen_large",
    "gemma_7b",
    "yi_6b",
    "minicpm3_4b",
    "h2o_danube_3_4b",
    "qwen2_vl_7b",
    "falcon_mamba_7b",
]

# canonical dashed ids (CLI --arch) -> module name
ALIASES = {a.replace("_", "-"): a for a in ARCH_IDS}


def _module(name: str):
    name = ALIASES.get(name, name)
    if name not in ARCH_IDS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ALIASES)}")
    return importlib.import_module(f"repro.configs.{name}")


def get_config(name: str) -> ModelConfig:
    return _module(name).CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    return _module(name).SMOKE


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
