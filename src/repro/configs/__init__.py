"""Assigned-architecture configs (11 archs) + shape sets.

``get_config(arch_id)`` returns the exact published config;
``get_reduced(arch_id)`` the smoke-test reduction of the same family.
"""
from .base import ARCHS, MLAConfig, ModelConfig, MoEConfig, SSMConfig, YarnConfig  # noqa: F401

# importing each module populates ARCHS
from . import (  # noqa: F401,E402
    deepseek_67b,
    deepseek_v2_lite,
    internvl2_26b,
    mamba2_2_7b,
    minicpm3_4b,
    qwen1_5_0_5b,
    qwen2_0_5b,
    qwen2_moe_a2_7b,
    qwen3_moe_235b,
    whisper_small,
    zamba2_2_7b,
)
from .shapes import SHAPES, ShapeSpec, all_cells, cell_applicable  # noqa: F401,E402

ARCH_IDS = tuple(sorted(ARCHS))


def get_config(arch_id: str) -> ModelConfig:
    try:
        return ARCHS[arch_id]["full"]
    except KeyError:
        raise KeyError(f"unknown arch {arch_id!r}; available: {ARCH_IDS}") from None


def get_reduced(arch_id: str) -> ModelConfig:
    try:
        return ARCHS[arch_id]["reduced"]
    except KeyError:
        raise KeyError(f"unknown arch {arch_id!r}; available: {ARCH_IDS}") from None
