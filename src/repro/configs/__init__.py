"""Config registry: ``get_arch(name)``, ``ARCHS``, ``SHAPES``."""
from __future__ import annotations

from .base import ArchConfig, LayerPattern, ShapeConfig, TrainConfig
from .shapes import SHAPES, TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K
from .system import SystemConfig, DEFAULT_SYSTEM, channel_gain, path_loss_db

from . import (
    olmoe_1b_7b,
    mistral_large_123b,
    jamba_1_5_large_398b,
    deepseek_7b,
    internvl2_2b,
    musicgen_large,
    yi_9b,
    mamba2_2_7b,
    minicpm_2b,
    llama4_scout_17b_a16e,
    gpt2_s,
    gpt2_m,
    moonlight_16b_a3b,
)

# The ten assigned architectures (dry-run / roofline targets).
ASSIGNED = (
    olmoe_1b_7b.CONFIG,
    mistral_large_123b.CONFIG,
    jamba_1_5_large_398b.CONFIG,
    deepseek_7b.CONFIG,
    internvl2_2b.CONFIG,
    musicgen_large.CONFIG,
    yi_9b.CONFIG,
    mamba2_2_7b.CONFIG,
    minicpm_2b.CONFIG,
    llama4_scout_17b_a16e.CONFIG,
)

# Paper's own models (benchmarks of Section VII).
PAPER_MODELS = (gpt2_s.CONFIG, gpt2_m.CONFIG)

# Configurations that train only (latent attention has no decode cache).
TRAIN_MODELS = (moonlight_16b_a3b.CONFIG,)

ARCHS = {c.name: c for c in ASSIGNED + PAPER_MODELS + TRAIN_MODELS}


def get_arch(name: str) -> ArchConfig:
    try:
        return ARCHS[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}") from None


def get_shape(name: str) -> ShapeConfig:
    try:
        return SHAPES[name]
    except KeyError:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}") from None


__all__ = [
    "ArchConfig", "LayerPattern", "ShapeConfig", "TrainConfig", "SystemConfig",
    "DEFAULT_SYSTEM", "channel_gain", "path_loss_db",
    "SHAPES", "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "ASSIGNED", "PAPER_MODELS", "TRAIN_MODELS", "ARCHS", "get_arch",
    "get_shape",
]
