"""Architecture registry: ``--arch <id>`` resolution for every entry point."""

from __future__ import annotations

from . import (
    deepseek_7b,
    deepseek_coder_33b,
    deepseek_v3_671b,
    granite_moe_3b,
    hubert_xlarge,
    llama32_vision_90b,
    minitron_8b,
    moonlight_16b_a3b,
    qwen2_5_32b,
    recurrentgemma_9b,
    rwkv6_7b,
)
from .base import ModelConfig, ShapeSpec, SHAPES, applicable_shapes

_MODULES = {
    m.ARCH_ID: m
    for m in (
        minitron_8b,
        deepseek_7b,
        deepseek_coder_33b,
        qwen2_5_32b,
        rwkv6_7b,
        deepseek_v3_671b,
        granite_moe_3b,
        hubert_xlarge,
        recurrentgemma_9b,
        llama32_vision_90b,
        moonlight_16b_a3b,
    )
}

ARCHS: tuple[str, ...] = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(ARCHS)}")
    return _MODULES[arch].config()


def get_smoke_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(ARCHS)}")
    return _MODULES[arch].smoke_config()


def cells(archs: tuple[str, ...] = ARCHS) -> list[tuple[str, ShapeSpec]]:
    """All runnable (arch, shape) cells after the DESIGN.md §4 skips."""
    out = []
    for a in archs:
        cfg = get_config(a)
        for s in applicable_shapes(cfg):
            out.append((a, s))
    return out
