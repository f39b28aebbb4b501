"""Config registry: ``get_config(arch_id)`` / ``--arch`` selection."""
from __future__ import annotations

import dataclasses

from repro.configs import (
    deepseek_v3_671b,
    granite_20b,
    internlm2_20b,
    internvl2_26b,
    nemotron4_15b,
    paper_mlp,
    phi3_mini_3p8b,
    qwen3_moe_30b_a3b,
    rwkv6_7b,
    whisper_medium,
    zamba2_2p7b,
)

from repro.configs.base import (
    INPUT_SHAPES,
    ModelConfig,
    ShapeConfig,
    TrainConfig,
    VFLConfig,
    reduced,
)

ARCH_REGISTRY: dict[str, ModelConfig] = {
    m.CONFIG.arch_id: m.CONFIG
    for m in (
        internvl2_26b,
        zamba2_2p7b,
        qwen3_moe_30b_a3b,
        deepseek_v3_671b,
        internlm2_20b,
        granite_20b,
        rwkv6_7b,
        whisper_medium,
        phi3_mini_3p8b,
        nemotron4_15b,
    )
}

PAPER_MLP = paper_mlp.CONFIG


def list_archs() -> list[str]:
    return sorted(ARCH_REGISTRY)


def get_config(arch_id: str) -> ModelConfig:
    try:
        return ARCH_REGISTRY[arch_id]
    except KeyError:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {', '.join(list_archs())}"
        ) from None


def driver_config(arch_id: str, *, use_reduced: bool = True,
                  n_layers: int = 0) -> ModelConfig:
    """The config a launch driver runs.

    ``use_reduced`` takes the CPU smoke variant (:func:`reduced`);
    otherwise every published width is kept. ``n_layers`` > 0 then
    replaces the depth and nothing else."""
    cfg = get_config(arch_id)
    if use_reduced:
        cfg = reduced(cfg)
    if n_layers < 0:
        raise ValueError(f"n_layers must be >= 0, got {n_layers}")
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def get_shape(name: str) -> ShapeConfig:
    try:
        return INPUT_SHAPES[name]
    except KeyError:
        raise KeyError(
            f"unknown shape {name!r}; available: {', '.join(sorted(INPUT_SHAPES))}"
        ) from None


__all__ = [
    "ARCH_REGISTRY",
    "INPUT_SHAPES",
    "ModelConfig",
    "PAPER_MLP",
    "ShapeConfig",
    "TrainConfig",
    "VFLConfig",
    "driver_config",
    "get_config",
    "get_shape",
    "list_archs",
    "reduced",
]
