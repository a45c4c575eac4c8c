"""Architecture registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

from .base import ModelConfig

from . import (
    arctic_480b,
    command_r_35b,
    gemma2_27b,
    granite_moe_3b,
    llama32_vision_11b,
    mamba2_130m,
    musicgen_medium,
    qwen2_72b,
    tinyllama_1b,
    zamba2_7b,
)

ARCHS = {
    "gemma2-27b": gemma2_27b.config,
    "command-r-35b": command_r_35b.config,
    "mamba2-130m": mamba2_130m.config,
    "llama-3.2-vision-11b": llama32_vision_11b.config,
    "granite-moe-3b-a800m": granite_moe_3b.config,
    "qwen2-72b": qwen2_72b.config,
    "tinyllama-1.1b": tinyllama_1b.config,
    "musicgen-medium": musicgen_medium.config,
    "zamba2-7b": zamba2_7b.config,
    "arctic-480b": arctic_480b.config,
}


def get_config(arch: str, *, reduced: bool = False) -> ModelConfig:
    if arch not in ARCHS:
        raise ValueError(f"unknown arch {arch!r}; have {sorted(ARCHS)}")
    cfg = ARCHS[arch]()
    return cfg.reduced() if reduced else cfg


__all__ = ["ARCHS", "ModelConfig", "get_config"]
