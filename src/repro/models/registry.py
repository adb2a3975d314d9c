"""--arch registry: id -> ArchConfig -> model."""
from __future__ import annotations

from typing import TYPE_CHECKING, Dict, List

import jax.numpy as jnp

if TYPE_CHECKING:  # avoid circular import (configs.base imports models.lm)
    from repro.configs.base import ArchConfig

_MODULES = {
    "command-r-plus-104b": "repro.configs.command_r_plus_104b",
    "smollm-135m": "repro.configs.smollm_135m",
    "glm4-9b": "repro.configs.glm4_9b",
    "qwen2.5-14b": "repro.configs.qwen25_14b",
    "jamba-v0.1-52b": "repro.configs.jamba_v01_52b",
    "jamba2-3b": "repro.configs.jamba2_3b",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi35_moe_42b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "mamba-130m": "repro.configs.mamba_130m",
}

_cache: Dict[str, "ArchConfig"] = {}


def list_archs() -> List[str]:
    return list(_MODULES)


def get_config(arch_id: str) -> "ArchConfig":
    if arch_id not in _cache:
        smoke = arch_id.endswith("-smoke")
        base_id = arch_id[:-6] if smoke else arch_id
        if base_id not in _MODULES:
            raise KeyError(f"unknown arch {arch_id!r}; known: {list_archs()}")
        import importlib

        cfg = importlib.import_module(_MODULES[base_id]).CONFIG
        _cache[arch_id] = cfg.smoke() if smoke else cfg
    return _cache[arch_id]


def build_model(arch_id: str, *, dtype=jnp.bfloat16, remat: str = "full",
                scan_layers: bool = True):
    return get_config(arch_id).build(dtype=dtype, remat=remat,
                                     scan_layers=scan_layers)
