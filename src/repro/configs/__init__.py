"""Architecture registry: one module per assigned architecture.

Usage:  from repro.configs import get_config;  cfg = get_config("tinyllama-1.1b")
"""
from __future__ import annotations

from repro.config import ModelConfig

from repro.configs.arctic_480b import CONFIG as _arctic
from repro.configs.rwkv6_3b import CONFIG as _rwkv6
from repro.configs.qwen15_05b import CONFIG as _qwen
from repro.configs.stablelm_12b import CONFIG as _stablelm
from repro.configs.musicgen_large import CONFIG as _musicgen
from repro.configs.tinyllama_11b import CONFIG as _tinyllama
from repro.configs.llava_next_mistral_7b import CONFIG as _llava
from repro.configs.deepseek_67b import CONFIG as _ds67
from repro.configs.hymba_15b import CONFIG as _hymba
from repro.configs.deepseek_v3_671b import CONFIG as _dsv3
from repro.configs.mellum2_12b import CONFIG as _mellum2

ARCHITECTURES = {
    c.name: c
    for c in [
        _arctic,
        _rwkv6,
        _qwen,
        _stablelm,
        _musicgen,
        _tinyllama,
        _llava,
        _ds67,
        _hymba,
        _dsv3,
        _mellum2,
    ]
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHITECTURES:
        raise KeyError(
            f"unknown arch {name!r}; available: {sorted(ARCHITECTURES)}"
        )
    return ARCHITECTURES[name]


def list_architectures():
    return sorted(ARCHITECTURES)
