"""Architecture config registry: ``get_config(arch_id)`` / ``ARCHS``."""

from __future__ import annotations

import dataclasses
from importlib import import_module

ARCHS = [
    "granite-moe-3b-a800m",
    "dbrx-132b",
    "olmo-1b",
    "llama3_2-3b",
    "qwen2-1_5b",
    "gemma-2b",
    "recurrentgemma-2b",
    "hubert-xlarge",
    "mamba2-1_3b",
    "internvl2-26b",
    "moonlight-16b-a3b",
]

_ALIASES = {
    "llama3.2-3b": "llama3_2-3b",
    "qwen2-1.5b": "qwen2-1_5b",
    "mamba2-1.3b": "mamba2-1_3b",
}


def canonical(arch: str) -> str:
    return _ALIASES.get(arch, arch)


def _build(arch: str, factory: str, overrides: dict):
    mod = import_module(
        f"repro.configs.{canonical(arch).replace('-', '_')}")
    cfg = getattr(mod, factory)()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def get_config(arch: str, **overrides):
    return _build(arch, "config", overrides)


def get_smoke_config(arch: str, **overrides):
    return _build(arch, "smoke_config", overrides)
