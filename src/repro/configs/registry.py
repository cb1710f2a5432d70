"""Architecture registry: ``--arch <id>`` → config + model builders."""

from __future__ import annotations

import importlib

_MODULES = {
    "llama3-8b": "repro.configs.llama3_8b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "stablelm-12b": "repro.configs.stablelm_12b",
    "zamba2-1.2b": "repro.configs.zamba2_1p2b",
    "qwen3-32b": "repro.configs.qwen3_32b",
    "granite-8b": "repro.configs.granite_8b",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "xlstm-1.3b": "repro.configs.xlstm_1p3b",
}

# Beyond the assigned pool: the paper's second evaluation model, and the
# served path's windowed/expert model.
_EXTRA = {"qwen3-8b": "repro.configs.qwen3_8b",
          "smallthinker-21b-a3b": "repro.configs.smallthinker_21b_a3b"}
_MODULES = dict(_MODULES, **_EXTRA)

ARCHS = tuple(m for m in _MODULES if m not in _EXTRA)
ALL_ARCHS = tuple(_MODULES)


def list_archs() -> tuple:
    return ARCHS


def get(arch_id: str):
    """Full-scale config for an assigned architecture."""
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; choose from {ARCHS}")
    return importlib.import_module(_MODULES[arch_id]).config()


def smoke(arch_id: str):
    """Reduced smoke-test config of the same family."""
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; choose from {ARCHS}")
    return importlib.import_module(_MODULES[arch_id]).smoke_config()


def is_whisper(cfg) -> bool:
    return type(cfg).__name__ == "WhisperConfig"


def build_adapter(cfg):
    """Engine adapter for any registered config."""
    if is_whisper(cfg):
        from repro.models.whisper import WhisperAdapter
        return WhisperAdapter(cfg)
    from repro.models.transformer import TransformerAdapter
    return TransformerAdapter(cfg)


def init_params(key, cfg, dtype=None):
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    if is_whisper(cfg):
        from repro.models import whisper
        return whisper.init_params(key, cfg, dtype)
    from repro.models import transformer
    return transformer.init_params(key, cfg, dtype)
