"""Config registry: ``get_config(name)`` / ``--arch <id>`` resolution."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (DecodeConfig, DegradeConfig, EncDecConfig,
                                ExecutionConfig, LadderRung, MLAConfig,
                                ModelConfig, MoEConfig, RouterConfig,
                                SSMConfig, ServerConfig, SupervisorConfig,
                                TrainConfig, default_block_size)

# arch id -> module (one file per assigned architecture + the paper's own)
_MODULES: Dict[str, str] = {
    "whisper-medium":   "repro.configs.whisper_medium",
    "mixtral-8x22b":    "repro.configs.mixtral_8x22b",
    "stablelm-12b":     "repro.configs.stablelm_12b",
    "stablelm-3b":      "repro.configs.stablelm_3b",
    "qwen3-14b":        "repro.configs.qwen3_14b",
    "xlstm-125m":       "repro.configs.xlstm_125m",
    "chatglm3-6b":      "repro.configs.chatglm3_6b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "hymba-1.5b":       "repro.configs.hymba_1_5b",
    "qwen2-vl-72b":     "repro.configs.qwen2_vl_72b",
    "llada-8b":         "repro.configs.llada_8b",
    "sdar-30b-a3b":     "repro.configs.sdar_30b_a3b",
}

# the pool the dry-run and the per-arch smoke tests cover; the diffusion
# LMs the decoders serve are not in it
ASSIGNED_ARCHS: List[str] = [k for k in _MODULES
                             if k not in ("llada-8b", "sdar-30b-a3b")]


def get_config(name: str) -> ModelConfig:
    """A config by arch id; ``<id>-tiny`` is its CPU test preset: the
    module's ``TINY`` where it has one, else ``CONFIG.reduced()``."""
    base = name[: -len("-tiny")] if name.endswith("-tiny") else name
    if base not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(_MODULES)}")
    module = importlib.import_module(_MODULES[base])
    if base == name:
        return module.CONFIG
    return getattr(module, "TINY", None) or module.CONFIG.reduced()


def list_configs() -> List[str]:
    return sorted(_MODULES)


__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "SSMConfig", "EncDecConfig",
    "DecodeConfig", "ExecutionConfig", "TrainConfig", "ServerConfig",
    "RouterConfig",
    "SupervisorConfig", "DegradeConfig", "LadderRung",
    "default_block_size",
    "get_config", "list_configs", "ASSIGNED_ARCHS",
]
