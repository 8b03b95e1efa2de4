"""Architecture registry + the four assigned input shapes.

Every entry cites its source (model card / paper) and matches the assigned
specification exactly.  ``get_config(name)`` returns the full config;
``get_config(name, smoke=True)`` the reduced same-family variant used by the
CPU smoke tests.
"""

from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import ModelConfig

_ARCHS = [
    "llama3_2_1b",
    "qwen2_1_5b",
    "whisper_base",
    "deepseek_v2_lite",
    "deepseek_v2_lite_ep8",
    "xlstm_350m",
    "mixtral_8x7b",
    "deepseek_67b",
    "hymba_1_5b",
    "paligemma_3b",
    "minitron_4b",
]

# public names (assignment ids) -> module names
ALIASES = {
    "llama3.2-1b": "llama3_2_1b",
    "qwen2-1.5b": "qwen2_1_5b",
    "whisper-base": "whisper_base",
    "deepseek-v2-lite-16b": "deepseek_v2_lite",
    "deepseek-v2-lite-16b-ep8": "deepseek_v2_lite_ep8",
    "xlstm-350m": "xlstm_350m",
    "mixtral-8x7b": "mixtral_8x7b",
    "deepseek-67b": "deepseek_67b",
    "hymba-1.5b": "hymba_1_5b",
    "paligemma-3b": "paligemma_3b",
    "minitron-4b": "minitron_4b",
}


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def arch_names() -> list[str]:
    return sorted(ALIASES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod_name = ALIASES.get(name, name.replace("-", "_").replace(".", "_"))
    if mod_name not in _ARCHS:
        raise ValueError(f"unknown architecture {name!r}; known: {arch_names()}")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    cfg: ModelConfig = mod.CONFIG
    return cfg.smoke() if smoke else cfg


def long_context_policy(cfg: ModelConfig) -> str:
    """How this arch runs long_500k (DESIGN.md shape/skip policy).

    'native'  — sub-quadratic by construction (SSM / hybrid / native SWA)
    'swa'     — dense arch served with the sliding-window variant
    """
    if cfg.family in ("ssm", "hybrid"):
        return "native"
    if cfg.sliding_window:
        return "native"
    return "swa"
