"""Assigned architecture configs.

``get_config(arch_id)`` returns the exact assigned ``ArchConfig``.
"""

from __future__ import annotations

import importlib

from repro.models.config import ArchConfig

ARCH_IDS = [
    "gemma2_2b",
    "deepseek_67b",
    "llama3_2_3b",
    "granite_8b",
    "whisper_large_v3",
    "kimi_k2_1t_a32b",
    "granite_moe_3b_a800m",
    "jamba_v0_1_52b",
    "llava_next_mistral_7b",
    "falcon_mamba_7b",
    "granite_4_0_h_small",
]

# Canonical hyphenated ids from the assignment → module names.
ALIASES = {
    "gemma2-2b": "gemma2_2b",
    "deepseek-67b": "deepseek_67b",
    "llama3.2-3b": "llama3_2_3b",
    "granite-8b": "granite_8b",
    "whisper-large-v3": "whisper_large_v3",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "granite-4.0-h-small": "granite_4_0_h_small",
}


def get_config(arch_id: str) -> ArchConfig:
    mod_name = ALIASES.get(arch_id, arch_id).replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def all_configs() -> dict[str, ArchConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
