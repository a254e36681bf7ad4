"""The served system for a Llama-architecture configuration: the program's
``ModelConfig`` built from the configuration file's published keys."""
from __future__ import annotations

from typing import Dict

from repro.config import AttnConfig, ModelConfig


def model_config(spec: Dict) -> ModelConfig:
    d = int(spec["hidden_size"])
    h = int(spec["num_attention_heads"])
    return ModelConfig(
        name=spec["name"],
        family="dense",
        num_layers=int(spec["num_hidden_layers"]),
        d_model=d,
        num_heads=h,
        num_kv_heads=int(spec["num_key_value_heads"]),
        d_ff=int(spec["intermediate_size"]),
        vocab_size=int(spec["vocab_size"]),
        head_dim=int(spec.get("head_dim") or d // h),
        block_pattern=("attn",),
        attn=AttnConfig(kind="full", rope_base=float(spec["rope_theta"])),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        norm_eps=float(spec["rms_norm_eps"]),
        dtype=spec["torch_dtype"],
    )
