"""The program's ``ModelConfig`` for a dense grouped-query-attention decoder
(Llama, Qwen2, Yi), from a configuration file's Hugging Face keys."""
from __future__ import annotations

from repro.configs.base import LayerSpec, ModelConfig


def model_config(spec: dict) -> ModelConfig:
    if spec.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{spec['name']}: only SwiGLU FFNs are dense_gqa")
    if float(spec["rms_norm_eps"]) != 1e-6:
        raise ValueError(f"{spec['name']}: the program's RMSNorm eps is 1e-6")
    heads = int(spec["num_attention_heads"])
    d = int(spec["hidden_size"])
    if int(spec.get("head_dim") or d // heads) * heads != d:
        raise ValueError(f"{spec['name']}: head_dim * heads != hidden_size")
    return ModelConfig(
        name=spec["name"],
        arch_type="dense",
        citation=spec["source"],
        num_layers=int(spec["num_hidden_layers"]),
        d_model=d,
        num_heads=heads,
        num_kv_heads=int(spec["num_key_value_heads"]),
        d_ff=int(spec["intermediate_size"]),
        vocab_size=int(spec["vocab_size"]),
        qkv_bias=bool(spec["attention_bias"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        rope_theta=float(spec["rope_theta"]),
        block_pattern=(LayerSpec(),),
        dtype=spec["torch_dtype"],
        param_dtype=spec["torch_dtype"],
    )
