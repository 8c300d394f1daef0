"""The benchmark's one adapter to the program under test: a configuration
file's published keys become the `LlamaConfig` the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> LlamaConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "ffn",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq",
}


def llama_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json. The architecture
    comes from its published keys; how the program stores weights and in
    which type it computes stay the program's defaults. `train.model`
    (remat, attention_impl, loss_chunk) rides on top for a training
    configuration."""
    from ray_tpu.models.llama import LlamaConfig
    if config.get("tie_word_embeddings"):
        raise ValueError("the program has no tied output head")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update(overrides)
    return LlamaConfig(**fields)
