"""The benchmark's adapter for the GraniteHybrid family: a configuration
file's published keys become the `GraniteHybridConfig` the program
takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> GraniteHybridConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "layer_types": "layer_types",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "shared_intermediate_size": "ffn",
    "mamba_n_heads": "mamba_heads",
    "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "ssm_state",
    "mamba_n_groups": "n_groups",
    "mamba_d_conv": "d_conv",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier",
    "logits_scaling": "logits_scaling",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq",
}
# what the program has one form of: a file that says otherwise is refused
ONE_FORM = (("model_type", "granitemoehybrid"), ("hidden_act", "silu"),
            ("normalization_function", "rmsnorm"),
            ("position_embedding_type", "nope"),
            ("tie_word_embeddings", True), ("attention_bias", False),
            ("mamba_proj_bias", False), ("mamba_conv_bias", True),
            ("num_local_experts", 0), ("num_experts_per_tok", 0))


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family."""
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig
    for key, want in ONE_FORM:
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree on "
                         "the depth")
    if config["mamba_n_heads"] * config["mamba_d_head"] \
            != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields["layer_types"] = tuple(fields["layer_types"])
    fields.update(overrides)
    return GraniteHybridConfig(**fields)


def published_keys(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a
    `GraniteHybridConfig` (the tests' way round: a toy configuration has
    no file)."""
    out = {theirs: getattr(cfg, ours) for theirs, ours in MODEL_KEYS.items()}
    out["layer_types"] = list(out["layer_types"])
    return out
