"""The benchmark's adapter for the Phi4Flash family: a configuration
file's published keys and its `assumed` Mamba sizes become the
`Phi4FlashConfig` the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> Phi4FlashConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "ffn",
    "sliding_window": "sliding_window",
    "mb_per_layer": "mb_per_layer",
    "layer_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq",
}
# `assumed` key (the phi4flash configuration class's default, not in the
# published file) -> Phi4FlashConfig field
ASSUMED_KEYS = {
    "mamba_d_state": "d_state",
    "mamba_d_conv": "d_conv",
    "mamba_expand": "expand",
    "mamba_dt_rank": "dt_rank",
}


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family."""
    from ray_tpu.models.phi4flash import Phi4FlashConfig
    for key, want in (("model_type", "phi4flash"), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True), ("mlp_bias", False),
                      ("lm_head_bias", False), ("embd_pdrop", 0),
                      ("resid_pdrop", 0)):
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update({ours: config["assumed"][theirs]["value"]
                   for theirs, ours in ASSUMED_KEYS.items()})
    fields.update(overrides)
    return Phi4FlashConfig(**fields)


def published_keys(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a `Phi4FlashConfig`
    (the tests' way round: a toy configuration has no file)."""
    return {theirs: getattr(cfg, ours) for theirs, ours in MODEL_KEYS.items()}
