"""The benchmark's adapter for the Trinity family: a configuration
file's published keys, its `deployment` and its `published` values
become the `TrinityConfig` the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> TrinityConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "num_dense_layers": "n_dense_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "ffn",
    "moe_intermediate_size": "moe_ffn",
    "num_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "route_scale": "route_scale",
    "route_norm": "route_norm",
    "global_attn_every_n_layers": "period",
    "sliding_window": "sliding_window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "mup_enabled": "mup_enabled",
    "max_position_embeddings": "max_seq",
}


def experts_held(config: Dict[str, Any]):
    lo, hi = config["deployment"]["experts_held"]
    return int(lo), int(hi)


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family.
    The router keeps the published width (`deployment.router_width`);
    `num_experts` in the file counts the experts held here."""
    from ray_tpu.models.trinity import TrinityConfig
    for key, want in (("model_type", "afmoe"), ("score_func", "sigmoid"),
                      ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("n_group", 1),
                      ("topk_group", 1), ("num_expert_groups", 1),
                      ("num_limited_groups", 1)):
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    lo, hi = experts_held(config)
    if hi - lo != config["num_experts"]:
        raise ValueError("deployment.experts_held and num_experts "
                         "disagree on how many experts are held")
    fields.update(n_routed_experts=config["deployment"]["router_width"],
                  experts_held=(lo, hi),
                  layer_types=tuple(config["layer_types"]))
    fields.update(overrides)
    return TrinityConfig(**fields)


def published_keys(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a `TrinityConfig`
    (the tests' way round: a toy configuration has no file)."""
    out = {theirs: getattr(cfg, ours) for theirs, ours in MODEL_KEYS.items()}
    out["layer_types"] = list(cfg.kinds)
    return out
