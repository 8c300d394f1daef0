"""The benchmark's adapter for the NemotronH family: a configuration
file's published keys and its `deployment` become the `NemotronHConfig`
the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> NemotronHConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "hybrid_override_pattern": "pattern",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "mamba_num_heads": "mamba_heads",
    "mamba_head_dim": "mamba_head_dim",
    "ssm_state_size": "ssm_state",
    "n_groups": "n_groups",
    "conv_kernel": "d_conv",
    "chunk_size": "chunk_size",
    "moe_intermediate_size": "moe_ffn",
    "moe_shared_expert_intermediate_size": "shared_ffn",
    "num_experts_per_tok": "moe_top_k",
    "routed_scaling_factor": "route_scale",
    "norm_topk_prob": "route_norm",
    "layer_norm_epsilon": "norm_eps",
    "time_step_min": "time_step_min",
    "time_step_max": "time_step_max",
    "time_step_floor": "time_step_floor",
    "max_position_embeddings": "max_seq",
}


def experts_held(config: Dict[str, Any]):
    lo, hi = config["deployment"]["experts_held"]
    return int(lo), int(hi)


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family.
    The router keeps the published width (`deployment.router_width`);
    `n_routed_experts` in the file counts the experts held here."""
    from ray_tpu.models.nemotron_h import NemotronHConfig
    for key, want in (("model_type", "nemotron_h"),
                      ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"),
                      ("tie_word_embeddings", False), ("n_group", 1),
                      ("topk_group", 1), ("n_shared_experts", 1),
                      ("use_bias", False), ("mlp_bias", False),
                      ("attention_bias", False),
                      ("mamba_proj_bias", False), ("use_conv_bias", True),
                      ("sliding_window", None)):
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree on the depth")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    lo, hi = experts_held(config)
    if hi - lo != config["n_routed_experts"]:
        raise ValueError("deployment.experts_held and n_routed_experts "
                         "disagree on how many experts are held")
    fields.update(n_routed_experts=config["deployment"]["router_width"],
                  experts_held=(lo, hi))
    fields.update(overrides)
    return NemotronHConfig(**fields)


def published_keys(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a `NemotronHConfig`
    (the tests' way round: a toy configuration has no file)."""
    out = {theirs: getattr(cfg, ours) for theirs, ours in MODEL_KEYS.items()}
    out.update(n_group=1, topk_group=1, rope_theta=10000)
    return out
