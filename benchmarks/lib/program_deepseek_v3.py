"""The benchmark's adapter for the DeepSeek-V3 family: a configuration
file's published keys, its `deployment` and its `published` values
become the `DeepseekV3Config` the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> DeepseekV3Config field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "first_k_dense_replace": "first_k_dense",
    "num_attention_heads": "n_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "intermediate_size": "ffn",
    "moe_intermediate_size": "moe_ffn",
    "n_shared_experts": "n_shared_experts",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "num_experts_per_tok": "moe_top_k",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq",
}
ROPE_KEYS = {
    "factor": "rope_factor", "beta_fast": "rope_beta_fast",
    "beta_slow": "rope_beta_slow", "mscale": "rope_mscale",
    "mscale_all_dim": "rope_mscale_all_dim",
    "original_max_position_embeddings": "rope_original_max",
}


def experts_held(config: Dict[str, Any]):
    lo, hi = config["deployment"]["experts_held"]
    return int(lo), int(hi)


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family.
    The router keeps the published width (`deployment.router_width`);
    `n_routed_experts` in the file counts the experts held here."""
    from ray_tpu.models.deepseek_v3 import DeepseekV3Config
    for key, want in (("model_type", "deepseek_v3"),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("attention_bias", False), ("moe_layer_freq", 1)):
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    if config["rope_scaling"]["type"] != "yarn":
        raise ValueError("the program has YaRN rope scaling only")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update({ours: config["rope_scaling"][theirs]
                   for theirs, ours in ROPE_KEYS.items()})
    lo, hi = experts_held(config)
    if hi - lo != config["n_routed_experts"]:
        raise ValueError("deployment.experts_held and n_routed_experts "
                         "disagree on how many experts are held")
    fields.update(n_routed_experts=config["deployment"]["router_width"],
                  experts_held=(lo, hi))
    fields.update(overrides)
    return DeepseekV3Config(**fields)
