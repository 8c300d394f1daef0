"""The benchmark's adapter for the Kimi Linear family: a configuration
file's published keys and its `deployment` become the `KimiLinearConfig`
the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> KimiLinearConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "first_k_dense_replace": "first_k_dense",
    "intermediate_size": "ffn",
    "num_attention_heads": "n_heads",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "moe_intermediate_size": "moe_ffn",
    "num_experts_per_token": "moe_top_k",
    "num_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "route_scale",
    "moe_renormalize": "route_norm",
    "rms_norm_eps": "norm_eps",
    "model_max_length": "max_seq",
}
# `linear_attn_config` key -> field
LINEAR_KEYS = {
    "num_heads": "kda_heads",
    "head_dim": "kda_head_dim",
    "short_conv_kernel_size": "d_conv",
    "kda_layers": "kda_layers",
    "full_attn_layers": "full_attn_layers",
}


def experts_held(config: Dict[str, Any]):
    lo, hi = config["deployment"]["experts_held"]
    return int(lo), int(hi)


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family.
    The router keeps the published width (`deployment.router_width`);
    `num_experts` in the file counts the experts held here."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    for key, want in (("model_type", "kimi_linear"), ("hidden_act", "silu"),
                      ("q_lora_rank", None), ("mla_use_nope", True),
                      ("moe_router_activation_func", "sigmoid"),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("rope_scaling", None),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0)):
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one key a query head")
    lin = config["linear_attn_config"]
    if len(lin["kda_layers"]) + len(lin["full_attn_layers"]) \
            != config["num_hidden_layers"]:
        raise ValueError("linear_attn_config's lists and num_hidden_layers "
                         "disagree on the depth")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update({ours: (tuple(lin[theirs]) if isinstance(lin[theirs], list)
                          else lin[theirs])
                   for theirs, ours in LINEAR_KEYS.items()})
    lo, hi = experts_held(config)
    if hi - lo != config["num_experts"]:
        raise ValueError("deployment.experts_held and num_experts disagree "
                         "on how many experts are held")
    fields.update(n_routed_experts=config["deployment"]["router_width"],
                  experts_held=(lo, hi),
                  gate_rank=config["assumed_sizes"]["gate_rank"])
    fields.update(overrides)
    return KimiLinearConfig(**fields)


def published_keys(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a `KimiLinearConfig`
    (the tests' way round: a toy configuration has no file)."""
    out = {theirs: getattr(cfg, ours) for theirs, ours in MODEL_KEYS.items()}
    out["linear_attn_config"] = {
        theirs: (list(getattr(cfg, ours))
                 if isinstance(getattr(cfg, ours), tuple)
                 else getattr(cfg, ours))
        for theirs, ours in LINEAR_KEYS.items()}
    out.update(num_expert_group=1, topk_group=1, rope_theta=10000.0,
               num_hidden_layers=cfg.n_layers)
    return out
