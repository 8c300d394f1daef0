"""The benchmark's adapter for the SmallThinker family: a configuration
file's published keys and its `deployment` become the
`SmallThinkerConfig` the program takes."""

from __future__ import annotations

from typing import Any, Dict

# published config.json key -> SmallThinkerConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "moe_ffn_hidden_size": "moe_ffn",
    "moe_num_primary_experts": "n_routed_experts",
    "moe_num_active_primary_experts": "moe_top_k",
    "sliding_window_size": "sliding_window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq",
}
LAYOUT_KEYS = ("sliding_window_layout", "rope_layout")


def experts_held(config: Dict[str, Any]):
    lo, hi = config["deployment"]["experts_held"]
    return int(lo), int(hi)


def model_config(config: Dict[str, Any], **overrides):
    """config: a parsed benchmarks/configs/<name>.json of this family."""
    from ray_tpu.models.smallthinker import SmallThinkerConfig
    for key, want in (("model_name", "smallthinker_21b_instruct"),
                      ("moe_primary_router_apply_softmax", True),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False),
                      ("rope_scaling", None)):
        if config.get(key) != want:
            raise ValueError(f"the program has no {key} {config.get(key)!r}")
    for key in LAYOUT_KEYS:
        if len(config[key]) != config["num_hidden_layers"]:
            raise ValueError(f"{key} and num_hidden_layers disagree on "
                             "the depth")
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update({key: tuple(config[key]) for key in LAYOUT_KEYS})
    fields["experts_held"] = experts_held(config)
    fields.update(overrides)
    return SmallThinkerConfig(**fields)


def published_keys(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a
    `SmallThinkerConfig` (the tests' way round: a toy configuration has
    no file)."""
    out = {theirs: getattr(cfg, ours) for theirs, ours in MODEL_KEYS.items()}
    out.update(sliding_window_layout=list(cfg.windowed),
               rope_layout=list(cfg.roped))
    return out
