"""Published peaks by `device_kind`, and the operations a token needs.

The one table the benchmark prices against. A device that is not here is
an error, never a default: `run.py` refuses to report on it.
"""

from __future__ import annotations

from typing import Any, Dict

# Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s "
                  "bf16, 819 GB/s, 16 GB per chip)",
    },
}


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that sit in a matrix multiplication, for a dense
    RoPE/GQA/SwiGLU decoder described by its published config keys: the
    four attention projections and three feed-forward matrices of every
    layer, and the output head. The embedding is a lookup, not a
    multiplication, and norms are vectors."""
    h = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    per_layer = h * (q + 2 * kv) + q * h + 3 * h * model["intermediate_size"]
    return model["num_hidden_layers"] * per_layer + h * model["vocab_size"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per token
    (recomputation does not count): 6 per matmul parameter, plus causal
    attention's QK^T and PV, 2 * 2 * seq * q_dim forward per token at
    full (non-causal) cost, halved for the causal mask, tripled for
    forward + backward: 6 * seq * q_dim per layer."""
    q = model["num_attention_heads"] * model["head_dim"]
    attn = 6.0 * seq * q * model["num_hidden_layers"]
    return 6.0 * matmul_params(model) + attn
