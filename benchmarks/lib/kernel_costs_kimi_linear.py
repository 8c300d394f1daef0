"""The least bytes and operations the Kimi Linear family's scan kernel
needs for what a tick carried: the numerators of its roofline share.

Counted from the arguments of the tick's `engine.dispatch` span and the
configuration's sizes, never from what the kernel happens to move or
compute (q, k, v and g handed over in float32 and head-major, beta
folded into two more arrays, padding to chunks, the levels' masked
products, the blocked solve): so a share computed from them cannot pass
100% of `peaks.PEAKS`, whatever chunked form a later kernel takes, and
what is missing to 100% is the kernel's own overhead.

THE SCAN (`kda_ragged_scan`, a KDA layer of H heads of K = V = d): each
token's q, k, v and o (bfloat16, H x d each), its decay g (float32, H x
d) and its beta (float32, H) once: 49,280 B at 32 heads of 128; each
LIVE row's state [H, d, d] float32 read and written once: 4,194,304 B.
Operations: the recurrence itself, S <- Diag(a) S, S^T k, the rank-one
update and S^T q: 6 d^2 a head a token, 3,145,728 a token a layer. A
tick's least time is the larger of bytes over the HBM peak and
operations over the bfloat16 peak.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16, F32 = 2, 4


def scan_layers(model: Dict[str, Any]) -> int:
    return len(model["linear_attn_config"]["kda_layers"])


def scan_sizes(model: Dict[str, Any]):
    """(H x d, H, H x d x d) of one KDA layer."""
    lin = model["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    return h * d, h, h * d * d


def token_bytes(model: Dict[str, Any]) -> int:
    e, h, _ = scan_sizes(model)
    return 4 * e * BF16 + e * F32 + h * F32


def scan_min_bytes(model, span) -> Optional[int]:
    """None of a span that does not say what its scan carried (a program
    without one)."""
    if "ssm_tokens" not in span or "ssm_rows" not in span:
        return None
    state = scan_sizes(model)[2]
    return scan_layers(model) * (
        span["ssm_tokens"] * token_bytes(model)
        + span["ssm_rows"] * 2 * state * F32)


def scan_min_flops(model, span) -> Optional[int]:
    if "ssm_tokens" not in span:
        return None
    return scan_layers(model) * span["ssm_tokens"] * 6 * scan_sizes(model)[2]
