"""The least bytes the GraniteHybrid family's scan kernel needs for what
a tick carried: the numerator of its roofline share.

Counted from the arguments of the tick's `engine.dispatch` span and the
configuration's sizes, never from what the kernel happens to move (x
handed to the scan in float32 and once more transposed, padding to
chunks and row tiles, B and C fetched once a head tile): so a share
computed from them cannot pass 100% of `peaks.PEAKS`, and what is
missing to 100% is the kernel's own overhead.

THE SCAN (`ssd_ragged_scan`, a Mamba-2 layer): each token's x
(bfloat16, H x P channels), Delta (float32, H), B and C (bfloat16, G x N
each) and y (float32, H x P) once; each LIVE row's state [H, P, N]
float32 read and written once. The BYTE side alone: a chunk's matrix
products (about 6 H P N operations a token) are a few percent of what
the bytes cost at the peaks' ratio, and the decode row's update runs on
the vector unit, which no peak in the table prices.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16, F32 = 2, 4


def mamba_layers(model: Dict[str, Any]) -> int:
    return sum(kind == "mamba" for kind in model["layer_types"])


def scan_sizes(model: Dict[str, Any]):
    """(H x P, H, G x N, H x P x N) of one Mamba-2 layer."""
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    n = model["mamba_d_state"]
    return h * p, h, model["mamba_n_groups"] * n, h * p * n


def scan_min_bytes(model, span) -> Optional[int]:
    """None of a span that does not say what its scan carried (a program
    without one)."""
    if "ssm_tokens" not in span or "ssm_rows" not in span:
        return None
    e, h, gn, state = scan_sizes(model)
    token = e * BF16 + h * F32 + 2 * gn * BF16 + e * F32
    return mamba_layers(model) * (
        span["ssm_tokens"] * token + span["ssm_rows"] * 2 * state * F32)
