"""The least bytes and operations the NemotronH family's scan kernel and
its ungated held experts need for what a tick carried: the numerators of
their roofline shares.

Counted from the arguments of the tick's `engine.dispatch` and
`engine.fold` spans and the configuration's sizes, never from what a
kernel happens to move or compute (x handed to the scan in float32 and
once more transposed, padding to chunks and row tiles, the one-hot
products that fetch an expert's rows, a weight tile read again for a
second row tile): so a share computed from them cannot pass 100% of
`peaks.PEAKS`, and what is missing to 100% is the kernel's own overhead.

THE SCAN (`ssd_ragged_scan`, a Mamba-2 layer): each token's x
(bfloat16, H x P channels), Delta (float32, H), B and C (bfloat16, G x N
each) and y (float32, H x P) once; each LIVE row's state [H, P, N]
float32 read and written once. The BYTE side alone: a chunk's matrix
products (scores, y, the state: about 6 H P N operations a token) are a
few percent of what the bytes cost at the peaks' ratio, and the decode
row's update runs on the vector unit, which no peak in the table prices.

THE HELD EXPERTS (scope `moe_experts`, an expert layer): the two
matrices of each (layer, expert) pair HIT, bfloat16, once; each landed
assignment's row in (bfloat16) and out (float32, the gate applied).
Operations: an assignment costs 2 x 2 x H x F (up, down; no gate
matrix). A tick's least time is the larger of bytes over the HBM peak
and operations over the bfloat16 peak.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16, F32 = 2, 4


def schedule(model: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind in the pattern the file runs."""
    pattern = model["hybrid_override_pattern"]
    return {"mamba": pattern.count("M"), "experts": pattern.count("E"),
            "attn": pattern.count("*")}


def scan_sizes(model: Dict[str, Any]):
    """(H x P, H, G x N, H x P x N) of one Mamba-2 layer."""
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    n = model["ssm_state_size"]
    return h * p, h, model["n_groups"] * n, h * p * n


def scan_min_bytes(model, span) -> Optional[int]:
    """None of a span that does not say what its scan carried (a program
    without one)."""
    if "ssm_tokens" not in span or "ssm_rows" not in span:
        return None
    e, h, gn, state = scan_sizes(model)
    token = e * BF16 + h * F32 + 2 * gn * BF16 + e * F32
    return schedule(model)["mamba"] * (
        span["ssm_tokens"] * token + span["ssm_rows"] * 2 * state * F32)


def expert_bytes(model: Dict[str, Any]) -> int:
    """One routed expert's two matrices, bfloat16."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"] * BF16


def experts_min_bytes(model, experts_hit: int, assignments: int) -> int:
    row = model["hidden_size"] * (BF16 + F32)
    return experts_hit * expert_bytes(model) + assignments * row


def experts_min_flops(model, assignments: int) -> int:
    return assignments * 4 * model["hidden_size"] * (
        model["moe_intermediate_size"])
