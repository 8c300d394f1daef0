"""The least bytes and operations the SmallThinker family's gated-ReLU
held experts need for what a tick carried: the numerators of their
roofline share.

Counted from the arguments of the tick's `engine.dispatch` and
`engine.fold` spans and the configuration's sizes, never from what a
kernel happens to move or compute (padding to row tiles, the one-hot
products that fetch an expert's rows, a weight tile read again for a
second row tile or a second column tile, x read once a visit): so a
share computed from them cannot pass 100% of `peaks.PEAKS`, and what is
missing to 100% is the kernels' own overhead.

THE HELD EXPERTS (`moe_grouped_up_reglu`, `moe_grouped_down_reglu`, an
expert layer): the three matrices (gate, up, down) of each (layer,
expert) pair HIT, bfloat16, once; each of the tick's tokens in
(bfloat16) and out (float32, the gates applied) once a layer, however
many of its picks landed. Operations: an assignment costs 3 x 2 x H x F
(gate, up, down). A tick's least time is the larger of bytes over the
HBM peak and operations over the bfloat16 peak: a decode tick of tens of
rows reads nearly every held expert for a few rows each and is bound by
the bytes; a 512-token chunk sends 48 rows to each and leans to the
operations.

Attention (28 query heads over 4 K/V heads, `ragged_paged_attention`
and `ragged_window_attention` on merged-rows pages) is left to the
shared share readers; its bytes a token are Trinity's rule at this
family's head counts (`kernel_costs_trinity.kv_row_bytes`).
"""

from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def expert_bytes(model: Dict[str, Any]) -> int:
    """One routed expert's three matrices, bfloat16."""
    return 3 * model["hidden_size"] * model["moe_ffn_hidden_size"] * BF16


def held_experts(model: Dict[str, Any]) -> int:
    """The (layer, expert) pairs held here."""
    lo, hi = model["deployment"]["experts_held"]
    return model["num_hidden_layers"] * (hi - lo)


def tokens(span: Dict[str, Any]) -> int:
    """The tick's valid rows, from its dispatch span."""
    if span.get("kind") == "decode":
        return span["rows"]
    return span["decode_rows"] + span["prefill_tokens"]


def experts_min_bytes(model, experts_hit: int, n_tokens: int) -> int:
    row = model["hidden_size"] * (BF16 + F32)
    return (experts_hit * expert_bytes(model)
            + model["num_hidden_layers"] * n_tokens * row)


def experts_min_flops(model, assignments: int) -> int:
    return assignments * 6 * model["hidden_size"] * (
        model["moe_ffn_hidden_size"])
