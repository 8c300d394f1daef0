"""The least bytes and operations the Trinity family's two attention
kernels need for what a tick carried: the numerators of their roofline
shares.

Counted from the arguments of the tick's `engine.dispatch` span and the
configuration's published sizes, never from what the kernel happens to
move or compute (padding to blocks, a chunk's context read once per
query block, the masked part of a boundary or diagonal block, a block
behind the window read and thrown away): so a share computed from them
cannot pass 100% of `peaks.PEAKS`, and what is missing to 100% is the
kernel's own overhead.

A FULL layer (`ragged_paged_attention`): each row's whole context,
`kv_tokens` (a prefill row's chunk's end, a decode row's position + 1)
and `attn_pairs` (a chunk of n tokens at context c keeps n * c +
n * (n + 1) / 2 pairs, a decode row c + 1). A WINDOW layer
(`ragged_window_attention`): the keys INSIDE the windows of the row's
queries, `win_kv_tokens` (min(c + n, n + window - 1) a row), and the
pairs the band keeps, `win_attn_pairs` (query i of a row keeps
min(c + i + 1, window)). K and V are read once each, q is read and o
written for the tick's tokens; a kept pair costs 2 multiply-adds (the
score and the value) over head_dim for each of the query heads.
"""

from __future__ import annotations

from typing import Any, Dict

LANES = 128
BYTES = 2         # bf16: the configuration's storage and compute type


def kv_row_bytes(model: Dict[str, Any]) -> int:
    """One token's K row and V row in one layer, at the pool's padded
    width."""
    width = -(-model["head_dim"] // LANES) * LANES
    return 2 * model["num_key_value_heads"] * width * BYTES


def layers_of(model: Dict[str, Any], kind: str) -> int:
    return sum(1 for k in model["layer_types"] if k == kind)


def _tokens(span: Dict[str, Any]) -> int:
    if span.get("kind") == "decode":
        return span["rows"]
    return span["decode_rows"] + span["prefill_tokens"]


def _qo_bytes(model: Dict[str, Any], span: Dict[str, Any]) -> int:
    return (_tokens(span) * 2 * model["num_attention_heads"]
            * model["head_dim"] * BYTES)


def _pair_flops(model: Dict[str, Any]) -> int:
    return 4 * model["num_attention_heads"] * model["head_dim"]


def full_attention_min_bytes(model, span) -> int:
    return layers_of(model, "full_attention") * (
        span["kv_tokens"] * kv_row_bytes(model) + _qo_bytes(model, span))


def full_attention_min_flops(model, span) -> int:
    return (layers_of(model, "full_attention") * _pair_flops(model)
            * span.get("attn_pairs", span["kv_tokens"]))


def window_attention_min_bytes(model, span) -> int:
    """None of a span that does not say what its window layers read (a
    program without them)."""
    if "win_kv_tokens" not in span:
        return None
    return layers_of(model, "sliding_attention") * (
        span["win_kv_tokens"] * kv_row_bytes(model)
        + _qo_bytes(model, span))


def window_attention_min_flops(model, span) -> int:
    if "win_attn_pairs" not in span:
        return None
    return (layers_of(model, "sliding_attention") * _pair_flops(model)
            * span["win_attn_pairs"])
