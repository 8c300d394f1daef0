"""The least bytes and operations the Phi4Flash family's scan kernel and
its shared-cache attention need for what a tick carried: the numerators
of their roofline shares.

Counted from the arguments of the tick's `engine.dispatch` span and the
configuration's sizes, never from what a kernel happens to move or
compute (the scan's B and C broadcast along the lanes, every slot's
state moved whether or not it had a token, padding to blocks, the zero
halves of the paired query heads, the value product done a head and not
a pair): so a share computed from them cannot pass 100% of
`peaks.PEAKS`, and what is missing to 100% is the kernel's own overhead.

THE SCAN (`ssm_ragged_scan`, a Mamba layer): each token's x (bfloat16),
delta and y (float32) over E channels and its B and C (float32, N each)
once; each live row's state [N, E] float32 read and written once. The
BYTE side alone: the recurrence is E x N multiply-adds and one
exponential a token on the vector unit, which no peak in the table
prices, so on a chunk, where the vector unit binds, the share reads low.

THE SHARED CACHE'S ATTENTION (`ragged_paged_attention`: the layer that
writes the full group, on every token, and the cross layers that read
it, on the sampling rows): K and V of each row's context read once a
layer (`kv_tokens`: a prefill row's chunk's end, a decode row's position
+ 1), q read and o written for the tokens the layer runs on. Operations:
a kept (query, key) pair costs each of the 40 query heads a 64-wide
score and each of the 20 PAIRS one 128-wide value product (the
difference of the two softmaxes taken first): 2 x (40 x 64 + 20 x 128) =
10,240, which no exact evaluation undercuts. The writing layer keeps
`attn_pairs` (a chunk of n tokens at context c: n c + n (n + 1) / 2), a
cross layer one query a row: `kv_tokens` pairs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16, F32 = 2, 4


def schedule(model: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind, by the schedule the model's docstring gives."""
    n = model["num_hidden_layers"]
    half = n // 2
    return {"mamba": half // 2 + 1, "swa": half // 2, "full": 1,
            "gmu": (n - half - 2) // 2, "cross": (n - half - 2) // 2}


def scan_sizes(model: Dict[str, Any]):
    a = model["assumed"]
    return (a["mamba_expand"]["value"] * model["hidden_size"],
            a["mamba_d_state"]["value"])


def _tokens(span: Dict[str, Any]) -> int:
    if span.get("kind") == "decode":
        return span["rows"]
    return span["decode_rows"] + span["prefill_tokens"]


def scan_min_bytes(model, span) -> Optional[int]:
    """None of a span that does not say what its scan carried (a program
    without one)."""
    if "ssm_tokens" not in span or "ssm_rows" not in span:
        return None
    e, n = scan_sizes(model)
    token = e * (BF16 + F32 + F32) + 2 * n * F32
    state = 2 * n * e * F32
    return schedule(model)["mamba"] * (
        span["ssm_tokens"] * token + span["ssm_rows"] * state)


def kv_row_bytes(model: Dict[str, Any]) -> int:
    """One token's K and V in one layer: 20 heads of 64 each, bf16."""
    d = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_key_value_heads"] * d * BF16


def _qo_bytes(model, tokens: int) -> int:
    return tokens * 2 * model["hidden_size"] * BF16


def pair_flops(model: Dict[str, Any]) -> int:
    heads = model["num_attention_heads"]
    d = model["hidden_size"] // heads
    return 2 * (heads * d + (heads // 2) * 2 * d)


def shared_attention_min_bytes(model, span) -> Optional[int]:
    if "cross_tokens" not in span:
        return None
    cross = schedule(model)["cross"]
    kv = span["kv_tokens"] * kv_row_bytes(model)
    return ((1 + cross) * kv + _qo_bytes(model, _tokens(span))
            + cross * _qo_bytes(model, span["cross_tokens"]))


def shared_attention_min_flops(model, span) -> Optional[int]:
    if "cross_tokens" not in span:
        return None
    return pair_flops(model) * (
        span.get("attn_pairs", span["kv_tokens"])
        + schedule(model)["cross"] * span["kv_tokens"])
