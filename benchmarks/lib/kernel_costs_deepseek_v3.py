"""The least bytes and operations the DeepSeek-V3 family's new layers
need for what a tick carried: the numerators of their roofline shares.

Counted from the arguments of the tick's `engine.dispatch` span
(`kv_tokens`, `rows`, `decode_rows`, `prefill_tokens`) and the
configuration's published sizes, never from what the kernel happens to
move or compute (padding to blocks, a chunk's context read once per
query block, masked halves of diagonal blocks, the absorbed form where
the decompressed one needs fewer operations): so a share computed from
them cannot pass 100% of `peaks.PEAKS`, and what is missing to 100% is
the kernel's own overhead and its choice of form.
"""

from __future__ import annotations

from typing import Any, Dict

LANES = 128
BYTES = 2         # bf16: the configuration's storage and compute type


def latent_row_bytes(model: Dict[str, Any]) -> int:
    """One token's row in one layer of the latent pool, at the pool's
    padded width (576 -> 640 lanes)."""
    width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return -(-width // LANES) * LANES * BYTES


def _tokens(span: Dict[str, Any]) -> int:
    if span.get("kind") == "decode":
        return span["rows"]
    return span["decode_rows"] + span["prefill_tokens"]


def mla_attention_min_bytes(model: Dict[str, Any],
                            span: Dict[str, Any]) -> int:
    """Each row's context (`kv_tokens` sums them: for a prefill row its
    chunk's end, for a decode row its position + 1) of latent rows ONCE
    (one row serves scores and values), plus the absorbed queries read
    and the latent outputs written for the tick's tokens, in every
    layer."""
    nh, rank = model["num_attention_heads"], model["kv_lora_rank"]
    width = rank + model["qk_rope_head_dim"]
    qo = _tokens(span) * nh * (width + rank) * BYTES
    return model["num_hidden_layers"] * (
        span["kv_tokens"] * latent_row_bytes(model) + qo)


def mla_attention_min_flops(model: Dict[str, Any],
                            span: Dict[str, Any]) -> int:
    """The fewer operations of the two forms attention over a latent
    cache has, for every (query, key) pair the causal rule keeps, 2 a
    multiply-add, every head, every layer.

    ABSORBED (what the kernel computes): scores at the row's published
    width and values at the latent's, 2 x heads x (576 + 512) a pair.
    DECOMPRESSED: per-head keys of nope + rope and values of v_head_dim,
    2 x heads x (192 + 128) a pair, after the up-projection of every
    latent row the queries read (2 x 512 x heads x (128 + 128) a row).
    A decode row reads its whole context for one query, so absorbed is
    its least; a chunk of n tokens shares one up-projection of its
    context, so decompressed is (3.4 times fewer a pair). The span says
    which pairs are which: `attn_pairs` (a chunk of n tokens at context
    c keeps n * c + n * (n + 1) / 2, a decode row its context + 1),
    `decode_pairs` the decode rows' part, `kv_tokens` each row's context
    at its last token. A span without `attn_pairs` gives `kv_tokens`,
    never more than the pairs; a ragged span without `decode_pairs`
    counts every pair at the decompressed rate and no up-projection:
    still a least count."""
    nh, rank = model["num_attention_heads"], model["kv_lora_rank"]
    rope = model["qk_rope_head_dim"]
    nope, dv = model["qk_nope_head_dim"], model["v_head_dim"]
    absorbed = 2 * nh * (rank + rope + rank)
    decompressed = 2 * nh * (nope + rope + dv)
    up = 2 * rank * nh * (nope + dv)
    pairs = span.get("attn_pairs", span["kv_tokens"])
    if span.get("kind") == "decode":
        dec, rows_read = pairs, 0
    elif "decode_pairs" in span:
        dec = span["decode_pairs"]
        rows_read = span["kv_tokens"] - dec
    else:
        dec, rows_read = 0, 0
    chunks = min(absorbed * (pairs - dec),
                 decompressed * (pairs - dec) + up * rows_read)
    return model["num_hidden_layers"] * (absorbed * dec + chunks)


def expert_bytes(model: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return (3 * model["hidden_size"] * model["moe_intermediate_size"]
            * BYTES)


def moe_experts_min_bytes(model: Dict[str, Any], experts_hit: int,
                          assignments: int) -> int:
    """The held experts that received a token, each read once, plus
    each assignment's activations in and out (a hidden row each way)."""
    return (experts_hit * expert_bytes(model)
            + assignments * 2 * model["hidden_size"] * BYTES)
