"""The least bytes an attention kernel has to move for what a tick
carried: the numerator of its share of the HBM roofline.

Counted from the arguments of the tick's `engine.dispatch` span and the
configuration's published sizes, never from what the kernel happens to
move (padding to blocks, pages read twice, the slots it walks with
nothing in them): so a share computed from it cannot pass 100% of
`peaks.PEAKS[...]["hbm_bytes_per_s"]`, and what is missing to 100% is
the kernel's own overhead. Attention at these sizes is bound by bytes,
not operations (about one multiply-add per byte of K and V per query
row), so no operation count is kept.
"""

from __future__ import annotations

from typing import Any, Dict

LANES = 128       # the pool pads each head's row to whole 128-lane vectors
KV_BYTES = 2      # bf16: the program's compute and KV type (`LlamaConfig`)


def pool_row_bytes(model: Dict[str, Any]) -> int:
    """Bytes of one token's K (or V) row in one layer of the paged pool:
    every KV head at the pool's padded width."""
    width = -(-model["head_dim"] // LANES) * LANES
    return model["num_key_value_heads"] * width * KV_BYTES


def _qo_bytes(model: Dict[str, Any], tokens: int) -> int:
    """q read and the output written once, for `tokens` query rows."""
    return 2 * tokens * model["num_attention_heads"] * model[
        "head_dim"] * KV_BYTES


def ragged_attention_min_bytes(model: Dict[str, Any],
                               span: Dict[str, Any]) -> int:
    """A ragged tick: each row's context (`kv_tokens` sums them: for a
    prefill row its chunk's end, for a decode row its position + 1) of K
    and of V once, plus q and o for the tokens in the tick, in every
    layer."""
    tokens = span["decode_rows"] + span["prefill_tokens"]
    per_layer = (2 * span["kv_tokens"] * pool_row_bytes(model)
                 + _qo_bytes(model, tokens))
    return model["num_hidden_layers"] * per_layer


def paged_decode_min_bytes(model: Dict[str, Any],
                           span: Dict[str, Any]) -> int:
    """A decode tick: each live row's context of K and of V once, plus q
    and o for one token a row, in every layer."""
    per_layer = (2 * span["kv_tokens"] * pool_row_bytes(model)
                 + _qo_bytes(model, span["rows"]))
    return model["num_hidden_layers"] * per_layer
