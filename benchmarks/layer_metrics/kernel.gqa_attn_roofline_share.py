"""`ragged_paged_attention`'s share of its roofline over the traced
ticks in a cell whose model has full-attention layers of 48 query heads
over 8 kv heads: for each tick the larger of its least bytes over the
HBM peak (each row's whole context, K and V once, q and o) and its
least operations over the bf16 peak (4 x 48 x 128 a kept pair: a chunk
of this head count is bound by operations, which
`kernel.ragged_attn_hbm_share` does not count), summed over the full
layers (`kernel_costs_trinity`, from the dispatch span's `kv_tokens` and
`attn_pairs`), over the kernel's time."""

from benchmarks.lib import kernel_costs_trinity as costs
from benchmarks.lib import spans_trinity

NAME = "kernel.gqa_attn_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    if "layer_types" not in (run.get("config") or {}):
        return None            # a model with one kind of layer
    return spans_trinity.roofline_share(
        run, spans_trinity.FULL_KERNELS,
        costs.full_attention_min_bytes, costs.full_attention_min_flops)
