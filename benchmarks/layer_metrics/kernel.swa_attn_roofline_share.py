"""The window kernel's share of its roofline over the traced ticks: for
each tick the least time the chip could take for what its window layers
carried, the larger of its least bytes over the HBM peak (each row's
keys INSIDE its window, K and V once, q and o) and its least operations
over the bf16 peak (4 x 48 x 128 a kept pair), summed over the window
layers (`kernel_costs_trinity`, from the dispatch span's
`win_kv_tokens` and `win_attn_pairs`), over `ragged_window_attention`'s
time. It counts what the work needs, not what the kernel moves, so it
cannot pass 100."""

from benchmarks.lib import kernel_costs_trinity as costs
from benchmarks.lib import spans_trinity

NAME = "kernel.swa_attn_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    return spans_trinity.roofline_share(
        run, spans_trinity.WINDOW_KERNELS,
        costs.window_attention_min_bytes, costs.window_attention_min_flops)
