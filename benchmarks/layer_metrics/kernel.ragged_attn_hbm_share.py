"""The ragged kernel's share of the HBM roofline over the traced ragged
ticks: the least bytes their work needs (`kernel_costs`: each row's
context of K and V once at the pool's row width, plus q and o, in every
layer, from the dispatch spans' arguments) over the kernel's time, over
the chip's HBM peak. It counts what the work needs, not what the kernel
moves, so it cannot pass 100."""

from benchmarks.lib import kernel_costs, peaks, span_reduce

NAME = "kernel.ragged_attn_hbm_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    peak = peaks.PEAKS.get(run.get("device_kind"), {}).get("hbm_bytes_per_s")
    if cap is None or peak is None:
        return None
    found = span_reduce.kernel_traffic(
        cap, "ragged", span_reduce.RAGGED_KERNELS,
        lambda span: kernel_costs.ragged_attention_min_bytes(run["config"], span))
    return 100.0 * found["bytes_per_s"] / peak if found else None
