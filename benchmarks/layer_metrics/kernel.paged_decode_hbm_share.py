"""The paged decode kernel's share of the HBM roofline over the traced
decode ticks: the least bytes their work needs (each live row's context
of K and V once at the pool's row width, plus q and o, in every layer,
from the dispatch spans' arguments) over the time in `paged_decode` /
`paged_decode_mp`, over the chip's HBM peak."""

from benchmarks.lib import kernel_costs, peaks, span_reduce

NAME = "kernel.paged_decode_hbm_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    peak = peaks.PEAKS.get(run.get("device_kind"), {}).get("hbm_bytes_per_s")
    if cap is None or peak is None:
        return None
    found = span_reduce.kernel_traffic(
        cap, "decode", span_reduce.DECODE_KERNELS,
        lambda span: kernel_costs.paged_decode_min_bytes(run["config"], span))
    return 100.0 * found["bytes_per_s"] / peak if found else None
