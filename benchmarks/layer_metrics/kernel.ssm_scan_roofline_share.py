"""The scan kernel's share of its roofline over the traced ticks: for
each tick the least BYTES its nine Mamba layers need (each token's x,
delta, B, C and y once, each live row's state in and out:
`kernel_costs_phi4flash.scan_min_bytes`, from the dispatch span's
`ssm_tokens` and `ssm_rows`) over the HBM peak, summed, over
`ssm_ragged_scan`'s time in those ticks. The byte side alone: the
recurrence's multiply-adds and exponentials run on the vector unit,
which the peaks table does not price, so on a chunk, where the vector
unit binds, this reads low; on a decode tick it says how far the kernel
is from moving only the live rows' state. It cannot pass 100."""

from benchmarks.lib import kernel_costs_phi4flash as costs
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.ssm_scan_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sp.quiet
def read(run):
    def least_seconds(args, peak):
        b = costs.scan_min_bytes(run["config"], args)
        return None if b is None else b / peak["hbm_bytes_per_s"]
    return sp.roofline_share(run, sp.SCAN_KERNELS, least_seconds)
