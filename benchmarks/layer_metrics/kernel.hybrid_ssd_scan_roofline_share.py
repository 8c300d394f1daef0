"""The Mamba-2 scan kernel's share of its roofline over the traced ticks
of a run of the GraniteHybrid family: for each tick the least BYTES its
36 scans need (each token's x, Delta, B, C and y once, each LIVE row's
state in and out once: `kernel_costs_granite_hybrid.scan_min_bytes`,
from the dispatch span's `ssm_tokens` and `ssm_rows` and the file's
sizes) over the HBM peak, summed, over `ssd_ragged_scan`'s time in those
ticks. The byte side alone: a chunk's matrix products are small beside
its bytes and a decode row's update runs on the vector unit, which the
peaks table does not price; on a decode tick it says how far the kernel
is from moving only the live rows' state. It cannot pass 100."""

from benchmarks.lib import kernel_costs_granite_hybrid as costs
from benchmarks.lib import spans_granite_hybrid as sg
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.hybrid_ssd_scan_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sg.quiet
def read(run):
    def least_seconds(args, peak):
        b = costs.scan_min_bytes(run["config"], args)
        return None if b is None else b / peak["hbm_bytes_per_s"]
    return sp.roofline_share(run, sg.SCAN_KERNELS, least_seconds)
