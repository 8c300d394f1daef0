"""The KDA scan kernel's share of its roofline over the traced ticks:
for each tick the larger of the least BYTES its twenty scans need (each
token's q, k, v, o, g and beta once, each LIVE row's state in and out:
`kernel_costs_kimi_linear.scan_min_bytes`, from the dispatch span's
`ssm_tokens` and `ssm_rows`) over the HBM peak and the least OPERATIONS
(the recurrence's 6 d^2 a head a token) over the bfloat16 peak, summed,
over `kda_ragged_scan`'s time in those ticks. What the work needs, not
what the chunked form computes: it cannot pass 100."""

from benchmarks.lib import kernel_costs_kimi_linear as costs
from benchmarks.lib import spans_kimi_linear as sk
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.kda_scan_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sk.quiet
def read(run):
    def least_seconds(args, peak):
        b = costs.scan_min_bytes(run["config"], args)
        f = costs.scan_min_flops(run["config"], args)
        if b is None or f is None:
            return None
        return max(b / peak["hbm_bytes_per_s"],
                   f / peak["bf16_flops"])
    return sp.roofline_share(run, sk.KDA_KERNELS, least_seconds)
