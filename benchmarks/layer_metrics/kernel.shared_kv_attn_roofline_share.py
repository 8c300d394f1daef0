"""The shared cache's attention as a share of its roofline over the
traced ticks: the ONE layer that writes the full page group (on every
token) and the seven cross layers that read it (on the sampling rows),
all `ragged_paged_attention`. For each tick the larger of the least
bytes over the HBM peak (each row's context, 5,120 B a token, once a
layer, plus q and o) and the least operations over the bf16 peak
(10,240 a kept pair a layer: `kernel_costs_phi4flash`), from the
dispatch span's `kv_tokens`, `attn_pairs` and `cross_tokens`, summed,
over the kernel's time in those ticks. It counts what the work needs,
not what the kernel moves, so it cannot pass 100."""

from benchmarks.lib import kernel_costs_phi4flash as costs
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.shared_kv_attn_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sp.quiet
def read(run):
    def least_seconds(args, peak):
        b = costs.shared_attention_min_bytes(run["config"], args)
        f = costs.shared_attention_min_flops(run["config"], args)
        if b is None or f is None:
            return None
        return max(b / peak["hbm_bytes_per_s"], f / peak["bf16_flops"])
    return sp.roofline_share(run, sp.SHARED_KERNELS, least_seconds)
