"""The latent-attention kernel's share of its roofline over the traced
ticks: for each tick the least time the chip could take for what the
tick carried, the larger of its least bytes over the HBM peak and its
least operations over the bf16 peak (`kernel_costs_deepseek_v3`, from
the dispatch span's arguments: each row's context of latent rows once,
q and o, and every (query, key) pair the causal rule keeps, a decode
row's at the absorbed form's count and a chunk's at the fewer of the
absorbed and the decompressed form's), summed, over the kernel's time.
It counts what the work needs, not what the kernel moves or computes
(the absorbed form for every row), so it cannot pass 100."""

from benchmarks.lib import kernel_costs_deepseek_v3 as costs
from benchmarks.lib import span_reduce, spans_deepseek_v3

NAME = "kernel.mla_attn_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    cap, peak = spans_deepseek_v3.capture_and_peaks(run)
    if cap is None or not peak:
        return None
    found = spans_deepseek_v3.per_program(
        cap, lambda name, scope: span_reduce.is_kernel(
            name, *spans_deepseek_v3.MLA_KERNELS))
    if not found:
        return None
    model = run["config"]
    least_s = sum(max(
        costs.mla_attention_min_bytes(model, p["args"])
        / peak["hbm_bytes_per_s"],
        costs.mla_attention_min_flops(model, p["args"])
        / peak["bf16_flops"]) for p, _ in found)
    return 100.0 * least_s / (sum(ns for _, ns in found) / 1e9)
