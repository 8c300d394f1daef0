"""The gated-ReLU held experts' share of their roofline over the traced
ticks: for each tick the larger of the least bytes (the three matrices
of each (layer, expert) pair hit, the tick's rows in and out a layer)
over the HBM peak and the least operations (6 x H x F an assignment)
over the bfloat16 peak (`kernel_costs_smallthinker`, from
`moe_experts_hit` and `moe_assignments` of the tick's `engine.fold`
span, counted on the device, and the rows of its `engine.dispatch`
span), summed, over the time the tick's program spent in the kernels
`moe_grouped_up_reglu` and `moe_grouped_down_reglu` (their `name=`). A
decode tick that reads nearly every held expert for a few rows each is
bound by the bytes. It cannot pass 100. Nothing for a program without
the kernels."""

from benchmarks.lib import kernel_costs_smallthinker as costs
from benchmarks.lib import span_reduce
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_smallthinker as ss

NAME = "moe.reglu_experts_roofline_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@ss.quiet
def read(run):
    cap, peak = sd.capture_and_peaks(run)
    if cap is None or not peak:
        return None
    folds = sd.folds_by_tick(cap)
    model = run["config"]
    least = ns = 0.0
    for p, spent in sd.per_program(
            cap, lambda name, scope: span_reduce.is_kernel(
                name, *ss.REGLU_KERNELS)):
        fold = folds.get(p["args"].get("tick"))
        if fold is None:
            continue          # folded after the capture ended
        least += max(
            costs.experts_min_bytes(model, fold["moe_experts_hit"],
                                    costs.tokens(p["args"]))
            / peak["hbm_bytes_per_s"],
            costs.experts_min_flops(model, fold["moe_assignments"])
            / peak["bf16_flops"])
        ns += spent
    if not ns:
        return None
    return 100.0 * least / (ns / 1e9)
