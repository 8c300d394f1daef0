"""The ungated held experts' share of their roofline over the traced
ticks: for each tick the larger of the least bytes (the two matrices of
each (layer, expert) pair hit, each landed assignment's row in and out)
over the HBM peak and the least operations (4 x H x F an assignment)
over the bfloat16 peak (`kernel_costs_nemotron_h`, from
`moe_experts_hit` and `moe_assignments` of the tick's `engine.fold`
span, counted on the device), summed, over the time the tick's program
spent under the scope `moe_experts`. A tick that reads every held expert
for a few rows each is bound by the bytes. It cannot pass 100."""

from benchmarks.lib import kernel_costs_nemotron_h as costs
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_nemotron_h as sn

NAME = "moe.relu2_experts_roofline_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@sn.quiet
def read(run):
    cap, peak = sd.capture_and_peaks(run)
    if cap is None or not peak:
        return None
    folds = sd.folds_by_tick(cap)
    least = ns = 0.0
    for p, spent in sd.per_program(
            cap, lambda name, scope: sd.in_scope(scope, sn.EXPERTS_SCOPE)):
        fold = folds.get(p["args"].get("tick"))
        if fold is None:
            continue          # folded after the capture ended
        hit, landed = fold["moe_experts_hit"], fold["moe_assignments"]
        least += max(
            costs.experts_min_bytes(run["config"], hit, landed)
            / peak["hbm_bytes_per_s"],
            costs.experts_min_flops(run["config"], landed)
            / peak["bf16_flops"])
        ns += spent
    if not ns:
        return None
    return 100.0 * least / (ns / 1e9)
