"""The routed experts' share of the HBM roofline over the traced ticks:
the bytes of the held experts that received a token in a tick (each
(layer, expert) pair once: `moe_experts_hit` of the tick's
`engine.fold` span, counted on the device and sent back with the tick's
tokens) plus each landed assignment's activations in and out, over the
time the tick's program spent under the scope `moe_experts`, over the
chip's HBM peak. A decode tick is bound by these bytes; a 512-token
tick by its matrix products, so the share falls there."""

from benchmarks.lib import kernel_costs_deepseek_v3 as costs
from benchmarks.lib import spans_deepseek_v3

NAME = "moe.experts_hbm_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


def read(run):
    cap, peak = spans_deepseek_v3.capture_and_peaks(run)
    if cap is None or not peak:
        return None
    folds = spans_deepseek_v3.folds_by_tick(cap)
    need = ns = 0
    for p, spent in spans_deepseek_v3.per_program(
            cap, lambda name, scope: spans_deepseek_v3.in_scope(
                scope, "moe_experts")):
        fold = folds.get(p["args"].get("tick"))
        if fold is None:
            continue          # folded after the capture ended
        need += costs.moe_experts_min_bytes(
            run["config"], fold["moe_experts_hit"],
            fold["moe_assignments"])
        ns += spent
    if not ns:
        return None
    return 100.0 * need / (ns / 1e9) / peak["hbm_bytes_per_s"]
