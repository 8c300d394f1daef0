"""Of the (layer, expert) pairs held here (12 x 64 = 768 in
`smallthinker-assist`), the share a tick reads: `moe_experts_hit` of the
traced ticks' `engine.fold` spans (counted on the device and sent back
behind the tick's tokens) over the pairs held, the mean over those
ticks. A decode tick of B rows with 6 picks of 64 reads 64 x (1 -
(58/64)^B) of a layer's experts: 79% at 16 rows, 96% at 32. It says
whether the cell still measures full load after a later PR moves the
knee. Nothing for another family's run."""

from benchmarks.lib import kernel_costs_smallthinker as costs
from benchmarks.lib import span_reduce
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_smallthinker as ss

NAME = "moe.experts_hit_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@ss.quiet
def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    folds = list(sd.folds_by_tick(cap).values())
    if not folds:
        return None
    held = costs.held_experts(run["config"])
    return 100.0 * sum(f["moe_experts_hit"] for f in folds) / (
        held * len(folds))
