"""How loaded a held expert is when it is read: the assignments landed
(`moe_assignments`) over the (layer, expert) pairs that received any
(`moe_experts_hit`), summed over the traced ticks' `engine.fold` spans
(both counted on the device and sent back behind the tick's tokens). A
512-token tick of this cell sends about 24 rows to each of its 64 held
experts; a decode tick of n rows reads most of them for n x 6 / 128 rows
each."""

from benchmarks.lib import span_reduce
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_nemotron_h as sn

NAME = "moe.rows_per_hit_expert"
UNIT = "count"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@sn.quiet
def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    folds = sd.folds_by_tick(cap).values()
    hit = sum(f["moe_experts_hit"] for f in folds)
    if not hit:
        return None
    return sum(f["moe_assignments"] for f in folds) / hit
