"""Share of chip 0's busy time in the traced window spent under the
named scope `moe_experts`: the routed experts held here (gathering each
expert's tokens, the grouped matrix products, the weighted sum back)."""

from benchmarks.lib import span_reduce, spans_deepseek_v3

NAME = "moe.experts_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: spans_deepseek_v3.in_scope(
            scope, "moe_experts"))
    return share or None       # no such scope in the program: nothing
