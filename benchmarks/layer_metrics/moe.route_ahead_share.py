"""Share of chip 0's busy time in the traced window spent under the
named scope `moe_router` of a family whose router reads the layer's
input AHEAD of attention (`models/smallthinker.py`): the logits, the
picks and their softmax, the held experts' gate matrix, the
assignments' rows sorted by expert and the grouped kernels' tile visits:
what the early router costs where the compiler does not hide it under
attention. Nothing for another family's run (their routers sit inside
`mlp`, behind attention)."""

from benchmarks.lib import span_reduce
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_smallthinker as ss

NAME = "moe.route_ahead_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@ss.quiet
def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: sd.in_scope(scope, ss.ROUTER_SCOPE))
    return share or None       # no such scope in the program: nothing
