"""Share of chip 0's busy time in the traced window spent under the
named scope `kda` (`models/kimi_linear.py`): a KDA mixer's projections,
its convs, the decay and beta, the scan kernel and the gated head norm:
the number that says whether the delta-rule layers, and not the experts
or the latent attention, do most of a tick's work. Nothing for a
program without the scope."""

from benchmarks.lib import span_reduce
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_kimi_linear as sk

NAME = "step.kda_layer_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@sk.quiet
def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: sd.in_scope(scope, sk.KDA_SCOPE))
    return share or None       # no such scope in the program: nothing
