"""Share of chip 0's busy time in the traced window spent under the
named scope `mamba_mixer` (`models/granite_hybrid.py`): a Mamba-2
mixer's projections, its conv (`ssm_conv`), Delta, the scan kernel and
the gated norm: the number that says whether the 36 recurrent layers,
and not the SwiGLU blocks, the 4 attention layers or the head, do most
of a tick's work. Nothing for a program of another family."""

from benchmarks.lib import span_reduce
from benchmarks.lib import spans_deepseek_v3 as sd
from benchmarks.lib import spans_granite_hybrid as sg

NAME = "step.mamba_mixer_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


@sg.quiet
def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: sd.in_scope(scope, sg.MAMBA_SCOPE))
    return share or None       # no such scope in the program: nothing
