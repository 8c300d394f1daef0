"""Share of chip 0's busy time in the traced window spent under the
named scope `sample`: choosing each row's token from its logits at the
end of `jit_step` and `jit_run` (penalty, temperature, the sort over the
vocabulary that top-p needs, the cut, the draw).

On trees before PR 28 this reads low. `_sample` there carried its keep
mask back to vocabulary order with a scatter, which XLA ran as a sort and
a fusion that lost their scope (`sort.2`, `fusion.6` in `chat-open`): PR
28's parent read 38.6% in `chat-open` and 26.6% in `dsv3-longchat` where
the truth, with those two, was 59.6% and ~41% of busy time (PR 25's
capture, whose ragged ticks were seven times longer: ~30% read, ~45%
true)."""

from benchmarks.lib import span_reduce

NAME = "step.sample_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.scope_of(scope) == "sample")
    return share or None       # no such scope in the program: nothing
