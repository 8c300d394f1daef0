"""Share of chip 0's busy time under the scope `loss_head`: the chunked
vocabulary head and the cross entropy, forward and transposed."""

from benchmarks.lib import span_reduce

NAME = "train.loss_head_share"
UNIT = "%"
LAYER = "trainer"
MOVES = "train_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    return span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.scope_of(scope) == "loss_head")
