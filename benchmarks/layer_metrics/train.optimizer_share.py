"""Share of chip 0's busy time under the scope `optimizer`: gradient
clipping, AdamW and the parameter update."""

from benchmarks.lib import span_reduce

NAME = "train.optimizer_share"
UNIT = "%"
LAYER = "trainer"
MOVES = "train_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    return span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.scope_of(scope) == "optimizer")
