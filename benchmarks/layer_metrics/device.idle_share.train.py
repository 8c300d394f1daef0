"""Share of the traced window in which no operation ran on chip 0, in a
training cell: 1 - union of the `XLA Ops` intervals / window."""

from benchmarks.lib import trace_reduce

NAME = "device.idle_share.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_tok_s"


def read(run):
    return trace_reduce.idle_share_pct(run.get("events") or [])
