"""Share of the traced window in which no operation ran on chip 0:
1 - union of the `XLA Ops` intervals / window, the window bounded by the
first and last program on the device."""

from benchmarks.lib import trace_reduce

NAME = "device.idle_share.serve"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tok_s"


def read(run):
    return trace_reduce.idle_share_pct(run.get("events") or [])
