"""Share of chip 0's idle time in the traced window that carries a label
saying what held the device: a phase of a tick, `server.deliver`, between
ticks, no work, or inside one program (the device's own). Without one:
in a tick but in no phase (`other`), or before the first and after the
last span of the capture. Want 95 or more: below it the tick has a part
that nobody named."""

from benchmarks.lib import span_reduce

NAME = "device.idle_attributed_share.serve"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    found = cap and span_reduce.idle_summary(cap)
    return found["attributed_share_pct"] if found else None
