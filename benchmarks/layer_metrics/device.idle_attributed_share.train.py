"""Share of chip 0's idle time in the traced window of a training cell that
carries a label: under `train.step` (the host inside the bundle's step),
between two of them (the runner making the next batch), or inside the
step program (the device's own). Without one: before the first and after
the last span of the capture. The idle time itself is
`device.idle_share.train`; where that is near 0 this share rests on a few
microseconds."""

from benchmarks.lib import span_reduce

NAME = "device.idle_attributed_share.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    found = cap and span_reduce.idle_summary(cap)
    return found["attributed_share_pct"] if found else None
