"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `ragged_window_attention` (its `name=`): the work-list kernel on
a sliding-window layer, its sweep started at the window's first block,
in ragged ticks and decode ticks alike."""

from benchmarks.lib import span_reduce, spans_trinity

NAME = "kernel.swa_attn_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.is_kernel(
            name, *spans_trinity.WINDOW_KERNELS))
    return share or None       # no such kernel in the program: nothing
