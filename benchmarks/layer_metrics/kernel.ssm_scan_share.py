"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `ssm_ragged_scan` (its `name=`): the Mamba layers' selective scan
over the tick's ragged token axis, in ragged ticks and decode ticks
alike. Nothing for a program without the kernel."""

from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.ssm_scan_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sp.quiet
def read(run):
    return sp.kernel_share(run, sp.SCAN_KERNELS)
