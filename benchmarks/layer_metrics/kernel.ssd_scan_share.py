"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `ssd_ragged_scan` (its `name=`): the Mamba-2 layers' chunked scan
over the tick's ragged token axis, its matrix body on a prompt's chunk
and its one-token body on decode rows alike. Nothing for a program
without the kernel."""

from benchmarks.lib import spans_nemotron_h as sn
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.ssd_scan_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sn.quiet
def read(run):
    return sp.kernel_share(run, sn.SCAN_KERNELS)
