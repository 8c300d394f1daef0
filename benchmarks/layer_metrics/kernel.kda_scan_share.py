"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `kda_ragged_scan` (its `name=`): the KDA layers' gated delta rule
over the tick's ragged token axis, its matrix body (the levels' masked
products and the blocked solve) on a prompt's chunk and its one-token
body on decode rows alike. Nothing for a program without the kernel."""

from benchmarks.lib import spans_kimi_linear as sk
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.kda_scan_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sk.quiet
def read(run):
    return sp.kernel_share(run, sk.KDA_KERNELS)
