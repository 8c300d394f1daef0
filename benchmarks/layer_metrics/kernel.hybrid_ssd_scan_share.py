"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `ssd_ragged_scan` (its `name=`) in a run of the GraniteHybrid
family: 36 Mamba-2 layers' scans of ONE group of 64 heads, taken eight
heads a grid step, the one-token body on tens of decode rows and the
matrix body on a prompt's chunk. (`kernel.ssd_scan_share` reads the same
kernel in the NemotronH family's runs and nothing here.)"""

from benchmarks.lib import spans_granite_hybrid as sg
from benchmarks.lib import spans_phi4flash as sp

NAME = "kernel.hybrid_ssd_scan_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


@sg.quiet
def read(run):
    return sp.kernel_share(run, sg.SCAN_KERNELS)
