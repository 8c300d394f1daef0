"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `ragged_paged_attention` (its `name=`, which the instruction
carries)."""

from benchmarks.lib import span_reduce

NAME = "kernel.ragged_attn_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    return span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.is_kernel(
            name, *span_reduce.RAGGED_KERNELS))
