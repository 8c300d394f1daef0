"""Share of chip 0's busy time in the traced window spent in the three
flash attention kernels: `flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`."""

from benchmarks.lib import span_reduce

NAME = "kernel.flash_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    return span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.is_kernel(
            name, *span_reduce.FLASH_KERNELS))
