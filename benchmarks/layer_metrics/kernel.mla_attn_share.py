"""Share of chip 0's busy time in the traced window spent in the Pallas
kernel `mla_ragged_attention` (its `name=`): latent attention over the
paged latent cache, in ragged ticks and decode ticks alike."""

from benchmarks.lib import span_reduce, spans_deepseek_v3

NAME = "kernel.mla_attn_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.is_kernel(
            name, *spans_deepseek_v3.MLA_KERNELS))
    return share or None       # no such kernel in the program: nothing
