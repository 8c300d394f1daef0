"""What the Trinity family's per-layer readers share: its kernels'
names and one kernel's share of its roofline over the traced ticks."""

from __future__ import annotations

from typing import Callable, Optional

from . import span_reduce, spans_deepseek_v3

WINDOW_KERNELS = ("ragged_window_attention",)
FULL_KERNELS = ("ragged_paged_attention",)


def roofline_share(run, kernels, min_bytes: Callable,
                   min_flops: Callable) -> Optional[float]:
    """100 x the least time the chip could take for what each traced
    tick's `kernels` carried (the larger of its least bytes over the
    HBM peak and its least operations over the bf16 peak, tick by
    tick: a chunk is bound by operations, a decode row by bytes),
    summed, over the kernels' time in those ticks. None where there is
    no capture, no such kernel, or a span that does not carry the
    counts."""
    cap, peak = spans_deepseek_v3.capture_and_peaks(run)
    if cap is None or not peak:
        return None
    found = spans_deepseek_v3.per_program(
        cap, lambda name, scope: span_reduce.is_kernel(name, *kernels))
    if not found:
        return None
    model = run["config"]
    least_s = 0.0
    for p, _ in found:
        b, f = min_bytes(model, p["args"]), min_flops(model, p["args"])
        if b is None or f is None:
            return None
        least_s += max(b / peak["hbm_bytes_per_s"], f / peak["bf16_flops"])
    return 100.0 * least_s / (sum(ns for _, ns in found) / 1e9)
