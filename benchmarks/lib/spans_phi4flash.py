"""What the Phi4Flash family's per-layer readers share: its kernels'
names and a guard that makes a reader say nothing, never raise, on a
run of another family, of a parent without this one, or on nothing."""

from __future__ import annotations

import functools
from typing import Callable

from . import span_reduce, spans_deepseek_v3

SCAN_KERNELS = ("ssm_ragged_scan",)
SHARED_KERNELS = ("ragged_paged_attention",)


def quiet(read: Callable) -> Callable:
    """`read(run)`, or None where the run lacks what it reads (a missing
    key, a capture of another shape): a reader new in a PR is run on the
    parent's program too, and has to leave its metric out there."""
    @functools.wraps(read)
    def guarded(run):
        try:
            if not isinstance(run, dict) or \
                    (run.get("config") or {}).get("model_type") != "phi4flash":
                return None
            return read(run)
        except (KeyError, TypeError, IndexError, AttributeError,
                ZeroDivisionError, ValueError):
            return None
    return guarded


def kernel_share(run, kernels):
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    share = span_reduce.share_of_busy(
        cap, lambda name, scope: span_reduce.is_kernel(name, *kernels))
    return share or None


def dispatch_args(run):
    """The arguments of the dispatch span of every traced program that
    found its span."""
    cap = span_reduce.capture(run)
    if cap is None:
        return []
    return [p["args"] for p in span_reduce.programs(cap)]


def roofline_share(run, kernels, least_seconds):
    """100 x the sum over the traced ticks of `least_seconds(dispatch
    args, peaks)`, what a tick's work needs at the device's peaks, over
    the time `kernels` took in those ticks; None where the capture, the
    peaks, the kernel or a span's count is absent."""
    cap, peak = spans_deepseek_v3.capture_and_peaks(run)
    if cap is None or not peak:
        return None
    found = spans_deepseek_v3.per_program(
        cap, lambda name, scope: span_reduce.is_kernel(name, *kernels))
    if not found:
        return None
    least = [least_seconds(p["args"], peak) for p, _ in found]
    if any(s is None for s in least):
        return None
    return 100.0 * sum(least) / (sum(ns for _, ns in found) / 1e9)
