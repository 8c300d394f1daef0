"""What the GraniteHybrid family's per-layer readers share: its scan
kernel's name, its mixer's scope and a guard that makes a reader say
nothing, never raise, on a run of another family, of a parent without
this one, or on nothing. The joins themselves are the other families'
(`spans_phi4flash`, `spans_deepseek_v3`)."""

from __future__ import annotations

import functools
from typing import Callable

SCAN_KERNELS = ("ssd_ragged_scan",)
MAMBA_SCOPE = "mamba_mixer"


def quiet(read: Callable) -> Callable:
    """`read(run)`, or None where the run lacks what it reads (a missing
    key, a capture of another shape): a reader new in a PR is run on the
    parent's program too, and has to leave its metric out there."""
    @functools.wraps(read)
    def guarded(run):
        try:
            if not isinstance(run, dict) or \
                    (run.get("config") or {}).get("model_type") \
                    != "granitemoehybrid":
                return None
            return read(run)
        except (KeyError, TypeError, IndexError, AttributeError,
                ZeroDivisionError, ValueError):
            return None
    return guarded

