"""What the SmallThinker family's per-layer readers share: its kernels'
names, its scopes and a guard that makes a reader say nothing, never
raise, on a run of another family, of a parent without this one, or on
nothing. The joins themselves are the other families'
(`spans_deepseek_v3`)."""

from __future__ import annotations

import functools
from typing import Callable

REGLU_KERNELS = ("moe_grouped_up_reglu", "moe_grouped_down_reglu")
ROUTER_SCOPE = "moe_router"
MODEL_NAME = "smallthinker_21b_instruct"


def quiet(read: Callable) -> Callable:
    """`read(run)`, or None where the run lacks what it reads (a missing
    key, a capture of another shape): a reader new in a PR is run on the
    parent's program too, and has to leave its metric out there."""
    @functools.wraps(read)
    def guarded(run):
        try:
            if not isinstance(run, dict) or \
                    (run.get("config") or {}).get("model_name") \
                    != MODEL_NAME:
                return None
            return read(run)
        except (KeyError, TypeError, IndexError, AttributeError,
                ZeroDivisionError, ValueError):
            return None
    return guarded
