"""What the DeepSeek-V3 family's per-layer readers share: the new
kernel's name, the new named scopes, and the join of a tick's program to
the fold span that says what its expert layers received."""

from __future__ import annotations

import bisect
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import span_reduce

MLA_KERNELS = ("mla_ragged_attention",)
FOLD = "engine.fold"


def in_scope(path: str, scope: str) -> bool:
    """Does an operation's scope path (`jit(run)/while/body/mlp/
    moe_experts/dot_general`) pass through the named scope `scope`?"""
    return any(re.sub(r"^(?:\w+\()+|\)+$", "", part) == scope
               for part in path.split("/"))


def per_program(cap, pick: Callable[[str, str], bool],
                kinds=("ragged", "decode")) -> List[Tuple[Dict, int]]:
    """(program, self ns of the operations `pick(name, scope path)`
    accepts inside it) for every program of `kinds` that found its
    dispatch span and spent any time there."""
    ops = [(start, ns) for name, scope, start, ns
           in span_reduce.op_self_ns(cap) if pick(name, scope)]
    starts = [o[0] for o in ops]
    out = []
    for p in span_reduce.programs(cap):
        if p["kind"] not in kinds:
            continue
        lo = bisect.bisect_left(starts, p["start"])
        hi = bisect.bisect_left(starts, p["end"])
        spent = sum(o[1] for o in ops[lo:hi])
        if spent:
            out.append((p, spent))
    return out


def folds_by_tick(cap) -> Dict[Any, Dict[str, Any]]:
    """The arguments of each `engine.fold` span that says what an
    expert family's tick received (`of`: the tick,
    `moe_experts_hit`, `moe_assignments`), by tick."""
    return {s[4]["of"]: s[4] for s in span_reduce._named(cap, FOLD)
            if "moe_experts_hit" in s[4]}


def capture_and_peaks(run) -> Tuple[Optional[Dict], Dict[str, Any]]:
    from . import peaks
    return (span_reduce.capture(run),
            peaks.PEAKS.get(run.get("device_kind"), {}))
