"""The engine's gap ledger over the window (PR 55): which cause made the
gaps between streamed tokens, and which made the TAIL of them.

`stats()["requests"]["gaps"]` books every gap between two tokens of a
request once, when the engine closes the call that surfaced the later
token, to one cause (`ray_tpu/llm/_internal/telemetry.py`, GAP_CAUSES):
per cause `n`, `seconds`, `between_s` (the part outside every call) and
`hist`, a table {bucket: count} over the gap in microseconds, 16 buckets
a factor of two. All monotone, so the window's table is the difference
of the two marks (`window_counters.delta`). The percentile is taken over
all causes and interpolated in its bucket; the TAIL is every gap at or
above the lower edge of the bucket that holds it.

Every number here comes from the two marks, so a run has it whatever its
capture holds. A reader of the ledger returns None in two cases alone: a
mark without `["requests"]["gaps"]` (a program without the ledger: the
parent of PR 55) and a run without events (`window_counters.delta`'s
rule). Otherwise it returns a number: 0.0 where no gap fell. What the
CAPTURE holds of the tail gaps (`engine.step`'s `gap_max_ms`) is a line
of text and in no metric.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Tuple

from . import span_reduce, window_counters
from . import trace_reduce as tr
from .harness import say

BUCKETS_PER_OCTAVE = 16
TAIL_PERCENTILE = 95.0
# the four shares of the tail; a `same_tick` gap is 0 and never in it
TAIL_GROUPS = {"decode": ("decode",), "ragged": ("ragged",),
               "refill": ("refill",), "held": ("held", "capture")}


def edges_us(bucket: int) -> Tuple[float, float]:
    """[lower, upper) of a bucket in microseconds: bucket 0 is
    [0, 1), bucket b >= 1 is [2**((b-1)/16), 2**(b/16))."""
    if bucket <= 0:
        return 0.0, 1.0
    return (2.0 ** ((bucket - 1) / BUCKETS_PER_OCTAVE),
            2.0 ** (bucket / BUCKETS_PER_OCTAVE))


def window(run: Dict[str, Any]) -> Optional[Dict[str, Dict[str, Any]]]:
    """{cause: {"n", "seconds", "between_s", "hist": {bucket: count}}}
    of the gaps booked inside the window, buckets as whole numbers.
    None for a run without events or a mark without the ledger."""
    d = window_counters.delta(run, "requests")
    if d is None or any(
            "gaps" not in run["marks"][end]["stats"]["requests"]
            for end in ("start", "end")):
        return None
    return {cause: {**row, "hist": {int(b): k for b, k
                                    in row["hist"].items() if k}}
            for cause, row in d["gaps"].items()}


def merged(hists) -> Dict[int, int]:
    out: Dict[int, int] = {}
    for hist in hists:
        for b, k in hist.items():
            out[b] = out.get(b, 0) + k
    return out


def percentile_us(hist: Dict[int, int], p: float) -> Tuple[float, int]:
    """(the p-th percentile in microseconds, the bucket that holds it):
    the order statistic at rank p/100 x (n - 1), as `stats.percentile`
    ranks it, placed in its bucket by its rank among that bucket's
    gaps. A table without a gap reads (0.0, 0)."""
    n = sum(hist.values())
    rank = p / 100.0 * (n - 1)
    before = 0
    for b in sorted(hist):
        if rank < before + hist[b]:
            lo, hi = edges_us(b)
            return lo + (hi - lo) * (rank - before) / hist[b], b
        before += hist[b]
    return 0.0, 0


def p95_us(table: Dict[str, Dict[str, Any]]) -> Tuple[float, int]:
    """The window's 95th percentile over all causes, in microseconds,
    and the bucket that holds it."""
    return percentile_us(merged(r["hist"] for r in table.values()),
                         TAIL_PERCENTILE)


def tail(table: Dict[str, Dict[str, Any]]) -> Tuple[int, Dict[str, int]]:
    """(the bucket that holds the percentile over all causes, the gaps
    at or above its lower edge by TAIL_GROUPS)."""
    edge = p95_us(table)[1]
    return edge, {
        group: sum(k for c in causes for b, k
                   in table.get(c, {"hist": {}})["hist"].items()
                   if b >= edge)
        for group, causes in TAIL_GROUPS.items()}


def tail_share(run: Dict[str, Any], group: str) -> Optional[float]:
    """Percent of the window's tail gaps booked to `group`; the four
    groups' shares add to 100, and all read 0.0 where the window booked
    no gap (or none but gaps of 0 inside one call)."""
    table = window(run)
    if table is None:
        return None
    by_group = tail(table)[1]
    return 100.0 * by_group[group] / max(sum(by_group.values()), 1)


def between_calls_share(run: Dict[str, Any]) -> Optional[float]:
    """Percent of the window's gap seconds, all causes, outside every
    call's wall; 0.0 where no gap took time."""
    table = window(run)
    if table is None:
        return None
    seconds = sum(r["seconds"] for r in table.values())
    return (100.0 * sum(r["between_s"] for r in table.values()) / seconds
            if seconds > 0 else 0.0)


def say_table(table: Dict[str, Dict[str, Any]]) -> None:
    """The whole table on one line: by cause n, share of the gaps, mean,
    median and 95th percentile in ms, and the between-calls share."""
    total = sum(r["n"] for r in table.values())
    parts = []
    for cause, r in table.items():
        if not r["n"]:
            continue
        p50, p95 = (percentile_us(r["hist"], p)[0] / 1e3
                    for p in (50.0, 95.0))
        parts.append(
            f"{cause} n={r['n']} {100.0 * r['n'] / total:.1f}% "
            f"mean={r['seconds'] / r['n'] * 1e3:.2f} p50={p50:.2f} "
            f"p95={p95:.2f} between="
            f"{100.0 * r['between_s'] / max(r['seconds'], 1e-12):.1f}%")
    edge, by_group = tail(table)
    say(f"[counters] gaps in the window by cause (n, share, mean ms, "
        f"p50, p95, between-calls share): "
        f"{'; '.join(parts) or 'none booked'}; {total} booked, tail from "
        f"{edges_us(edge)[0] / 1e3:.2f} ms: {by_group}")


def host_idle(cap) -> List[Tuple[int, int]]:
    """Chip 0's idle intervals that no single program covers
    (`span_reduce.idle_intervals`), on the HOST's clock: each is laid so
    as to end when the runtime enqueued the program that ended it
    (`span_reduce.idle_by_label`'s rule; the two clocks disagree by a
    millisecond, so no device time is laid over a host span). An
    interval with no such record is left out."""
    mods = span_reduce.chip0(cap, tr.MODULES)
    starts = [m[3] for m in mods]
    out: List[Tuple[int, int]] = []
    for a, b in span_reduce.idle_intervals(cap):
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and mods[i][3] + mods[i][4] >= b:
            continue                           # the program's own
        at = (span_reduce.enqueued_at(cap, mods[i + 1])
              if i + 1 < len(mods) else None)
        if at is not None:
            out.append((at - (b - a), at))
    return sorted(out)


def tail_in_capture(cap, edge_ms: float) -> Tuple[int, float, float]:
    """Over the captured `engine.step` spans whose longest gap
    (`gap_max_ms`) is over 0 and at or above `edge_ms`: how many, the
    seconds of those gaps' intervals, [span end - gap, span end], and
    the percent of them for which chip 0 stood idle outside every
    program."""
    idle = host_idle(cap)
    starts = [a for a, _ in idle]
    n = gaps_ns = idle_ns = 0
    for s in span_reduce.ticks(cap):
        gap_ms = float(s[4].get("gap_max_ms") or 0.0)
        if s[1] != span_reduce.TICK or gap_ms <= 0.0 or gap_ms < edge_ms:
            continue
        lo, hi = s[3] - int(gap_ms * 1e6), s[3]
        n += 1
        gaps_ns += hi - lo
        j = max(bisect.bisect_right(starts, lo) - 1, 0)
        while j < len(idle) and idle[j][0] < hi:
            idle_ns += max(min(idle[j][1], hi) - max(idle[j][0], lo), 0)
            j += 1
    return n, gaps_ns / 1e9, 100.0 * idle_ns / gaps_ns if gaps_ns else 0.0


def say_capture(cap, table: Dict[str, Dict[str, Any]]) -> None:
    """What the capture holds of the window's tail gaps, as text: the
    capture is 4 s of the window and may hold none (or be none)."""
    n, seconds, idle = (tail_in_capture(
        cap, edges_us(tail(table)[0])[0] / 1e3) if cap else (0, 0.0, 0.0))
    say("[spans] tail gaps in the capture: " + (
        f"n={n}, {seconds:.4f} s, chip idle inside {idle:.1f}%" if n
        else "none in the capture"))


def p95_said(run: Dict[str, Any]) -> Optional[float]:
    """`engine.gap_p95_ms`, the first of the ledger's readers: it says
    the window's table and the capture's line on the way."""
    table = window(run)
    if table is None:
        return None
    say_table(table)
    say_capture(span_reduce.capture(run), table)
    return p95_us(table)[0] / 1e3
