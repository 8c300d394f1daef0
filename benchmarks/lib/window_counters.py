"""The engine's monotone counters over the window: `stats()` is stored
whole at both of its ends (`run["marks"]`), and what a counter did in
between is the difference. A program that lacks a counter (the parent of
the PR that adds it) gives None, and the reader leaves its metric out. So
does a run that took no trace: per-layer metrics belong to the traced run
(`tests/benchmark`'s CPU rehearsal takes none, and pins the metrics that
a run without one reports)."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple


def _minus(b: Any, a: Any) -> Any:
    """b - a for numbers, key by key for tables of them (a key that
    appeared inside the window counts from 0)."""
    if isinstance(b, dict):
        a = a or {}
        return {k: _minus(v, a.get(k)) for k, v in b.items()}
    return b - (a or 0)


def delta(run: Dict[str, Any], key: str) -> Optional[Dict[str, Any]]:
    """stats()[key] at the window's end less the same at its start."""
    marks = run.get("marks") or {}
    if not run.get("events") or "start" not in marks or "end" not in marks:
        return None
    a, b = marks["start"]["stats"], marks["end"]["stats"]
    if key not in a or key not in b:
        return None
    return _minus(b[key], a[key])


def longest_stall(run: Dict[str, Any]
                  ) -> Optional[Tuple[str, float, Dict[str, Any]]]:
    """(which, milliseconds, the tick's record) of the longest stall
    among the ticks that started inside the window: the longest gap
    before a tick while work remained ("gap"), or the largest excess of
    a tick's wall over the median wall of its kind ("tick"). The ring
    holds 1,024 ticks and names its eight worst of each sort, so a
    window's worst is there unless the ramp before it held eight
    worse."""
    marks = run.get("marks") or {}
    if not run.get("events"):
        return None
    try:
        since = marks["start"]["stats"]["tick_times"]["now"]
        longest = marks["end"]["stats"]["tick_times"]["longest"]
    except KeyError:
        return None
    found = [("tick", r["excess_ms"], r) for r in longest["stalls"]
             if r["start"] >= since]
    found += [("gap", r["gap_ms"], r) for r in longest["gaps"]
              if r["start"] >= since]
    return max(found, key=lambda f: f[1]) if found else None
