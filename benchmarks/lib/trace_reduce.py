"""From a profiler trace to numbers: the one reduction every PR shares.

An event is `[plane, line, name, start_ns, duration_ns]`. Only the device
planes (`/device:TPU:N`) and their lines `XLA Modules` (one event per
executed program: `jit_step` is a decode tick, `jit_run` a ragged tick)
and `XLA Ops` (one per operation, a `while` spanning its body's) are kept.

The first second or so of a capture is the profiler starting, and its end
the profiler stopping, so the window is bounded by the first and the last
module event on the device, never by the capture's own start and stop.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

Event = Sequence          # [plane, line, name, start_ns, duration_ns]
MODULES, OPS = "XLA Modules", "XLA Ops"
DEVICE_PLANE = "/device:TPU:"


def extract(log_dir: str) -> List[list]:
    """Events of the newest `.xplane.pb` under log_dir, device planes and
    the two lines above only."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    events: List[list] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name not in (MODULES, OPS):
                continue
            short = op_name if line.name == OPS else str
            for ev in line.events:
                events.append([plane.name, line.name, short(ev.name),
                               int(ev.start_ns), int(ev.duration_ns)])
    return events


_HLO = re.compile(r"^%?(?P<name>\S+) = .*? (?P<opcode>[a-z\-]+)\(")


def op_name(text: str) -> str:
    """The trace prints an operation as its whole HLO instruction,
    `%fusion.12 = bf16[...] fusion(...), kind=...`; keep `fusion.12`, and
    for a custom call (a Pallas kernel) its opcode too."""
    m = _HLO.match(text)
    if m is None:
        return text[:80]
    if m["opcode"] in ("fusion", m["name"].split(".")[0]):
        return m["name"]
    return f"{m['name']}[{m['opcode']}]"


def planes(events: Sequence[Event]) -> List[str]:
    """Device planes among the events, by chip number."""
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PLANE)},
                  key=lambda p: int(p[len(DEVICE_PLANE):].split()[0]))


def _of(events, plane, line):
    return sorted((e for e in events if e[0] == plane and e[1] == line),
                  key=lambda e: e[3])


def window_ns(events: Sequence[Event], plane: str
              ) -> Optional[Tuple[int, int]]:
    """(start of the first module event, end of the last) on one plane."""
    mods = _of(events, plane, MODULES)
    if not mods:
        return None
    return mods[0][3], max(e[3] + e[4] for e in mods)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_intervals(events: Sequence[Event], plane: str
                   ) -> List[Tuple[int, int]]:
    """Union of the intervals in which an operation ran on `plane`,
    clipped to its window."""
    win = window_ns(events, plane)
    if win is None:
        return []
    lo, hi = win
    spans = [(max(e[3], lo), min(e[3] + e[4], hi))
             for e in _of(events, plane, OPS)]
    return _union([(a, b) for a, b in spans if b > a])


def busy_and_window_s(events: Sequence[Event]) -> Tuple[float, float]:
    """(seconds an operation ran on the device, averaged over the planes
    that ran any; length of the traced window in seconds, the longest
    plane's)."""
    busy, wins = [], []
    for plane in planes(events):
        win = window_ns(events, plane)
        if win is None:
            continue
        wins.append((win[1] - win[0]) / 1e9)
        busy.append(sum(b - a for a, b in busy_intervals(events, plane))
                    / 1e9)
    if not wins:
        return 0.0, 0.0
    return sum(busy) / len(busy), max(wins)


def idle_share_pct(events: Sequence[Event], plane: Optional[str] = None
                   ) -> Optional[float]:
    """100 * (1 - busy / window) on one plane (default: the first)."""
    names = planes(events)
    if not names:
        return None
    plane = plane or names[0]
    win = window_ns(events, plane)
    if win is None or win[1] <= win[0]:
        return None
    busy = sum(b - a for a, b in busy_intervals(events, plane))
    return 100.0 * (1.0 - busy / (win[1] - win[0]))


def module_name(name: str) -> str:
    """`jit_step(1234567890)` -> `jit_step`."""
    return name.split("(", 1)[0]


def module_durations_ms(events: Sequence[Event], module: str,
                        plane: Optional[str] = None) -> List[float]:
    names = planes(events)
    if not names:
        return []
    plane = plane or names[0]
    return [e[4] / 1e6 for e in _of(events, plane, MODULES)
            if module_name(e[2]) == module]


def module_median_ms(events: Sequence[Event], module: str
                     ) -> Optional[float]:
    durs = module_durations_ms(events, module)
    return statistics.median(durs) if durs else None


def op_self_seconds(events: Sequence[Event], plane: Optional[str] = None
                    ) -> Dict[str, float]:
    """Seconds per operation name on one plane, counting for each event
    only the time no operation nested inside it covers (a `while` is
    charged what its body's operations leave)."""
    names = planes(events)
    if not names:
        return {}
    plane = plane or names[0]
    totals: Dict[str, float] = {}
    stack: List[list] = []        # [name, end_ns, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            totals[name] = totals.get(name, 0.0) + self_ns / 1e9

    for _, _, name, start, dur in _of(events, plane, OPS):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return totals


def idle_gaps_s(events: Sequence[Event], plane: Optional[str] = None
                ) -> Dict[str, float]:
    """Idle seconds inside the window on one plane, by the programs on
    either side of each gap (`jit_step->jit_run`): what the host was
    getting ready. Gaps inside one program carry its name alone."""
    names = planes(events)
    if not names:
        return {}
    plane = plane or names[0]
    mods = _of(events, plane, MODULES)
    starts = [e[3] for e in mods]
    busy = busy_intervals(events, plane)
    out: Dict[str, float] = {}
    for (_, a_end), (b_start, _) in zip(busy, busy[1:]):
        i = bisect.bisect_right(starts, a_end) - 1
        prev = module_name(mods[i][2]) if i >= 0 else "?"
        if i >= 0 and mods[i][3] + mods[i][4] >= b_start:
            label = prev
        else:
            nxt = module_name(mods[i + 1][2]) if i + 1 < len(mods) else "?"
            label = f"{prev}->{nxt}"
        out[label] = out.get(label, 0.0) + (b_start - a_end) / 1e9
    return out


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, s] for n, s in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def breakdown(events: Sequence[Event]) -> Dict[str, List[list]]:
    return {"device_ops": top(op_self_seconds(events)),
            "idle_gaps": top(idle_gaps_s(events))}
