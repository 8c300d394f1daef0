"""Ticks the engine's anomaly detector flagged inside the window: the
delta of `stats()["anomaly"]["anomalies_total"]`, with the flags by kind,
the ticks judged and the last flagged tick on an earlier line. A healthy
window wants 0: every flag is a flight event, feeds the rate the fleet
watchdog pages on, and may arm a capture and a bundle."""

import json

from benchmarks.lib.harness import say

NAME = "engine.anomaly_flags_in_window"
UNIT = "count"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    marks = run.get("marks") or {}
    if not run.get("events") or "start" not in marks or "end" not in marks:
        return None
    a, b = (marks[k]["stats"].get("anomaly") or {}
            for k in ("start", "end"))
    if "anomalies_total" not in a or "anomalies_total" not in b:
        return None
    n = b["anomalies_total"] - a["anomalies_total"]
    before = a.get("by_kind") or {}
    by_kind = {k: v - before.get(k, 0)
               for k, v in (b.get("by_kind") or {}).items()
               if v - before.get(k, 0)}
    say(f"[counters] anomaly flags in the window: {n} "
        f"{json.dumps(by_kind)} of {b.get('ticks', 0) - a.get('ticks', 0)}"
        f" ticks judged; the last (since start-up): "
        f"{json.dumps(b.get('last'))}")
    return n
