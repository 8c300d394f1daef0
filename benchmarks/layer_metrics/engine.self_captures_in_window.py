"""What the engine's own tracing did inside the window: profile captures
armed (by the tick-anomaly detector or an operator) plus black-box
bundles dumped, from the deltas of `stats()["self_captures"]`. Arming is
what counts: in a traced run the benchmark's own profiler session makes
the engine's `start_trace` fail. Want 0: a capture starts and stops
inside `step()` under the step lock."""

import json

from benchmarks.lib import window_counters
from benchmarks.lib.harness import say

NAME = "engine.self_captures_in_window"
UNIT = "count"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    d = window_counters.delta(run, "self_captures")
    if d is None:
        return None
    n = (sum(d["profiles_armed"].values())
         + sum(d["blackbox_dumps"].values()))
    if n:
        # which ticks the detector flagged, and the last of them
        anomaly = run["marks"]["end"]["stats"].get("anomaly") or {}
        say(f"[counters] self captures in the window: {json.dumps(d)}; "
            f"anomalies by kind (since start-up) "
            f"{json.dumps(anomaly.get('by_kind'))}, the last: "
            f"{json.dumps(anomaly.get('last'))}")
    return n
