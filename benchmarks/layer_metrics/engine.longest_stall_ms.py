"""The longest stall of the window, from `stats()["tick_times"]["longest"]`
at its end: the longest gap between two ticks while work remained, or the
largest excess of one tick's wall over the median wall of its kind,
whichever is larger, among the records that started inside the window.
Which of the two, and what that tick carried and did, on an earlier
line."""

import json

from benchmarks.lib import window_counters
from benchmarks.lib.harness import say

NAME = "engine.longest_stall_ms"
UNIT = "ms"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    found = window_counters.longest_stall(run)
    if found is None:
        return None
    what, ms, record = found
    say(f"[counters] longest stall in the window: {what} {ms:.3f} ms "
        f"{json.dumps(record)}")
    return ms
