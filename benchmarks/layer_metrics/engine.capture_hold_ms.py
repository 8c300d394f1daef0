"""Milliseconds of the window for which the engine's own tracing held the
step lock: the delta of `stats()["self_captures"]["lock_hold_s"]`, which
the engine books around what it does under the lock when a tick is
flagged or a capture is armed (the arming, the ticks' counting, an
abort's stop in line). Starting a trace, stopping and writing it and
dumping a bundle are a writer thread's since PR 39, so this wants ~0; a
capture that stalled the streams shows here, one that did not does not.
A program without the counter (the parent of PR 39) is left out."""

from benchmarks.lib import window_counters

NAME = "engine.capture_hold_ms"
UNIT = "ms"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    d = window_counters.delta(run, "self_captures")
    if d is None or "lock_hold_s" not in d:
        return None
    return d["lock_hold_s"] * 1e3
