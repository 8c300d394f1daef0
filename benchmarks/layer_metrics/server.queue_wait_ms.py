"""Mean wait from submission to admission over the requests admitted in the
window: the delta of the engine telemetry's monotone `slo_totals()` sums
between the window's two ends (a program counter; host side)."""

NAME = "server.queue_wait_ms"
UNIT = "ms"
LAYER = "server"
MOVES = "itl_p95_ms"


def read(run):
    marks = run.get("marks") or {}
    if "start" not in marks or "end" not in marks:
        return None
    a, b = marks["start"]["slo"], marks["end"]["slo"]
    n = b["queue_n"] - a["queue_n"]
    return (b["queue_s"] - a["queue_s"]) / n * 1e3 if n > 0 else None
