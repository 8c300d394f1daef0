"""Host milliseconds per engine tick, `stats()["tick_times"]["host_ms_avg"]`
over the last 512 ticks of the window: the host's clock around the
engine's own fold and packing, not device time."""

NAME = "engine.host_ms_per_tick"
UNIT = "ms"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    marks = run.get("marks") or {}
    if "end" not in marks:
        return None
    return marks["end"]["stats"]["tick_times"]["host_ms_avg"]
