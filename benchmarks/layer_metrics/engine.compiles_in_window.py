"""Programs the engine built inside the window: the delta of
`stats()["jit_cache"]["compiled_programs"]`. The warm-up is there to keep
it at 0; above 0 it is reported, not a failure."""

NAME = "engine.compiles_in_window"
UNIT = "count"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    marks = run.get("marks") or {}
    if "start" not in marks or "end" not in marks:
        return None
    key = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    return key(marks["end"]) - key(marks["start"])
