"""Mean number of live decode slots, sampled each second of the window
from `stats()["active"]`. Below the knee it settles near rate x a
request's life; a rate the engine does not sustain shows here first, as
slots that fill from one cycle of the traffic to the next (the sweep's
criterion, benchmarks/sweep.py)."""

NAME = "engine.live_slots"
UNIT = "count"
LAYER = "engine scheduler"
MOVES = "serve_tok_s"


def read(run):
    samples = (run.get("marks") or {}).get("live")
    return sum(samples) / len(samples) if samples else None
