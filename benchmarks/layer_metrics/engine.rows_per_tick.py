"""Tokens generated per engine tick in the window: the delta of
`stats()["requests"]["generated_tokens"]` over the delta of
`stats()["ticks"]`: how full the decode batch runs."""

NAME = "engine.rows_per_tick"
UNIT = "count"
LAYER = "engine scheduler"
MOVES = "serve_tok_s"


def read(run):
    marks = run.get("marks") or {}
    if "start" not in marks or "end" not in marks:
        return None
    a, b = marks["start"]["stats"], marks["end"]["stats"]
    ticks = b["ticks"] - a["ticks"]
    toks = (b["requests"]["generated_tokens"]
            - a["requests"]["generated_tokens"])
    return toks / ticks if ticks > 0 else None
