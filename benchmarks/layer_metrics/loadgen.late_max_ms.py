"""How late the open-loop generator sent: the largest send time minus due
time over the measured requests, on the generator's own clock. A starved
generator must not be read as a fast server. The largest, not a
percentile: a window holds a few tens of requests."""

NAME = "loadgen.late_max_ms"
UNIT = "ms"
LAYER = "load generator"
MOVES = "serve_tok_s"


def read(run):
    return (run.get("client") or {}).get("late_max_ms")
