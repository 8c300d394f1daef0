"""Milliseconds per tick for which chip 0 stood idle while requests were
live and the host was in no `engine.step`: under `server.deliver` (the
pump handing tokens to the streams) or between that and the next tick
(the event loop, the executor's hand-over)."""

from benchmarks.lib import span_reduce

NAME = "server.idle_between_ticks_ms"
UNIT = "ms"
LAYER = "server"
MOVES = "serve_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    found = cap and span_reduce.idle_summary(cap)
    return found["between_ticks_ms_per_tick"] if found else None
