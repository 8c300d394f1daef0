"""Milliseconds per tick for which chip 0 stood idle while the host was
inside `engine.step`: every idle interval that is not one program's own
is laid on the host's clock to end where the runtime enqueued the program
that ended it, and counts here for the part an `engine.step` span covers.
The table by phase is on the `[spans] idle` line and in
`span_tables.json`."""

from benchmarks.lib import span_reduce

NAME = "engine.idle_in_tick_ms"
UNIT = "ms"
LAYER = "engine scheduler"
MOVES = "serve_tok_s"


def read(run):
    cap = span_reduce.capture(run)
    found = cap and span_reduce.idle_summary(cap)
    return found["in_tick_ms_per_tick"] if found else None
