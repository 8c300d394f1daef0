"""Device microseconds per token of the traced ragged ticks: the time of
the `jit_run` programs over the tokens their `engine.dispatch` spans say
they carried (decode rows plus prefill tokens). `step.ragged_ms` is a
median over programs of up to 35 shapes; this is what a token in them
costs, and the table by (T, ctx, rows) is on the `[spans] ragged_cost`
line."""

from benchmarks.lib import span_reduce

NAME = "step.ragged_us_per_token"
UNIT = "us"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    found = cap and span_reduce.ragged_cost(cap)
    return found["us_per_token"] if found else None
