"""Median device duration of a ragged (prefill or mixed) tick: the
`jit_run` events on the `XLA Modules` line of chip 0."""

from benchmarks.lib import trace_reduce

NAME = "step.ragged_ms"
UNIT = "ms"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


def read(run):
    return trace_reduce.module_median_ms(run.get("events") or [], "jit_run")
