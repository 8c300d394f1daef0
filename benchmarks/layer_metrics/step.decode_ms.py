"""Median device duration of a pure-decode tick: the `jit_step` events on
the `XLA Modules` line of chip 0 in the traced part of the window."""

from benchmarks.lib import trace_reduce

NAME = "step.decode_ms"
UNIT = "ms"
LAYER = "model forwards"
MOVES = "itl_p95_ms"


def read(run):
    return trace_reduce.module_median_ms(run.get("events") or [], "jit_step")
