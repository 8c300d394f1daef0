"""Median device duration of the train step: of the programs on chip 0's
`XLA Modules` line, the one that took most of the traced time."""

from benchmarks.lib import trace_reduce

NAME = "train.step_ms"
UNIT = "ms"
LAYER = "trainer"
MOVES = "train_tok_s"


def read(run):
    events = run.get("events") or []
    names = trace_reduce.planes(events)
    if not names:
        return None
    totals = {}
    for e in events:
        if e[0] == names[0] and e[1] == trace_reduce.MODULES:
            name = trace_reduce.module_name(e[2])
            totals[name] = totals.get(name, 0) + e[4]
    if not totals:
        return None
    return trace_reduce.module_median_ms(events, max(totals, key=totals.get))
