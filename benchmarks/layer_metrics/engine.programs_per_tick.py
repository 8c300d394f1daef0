"""Programs the device ran per engine tick in the traced part of the
window: events on chip 0's `XLA Modules` line between the first
`engine.step` span's start and the last one's end, over the number of
those spans. The engine's own `dispatches_per_step` counts the tick's
forward alone; the key split, the position update and the state refresh
are programs too. Want 1."""

from benchmarks.lib import span_reduce

NAME = "engine.programs_per_tick"
UNIT = "count"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    cap = span_reduce.capture(run)
    found = cap and span_reduce.programs_per_tick(cap)
    return found["per_tick"] if found else None
