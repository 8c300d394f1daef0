"""The host's milliseconds per engine tick in the window: the delta of
`stats()["tick_phases"]["seconds"]` over every phase but `readback_wait`
(scheduling, packing, accounting, dispatch, fold, refresh, and `other`:
tick wall that no phase covers), over the delta of its `ticks`. What
`engine.host_ms_per_tick` was taken for; that one reads the fold alone."""

from benchmarks.lib import window_counters

NAME = "engine.tick_host_ms"
UNIT = "ms"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    d = window_counters.delta(run, "tick_phases")
    if d is None or d["ticks"] <= 0:
        return None
    host_s = sum(v for k, v in d["seconds"].items() if k != "readback_wait")
    return host_s / d["ticks"] * 1e3
