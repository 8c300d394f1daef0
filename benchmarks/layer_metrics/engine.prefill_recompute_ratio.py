"""Prefill work done twice in the window: the delta of
`stats()["prefill"]["prefill_tokens_dispatched"]` over the delta of
`["prompt_tokens_admitted"]` (prompt tokens of the requests admitted,
less the prefix cache's share), less one. 0 unless a request was
preempted and recomputed or restarted; admissions and their last chunks
can fall on either side of the window's ends, so a few percent either way
is the window's edge."""

from benchmarks.lib import window_counters

NAME = "engine.prefill_recompute_ratio"
UNIT = "ratio"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    d = window_counters.delta(run, "prefill")
    if d is None or d["prompt_tokens_admitted"] <= 0:
        return None
    return (d["prefill_tokens_dispatched"]
            / d["prompt_tokens_admitted"] - 1.0)
