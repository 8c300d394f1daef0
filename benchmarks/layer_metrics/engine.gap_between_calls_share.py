"""Percent of the window's gap seconds, all causes, that lay outside every
`engine.step` call's wall: the pump's delivery, the event loop and
whatever else held the process between two calls while work remained
(`between_s` over `seconds` of the gap ledger, `lib/gap_ledger.py`).
Read from the two marks' counters alone: None only from a program
without the ledger or a run without events; 0.0 where the window booked
no gap."""

from benchmarks.lib import gap_ledger

NAME = "engine.gap_between_calls_share"
UNIT = "%"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    return gap_ledger.between_calls_share(run)
