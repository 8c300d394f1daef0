"""The 95th percentile of the gaps between streamed tokens that the engine
booked inside the window, over all causes, interpolated in its bucket
(`lib/gap_ledger.py`; 16 buckets a factor of two, so to 4.4%). The
engine's ledger stamps a token with the end of the `engine.step` call
that surfaced it and holds every interactive request; `itl_p95_ms`
beside it is the client's clock over the measured requests alone, so the
difference bounds what delivery and the choice of requests add. Read
from the two marks' counters alone: None only from a program without the
ledger or a run without events; 0.0 where the window booked no gap. The
whole table by cause, and what the capture holds of the tail, are on
earlier lines."""

from benchmarks.lib import gap_ledger

NAME = "engine.gap_p95_ms"
UNIT = "ms"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    return gap_ledger.p95_said(run)
