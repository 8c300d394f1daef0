"""Of the window's TAIL gaps between streamed tokens (those at or above the
lower edge of the bucket that holds the 95th percentile over all
causes), the percent the engine booked to `held` or `capture`: over half
the gap outside every call, a decode or refill gap over four times its
cause's running mean, or a profile capture's trace live or being
written, ROADMAP A13 (`lib/gap_ledger.py`; the decode, ragged, refill
and held shares add to 100). Read from the two marks' counters alone:
None only from a program without the ledger or a run without events, 0.0
where no tail gap fell to it."""

from benchmarks.lib import gap_ledger

NAME = "engine.tail_gap_held_share"
UNIT = "%"
LAYER = "engine scheduler"
MOVES = "itl_p95_ms"


def read(run):
    return gap_ledger.tail_share(run, "held")
