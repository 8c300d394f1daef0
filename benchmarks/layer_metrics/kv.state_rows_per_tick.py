"""Rows whose recurrent state a traced tick read and wrote, the mean
over the traced ticks: the dispatch span's `ssm_rows` (a decode row and
a prompt's chunk are a row each). At 153 MB in and out a row a tick the
state's traffic passes the 6.4 GB of weights near 40 rows: this says
how full the ticks are that the scan kernel's shares were read on.
Nothing for a program whose spans carry no such count."""

from benchmarks.lib import spans_granite_hybrid as sg
from benchmarks.lib import spans_phi4flash as sp

NAME = "kv.state_rows_per_tick"
UNIT = "count"
LAYER = "cache manager"
MOVES = "serve_tok_s"


@sg.quiet
def read(run):
    rows = [args["ssm_rows"] for args in sp.dispatch_args(run)]
    return sum(rows) / len(rows) if rows else None
