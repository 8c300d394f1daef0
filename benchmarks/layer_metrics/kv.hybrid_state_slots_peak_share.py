"""How near the state group came to refusing admissions in a run of the
GraniteHybrid family: the most slots that held their recurrent state at
once in the ramp and the window (the runner resets the peaks before
them) over the slots there are, from `stats()["cache_groups"]` at the
window's end. Pages are plenty in this cell (8 KB a token); 76 MB of
state a slot is what runs out. Nothing for a program whose stats have no
state group."""

from benchmarks.lib import spans_granite_hybrid as sg

NAME = "kv.hybrid_state_slots_peak_share"
UNIT = "%"
LAYER = "cache manager"
MOVES = "serve_tok_s"


@sg.quiet
def read(run):
    groups = run["marks"]["end"]["stats"]["cache_groups"]
    state = [g for g in groups if g.get("kind") == "state"]
    if not state or not state[0]["slots_total"]:
        return None
    return 100.0 * state[0]["slots_peak"] / state[0]["slots_total"]
