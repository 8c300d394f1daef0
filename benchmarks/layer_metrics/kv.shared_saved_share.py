"""What one shared cache, a window and a fixed state save: 1 - the bytes
ALL cache groups held (the full group's pages of ONE layer, the window
group's pages, the state group's bytes a slot) when the cache's bytes in
use were at their peak in the ramp and the window (the runner resets the
peaks before them), over what the same sequences would have held with a
K and V of their own in each of the 16 attention layers (the writers and
readers of the page groups) and no window: the full group's pages at
that moment in every one of them. From `stats()["cache_groups"]` at the
window's end. Nothing for a program whose stats have no state group."""

from benchmarks.lib import spans_phi4flash as sp

NAME = "kv.shared_saved_share"
UNIT = "%"
LAYER = "cache manager"
MOVES = "serve_tok_s"


@sp.quiet
def read(run):
    end = run["marks"]["end"]["stats"]
    groups = end["cache_groups"]
    state = [g for g in groups if g.get("kind") == "state"]
    paged = [g for g in groups if g.get("kind") != "state"]
    full = next(g for g in paged if not g.get("window"))
    if not state or not full.get("readers") or not full["pages_at_peak"]:
        return None
    page = run["config"]["engine"]["page_size"]
    held = sum(g["pages_at_peak"] * page * len(g["layers"])
               * g["row"]["bytes_per_token_layer"] for g in paged)
    held += sum(g["slots_at_peak"] * g["bytes_per_slot"] for g in state)
    attending = sum(len(g["layers"]) + len(g.get("readers") or ())
                    for g in paged)
    dense = (full["pages_at_peak"] * page * attending
             * full["row"]["bytes_per_token_layer"])
    return 100.0 * (1.0 - held / dense)
