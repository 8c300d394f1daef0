"""What the window group saves: 1 - the bytes the live sequences held,
every cache group, over what they would have held with every layer
full (the full group's pages in every layer), when the cache's bytes in
use were at their peak in the ramp and the window (the runner resets
the peaks before them); from `stats()["cache_groups"]` at the window's
end (`pages_at_peak`, the row's bytes and the layers of each group).
Nothing for a program whose stats have no groups, or none with a
window."""

NAME = "kv.window_saved_share"
UNIT = "%"
LAYER = "cache manager"
MOVES = "serve_tok_s"


def read(run):
    end = ((run.get("marks") or {}).get("end") or {}).get("stats") or {}
    groups = end.get("cache_groups") or []
    if not any(g.get("window") for g in groups):
        return None
    per_page = [len(g["layers"]) * g["row"]["bytes_per_token_layer"]
                for g in groups]
    full = next(g for g in groups if not g.get("window"))
    if not full.get("pages_at_peak"):
        return None
    held = sum(g["pages_at_peak"] * b for g, b in zip(groups, per_page))
    return 100.0 * (1.0 - held / (full["pages_at_peak"] * sum(per_page)))
