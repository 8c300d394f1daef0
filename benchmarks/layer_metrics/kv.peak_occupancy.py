"""Peak share of the KV pool's pages in use, sampled each second of the
window from `stats()` (1 - free_pages / total_pages)."""

NAME = "kv.peak_occupancy"
UNIT = "%"
LAYER = "cache manager"
MOVES = "serve_tok_s"


def read(run):
    samples = (run.get("marks") or {}).get("occupancy")
    return 100.0 * max(samples) if samples else None
