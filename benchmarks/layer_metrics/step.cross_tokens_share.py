"""The cross-decoder's share of a tick's rows: the tokens the layers
above the shared cache ran on (`cross_tokens`: one a row that samples)
over the tokens the traced ticks carried, both from the dispatch spans.
100 in a decode tick; a 512-token chunk beside 40 decode rows reads 7.4:
the chunk's other tokens stop at the layer that writes the shared
cache. Nothing for a program whose spans carry no `cross_tokens`."""

from benchmarks.lib import spans_phi4flash as sp

NAME = "step.cross_tokens_share"
UNIT = "%"
LAYER = "model forwards"
MOVES = "serve_tok_s"


@sp.quiet
def read(run):
    spans = [a for a in sp.dispatch_args(run) if "cross_tokens" in a]
    tokens = sum(a["rows"] if a.get("kind") == "decode"
                 else a["decode_rows"] + a["prefill_tokens"]
                 for a in spans)
    if not tokens:
        return None
    return 100.0 * sum(a["cross_tokens"] for a in spans) / tokens
