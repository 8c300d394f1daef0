"""What keeping a cache in a quarter of the layers saves: 1 - the bytes
the latent group's pages and the held slots' recurrent state held, over
what the same pages would have held were EVERY layer latent (the latent
group's row in the state group's layers too), each group at its own
fullest in the ramp and the window (the runner resets the peaks before
them; a manager without a window group keeps no joint peak); from
`stats()["cache_groups"]` at the window's end (`pages_peak` and the
row's bytes of the latent group, `slots_peak` and `bytes_per_slot` of
the state group) and the engine's page size. The model's own claim at
this traffic's contexts: 43 MB of state a slot weigh against 1,280 B a
token a layer, so a slot under 1,700 tokens costs MORE than it saves
and the share can be negative. Nothing for a program whose stats have no latent group beside
a state group."""

from benchmarks.lib import spans_kimi_linear as sk

NAME = "kv.linear_saved_share"
UNIT = "%"
LAYER = "cache manager"
MOVES = "serve_tok_s"


@sk.quiet
def read(run):
    groups = run["marks"]["end"]["stats"]["cache_groups"]
    latent = [g for g in groups if g.get("kind") != "state"]
    state = [g for g in groups if g.get("kind") == "state"]
    if len(latent) != 1 or len(state) != 1 \
            or not latent[0].get("pages_peak"):
        return None
    latent, state = latent[0], state[0]
    a_layer = (latent["pages_peak"] * run["config"]["engine"]["page_size"]
               * latent["row"]["bytes_per_token_layer"])
    held = (a_layer * len(latent["layers"])
            + state["slots_peak"] * state["bytes_per_slot"])
    return 100.0 * (1.0 - held / (
        a_layer * (len(latent["layers"]) + len(state["layers"]))))
