"""Ulysses (DeepSpeed-style) sequence parallelism, GSPMD-native.

Net-new TPU work (SURVEY.md §2.4: SP is absent from the reference; ring
attention covers the ppermute formulation, this file covers the
all-to-all one). Ulysses trades the ring's O(sp) K/V hops for two
all-to-alls: activations arrive sequence-sharded over `sp`, attention
runs with *heads* sharded over `sp` (each device sees the full sequence
for its head slice), and the output is resharded back to
sequence-sharded.

Rather than hand-writing `lax.all_to_all`, we express both reshards as
sharding constraints and let GSPMD lower them to all-to-alls over ICI —
the idiomatic TPU formulation: the same attention kernel (XLA or Pallas
flash) runs unmodified between the two constraints, and XLA is free to
fuse/overlap the collectives.

Requires n_heads (and n_kv_heads, after GQA head repetition) divisible
by sp*tp for a balanced shard; XLA pads otherwise.
"""

from __future__ import annotations

import jax

from .attention import attention as attention_op, _repeat_kv
from ..parallel.mesh import AXIS_SP, AXIS_TP
from ..parallel.sharding import with_logical_constraint as wlc

# During the attention body, heads absorb the sp axis (alongside tp) and
# the sequence axis is gathered.
_UL_RULES = {
    "ul_heads": (AXIS_TP, AXIS_SP),
    "ul_seq": None,
}


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, impl: str = "auto",
                      mesh=None) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, S, KVH, D), sequence-sharded over sp.

    Returns (B, S, H, D) sequence-sharded. The two wlc pairs below are the
    entire Ulysses algorithm: seq-shard -> head-shard (all-to-all), local
    full-sequence attention, head-shard -> seq-shard (all-to-all).
    """
    h = q.shape[2]
    # GQA: repeat K/V up to the full head count first so the head axis is
    # divisible by sp*tp in the common configs (kv_heads alone usually
    # isn't once sp > 1).
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)

    q = wlc(q, "batch", "ul_seq", "ul_heads", "head_dim", rules=_UL_RULES)
    k = wlc(k, "batch", "ul_seq", "ul_heads", "head_dim", rules=_UL_RULES)
    v = wlc(v, "batch", "ul_seq", "ul_heads", "head_dim", rules=_UL_RULES)

    # the flash kernel (if impl resolves to it) runs per head shard:
    # same (tp, sp) head split the constraints above just established
    out = attention_op(q, k, v, causal=causal, impl=impl, mesh=mesh,
                       head_axes=_UL_RULES["ul_heads"])

    return wlc(out, "batch", "seq", "heads", "head_dim")
