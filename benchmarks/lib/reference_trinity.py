"""Plain reference of Trinity's (`model_type` "afmoe") forward pass, in
float32.

As the published modelling code computes it
(https://huggingface.co/arcee-ai/Trinity-Large-Preview, `config.json`
and `modeling_afmoe.py`): the embedding scaled by sqrt(hidden)
(`mup_enabled`); each layer an attention block and a feed-forward block,
each normed going in AND coming out (four RMS norms a layer:
x = x + Norm_post(Block(Norm_pre(x)))); grouped-query attention with a
per-head RMS norm on q and k, rotate-half rope on `sliding_attention`
layers ONLY (none on `full_attention` layers), a causal mask on a full
layer and a causal band of `sliding_window` keys on a window layer
(query i sees keys j with i - window < j <= i), and the output
multiplied by sigmoid(y W_gate) before W_o; `num_dense_layers` SwiGLU
layers, then expert layers: sigmoid scores over all routed experts,
the picks the `num_experts_per_tok` largest of scores + `expert_bias`
(one routing group; ties to the lower index), gate weights the UNBIASED
scores at the picks over their sum (`route_norm`), times `route_scale`,
a shared expert, and a LOOP over the experts. A mask a layer kind, no
kernel, no cache, no batching, float32 throughout under
`jax.default_matmul_precision("highest")` (a TPU multiplies float32 in
lower precision otherwise). It runs op by op, upcasts one matrix at a
time and takes queries and feed-forward rows in slices, so that a
10k-token sequence at the published widths fits beside the engine
(48 heads x 128 query rows x 10k keys x 4 B = 0.25 GB a slice).

It takes the parameter tree of the system under test (`embed`,
`layers`: a list of one tree a layer, an expert layer where it has a
`router`, `final_norm`, `lm_head`) and `experts_held` = (lo, hi), and
changes no value. Departures from the
published code, each forced by that tree or by the chip's share:

- Matrices come stored [in, out] (the checkpoint's `nn.Linear` weights
  are [out, in]), a layer's routed experts stacked on a leading axis.
- Only the routed experts lo..hi-1 exist in the tree; the router still
  scores and picks among all of them and the picks that fall outside
  the range add nothing (the model-configs guide's section 4): what the
  absent chips would add is left out here as in the system.
- `load_balance_coeff` (a training loss) has no part in a forward.

`variant` (a set of words) leaves one thing out or puts one wrong, for
the readings a comparison's limits have to stay under
(`checks_trinity.precision_probe`); the reference itself takes none.
"""

from __future__ import annotations

import math
from typing import Any, Dict, FrozenSet, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
SLIDING = "sliding_attention"
# None: operands as stored. A narrower type (float8_e4m3fn) rounds every
# stored matrix and vector to it first: the forward in the precision
# below the stated one
_OPERANDS = None
# what is left out or put wrong, of: "all_full" (a window layer attends
# to everything), "all_window" (a full layer is windowed),
# "rope_on_full", "no_gate", "no_qk_norm", "no_route_scale",
# "no_embed_scale"
_VARIANT: FrozenSet[str] = frozenset()

# query rows a slice of attention takes, and rows a slice of a
# feed-forward block takes
Q_ROWS, FFN_ROWS = 128, 2048


def _f32(a):
    a = jnp.asarray(a)
    if _OPERANDS is not None and jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(_OPERANDS)
    return a.astype(F32)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def _rope(model, x):
    """x: [S, heads, d] at positions 0..S-1, rotate-half over all d."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / model["rope_theta"] ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(model, w, x, kind: str):
    """x: [S, H] -> the attention block's output [S, H], before the
    post-attention norm. Queries are taken Q_ROWS at a time (a loop, so
    that the float32 scores of a long sequence fit): a query's sums are
    its own, so the slicing changes no value."""
    s = x.shape[0]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    y = _rms_norm(x, w["ln_in"], eps)
    q = (y @ _f32(w["wq"])).reshape(s, nh, d)
    k = (y @ _f32(w["wk"])).reshape(s, nkv, d)
    v = (y @ _f32(w["wv"])).reshape(s, nkv, d)
    g = y @ _f32(w["wgate"])
    if "no_qk_norm" not in _VARIANT:
        q = _rms_norm(q, w["q_norm"], eps)
        k = _rms_norm(k, w["k_norm"], eps)
    window = kind == SLIDING
    if window or "rope_on_full" in _VARIANT:
        q, k = _rope(model, q), _rope(model, k)
    if "all_full" in _VARIANT:
        window = False
    if "all_window" in _VARIANT:
        window = True
    # query head h reads kv head h // (nh / nkv)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    j = jnp.arange(s)[None, :]
    out = []
    for i0 in range(0, s, Q_ROWS):
        i = jnp.arange(i0, min(i0 + Q_ROWS, s))[:, None]
        keep = j <= i
        if window:
            keep = keep & (j > i - model["sliding_window"])
        scores = jnp.einsum("qhd,khd->hqk", q[i0:i0 + Q_ROWS], k
                            ) / math.sqrt(d)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        # waited for, so that a long sequence's loop does not run ahead
        # of the device and hold every slice's scores at once
        out.append(jax.block_until_ready(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)))
    o = jnp.concatenate(out).reshape(s, -1)
    if "no_gate" not in _VARIANT:
        o = o * jax.nn.sigmoid(g)
    return o @ _f32(w["wo"])


def _swiglu(y, wg, wi, wd):
    wg, wi, wd = _f32(wg), _f32(wi), _f32(wd)
    return jnp.concatenate([
        (jax.nn.silu(y[r:r + FFN_ROWS] @ wg) * (y[r:r + FFN_ROWS] @ wi))
        @ wd for r in range(0, y.shape[0], FFN_ROWS)])


def route(model, scores, bias) -> Tuple[jax.Array, jax.Array]:
    """scores: [S, E] sigmoid scores; bias: [E] -> (gate weights [S, k],
    expert indices [S, k]); ties go to the lower index."""
    k = model["num_experts_per_tok"]
    idx = jnp.argsort(-(scores + _f32(bias)), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=1)
    if model.get("route_norm", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in _VARIANT:
        w = w * model["route_scale"]
    return w, idx


def experts(model, w, y, experts_held):
    """y: [S, H] normalised -> shared expert + the held experts' part of
    the routed sum, a loop over the experts held."""
    lo, hi = experts_held
    scores = jax.nn.sigmoid(y @ _f32(w["router"]))
    gate, idx = route(model, scores, w["router_bias"])
    sh = w["shared"]
    out = _swiglu(y, sh["wg"], sh["wi"], sh["wd"])
    ex = w["experts"]
    for e in range(lo, hi):
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)     # [S]
        out = out + g[:, None] * _swiglu(
            y, ex["wg"][e - lo], ex["wi"][e - lo], ex["wd"][e - lo])
    return out


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens,
           experts_held: Tuple[int, int], operands=None, rows=None,
           variant=()):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence,
    or of its positions `rows` alone (the head is the last thing
    computed: a long sequence's other rows are not wanted).
    `operands`: see `_OPERANDS`; `variant`: see `_VARIANT` (None and ()
    for the reference itself)."""
    global _OPERANDS, _VARIANT
    _OPERANDS, _VARIANT = operands, frozenset(variant)
    try:
        return _logits(model, params, tokens, experts_held, rows)
    finally:
        _OPERANDS, _VARIANT = None, frozenset()


def _logits(model, params, tokens, experts_held, rows):
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        if model.get("mup_enabled") and "no_embed_scale" not in _VARIANT:
            x = x * math.sqrt(model["hidden_size"])
        kinds = model["layer_types"]
        if len(kinds) != len(params["layers"]):
            raise ValueError(f"{len(params['layers'])} layers in the "
                             f"tree, {len(kinds)} layer_types")
        for w, kind in zip(params["layers"], kinds):
            a = attention(model, w, x, kind)
            x = x + _rms_norm(a, w["ln_post_attn"], eps)
            y = _rms_norm(x, w["ln_pre_mlp"], eps)
            if "router" in w:
                f = experts(model, w, y, experts_held)
            else:
                f = _swiglu(y, w["wg"], w["wi"], w["wd"])
            x = x + _rms_norm(f, w["ln_post_mlp"], eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = _rms_norm(x, params["final_norm"], eps)
        return x @ _f32(params["lm_head"])
