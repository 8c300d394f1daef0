"""Plain reference of SmallThinker's (`model_name`
"smallthinker_21b_instruct") forward pass, in float32.

From the published `config.json`
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct) and the
family's description, for layer l with input x [S, hidden]:

1. Router, first: r = x W_r; idx = the `moe_num_active_primary_experts`
   largest of r (ties to the lower index); w = softmax over the picked
   logits (`moe_primary_router_apply_softmax` with `norm_topk_prob`: the
   softmax over all experts with the picks renormalised). ASSUMED: the
   router reads x itself, ahead of the input norm (`_router_input`, the
   one line that holds the choice; "router placed before attention").
2. Attention: y = RMSNorm_in(x); q, k, v = y W_q, y W_k, y W_v (28 query
   heads over 4 K/V heads of 128), no bias, no QK norm, no gate;
   `rope_layout[l]` 1: rotate-half rope, theta 1.5e6, on q and k, 0:
   none; `sliding_window_layout[l]` 1: query i sees keys j with
   i - window < j <= i, 0: j <= i; scores q.k / sqrt(128), softmax;
   x = x + o W_o.
3. Experts: y = RMSNorm_post(x); f = sum_k w_k W_down[e_k](
   relu(W_gate[e_k] y) * (W_up[e_k] y) ); x = x + f. A LOOP over the
   experts held, each on every row, weighted by the gate (zero where the
   row did not pick it). No shared expert, no dense layer.
4. Final RMSNorm, then the untied head.

No kernel, no cache, no batching, float32 throughout under
`jax.default_matmul_precision("highest")` (a TPU multiplies float32 in
lower precision otherwise). It upcasts one matrix at a time and takes
queries in slices of Q_ROWS, so that a 13k-token sequence at the
published widths fits beside the engine (28 heads x 128 query rows x
13k keys x 4 B = 0.19 GB a slice); each kind of block is one `jit` (the
matrices its arguments), so a sequence compiles a handful of programs.

It takes the parameter tree of the system under test (`embed`,
`layers`: a list of one tree a layer, `experts`: the stacks of every
layer's held experts, `final_norm`, `lm_head`) and `experts_held` =
(lo, hi), and changes no value. Departures from the published code,
each forced by that tree or by the chip's share: matrices come stored
[in, out]; only the routed experts lo..hi-1 exist in the tree, the
router still picks among all of them and a pick outside the range adds
nothing (the model-configs guide's section 4).

`picks`: a list of (w, idx) a layer to use in place of the router's own
(the program's, so that a pick the two sides round apart does not decide
the comparison); the router is then judged on its logits alone
(`router_logits`). `variant` (a set of words) leaves one thing out or
puts one wrong, for the readings a comparison's limits have to stay
under (`checks_smallthinker.precision_probe`); the reference itself
takes none.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# None: operands as stored. A narrower type (float8_e4m3fn) rounds every
# stored matrix and vector to it first: the forward in the precision
# below the stated one
_OPERANDS = None
# what is left out or put wrong, of: "router_after_norm" (the router
# reads RMSNorm_in(x)), "router_after_attn" (it reads the experts'
# input), "no_pick_norm" (weights the softmax over ALL experts at the
# picks, not renormalised), "silu_gate" (SwiGLU), "no_gate" (relu(W_up
# y) alone), "all_full", "all_window", "rope_everywhere", "no_rope",
# "rope_interleaved" (pairs (2i, 2i + 1) rotated, not halves)
_VARIANT: FrozenSet[str] = frozenset()

# query rows a slice of attention takes
Q_ROWS = 128


@contextlib.contextmanager
def computing(operands=None, variant=()):
    """The reference's blocks called inside compute with `operands` (see
    `_OPERANDS`) and `variant` (see `_VARIANT`), at the highest matrix
    precision; None and () are the reference itself."""
    global _OPERANDS, _VARIANT
    _OPERANDS, _VARIANT = operands, frozenset(variant)
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        _OPERANDS, _VARIANT = None, frozenset()


def _f32(a):
    a = jnp.asarray(a)
    if _OPERANDS is not None and jnp.issubdtype(a.dtype, jnp.floating):
        # behind a barrier: without it the narrowing conversion, widened
        # again at once inside a `jit`, left no trace on the chip (the
        # probe's expert layer read 0.0 there and 0.05 on the CPU)
        a = jax.lax.optimization_barrier(a.astype(_OPERANDS))
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
    if "rope_interleaved" in _VARIANT:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _jit(fn):
    """One program a kind of block, re-traced when what is left out or
    put wrong changes (`_OPERANDS`, `_VARIANT` are read at trace time)."""
    cached = functools.lru_cache(maxsize=None)(
        lambda operands, variant, static: jax.jit(
            functools.partial(fn, **dict(static))))

    @functools.wraps(fn)
    def call(*args, **static):
        return cached(_OPERANDS, _VARIANT, tuple(sorted(static.items())))(
            *args)
    return call


def _model_key(model) -> tuple:
    """The model's numbers that a block's program reads, hashable."""
    return tuple((k, model[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "sliding_window_size",
        "moe_num_active_primary_experts"))


@_jit
def _qkv(w, x, *, model, roped):
    model = dict(model)
    s = x.shape[0]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    y = _rms_norm(x, w["ln_in"], model["rms_norm_eps"])
    q = (y @ _f32(w["wq"])).reshape(s, nh, d)
    k = (y @ _f32(w["wk"])).reshape(s, nkv, d)
    v = (y @ _f32(w["wv"])).reshape(s, nkv, d)
    if roped:
        q, k = _rope(model, q), _rope(model, k)
    return q, k, v


@_jit
def _attend(q, k, v, i0, *, window):
    """Queries i0 .. i0 + Q_ROWS - 1 of q [S padded, heads, d] against
    every key; k, v: [S, kv heads, d]; query head h reads kv head
    h // group. i0 is an operand: one program a sequence length."""
    q = jax.lax.dynamic_slice_in_dim(q, i0, Q_ROWS, 0)
    nq, nh, d = q.shape
    s, nkv = k.shape[0], k.shape[1]
    i = i0 + jnp.arange(nq)[:, None]
    j = jnp.arange(s)[None, :]
    keep = j <= i
    if window:
        keep = keep & (j > i - window)
    qg = q.reshape(nq, nkv, nh // nkv, d)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k) / math.sqrt(d)
    scores = jnp.where(keep[None, None], scores, -jnp.inf)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(nq, nh * d)


def attention(model, w, x, windowed: int, roped: int):
    """x: [S, H], the layer's input -> the attention block's output
    [S, H] (o W_o, before the residual). Queries are taken Q_ROWS at a
    time (a query's sums are its own: the slicing changes no value)."""
    if "all_full" in _VARIANT:
        windowed = 0
    if "all_window" in _VARIANT:
        windowed = 1
    if "rope_everywhere" in _VARIANT:
        roped = 1
    if "no_rope" in _VARIANT:
        roped = 0
    s = x.shape[0]
    q, k, v = _qkv(w, x, model=_model_key(model), roped=bool(roped))
    pad = -s % Q_ROWS
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    window = model["sliding_window_size"] if windowed else 0
    # waited for, so that a long sequence's loop does not run ahead of
    # the device and hold every slice's scores at once
    out = [jax.block_until_ready(_attend(
        q, k, v, jnp.int32(i0), window=window))
        for i0 in range(0, s, Q_ROWS)]
    return _out_proj(jnp.concatenate(out)[:s], w["wo"])


@_jit
def _out_proj(o, wo):
    return o @ _f32(wo)


# columns of the head a slice takes: 151,936 x 2560 upcast whole is
# 1.56 GB beside an engine that leaves 2
HEAD_COLS = 8


@_jit
def _head_cols(x, head, j, *, cols):
    return x @ _f32(jax.lax.dynamic_slice_in_dim(head, j * cols, cols, 1))


def _head(x, head):
    v = head.shape[1]
    n = HEAD_COLS if v % HEAD_COLS == 0 else 1
    return jnp.concatenate([_head_cols(x, head, jnp.int32(j), cols=v // n)
                            for j in range(n)], axis=-1)


def _router_input(model, w, x):
    """What the router of a layer reads: the layer's input x itself."""
    if "router_after_norm" in _VARIANT:
        return _rms_norm(x, w["ln_in"], model["rms_norm_eps"])
    return x


@_jit
def _route(w_r, x, *, top_k):
    logits = x @ _f32(w_r)
    idx = jnp.argsort(-logits, axis=-1, stable=True)[:, :top_k]
    top = jnp.take_along_axis(logits, idx, axis=1)
    if "no_pick_norm" in _VARIANT:
        w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx,
                                axis=1)
    else:
        w = jax.nn.softmax(top, axis=-1)
    return w, idx.astype(jnp.int32), logits


def picks_of(model, logits) -> Tuple[jax.Array, jax.Array]:
    """A router's logits [S, E] -> its picks (w, idx) [S, k]."""
    k = model["moe_num_active_primary_experts"]
    idx = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    return (jax.nn.softmax(jnp.take_along_axis(logits, idx, axis=1),
                           axis=-1), idx.astype(jnp.int32))


def route(model, w, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [S, H], the layer's input -> (gate weights [S, k], expert
    indices [S, k], the logits [S, E]); ties go to the lower index."""
    return _route(w["router"], _router_input(model, w, x),
                  top_k=model["moe_num_active_primary_experts"])


@_jit
def _one_expert(y, gate, idx, wg, wi, wd, e):
    g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)          # [S]
    up = y @ _f32(wi)
    if "no_gate" in _VARIANT:
        mid = jax.nn.relu(up)
    elif "silu_gate" in _VARIANT:
        mid = jax.nn.silu(y @ _f32(wg)) * up
    else:
        mid = jax.nn.relu(y @ _f32(wg)) * up
    return g[:, None] * (mid @ _f32(wd))


def experts(model, stacks, y, gate, idx, experts_held, base: int = 0):
    """y: [S, H] normalised; gate, idx: the picks [S, k] -> the held
    experts' part of the routed sum, a loop over the experts held
    (`stacks`: {"wg", "wi", "wd"}, expert e of the layer at row base +
    e - lo)."""
    lo, hi = experts_held
    out = jnp.zeros_like(y)
    # the index as an operand: one program a stack, not one an expert
    take = lambda a, at: jax.lax.dynamic_index_in_dim(
        a, jnp.int32(at), 0, keepdims=False)
    for e in range(lo, hi):
        at = base + e - lo
        out = out + _one_expert(
            y, gate, idx, take(stacks["wg"], at), take(stacks["wi"], at),
            take(stacks["wd"], at), jnp.int32(e))
    return out


@_jit
def _post_norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


def layer(model, params, li: int, x, experts_held, picks=None):
    """Layer `li` on its input x [S, H] -> (its output, its router's
    logits [S, E])."""
    w = params["layers"][li]
    n_held = experts_held[1] - experts_held[0]
    # 1. the router, on the layer's input
    gate, idx, logits = route(model, w, x)
    # 2. attention
    x = x + attention(model, w, x, model["sliding_window_layout"][li],
                      model["rope_layout"][li])
    # 3. the experts
    y = _post_norm(x, w["ln_post"], eps=model["rms_norm_eps"])
    if "router_after_attn" in _VARIANT:
        gate, idx, logits = _route(
            w["router"], y, top_k=model["moe_num_active_primary_experts"])
    if picks is not None:
        gate, idx = picks
    x = x + experts(model, params["experts"], y, gate, idx, experts_held,
                    base=li * n_held)
    return x, logits


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens,
           experts_held: Tuple[int, int], operands=None, rows=None,
           variant=(), picks=None, with_router: bool = False):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence, or
    of its positions `rows` alone (the head is the last thing computed).
    `operands`: see `_OPERANDS`; `variant`: see `_VARIANT` (None and ()
    for the reference itself); `picks`: [(w, idx) a layer] or None;
    `with_router`: also the routers' logits, a list of [S, E] a layer."""
    with computing(operands, variant):
        out = _logits(model, params, tokens, experts_held, rows, picks)
    return out if with_router else out[0]


def _logits(model, params, tokens, experts_held, rows, picks):
    n = len(params["layers"])
    for key in ("sliding_window_layout", "rope_layout"):
        if len(model[key]) != n:
            raise ValueError(f"{n} layers in the tree, {len(model[key])} "
                             f"entries in {key}")
    x = _f32(params["embed"][tokens])
    routers = []
    for li in range(n):
        x, r = layer(model, params, li, x, experts_held,
                     None if picks is None else picks[li])
        routers.append(r)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    x = _post_norm(x, params["final_norm"], eps=model["rms_norm_eps"])
    return _head(x, params["lm_head"]), routers
