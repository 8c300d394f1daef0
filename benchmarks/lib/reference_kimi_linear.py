"""Plain reference of Kimi-Linear-48B-A3B's (`model_type`
"kimi_linear", arXiv:2510.26692) forward pass, in float32.

The model, for every layer l, as the published `config.json`, report
and `modeling_kimi.py` give it:

    h <- h + Mixer_l(RMSNorm(h));  h <- h + FF_l(RMSNorm(h))

then RMSNorm and the untied head. RMSNorm with a weight, `rms_norm_eps`.
No bias in any linear map.

- KDA mixer (`linear_attn_config.kda_layers`, 1-based; H heads of K = V
  = `head_dim`): q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)),
  SiLU(conv(x W_v)), causal depthwise convs of `short_conv_kernel_size`
  taps with no bias; q <- q / sqrt(sum q^2 + 1e-6) a head, k likewise,
  q <- q K^-1/2; g_t = -exp(A_log[h]) softplus((x W_f1 W_f2)_t +
  dt_bias) a CHANNEL; beta_t = sigmoid(x W_beta); the recurrence, a
  `lax.scan` a TOKEN from zero state (NOT the chunked form the program
  runs):
      S <- Diag(e^{g_t}) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
      o_t = S^T q_t
  out = (RMSNorm_head(o_t) * sigmoid((x W_g1 W_g2)_t)) W_o, the norm
  over each head's values with one weight [V].
- MLA mixer (`full_attn_layers`), the PLAIN form: q = x W_q a head
  [q_nope | q_pe]; [c | k_pe] = x W_kva; c <- RMSNorm(c); k_nope_h = c
  W_kb[h], v_h = c W_vb[h]; key of head h = [k_nope_h | k_pe], k_pe the
  same for every head and NOT rotated, nor q_pe (`mla_use_nope`);
  scores q . k / sqrt(nope + rope), causal over the whole sequence,
  softmax; out = concat_h(P v_h) W_o. No absorbed form, no cache.
- Feed-forward: the first `first_k_dense_replace` layers SwiGLU at
  `intermediate_size`; the others s = sigmoid(x W_r) over every routed
  expert, picks = the `num_experts_per_token` largest of s + b, weights
  the picked s over their sum (`moe_renormalize`) times
  `routed_scaling_factor`, out = SwiGLU_shared(x) + the weighted sum
  over the picks that fall on the experts HELD, `experts_held` = [lo,
  hi): the same share as the program's; what the absent experts would
  add is left out.

Every layer on every token, no cache, no kernel, no batching, float32
throughout under `jax.default_matmul_precision("highest")`. One `jit` a
kind of block (re-traced when `operands` or `variant` change); attention
takes its queries in slices and the head its vocabulary in slices, so
that a 12k-token sequence fits beside the engine at the published
widths.

It takes the system's parameters as one tree a layer
(`kimi_linear.layer_trees`) and changes no value; it imports nothing of
`ray_tpu/models/kimi_linear.py`. Departures from the published code,
each forced by that tree: matrices come stored [in, out]; W_q, W_k, W_v
of a KDA layer come as one matrix `wqkv` and its three convs' taps as
one [K, 3 x H x d]; W_f1 and W_g1 as one `w_down`; W_kvb split into
`wkb` and `wvb` [c, heads, d]; `head_dim`, `rope_theta` and
`rope_scaling` are read by nothing (nor do the published layers rotate).

`variant` (a set of words) puts one thing wrong, for the readings a
comparison's limits have to stay under
(`checks_kimi_linear.precision_probe`); the reference itself takes none.
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
# what is put wrong, of: "state_bf16" (the recurrent state rounded to
# bfloat16 after every token), "state_reset" / "conv_reset" (the state /
# the conv's inputs start from zeros again every `CHUNK` tokens: not
# carried over a chunk boundary), "no_beta" (beta = 1), "decay_a_head"
# (one decay a head, the mean of its channels'), "no_decay" (g = 0),
# "no_qk_norm", "gate_before_norm" (the output gate inside the head
# norm), "rotary" (rotate-half rope on q_pe and k_pe), "no_route_norm",
# "no_route_scale"
_VARIANT: FrozenSet[str] = frozenset()
CHUNK = 512
# query rows a slice of attention takes, vocabulary rows a slice of the
# head takes
Q_ROWS, V_ROWS = 128, 8192


@contextlib.contextmanager
def computing(operands=None, variant=(), chunk: int = 512):
    """The reference's blocks called inside compute with `operands` (see
    `_OPERANDS`) and `variant` (see `_VARIANT`), at the highest matrix
    precision; None and () are the reference itself."""
    global _OPERANDS, _VARIANT, CHUNK
    _OPERANDS, _VARIANT, CHUNK = operands, frozenset(variant), int(chunk)
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        _OPERANDS, _VARIANT, CHUNK = None, frozenset(), 512


def _f32(a):
    a = jnp.asarray(a)
    if _OPERANDS is not None and jnp.issubdtype(a.dtype, jnp.floating):
        # behind a barrier: without it the narrowing conversion, widened
        # again at once inside a `jit`, leaves no trace on the chip
        a = jax.lax.optimization_barrier(a.astype(_OPERANDS))
    return a.astype(F32)


def rms_norm(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                       keepdims=True) + eps)) * _f32(w)


def _jit(fn):
    """One program a kind of block, re-traced when what is put wrong
    changes (`_OPERANDS`, `_VARIANT`, `CHUNK` are read at trace time)."""
    cached = functools.lru_cache(maxsize=None)(
        lambda operands, variant, chunk, static: jax.jit(
            functools.partial(fn, **dict(static))))

    @functools.wraps(fn)
    def call(*args, **static):
        return cached(_OPERANDS, _VARIANT, CHUNK,
                      tuple(sorted(static.items())))(*args)
    return call


def _key(model: Dict[str, Any]) -> tuple:
    """The model's numbers that a block's program reads, hashable."""
    lin = model["linear_attn_config"]
    return tuple(sorted({
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        **{k: model[k] for k in (
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
            "num_experts_per_token", "routed_scaling_factor",
            "moe_renormalize", "rope_theta")}}.items()))


# ------------------------------------------------------------------ the KDA

@_jit
def _kda_inputs(w, u, *, model):
    """u: [S, H] normalised -> (q, k, v [S, heads, d], g [S, heads, d],
    beta [S, heads], the output gate [S, heads x d])."""
    model = dict(model)
    s = u.shape[0]
    hk, d = model["kda_heads"], model["kda_head_dim"]
    qkv = u @ _f32(w["wqkv"])
    taps = _f32(w["conv_w"])                                # [K, 3 e]
    k = taps.shape[0]
    pos = jnp.arange(s)
    conv = qkv * taps[k - 1]
    for back in range(1, k):
        prev = jnp.pad(qkv, ((back, 0), (0, 0)))[:s]
        if "conv_reset" in _VARIANT:
            prev = jnp.where((pos % CHUNK >= back)[:, None], prev, 0.0)
        conv = conv + prev * taps[k - 1 - back]
    q, key, v = (m.reshape(s, hk, d)
                 for m in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    if "no_qk_norm" not in _VARIANT:
        unit = lambda m: m * jax.lax.rsqrt(
            jnp.sum(jnp.square(m), axis=-1, keepdims=True) + 1e-6)
        q, key = unit(q), unit(key)
    q = q * d ** -0.5
    f_low, g_low = jnp.split(u @ _f32(w["w_down"]), 2, axis=-1)
    g = -jnp.exp(_f32(w["a_log"]))[None, :, None] * jax.nn.softplus(
        f_low @ _f32(w["f_up"]) + _f32(w["dt_bias"])).reshape(s, hk, d)
    if "decay_a_head" in _VARIANT:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    if "no_decay" in _VARIANT:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(u @ _f32(w["w_beta"]))
    if "no_beta" in _VARIANT:
        beta = jnp.ones_like(beta)
    gate = jax.nn.sigmoid(g_low @ _f32(w["g_up"]))
    return q, key, v, g, beta, gate


@_jit
def _recurrence(q, k, v, g, beta):
    """The delta rule, one token a step from zero state. q, k, g: [S,
    heads, K]; v: [S, heads, V]; beta: [S, heads]. Returns o [S, heads,
    V]."""
    s_len, hk, dk = q.shape
    pos = jnp.arange(s_len)
    reset = ((pos % CHUNK == 0) if "state_reset" in _VARIANT
             else jnp.zeros((s_len,), bool))
    narrow = "state_bf16" in _VARIANT

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t, zero = inp
        s = jnp.where(zero, 0.0, s)
        s = jnp.exp(g_t)[:, :, None] * s
        vp = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * vp[:, None, :]
        if narrow:
            # `reduce_precision`: a cast to bfloat16 and back is an
            # excess of precision the TPU's compiler is free to keep
            s = jax.lax.reduce_precision(s, 8, 7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((hk, dk, v.shape[-1]), F32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta, reset))[1]


@_jit
def _kda_output(w, o, gate, *, eps):
    s = o.shape[0]
    if "gate_before_norm" in _VARIANT:
        o = o * gate.reshape(o.shape)
        gate = 1.0
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps) * _f32(w["norm"])
    return (o.reshape(s, -1) * gate) @ _f32(w["wo"])


def kda(model: Dict[str, Any], w, u):
    """u: [S, H] normalised -> the KDA mixer's output [S, H]."""
    q, k, v, g, beta, gate = _kda_inputs(w, u, model=_key(model))
    return _kda_output(w, _recurrence(q, k, v, g, beta), gate,
                       eps=model["rms_norm_eps"])


# ------------------------------------------------------------------ the MLA

def _rotate_half(x, theta):
    """x: [S, ..., d] at positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@_jit
def _mla_qkv(w, u, *, model):
    """u: [S, H] normalised -> (q [S, heads, nope + rope], keys the
    same shape, values [S, heads, v])."""
    model = dict(model)
    s = u.shape[0]
    nh, c = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    q = (u @ _f32(w["wq"])).reshape(s, nh, nope + rope)
    kv = u @ _f32(w["wkva"])
    lat = rms_norm(kv[:, :c], w["kv_norm"], model["rms_norm_eps"])
    k_pe = kv[:, c:]
    if "rotary" in _VARIANT:
        q = jnp.concatenate([q[..., :nope], _rotate_half(
            q[..., nope:], model["rope_theta"])], -1)
        k_pe = _rotate_half(k_pe, model["rope_theta"])
    k_nope = jnp.einsum("sc,chn->shn", lat, _f32(w["wkb"]))
    v = jnp.einsum("sc,chv->shv", lat, _f32(w["wvb"]))
    keys = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (s, nh, rope))], -1)
    return q, keys, v


@_jit
def _attend(q, k, v, i0):
    """Queries i0 .. i0 + Q_ROWS - 1 of q [S padded, heads, d] against
    every key. i0 is an operand: one program a sequence length."""
    q = jax.lax.dynamic_slice_in_dim(q, i0, Q_ROWS, 0)
    nq, nh, d = q.shape
    keep = jnp.arange(k.shape[0])[None, :] <= i0 + jnp.arange(nq)[:, None]
    scores = jnp.einsum("qhd,shd->hqs", q, k) / math.sqrt(d)
    scores = jnp.where(keep[None], scores, -jnp.inf)
    o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(nq, -1)


@_jit
def _project(w, o):
    return o @ _f32(w)


def mla(model: Dict[str, Any], w, u):
    """u: [S, H] normalised -> the latent attention mixer's output."""
    s = u.shape[0]
    q, k, v = _mla_qkv(w, u, model=_key(model))
    q = jnp.pad(q, ((0, -s % Q_ROWS), (0, 0), (0, 0)))
    # waited for, so that a long sequence's loop does not run ahead of
    # the device and hold every slice's scores at once
    out = [jax.block_until_ready(_attend(q, k, v, jnp.int32(i0)))
           for i0 in range(0, s, Q_ROWS)]
    return _project(w["wo"], jnp.concatenate(out)[:s])


# --------------------------------------------------------- the feed-forward

def _swiglu(x, wg, wi, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wi))) @ _f32(wd)


@_jit
def dense_ffn(w, u):
    return _swiglu(u, w["wg"], w["wi"], w["wd"])


@_jit
def shared_expert(w, u):
    return _swiglu(u, w["shared_wg"], w["shared_wi"], w["shared_wd"])


@_jit
def _route(w, u, idx, *, model, given):
    model = dict(model)
    scores = jax.nn.sigmoid(u @ _f32(w["router"]))
    if not given:
        _, idx = jax.lax.top_k(scores + _f32(w["router_bias"]),
                               model["num_experts_per_token"])
    gate = jnp.take_along_axis(scores, idx, axis=1)
    if model["moe_renormalize"] and "no_route_norm" not in _VARIANT:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in _VARIANT:
        gate = gate * model["routed_scaling_factor"]
    return gate, idx


@_jit
def _one_expert(wg, wi, wd, u, gate, idx, e, out):
    g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)         # [S]
    return out + g[:, None] * _swiglu(u, wg, wi, wd)


def experts(model: Dict[str, Any], w, u, experts_held: Tuple[int, int],
            picks: Optional[Any] = None):
    """u: [S, H] normalised -> shared expert + the held experts' part of
    the routed sum, a loop over the experts held. `picks`: the indices
    [S, k] another router picked, in place of this one's (the program's,
    so that rounding flips no pick); their weights are this router's
    own."""
    if model.get("num_expert_group", 1) != 1 or model.get("topk_group",
                                                          1) != 1:
        raise ValueError("the routing written down is one group")
    lo, hi = experts_held
    given = picks is not None
    gate, idx = _route(w, u, picks if given else jnp.zeros((1, 1), jnp.int32),
                       model=_key(model), given=given)
    out = shared_expert(w, u)
    for e in range(lo, hi):
        out = _one_expert(w["wg"][e - lo], w["wi"][e - lo], w["wd"][e - lo],
                          u, gate, idx, jnp.int32(e), out)
    return out


# ---------------------------------------------------------------- the model

@_jit
def _norm(x, w, *, eps):
    return rms_norm(x, w, eps)


@_jit
def _head(x, w):
    return x @ _f32(w)


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens,
           experts_held: Tuple[int, int], operands=None, rows=None,
           variant=(), chunk: int = 512):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence, or
    of its positions `rows` alone (the head is the last thing computed).
    `operands`: see `_OPERANDS`; `variant`: see `_VARIANT` (None and ()
    for the reference itself); `chunk`: the tokens a tick of the system
    takes of one sequence, where two of the variants forget."""
    eps = model["rms_norm_eps"]
    lin = model["linear_attn_config"]
    kinds = {**{l: "kda" for l in lin["kda_layers"]},
             **{l: "mla" for l in lin["full_attn_layers"]}}
    if sorted(kinds) != list(range(1, len(params["layers"]) + 1)):
        raise ValueError(f"{len(params['layers'])} layers in the tree, "
                         f"{sorted(kinds)} in the two lists")
    with computing(operands, variant, chunk):
        x = _f32(params["embed"][tokens])
        for l, w in enumerate(params["layers"]):
            mixer, ff = w["mixer"], w["ff"]
            u = _norm(x, mixer["ln"], eps=eps)
            x = x + (kda if kinds[l + 1] == "kda" else mla)(model, mixer, u)
            u = _norm(x, ff["ln"], eps=eps)
            if l < model["first_k_dense_replace"]:
                x = x + dense_ffn(ff, u)
            else:
                x = x + experts(model, ff, u, experts_held)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = _norm(x, params["final_norm"], eps=eps)
        head = params["lm_head"]                            # [H, V]
        return jnp.concatenate(
            [_head(x, head[:, r:r + V_ROWS])
             for r in range(0, head.shape[1], V_ROWS)], axis=-1)
