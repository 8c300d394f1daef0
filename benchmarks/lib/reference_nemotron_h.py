"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B's (`model_type`
"nemotron_h") forward pass, in float32.

The model, for layer l of kind `hybrid_override_pattern[l]`, as the
published `config.json` and the `nemotron_h` modeling code give it:

    h <- h + Mixer_l(RMSNorm_l(h));  then RMSNorm_f, then
    logits = h W_head^T   (untied)

RMSNorm with a weight, `layer_norm_epsilon`. No bias in any linear map;
the conv has one. ONE mixer a layer:

- `M`, Mamba-2 (H = `mamba_num_heads` heads of P = `mamba_head_dim`;
  G = `n_groups`, N = `ssm_state_size`; `conv_kernel` taps).
  [z, xBC, dt] = u W_in; xBC <- silu(conv(xBC) + b_c), causal,
  depthwise, over x, B and C together; Delta = softplus(dt + dt_bias),
  not clamped; A = -exp(A_log), a scalar a head; g(h) = h // (H / G):
      S_t[h] = exp(Delta_t[h] A[h]) S_{t-1}[h]
               + Delta_t[h] x_t[h] (outer) B_t[g(h)]
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
  y <- y silu(z) FIRST, then RMSNorm over each group's channels
  separately, times a weight; out = y W_out.
- `E`, experts. s = sigmoid(u W_r) over every routed expert; picks = the
  `num_experts_per_tok` largest of s + b (`n_group` 1: no group limit);
  weights = the picked s over their sum (`norm_topk_prob`) times
  `routed_scaling_factor`. Expert(u) = relu(u W_up)^2 W_down, no gate
  matrix (`mlp_hidden_act` "relu2"). out = the shared expert (the same
  form, wider) + the weighted sum over the picks that fall on the
  experts HELD, `experts_held` = [lo, hi): the same share as the
  program's; what the absent experts would add is left out.
- `*`, attention. `num_attention_heads` query heads over
  `num_key_value_heads` K/V heads of `head_dim`, scale 1/sqrt(head_dim),
  causal over the whole sequence, NO positional encoding.

The recurrence is a sequential `lax.scan`, one token a step from zero
state (NOT the chunked form the program uses), every layer on every
token, no cache, no kernel, no batching, float32 throughout under
`jax.default_matmul_precision("highest")` (a TPU multiplies float32 in
lower precision otherwise). It runs op by op, upcasts one matrix and
one expert at a time, takes attention's queries in slices and the
head's vocabulary in slices, so that it fits beside the engine at the
published widths.

It takes the system's parameters as one tree a layer
(`nemotron_h.layer_trees`) and changes no value; it imports nothing of
`ray_tpu/models/nemotron_h.py`. Departures from the published code, each
forced by that tree: matrices come stored [in, out] except the experts'
W_up, which comes [F, H] as `nn.Linear` keeps it; the conv's taps come
[K, C] (published [C, 1, K]); `rope_theta` and `partial_rotary_factor`
are read by nothing (nor does the published attention read them).

`variant` (a set of words) puts one thing wrong, for the readings a
comparison's limits have to stay under
(`checks_nemotron_h.precision_probe`); the reference itself takes none.
"""

from __future__ import annotations

import math
from typing import Any, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# None: operands as stored. A narrower type (float8_e4m3fn) rounds every
# stored matrix and vector to it first: the forward in the precision
# below the stated one
_OPERANDS = None
# what is put wrong, of: "state_bf16" (the scan state rounded to
# bfloat16 after every token), "state_reset" / "conv_reset" (the state /
# the conv's inputs start from zeros again every `CHUNK` tokens: not
# carried over a chunk boundary), "neighbour_leak" (a run starts from
# the state another run's tokens left: here, a pass over the sequence
# itself), "group_by_mod" (head h reads group h mod G), "norm_all" (one
# RMS over all channels), "norm_before_gate", "relu_not_squared",
# "gated_experts" (silu(u W_up) (u W_up): a gate matrix), "no_route_norm",
# "no_route_scale", "no_d", "no_dt_bias", "rotary" (rotate-half rope on
# q and k)
_VARIANT: FrozenSet[str] = frozenset()
CHUNK = 512
# query rows a slice of attention takes, vocabulary rows a slice of the
# head takes
Q_ROWS, V_ROWS = 128, 16384


def _f32(a):
    a = jnp.asarray(a)
    if _OPERANDS is not None and jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(_OPERANDS)
    return a.astype(F32)


def rms_norm(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                       keepdims=True) + eps)) * _f32(w)


def _act(u):
    if "relu_not_squared" in _VARIANT:
        return jax.nn.relu(u)
    if "gated_experts" in _VARIANT:
        return jax.nn.silu(u) * u
    return jnp.square(jax.nn.relu(u))


@jax.jit
def _recurrence(a, delta, x, b, c, of_head, reset, s0, narrow):
    """The scan, one token a step (a function of its own so that its one
    compilation serves every layer and sequence). a: [H]; delta: [S, H];
    x: [S, H, P]; b, c: [S, G, N]; of_head: [H], each head's group;
    reset: [S] bool; s0: [H, P, N]; narrow: a scalar bool (round the
    state to bfloat16 after a token). Returns (y [S, H, P], the last
    state)."""
    def step(s, inp):
        d_t, x_t, b_t, c_t, zero = inp
        b_t, c_t = b_t[of_head], c_t[of_head]               # [H, N]
        s = jnp.where(zero, 0.0, s)
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        # `reduce_precision`: a cast to bfloat16 and back is an excess of
        # precision that the TPU's compiler is free to keep (it did: the
        # variant read 0.0 on the chip)
        s = jnp.where(narrow, jax.lax.reduce_precision(s, 8, 7), s)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    with jax.default_matmul_precision("highest"):
        s, y = jax.lax.scan(step, s0, (delta, x, b, c, reset))
    return y, s


def mamba(model: Dict[str, Any], w, u):
    """u: [S, H] normalised -> the Mamba-2 mixer's output [S, H]."""
    s_len = u.shape[0]
    hm, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    e = hm * p
    z, xbc, dt = jnp.split(u @ _f32(w["in_proj"]), [e, 2 * e + 2 * g * n],
                           axis=-1)
    taps = _f32(w["conv_w"])                                # [K, C]
    k = taps.shape[0]
    pos = jnp.arange(s_len)
    conv = xbc * taps[k - 1]
    for back in range(1, k):
        prev = jnp.pad(xbc, ((back, 0), (0, 0)))[:s_len]
        if "conv_reset" in _VARIANT:
            prev = jnp.where((pos % CHUNK >= back)[:, None], prev, 0.0)
        conv = conv + prev * taps[k - 1 - back]
    xbc = jax.nn.silu(conv + _f32(w["conv_b"]))
    x, b, c = jnp.split(xbc, [e, e + g * n], axis=-1)
    x = x.reshape(s_len, hm, p)
    of_head = (jnp.arange(hm) % g if "group_by_mod" in _VARIANT
               else jnp.arange(hm) // (hm // g))
    b, c = b.reshape(s_len, g, n), c.reshape(s_len, g, n)
    bias = 0.0 if "no_dt_bias" in _VARIANT else _f32(w["dt_bias"])
    delta = jax.nn.softplus(dt + bias)
    a = -jnp.exp(_f32(w["a_log"]))
    reset = ((pos % CHUNK == 0) if "state_reset" in _VARIANT
             else jnp.zeros((s_len,), bool))
    narrow = jnp.asarray("state_bf16" in _VARIANT)
    s0 = jnp.zeros((hm, p, n), F32)
    if "neighbour_leak" in _VARIANT:
        s0 = _recurrence(a, delta, x, b, c, of_head, reset, s0, narrow)[1]
    y = _recurrence(a, delta, x, b, c, of_head, reset, s0, narrow)[0]
    if "no_d" not in _VARIANT:
        y = y + _f32(w["d_skip"])[:, None] * x
    y = y.reshape(s_len, e)
    gate = jax.nn.silu(z)
    eps = model["layer_norm_epsilon"]

    def group_norm(v):
        if "norm_all" in _VARIANT:
            return v * jax.lax.rsqrt(
                jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)
        v = v.reshape(s_len, g, -1)
        return (v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                           keepdims=True) + eps)
                ).reshape(s_len, e)

    if "norm_before_gate" in _VARIANT:
        y = group_norm(y) * _f32(w["norm"]) * gate
    else:
        y = group_norm(y * gate) * _f32(w["norm"])
    return y @ _f32(w["out_proj"])


def route(model: Dict[str, Any], w, u, idx=None):
    """u: [S, H] -> (gate weights [S, k], expert indices [S, k]). `idx`:
    another router's picks to weigh in place of this one's."""
    scores = jax.nn.sigmoid(u @ _f32(w["router"]))
    if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
        raise ValueError("the routing written down is one group")
    if idx is None:
        _, idx = jax.lax.top_k(scores + _f32(w["router_bias"]),
                               model["num_experts_per_tok"])
    gate = jnp.take_along_axis(scores, idx, axis=1)
    if model["norm_topk_prob"] and "no_route_norm" not in _VARIANT:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in _VARIANT:
        gate = gate * model["routed_scaling_factor"]
    return gate, idx


def shared_expert(w, u):
    return _act(u @ _f32(w["shared_up"])) @ _f32(w["shared_down"])


def experts(model: Dict[str, Any], w, u, experts_held: Tuple[int, int],
            picks: Optional[Any] = None):
    """u: [S, H] normalised -> shared expert + the held experts' part of
    the routed sum, a loop over the experts held. `picks`: the indices
    [S, k] another router picked, in place of this one's (the
    program's, so that rounding flips no pick); their weights are this
    router's own."""
    lo, hi = experts_held
    gate, idx = route(model, w, u, picks)
    out = shared_expert(w, u)
    for e in range(lo, hi):
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)     # [S]
        out = out + g[:, None] * (_act(u @ _f32(w["up"][e - lo]).T)
                                  @ _f32(w["down"][e - lo]))
    return out


def _rotate_half(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(model: Dict[str, Any], w, u):
    """u: [S, H] normalised -> the attention mixer's output [S, H]."""
    s_len = u.shape[0]
    heads, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    q = (u @ _f32(w["wq"])).reshape(s_len, kvh, heads // kvh, d)
    k = (u @ _f32(w["wk"])).reshape(s_len, kvh, d)
    v = (u @ _f32(w["wv"])).reshape(s_len, kvh, d)
    if "rotary" in _VARIANT:
        pos = jnp.arange(s_len)
        q = _rotate_half(q.reshape(s_len, heads, d), pos,
                         model.get("rope_theta", 10000)).reshape(q.shape)
        k = _rotate_half(k, pos, model.get("rope_theta", 10000))
    keys = jnp.arange(s_len)[None, :]
    out = []
    for r0 in range(0, s_len, Q_ROWS):
        rows = jnp.arange(r0, min(r0 + Q_ROWS, s_len))[:, None]
        sc = jnp.einsum("tjrd,sjd->jrts", q[r0:r0 + Q_ROWS], k) \
            / math.sqrt(d)
        pr = jax.nn.softmax(
            jnp.where((keys <= rows)[None, None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("jrts,sjd->tjrd", pr, v)
        out.append(o.reshape(o.shape[0], heads * d))
    return jnp.concatenate(out) @ _f32(w["wo"])


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens,
           experts_held: Tuple[int, int], operands=None, rows=None,
           variant=(), chunk: int = 512):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence, or
    of its positions `rows` alone (the head is the last thing computed).
    `operands`: see `_OPERANDS`; `variant`: see `_VARIANT` (None and ()
    for the reference itself); `chunk`: the tokens a tick of the system
    takes of one sequence, where two of the variants forget."""
    global _OPERANDS, _VARIANT, CHUNK
    _OPERANDS, _VARIANT, CHUNK = operands, frozenset(variant), int(chunk)
    try:
        return _logits(model, params, tokens, experts_held, rows)
    finally:
        _OPERANDS, _VARIANT = None, frozenset()


def _logits(model, params, tokens, experts_held, rows):
    eps = model["layer_norm_epsilon"]
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers in the tree, "
                         f"{len(pattern)} in the pattern")
    mixer = {"M": lambda w, u: mamba(model, w, u),
             "E": lambda w, u: experts(model, w, u, experts_held),
             "*": lambda w, u: attention(model, w, u)}
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for w, kind in zip(params["layers"], pattern):
            x = x + mixer[kind](w, rms_norm(x, w["ln"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rms_norm(x, params["final_norm"], eps)
        head = params["lm_head"]                            # [H, V]
        return jnp.concatenate(
            [x @ _f32(head[:, r:r + V_ROWS])
             for r in range(0, head.shape[1], V_ROWS)], axis=-1)
