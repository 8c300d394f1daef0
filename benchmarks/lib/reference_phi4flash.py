"""Plain reference of Phi-4-mini-flash-reasoning's (`model_type`
"phi4flash", the SambaY decoder-hybrid-decoder of arXiv:2507.06607)
forward pass, in float32.

The model, for layer l of `num_hidden_layers` = 32 (half = 16), as the
published `config.json`, `modeling_phi4flash.py` and the paper give it:

    h <- h + Mixer_l(LN(h));  h <- h + MLP_l(LN(h));  then LN, then
    logits = h E^T   (E the embedding: `tie_word_embeddings`)

LN is LayerNorm with weight and bias (`layer_norm_eps`). No positional
encoding. MLP(u) = (up * silu(gate)) W2, [gate, up] = u W1, no bias.
The mixer by layer index (`mb_per_layer` 2; `sliding_window` on the odd
layers of the first half):

- l even, l <= half: Mamba-1. [x, z] = u W_in; x <- silu(conv(x) +
  b_c), a causal depthwise convolution of `mamba_d_conv` taps;
  [r, B, C] = x W_x; delta = softplus(r W_dt + b_dt); A = -exp(A_log);
  s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) outer B_t;
  y_t = s_t C_t + D x_t; out = (y * silu(z)) W_out. Layer `half` keeps
  m := y (before the gate).
- l odd, l < half: window attention, query i sees keys j with
  i - window < j <= i. l = half + 1: every key j <= i; its K and V are
  what the cross layers read. Differential attention (arXiv:2410.05258):
  query heads pair (q1_i, q2_i), key heads pair (k1_j, k2_j), value
  heads pair into V_j = [v1_j | v2_j], j = i // 2;
  O_i = (1 - lam0) RMSNorm((softmax(q1_i k1_j^T / sqrt(d))
        - lam softmax(q2_i k2_j^T / sqrt(d))) V_j);
  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
  lam0 = 0.8 - 0.6 exp(-0.3 l); out = [O_0 ...] W_o + b_o;
  [q, k, v] = u W_qkv + b.
- l even, l >= half + 2: gated memory unit, out = (m * silu(u W1)) W2.
- l odd, l >= half + 3: cross attention, q = u W_q + b_q; K and V are
  layer half + 1's; every key j <= i; differential with this layer's lam.

A sequential scan over the whole history (`lax.scan`, one token a step,
from zero state), every layer on every token, one mask a layer kind, no
cache, no kernel, no batching, float32 throughout under
`jax.default_matmul_precision("highest")` (a TPU multiplies float32 in
lower precision otherwise). It runs op by op, upcasts one matrix at a
time, takes attention's queries in slices and the head's vocabulary in
slices, so that it fits beside the engine at the published widths.

It takes the parameter tree of the system under test (`embed`, `layers`:
a list of one tree a layer, `final_norm`) and changes no value; it
imports nothing of `ray_tpu/models/phi4flash.py`. Departures from the
published code, each forced by that tree:

- Matrices come stored [in, out] (`nn.Linear` keeps [out, in]).
- `A_log` comes [N, E] (the published tensor is [E, N]); the conv's
  taps come [K, E] (published [E, 1, K]).
- Which heads pair: adjacent ones (q heads 2i and 2i + 1, k and v heads
  2j and 2j + 1). The published code splits the head axis in halves
  after a reshape; with weights drawn from a seed any fixed pairing is
  the same model.
- `embd_pdrop` / `resid_pdrop` (0 as published) have no part.

`variant` (a set of words) puts one thing wrong, for the readings a
comparison's limits have to stay under
(`checks_phi4flash.precision_probe`); the reference itself takes none.
"""

from __future__ import annotations

import math
from typing import Any, Dict, FrozenSet

import jax
import jax.numpy as jnp

F32 = jnp.float32
# None: operands as stored. A narrower type (float8_e4m3fn) rounds every
# stored matrix and vector to it first: the forward in the precision
# below the stated one
_OPERANDS = None
# what is put wrong, of: "state_reset" / "conv_reset" (the scan state /
# the conv's inputs start from zeros again every `CHUNK` tokens: not
# carried over a chunk boundary), "all_full" (a window layer attends to
# everything), "full_windowed" (layer half + 1 is windowed),
# "cross_reads_window" (the cross layers read the last WINDOW layer's K
# and V, under its window), "no_subtraction" (lam = 0), "m_after_gate",
# "untied_head" (the head is another matrix than the embedding)
_VARIANT: FrozenSet[str] = frozenset()
CHUNK = 512
# query rows a slice of attention takes, vocabulary rows a slice of the
# head takes
Q_ROWS, V_ROWS = 256, 32768


def _f32(a):
    a = jnp.asarray(a)
    if _OPERANDS is not None and jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(_OPERANDS)
    return a.astype(F32)


def kinds(model: Dict[str, Any]):
    n = model["num_hidden_layers"]
    half = n // 2
    if model["mb_per_layer"] != 2 or n % 4:
        raise ValueError("the schedule written down is mb_per_layer 2 "
                         "over a multiple of 4 layers")

    def kind(l):
        if l % 2 == 0:
            return "mamba" if l <= half else "gmu"
        return "swa" if l < half else "full" if l == half + 1 else "cross"
    return [kind(l) for l in range(n)]


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(p["w"]) + _f32(p["b"])


def mlp(w, u):
    gate, up = jnp.split(u @ _f32(w["w1"]), 2, axis=-1)
    return (up * jax.nn.silu(gate)) @ _f32(w["w2"])


@jax.jit
def _recurrence(a, delta, x, b, c, reset):
    """The scan, one token a step from zero state (a function of its
    own so that its one compilation serves every layer and sequence)."""
    def step(s, inp):
        d_t, x_t, b_t, c_t, zero = inp
        s = jnp.where(zero, 0.0, s)
        s = jnp.exp(d_t[None, :] * a) * s + (d_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(step, jnp.zeros_like(a),
                            (delta, x, b, c, reset))[1]


def mamba(w, u, keep_after_gate=False):
    """u: [S, H] -> (the mixer's output [S, H], the memory [S, E])."""
    s_len = u.shape[0]
    x, z = jnp.split(u @ _f32(w["in_proj"]), 2, axis=-1)
    taps = _f32(w["conv_w"])                                # [K, E]
    k = taps.shape[0]
    pos = jnp.arange(s_len)
    conv = x * taps[k - 1]
    for back in range(1, k):
        prev = jnp.pad(x, ((back, 0), (0, 0)))[:s_len]
        if "conv_reset" in _VARIANT:
            prev = jnp.where((pos % CHUNK >= back)[:, None], prev, 0.0)
        conv = conv + prev * taps[k - 1 - back]
    x = jax.nn.silu(conv + _f32(w["conv_b"]))
    n = w["a_log"].shape[0]
    r_width = w["dt_proj"].shape[0]
    rbc = x @ _f32(w["x_proj"])
    r, b, c = jnp.split(rbc, [r_width, r_width + n], axis=-1)
    delta = jax.nn.softplus(r @ _f32(w["dt_proj"]) + _f32(w["dt_bias"]))
    a = -jnp.exp(_f32(w["a_log"]))                          # [N, E]
    reset = ((pos % CHUNK == 0) if "state_reset" in _VARIANT
             else jnp.zeros((s_len,), bool))
    y = _recurrence(a, delta, x, b, c, reset) + _f32(w["d_skip"]) * x
    gated = y * jax.nn.silu(z)
    return gated @ _f32(w["out_proj"]), (gated if keep_after_gate else y)


def _lam0(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def differential(model, w, q, k, v, layer: int, window):
    """q: [S, heads, d]; k, v: [S, kv heads, d] -> [S, heads * d]: the
    pairs' two softmaxes under the layer's mask, their difference on the
    paired values, the sub-norm and (1 - lam0)."""
    s_len, h, d = q.shape
    kvh = k.shape[1]
    per = (h // 2) // (kvh // 2)              # query pairs a key pair
    lam0 = _lam0(layer)
    lam = (jnp.exp(jnp.sum(_f32(w["lam_q1"]) * _f32(w["lam_k1"])))
           - jnp.exp(jnp.sum(_f32(w["lam_q2"]) * _f32(w["lam_k2"]))) + lam0)
    if "no_subtraction" in _VARIANT:
        lam = 0.0
    q = q.reshape(s_len, kvh // 2, per, 2, d)
    k = k.reshape(s_len, kvh // 2, 2, d)
    vv = v.reshape(s_len, kvh // 2, 2 * d)
    keys = jnp.arange(s_len)[None, :]
    out = []
    for r0 in range(0, s_len, Q_ROWS):
        rows = jnp.arange(r0, min(r0 + Q_ROWS, s_len))[:, None]
        mask = keys <= rows
        if window is not None:
            mask = mask & (keys > rows - window)
        probs = []
        for half in (0, 1):
            sc = jnp.einsum("tjrd,sjd->jrts", q[r0:r0 + Q_ROWS, :, :, half],
                            k[:, :, half]) / math.sqrt(d)
            probs.append(jax.nn.softmax(
                jnp.where(mask[None, None], sc, -jnp.inf), axis=-1))
        o = jnp.einsum("jrts,sjd->tjrd", probs[0] - lam * probs[1], vv)
        out.append(o.reshape(o.shape[0], h // 2, 2 * d))
    o = jnp.concatenate(out)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + model["layer_norm_eps"])
    o = o * _f32(w["subln"]) * (1.0 - lam0)
    return o.reshape(s_len, -1)


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens,
           operands=None, rows=None, variant=(), chunk: int = 512):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence, or
    of its positions `rows` alone (the head is the last thing computed).
    `operands`: see `_OPERANDS`; `variant`: see `_VARIANT` (None and ()
    for the reference itself); `chunk`: the tokens a tick of the system
    takes of one sequence, where two of the variants forget."""
    global _OPERANDS, _VARIANT, CHUNK
    _OPERANDS, _VARIANT, CHUNK = operands, frozenset(variant), int(chunk)
    try:
        return _logits(model, params, tokens, rows)
    finally:
        _OPERANDS, _VARIANT = None, frozenset()


def _logits(model, params, tokens, rows):
    eps = model["layer_norm_eps"]
    heads, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["hidden_size"] // heads
    window = model["sliding_window"]
    kind_of = kinds(model)
    if len(kind_of) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers in the tree, "
                         f"{len(kind_of)} in the schedule")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        s_len = x.shape[0]
        memory = shared = last_window = None
        for l, (w, kind) in enumerate(zip(params["layers"], kind_of)):
            u = layer_norm(x, w["ln1"], eps)
            if kind == "mamba":
                out, m = mamba(w, u, "m_after_gate" in _VARIANT)
                if l == len(kind_of) // 2:
                    memory = m
            elif kind == "gmu":
                out = ((memory * jax.nn.silu(u @ _f32(w["gmu_in"])))
                       @ _f32(w["gmu_out"]))
            else:
                if kind == "cross":
                    q = u @ _f32(w["wq"]) + _f32(w["bq"])
                    (k, v), win = shared, None
                    if "cross_reads_window" in _VARIANT:
                        (k, v), win = last_window, window
                else:
                    qkv = u @ _f32(w["wqkv"]) + _f32(w["bqkv"])
                    q, k, v = jnp.split(
                        qkv, [heads * d, (heads + kvh) * d], axis=-1)
                    k = k.reshape(s_len, kvh, d)
                    v = v.reshape(s_len, kvh, d)
                    win = window if kind == "swa" else None
                    if kind == "swa":
                        last_window = (k, v)
                        if "all_full" in _VARIANT:
                            win = None
                    else:
                        shared = (k, v)
                        if "full_windowed" in _VARIANT:
                            win = window
                o = differential(model, w, q.reshape(s_len, heads, d), k,
                                 v, l, win)
                out = o @ _f32(w["wo"]) + _f32(w["bo"])
            x = x + out
            x = x + mlp(w, layer_norm(x, w["ln2"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = layer_norm(x, params["final_norm"], eps)
        embed = params["embed"]
        if "untied_head" in _VARIANT:
            # another matrix of the embedding's scale: its rows rolled
            embed = jnp.roll(embed, 1, axis=0)
        return jnp.concatenate(
            [x @ _f32(embed[r:r + V_ROWS]).T
             for r in range(0, embed.shape[0], V_ROWS)], axis=-1)
