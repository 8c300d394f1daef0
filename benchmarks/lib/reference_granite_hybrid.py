"""Plain reference of granite-4.0-h-micro's (`model_type`
"granitemoehybrid", `num_local_experts` 0) forward pass, in float32.

The model, for layer l of kind `layer_types[l]`, as the published
`config.json` and the `granitemoehybrid` modeling code give it:

    h_0 = embedding_multiplier E[token]
    u = RMSNorm(h);  h <- h + residual_multiplier Mixer_l(u)
    u = RMSNorm(h);  h <- h + residual_multiplier W_out(silu(a) * b),
                                                  [a, b] = u W_in
    logits = RMSNorm(h) E^T / logits_scaling       (the head is tied)

RMSNorm with a weight, `rms_norm_eps`. No bias in any linear map; the
conv has one. The mixers:

- `mamba`, Mamba-2 (H = `mamba_n_heads` heads of P = `mamba_d_head`;
  G = `mamba_n_groups`, N = `mamba_d_state`; `mamba_d_conv` taps).
  [z, xBC, dt] = u W_in; xBC <- silu(conv(xBC) + b_c), causal,
  depthwise, over x, B and C together; Delta = softplus(dt + dt_bias),
  not clamped; A = -exp(A_log), a scalar a head; g(h) = h // (H / G):
      S_t[h] = exp(Delta_t[h] A[h]) S_{t-1}[h]
               + Delta_t[h] x_t[h] (outer) B_t[g(h)]
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
  y <- y silu(z) FIRST, then RMSNorm over each group's channels (one
  group: all of them), times a weight; out = y W_out.
- `attention`. `num_attention_heads` query heads over
  `num_key_value_heads` K/V heads of hidden / heads, scores q.k x
  `attention_multiplier`, causal over the whole sequence, NO positional
  encoding (`position_embedding_type` "nope").

The recurrence is a sequential `lax.scan`, one token a step from zero
state (NOT the chunked form the program uses), every layer on every
token, no cache, no kernel, no batching, float32 throughout under
`jax.default_matmul_precision("highest")` (a TPU multiplies float32 in
lower precision otherwise). A kind of block is one `jit` (the mixers,
the SwiGLU block, the head), a matrix upcast where it is used;
attention takes its queries in slices and the head its vocabulary in
slices, so that it fits beside the engine at the published widths.

It takes the system's parameters as one tree a layer
(`granite_hybrid.layer_trees`: a layer its mixer's leaves and "mlp") and changes no
value; it imports nothing of `ray_tpu/models` or `ray_tpu/ops`.
Departures from the published code, each forced by that tree: matrices
come stored [in, out], W_in of the SwiGLU block as its two halves "wg"
(under the silu) and "wi"; the conv's taps come [K, C] (published
[C, 1, K]); `rope_theta` is read by nothing.

`variant` (words) puts one thing wrong, for the readings a comparison's
limits have to stay under (`checks_granite_hybrid.precision_probe`);
the reference itself takes none.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, FrozenSet, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# what `variant` may hold: "state_bf16" (the scan state rounded to
# bfloat16 after every token), "delta_bf16" (Delta rounded to bfloat16),
# "state_reset" / "conv_reset" (the state / the conv's inputs start from
# zeros again every `chunk` tokens: not carried over a chunk boundary),
# "no_embedding_multiplier", "no_residual_multiplier",
# "no_attention_multiplier" (scores by 1/sqrt(d)), "no_logits_scaling",
# "norm_before_gate", "no_d", "no_dt_bias", "rotary" (rotate-half rope on
# q and k)
VARIANTS = ("state_bf16", "delta_bf16", "state_reset", "conv_reset",
            "no_embedding_multiplier", "no_residual_multiplier",
            "no_attention_multiplier", "no_logits_scaling",
            "norm_before_gate", "no_d", "no_dt_bias", "rotary")
# query rows a slice of attention takes, vocabulary rows a slice of the
# head takes
Q_ROWS, V_ROWS = 256, 16384


class _How(NamedTuple):
    """Hashable, so a block's `jit` takes it as a static argument.
    operands: None (as stored) or a narrower type every stored matrix
    and vector is rounded to first (float8_e4m3fn: the forward in the
    precision below the stated one)."""
    operands: Optional[Any]
    variant: FrozenSet[str]
    chunk: int


def how(operands=None, variant=(), chunk: int = 512) -> _How:
    bad = set(variant) - set(VARIANTS)
    if bad:
        raise ValueError(f"no variant {sorted(bad)}")
    return _How(operands, frozenset(variant), int(chunk))


PLAIN = how()


def _f32(a, hw: _How):
    a = jnp.asarray(a)
    if hw.operands is not None and jnp.issubdtype(a.dtype, jnp.floating):
        # behind a barrier: a round trip through a narrower type is an
        # excess of precision the TPU's compiler is free to keep
        a = jax.lax.optimization_barrier(a.astype(hw.operands))
    return a.astype(F32)


def _sizes(model: Dict[str, Any]) -> Tuple:
    """The published keys the blocks read, hashable."""
    return tuple((k, model[k]) for k in (
        "hidden_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "num_attention_heads", "num_key_value_heads",
        "rms_norm_eps", "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling"))


def rms_norm(x, w, eps, hw: _How = PLAIN):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                       keepdims=True) + eps)) * _f32(w, hw)


def _recurrence(a, delta, x, b, c, of_head, reset, narrow: bool):
    """The scan, one token a step from zero state. a: [H]; delta:
    [S, H]; x: [S, H, P]; b, c: [S, G, N]; of_head: [H], each head's
    group; reset: [S] bool. Returns y [S, H, P]."""
    def step(s, inp):
        d_t, x_t, b_t, c_t, zero = inp
        b_t, c_t = b_t[of_head], c_t[of_head]               # [H, N]
        s = jnp.where(zero, 0.0, s)
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if narrow:
            # `reduce_precision`: a cast to bfloat16 and back the
            # compiler may drop
            s = jax.lax.reduce_precision(s, 8, 7)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s0 = jnp.zeros(x.shape[1:] + (b.shape[-1],), F32)
    return jax.lax.scan(step, s0, (delta, x, b, c, reset))[1]


@functools.partial(jax.jit, static_argnums=(0, 3))
def mamba(sizes: Tuple, w, u, hw: _How = PLAIN):
    """u: [S, H] normalised -> the Mamba-2 mixer's output [S, H]."""
    m = dict(sizes)
    f = lambda a: _f32(a, hw)
    s_len = u.shape[0]
    hm, p = m["mamba_n_heads"], m["mamba_d_head"]
    g, n = m["mamba_n_groups"], m["mamba_d_state"]
    e = hm * p
    with jax.default_matmul_precision("highest"):
        z, xbc, dt = jnp.split(u @ f(w["in_proj"]), [e, 2 * e + 2 * g * n],
                               axis=-1)
        taps = f(w["conv_w"])                               # [K, C]
        k = taps.shape[0]
        pos = jnp.arange(s_len)
        conv = xbc * taps[k - 1]
        for back in range(1, k):
            prev = jnp.pad(xbc, ((back, 0), (0, 0)))[:s_len]
            if "conv_reset" in hw.variant:
                prev = jnp.where((pos % hw.chunk >= back)[:, None], prev,
                                 0.0)
            conv = conv + prev * taps[k - 1 - back]
        xbc = jax.nn.silu(conv + f(w["conv_b"]))
        x, b, c = jnp.split(xbc, [e, e + g * n], axis=-1)
        x = x.reshape(s_len, hm, p)
        of_head = jnp.arange(hm) // (hm // g)
        b, c = b.reshape(s_len, g, n), c.reshape(s_len, g, n)
        bias = 0.0 if "no_dt_bias" in hw.variant else f(w["dt_bias"])
        delta = jax.nn.softplus(dt + bias)
        if "delta_bf16" in hw.variant:
            delta = jax.lax.reduce_precision(delta, 8, 7)
        reset = ((pos % hw.chunk == 0) if "state_reset" in hw.variant
                 else jnp.zeros((s_len,), bool))
        y = _recurrence(-jnp.exp(f(w["a_log"])), delta, x, b, c, of_head,
                        reset, "state_bf16" in hw.variant)
        if "no_d" not in hw.variant:
            y = y + f(w["d_skip"])[:, None] * x
        y = y.reshape(s_len, e)
        gate = jax.nn.silu(z)
        eps = m["rms_norm_eps"]

        def group_norm(v):
            v = v.reshape(s_len, g, -1)
            return (v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                               keepdims=True) + eps)
                    ).reshape(s_len, e)

        if "norm_before_gate" in hw.variant:
            y = group_norm(y) * f(w["norm"]) * gate
        else:
            y = group_norm(y * gate) * f(w["norm"])
        return y @ f(w["out_proj"])


def _rotate_half(x, pos, theta=10000.0):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@functools.partial(jax.jit, static_argnums=(0, 3))
def attention(sizes: Tuple, w, u, hw: _How = PLAIN):
    """u: [S, H] normalised -> the attention mixer's output [S, H]."""
    m = dict(sizes)
    f = lambda a: _f32(a, hw)
    s_len = u.shape[0]
    heads, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // heads
    scale = (d ** -0.5 if "no_attention_multiplier" in hw.variant
             else m["attention_multiplier"])
    with jax.default_matmul_precision("highest"):
        q = (u @ f(w["wq"])).reshape(s_len, heads, d)
        k = (u @ f(w["wk"])).reshape(s_len, kvh, d)
        v = (u @ f(w["wv"])).reshape(s_len, kvh, d)
        if "rotary" in hw.variant:
            pos = jnp.arange(s_len)
            q, k = _rotate_half(q, pos), _rotate_half(k, pos)
        q = q.reshape(s_len, kvh, heads // kvh, d)
        keys = jnp.arange(s_len)[None, :]
        out = []
        for r0 in range(0, s_len, Q_ROWS):
            rows = jnp.arange(r0, min(r0 + Q_ROWS, s_len))[:, None]
            sc = jnp.einsum("tjrd,sjd->jrts", q[r0:r0 + Q_ROWS], k) * scale
            pr = jax.nn.softmax(
                jnp.where((keys <= rows)[None, None], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("jrts,sjd->tjrd", pr, v)
            out.append(o.reshape(o.shape[0], heads * d))
        return jnp.concatenate(out) @ f(w["wo"])


@functools.partial(jax.jit, static_argnums=(2,))
def swiglu(w, u, hw: _How = PLAIN):
    """u: [S, H] normalised -> W_out(silu(a) * b), [a, b] = u W_in."""
    f = lambda a: _f32(a, hw)
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ f(w["wg"])) * (u @ f(w["wi"]))) @ f(w["wd"])


def layer(sizes: Tuple, kind: str, w, x, hw: _How = PLAIN):
    """One layer on the residual stream x [S, H]: the mixer of `kind`,
    then the SwiGLU block, each after its norm and times the residual
    multiplier."""
    m = dict(sizes)
    eps = m["rms_norm_eps"]
    res = (1.0 if "no_residual_multiplier" in hw.variant
           else m["residual_multiplier"])
    mixer = mamba if kind == "mamba" else attention
    x = x + res * mixer(sizes, {k: v for k, v in w.items() if k != "mlp"},
                        _norm(x, w["ln"], eps, hw), hw)
    return x + res * swiglu(w["mlp"], _norm(x, w["mlp"]["ln"], eps, hw), hw)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _norm(x, w, eps, hw):
    return rms_norm(x, w, eps, hw)


@functools.partial(jax.jit, static_argnums=(2,))
def _head_slice(x, rows, hw: _How):
    with jax.default_matmul_precision("highest"):
        return x @ _f32(rows, hw).T


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens,
           operands=None, rows=None, variant=(), chunk: int = 512):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence, or
    of its positions `rows` alone (the head is the last thing computed).
    `operands`, `variant`: see `how` (None and () for the reference
    itself); `chunk`: the tokens a tick of the system takes of one
    sequence, where two of the variants forget."""
    hw = how(operands, variant, chunk)
    sizes = _sizes(model)
    kinds = model["layer_types"]
    if len(kinds) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers in the tree, "
                         f"{len(kinds)} in layer_types")
    x = _f32(params["embed"][jnp.asarray(tokens)], hw)
    if "no_embedding_multiplier" not in hw.variant:
        x = x * model["embedding_multiplier"]
    for w, kind in zip(params["layers"], kinds):
        x = layer(sizes, kind, w, x, hw)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    x = _norm(x, params["final_norm"], model["rms_norm_eps"], hw)
    embed = params["embed"]                                 # [V, H]
    out = jnp.concatenate(
        [_head_slice(x, embed[r:r + V_ROWS], hw)
         for r in range(0, embed.shape[0], V_ROWS)], axis=-1)
    if "no_logits_scaling" in hw.variant:
        return out
    return out / model["logits_scaling"]
