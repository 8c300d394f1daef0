"""Plain reference of DeepSeek-V3's forward pass, in float32.

As the published modelling code computes it
(https://huggingface.co/deepseek-ai/DeepSeek-V3, `modeling_deepseek.py`
and `config.json`): pre-norm residual blocks; multi-head latent
attention in its NON-absorbed form (per-head keys and values are
decompressed from the latent for the whole sequence, no cache); YaRN
rotary embedding on the rope dimensions; `first_k_dense_replace` SwiGLU
layers, then expert layers: sigmoid scores over all routed experts, the
selection bias, the best `topk_group` of `n_group` groups by the sum of
their two largest biased scores, the `num_experts_per_tok` largest
biased scores inside them, gate weights the unbiased scores at the
picks, normalised and scaled, a shared expert, and a LOOP over the
experts (and, for a long sequence, over the heads a few at a time, so
that their float32 scores fit). No kernel, no cache, no batching,
float32 throughout under
`jax.default_matmul_precision("highest")` (a TPU multiplies float32 in
lower precision otherwise). It runs op by op and upcasts one matrix at
a time, so that at the published widths it fits beside the engine.

It takes the parameter tree of the system under test
(`embed`, `layers`: a list of one tree a layer, an expert layer where
it has a `router`, `final_norm`, `lm_head`) and `experts_held` = (lo, hi), and changes no
value. Departures from the published code, each forced by that tree or
by the chip's share:

- W_kvb comes split into its key part `wkb` [latent, heads, nope] and
  its value part `wvb` [latent, heads, v] (the checkpoint fuses them).
- Rope pairs dimension i with i + d/2 (rotate-half), not 2i with 2i+1
  as the checkpoint's layout has it: the same scores under a fixed
  permutation of W_qb's and W_kva's rope columns.
- Only the routed experts lo..hi-1 exist in the tree; the router still
  scores and picks among all of them and the picks that fall outside
  the range add nothing (the model-configs guide's section 4): what
  the absent chips would add is left out here as in the system.
- A group's masked-out scores are -inf, not 0.0 (the published code
  fills 0.0, which is the same choice while biased scores are positive).
- The multi-token-prediction module is not run.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# None: operands as stored. A narrower type (float8_e4m3fn) rounds every
# stored matrix and vector to it first: what the forward gives when
# computed in the precision below the stated one, for the reading that a
# comparison's limit has to stay under (checks_deepseek_v3.precision_probe)
_OPERANDS = None


def _f32(a):
    a = jnp.asarray(a)
    if _OPERANDS is not None and jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(_OPERANDS)
    return a.astype(F32)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def yarn_inv_freq(model: Dict[str, Any]):
    """[rope_dim / 2] inverse frequencies after YaRN's blend."""
    d, base = model["qk_rope_head_dim"], model["rope_theta"]
    rs = model.get("rope_scaling")
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=F32) / d)
    if not rs:
        return extra
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    inter = extra / rs["factor"]
    return inter * ramp + extra * (1.0 - ramp)


def _mscale(factor, m):
    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(model: Dict[str, Any]) -> float:
    d = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    rs = model.get("rope_scaling")
    m = _mscale(rs["factor"], rs.get("mscale_all_dim", 0)) if rs else 1.0
    return d ** -0.5 * m * m


def _rope(model, x):
    """x: [S, ..., d] at positions 0..S-1, rotate-half."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(model)
    rs = model.get("rope_scaling")
    m = (_mscale(rs["factor"], rs.get("mscale", 1))
         / _mscale(rs["factor"], rs.get("mscale_all_dim", 0))) if rs else 1.0
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# (head, query, key) scores held at once, float32: every head of a short
# sequence, two heads of a 6k-token one (0.3 GB)
SCORES = 1 << 27


def attention(model, w, x):
    """x: [S, H] -> the attention block's output [S, H]. Heads are
    taken a few at a time (a loop, so that the float32 scores of a long
    sequence fit): each head's sums are its own, so the grouping changes
    no value."""
    s = x.shape[0]
    nh = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    y = _rms_norm(x, w["ln1"], eps)
    cq = _rms_norm(y @ _f32(w["wqa"]), w["q_norm"], eps)
    kv = y @ _f32(w["wkva"])
    c_kv = _rms_norm(kv[:, :rank], w["kv_norm"], eps)
    k_pe = _rope(model, kv[:, rank:])                       # [S, rope]
    wqb = _f32(w["wqb"]).reshape(-1, nh, nope + rope)
    wkb, wvb = _f32(w["wkb"]), _f32(w["wvb"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = max(min(SCORES // (s * s), nh), 1)
    group = 1 << (group.bit_length() - 1)     # a divisor of 128 heads
    out = []
    for h in range(0, nh, group):
        q = jnp.einsum("sc,chd->shd", cq, wqb[:, h:h + group])
        q_nope, q_pe = q[..., :nope], _rope(model, q[..., nope:])
        k_nope = jnp.einsum("sc,chn->shn", c_kv, wkb[:, h:h + group])
        v = jnp.einsum("sc,chv->shv", c_kv, wvb[:, h:h + group])
        scores = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_pe, k_pe)
                  ) * softmax_scale(model)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        # waited for, so that a long sequence's loop does not run ahead
        # of the device and hold every group's scores at once
        out.append(jax.block_until_ready(jnp.einsum(
            "hqk,khv->qhv", jax.nn.softmax(scores, axis=-1), v)))
    o = jnp.concatenate(out, axis=1)
    return o.reshape(s, -1) @ _f32(w["wo"])


def _swiglu(y, wg, wi, wd):
    return (jax.nn.silu(y @ _f32(wg)) * (y @ _f32(wi))) @ _f32(wd)


def route(model, scores, bias) -> Tuple[jax.Array, jax.Array]:
    """scores: [S, E] sigmoid scores; bias: [E] -> (gate weights [S, k],
    expert indices [S, k]); ties go to the lower index."""
    s, e = scores.shape
    g, keep, k = (model["n_group"], model["topk_group"],
                  model["num_experts_per_tok"])
    choice = scores + _f32(bias)
    per_group = choice.reshape(s, g, e // g)
    group_score = jnp.sort(per_group, axis=-1)[..., -2:].sum(-1)
    best = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
    on = jnp.zeros((s, g), bool).at[jnp.arange(s)[:, None], best].set(True)
    masked = jnp.where(jnp.repeat(on, e // g, axis=1), choice, -jnp.inf)
    idx = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=1)
    if model.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * model["routed_scaling_factor"], idx


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
           experts_held: Tuple[int, int], operands=None, rows=None):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence,
    or of its positions `rows` alone (the head is the last thing
    computed: a long sequence's other rows are not wanted).
    `operands`: see `_OPERANDS` (None for the reference itself)."""
    global _OPERANDS
    _OPERANDS = operands
    try:
        return _logits(model, params, tokens, experts_held, rows)
    finally:
        _OPERANDS = None


def _logits(model, params, tokens, experts_held, rows):
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for w in params["layers"]:
            x = x + attention(model, w, x)
            y = _rms_norm(x, w["ln2"], eps)
            if "router" in w:
                x = x + experts(model, w, y, experts_held)
            else:
                x = x + _swiglu(y, w["wg"], w["wi"], w["wd"])
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = _rms_norm(x, params["final_norm"], eps)
        return x @ _f32(params["lm_head"])
