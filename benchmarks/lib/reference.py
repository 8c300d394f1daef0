"""Plain reference of the dense RoPE/GQA/SwiGLU decoder, in float32.

InternLM2.5 as its published modelling code computes it: pre-norm
residual blocks, RMSNorm, rotate-half rotary embedding on queries and
keys, grouped-query causal softmax attention, SwiGLU feed-forward, untied
output head. No kernel, no cache, no batching tricks, float32 throughout
and `jax.default_matmul_precision("highest")` (a TPU multiplies float32
in lower precision otherwise).

Departures: the published checkpoint fuses wqkv; here the matrices are
read from the parameter tree of the system under test (`embed`,
`layers.{wq,wk,wv,wo,wg,wi,wd,ln1,ln2}` stacked over layers,
`final_norm`, `lm_head`), whose values it must not change.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: (S, H, D). Rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(model: Dict[str, Any], x, w):
    s = x.shape[0]
    nq, nkv, d = (model["num_attention_heads"],
                  model["num_key_value_heads"], model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    y = _rms_norm(x, w["ln1"], eps)
    q = _rope((y @ w["wq"]).reshape(s, nq, d), theta)
    k = _rope((y @ w["wk"]).reshape(s, nkv, d), theta)
    v = (y @ w["wv"]).reshape(s, nkv, d)
    group = nq // nkv                   # query head h reads kv head h // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(s, nq * d) @ w["wo"]
    y = _rms_norm(x, w["ln2"], eps)
    return x + (jax.nn.silu(y @ w["wg"]) * (y @ w["wi"])) @ w["wd"]


def logits(model: Dict[str, Any], params: Dict[str, Any], tokens):
    """tokens: (S,) int -> (S, vocab) float32 logits of one sequence."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])

        def body(x, w):
            return _layer(model, x, f32(w)), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, f32(params["final_norm"]), model["rms_norm_eps"])
        return x @ f32(params["lm_head"])


def loss(model: Dict[str, Any], params: Dict[str, Any], tokens):
    """Mean next-token cross-entropy of one sequence, (S,) int."""
    lg = logits(model, params, tokens[:-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)
    return -jnp.mean(picked)
