"""Llama-family transformer LM, TPU-first.

Net-new compute path: the reference delegates all modeling to external
torch/vLLM; here the flagship LM is native JAX — functional (pytree params,
no framework), layers stacked on a leading axis and executed with lax.scan
(single layer compile + clean rematerialization), GQA + RoPE + SwiGLU +
RMSNorm, logical-axis sharding annotations throughout so the same code runs
DP/FSDP/TP/SP by changing the mesh (parallel/sharding.py), and attention
dispatched to the Pallas flash kernel on TPU or the ring kernel when the
sequence axis is sharded.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.attention import attention as attention_op
from ..ops.moe import moe_ffn
from ..ops.ring_attention import ring_attention_sharded
from ..ops.ulysses import ulysses_attention
from ..parallel.mesh import AXIS_SP
from ..parallel.sharding import with_logical_constraint as wlc


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq: int = 8192
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # storage dtype
    attention_impl: str = "auto"       # auto | xla | pallas | ring | ulysses
    # MoE (Mixtral-style): n_experts=0 -> dense SwiGLU; >0 -> every layer's
    # MLP is a top-k expert mixture (ops/moe.py), experts sharded over ep.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Dropless dispatch (ops/moe.py): sort + ragged_dot grouped matmuls,
    # ragged_all_to_all over the ep axis — no capacity drops; the
    # capacity_factor knob is ignored when True.
    moe_dropless: bool = False
    # Pipeline parallelism: microbatch count used when the mesh has pp>1
    # (models/pipeline.py). Must divide the per-step batch.
    pp_microbatches: int = 4
    # Schedule for the pp training step: "gpipe" (models/pipeline.py,
    # autodiff backward, supports MoE) or "1f1b" (models/pipeline_1f1b.py
    # hand-scheduled interleaved 1F1B: O(stages) activation stash,
    # fill/drain bubble shrunk by pp_interleave; dense layers only).
    pp_schedule: str = "gpipe"
    pp_interleave: int = 2          # model chunks per device under 1f1b
    remat: bool = True
    # "dots_no_batch" saves matmul outputs (fastest when HBM allows);
    # "nothing" fully rematerializes each layer in backward (~1B params on
    # a 16 GiB chip needs this).
    remat_policy: str = "dots_no_batch"
    # Cross-entropy sequence chunk: bounds logits to (B, chunk, vocab) per
    # step instead of materializing (B, S, vocab). 0 = unchunked.
    loss_chunk: int = 512

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def num_params(self) -> int:
        ffn_mult = max(self.n_experts, 1)
        per_layer = (self.hidden * (self.q_dim + 2 * self.kv_dim)
                     + self.q_dim * self.hidden
                     + 3 * self.hidden * self.ffn * ffn_mult
                     + (self.hidden * self.n_experts if self.n_experts else 0)
                     + 2 * self.hidden)
        return (self.vocab_size * self.hidden * 2
                + self.n_layers * per_layer + self.hidden)


# Model-size presets (Llama-3 family shapes).
PRESETS: Dict[str, LlamaConfig] = {
    "debug": LlamaConfig(vocab_size=256, hidden=128, n_layers=2, n_heads=4,
                         n_kv_heads=2, head_dim=32, ffn=256, max_seq=256),
    "tiny": LlamaConfig(vocab_size=2048, hidden=512, n_layers=4, n_heads=8,
                        n_kv_heads=4, head_dim=64, ffn=1536, max_seq=2048),
    # Mixtral-shaped MoE variants for tests/dryruns.
    "debug_moe": LlamaConfig(vocab_size=256, hidden=128, n_layers=2,
                             n_heads=4, n_kv_heads=2, head_dim=32, ffn=256,
                             max_seq=256, n_experts=4, moe_top_k=2),
    "8x7b": LlamaConfig(vocab_size=32000, hidden=4096, n_layers=32,
                        n_heads=32, n_kv_heads=8, head_dim=128, ffn=14336,
                        n_experts=8, moe_top_k=2),
    "1b": LlamaConfig(vocab_size=128256, hidden=2048, n_layers=16,
                      n_heads=32, n_kv_heads=8, head_dim=64, ffn=8192),
    "3b": LlamaConfig(vocab_size=128256, hidden=3072, n_layers=28,
                      n_heads=24, n_kv_heads=8, head_dim=128, ffn=8192),
    "8b": LlamaConfig(vocab_size=128256, hidden=4096, n_layers=32,
                      n_heads=32, n_kv_heads=8, head_dim=128, ffn=14336),
    "70b": LlamaConfig(vocab_size=128256, hidden=8192, n_layers=80,
                       n_heads=64, n_kv_heads=8, head_dim=128, ffn=28672),
}


def config(name_or_cfg, **overrides) -> LlamaConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


# --------------------------------------------------------------------- params

def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree of logical-axis tuples mirroring init_params' structure."""
    if cfg.n_experts:
        mlp_axes = {
            "router": ("layers", "embed", None),
            "wi": ("layers", "experts", "embed", "mlp"),
            "wg": ("layers", "experts", "embed", "mlp"),
            "wd": ("layers", "experts", "mlp", "embed"),
        }
    else:
        mlp_axes = {
            "wi": ("layers", "embed", "mlp"),
            "wg": ("layers", "embed", "mlp"),
            "wd": ("layers", "mlp", "embed"),
        }
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            **mlp_axes,
            "ln1": ("layers", None),
            "ln2": ("layers", None),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Initialize parameters (layers stacked on the leading axis)."""
    keys = jax.random.split(key, 10)
    h, L = cfg.hidden, cfg.n_layers
    pd = cfg.param_dtype

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(pd)

    if cfg.n_experts:
        E = cfg.n_experts
        mlp = {
            "router": dense(keys[9], (L, h, E), h),
            "wi": dense(keys[5], (L, E, h, cfg.ffn), h),
            "wg": dense(keys[6], (L, E, h, cfg.ffn), h),
            "wd": dense(keys[7], (L, E, cfg.ffn, h), cfg.ffn),
        }
    else:
        mlp = {
            "wi": dense(keys[5], (L, h, cfg.ffn), h),
            "wg": dense(keys[6], (L, h, cfg.ffn), h),
            "wd": dense(keys[7], (L, cfg.ffn, h), cfg.ffn),
        }
    return {
        "embed": dense(keys[0], (cfg.vocab_size, h), h),
        "layers": {
            "wq": dense(keys[1], (L, h, cfg.q_dim), h),
            "wk": dense(keys[2], (L, h, cfg.kv_dim), h),
            "wv": dense(keys[3], (L, h, cfg.kv_dim), h),
            "wo": dense(keys[4], (L, cfg.q_dim, h), cfg.q_dim),
            **mlp,
            "ln1": jnp.ones((L, h), pd),
            "ln2": jnp.ones((L, h), pd),
        },
        "final_norm": jnp.ones((h,), pd),
        "lm_head": dense(keys[8], (h, cfg.vocab_size), h),
    }


# -------------------------------------------------------------------- modules

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def rope_frequencies(cfg: LlamaConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """positions: (S,) -> cos/sin of shape (S, head_dim//2), float32."""
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (cfg.rope_theta
                      ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); rotate-half RoPE."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


def _attend(cfg: LlamaConfig, q, k, v, mesh: Optional[Mesh]):
    impl = cfg.attention_impl
    if impl == "auto" and mesh is not None \
            and dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS_SP, 1) > 1:
        impl = "ring"
    if impl == "ring":
        if mesh is None:
            raise ValueError("ring attention requires a mesh")
        return ring_attention_sharded(q, k, v, mesh, causal=True)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, causal=True, mesh=mesh)
    return attention_op(q, k, v, causal=True, impl=impl, mesh=mesh)


def decoder_layer(cfg: LlamaConfig, x: jax.Array, layer: Dict[str, jax.Array],
                  cos: jax.Array, sin: jax.Array,
                  mesh: Optional[Mesh]) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux) — aux is the MoE load-balance loss (0 when dense)."""
    b, s, h = x.shape
    dt = cfg.dtype

    # Attention block. The scope names (here, in loss_fn and in the
    # train step) are what the benchmark's span tables key device time
    # on; the transposed ops of the backward pass carry them too.
    with jax.named_scope("attn"):
        y = rms_norm(x, layer["ln1"], cfg.norm_eps)
        q = (y @ layer["wq"].astype(dt)).reshape(
            b, s, cfg.n_heads, cfg.head_dim)
        k = (y @ layer["wk"].astype(dt)).reshape(
            b, s, cfg.n_kv_heads, cfg.head_dim)
        v = (y @ layer["wv"].astype(dt)).reshape(
            b, s, cfg.n_kv_heads, cfg.head_dim)
        q = wlc(apply_rope(q, cos, sin), "batch", "seq", "heads", "head_dim")
        k = wlc(apply_rope(k, cos, sin), "batch", "seq", "kv_heads",
                "head_dim")
        v = wlc(v, "batch", "seq", "kv_heads", "head_dim")
        attn = _attend(cfg, q, k, v, mesh).reshape(b, s, cfg.q_dim)
        x = x + wlc(attn @ layer["wo"].astype(dt), "batch", "seq",
                    "act_embed")

    # MLP block: dense SwiGLU or top-k expert mixture
    with jax.named_scope("mlp"):
        y = rms_norm(x, layer["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            out, aux = moe_ffn(
                y, layer["router"], layer["wi"], layer["wg"], layer["wd"],
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dropless=cfg.moe_dropless, mesh=mesh)
            x = x + wlc(out, "batch", "seq", "act_embed")
            return x, aux
        gate = jax.nn.silu(y @ layer["wg"].astype(dt))
        up = y @ layer["wi"].astype(dt)
        mlp = wlc(gate * up, "batch", "seq", "mlp")
        x = x + wlc(mlp @ layer["wd"].astype(dt), "batch", "seq",
                    "act_embed")
    return x, jnp.zeros((), jnp.float32)


_REMAT_POLICIES = {
    "dots_no_batch":
        lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "nothing": lambda: None,
}


def hidden_states_with_aux(cfg: LlamaConfig, params: Dict[str, Any],
                           tokens: jax.Array,
                           mesh: Optional[Mesh] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32 -> (final-norm hidden states (B, S, hidden),
    summed MoE aux loss). Dispatches to the GPipe pipeline when the mesh
    has a pp axis > 1 (models/pipeline.py)."""
    if mesh is not None and dict(
            zip(mesh.axis_names, mesh.devices.shape)).get("pp", 1) > 1:
        from .pipeline import pipelined_hidden_states
        return pipelined_hidden_states(cfg, params, tokens, mesh)

    b, s = tokens.shape
    dt = cfg.dtype
    # constrain BOTH gather operands: tokens to the activation layout,
    # and the table's feature dim to replicated (act_embed) just for the
    # lookup. Leaving the table's fsdp-sharded feature dim in place makes
    # the partitioner emit the gather feature-sharded and then
    # "involuntarily rematerialize" (replicate + repartition) it into the
    # batch/seq activation layout the next constraint demands.
    with jax.named_scope("embed"):
        tokens = wlc(tokens, "batch", "seq")
        table = wlc(params["embed"].astype(dt), "vocab", "act_embed")
        x = table[tokens]
        x = wlc(x, "batch", "seq", "act_embed")
        positions = jnp.arange(s)
        cos, sin = rope_frequencies(cfg, positions)

    layer_fn = lambda x, layer: decoder_layer(cfg, x, layer, cos, sin, mesh)
    if cfg.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=_REMAT_POLICIES[cfg.remat_policy]())
    x, aux = jax.lax.scan(layer_fn, x, params["layers"])

    return rms_norm(x, params["final_norm"], cfg.norm_eps), jnp.sum(aux)


def hidden_states(cfg: LlamaConfig, params: Dict[str, Any],
                  tokens: jax.Array,
                  mesh: Optional[Mesh] = None) -> jax.Array:
    """tokens: (B, S) int32 -> final-norm hidden states (B, S, hidden)."""
    return hidden_states_with_aux(cfg, params, tokens, mesh)[0]


def _head_logits(cfg: LlamaConfig, x: jax.Array, lm_head: jax.Array):
    # bf16 operands + f32 accumulation: full-f32 operands would run the
    # largest matmul in the model off the MXU fast path
    logits = jnp.einsum("bsh,hv->bsv", x.astype(cfg.dtype),
                        lm_head.astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return wlc(logits, "batch", "seq", "vocab")


def forward(cfg: LlamaConfig, params: Dict[str, Any], tokens: jax.Array,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    x = hidden_states(cfg, params, tokens, mesh)
    return _head_logits(cfg, x, params["lm_head"])


def loss_fn(cfg: LlamaConfig, params: Dict[str, Any], tokens: jax.Array,
            mesh: Optional[Mesh] = None,
            mask: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Next-token cross entropy. tokens: (B, S); mask: (B, S) or None.

    The head matmul + softmax run in sequence chunks (cfg.loss_chunk) under
    remat, so the (B, S, vocab) logits tensor never materializes — at Llama
    vocab sizes it would dwarf every other activation.
    """
    b, s = tokens.shape
    x, moe_aux = hidden_states_with_aux(cfg, params, tokens, mesh)  # (B,S,h)
    with jax.named_scope("loss_head"):
        # shift: position i predicts token i+1; last position is masked out.
        # The weight for position i is the TARGET's mask (mask[i+1]), so
        # predictions of padding tokens never contribute.
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        if mask is not None:
            m = jnp.concatenate(
                [mask[:, 1:].astype(jnp.float32),
                 jnp.zeros((b, 1), jnp.float32)], axis=1)
        else:
            m = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)

        # Largest divisor of s within the configured chunk bound, so chunking
        # never silently disables on awkward sequence lengths (a full-vocab
        # (B, S, V) logits tensor is an OOM cliff, not a fallback).
        chunk = 0
        if cfg.loss_chunk:
            c = min(cfg.loss_chunk, s)
            while c > 1 and s % c:
                c -= 1
            chunk = c
        if chunk and s > chunk:
            n = s // chunk

            def chunk_nll(x_c, t_c):
                logits = _head_logits(cfg, x_c, params["lm_head"])
                lse = jax.nn.logsumexp(logits, axis=-1)
                tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
                return lse - tgt                               # (B, chunk)

            chunk_nll = jax.checkpoint(chunk_nll)              # drop chunk logits

            def body(_, xc_tc):
                return None, chunk_nll(*xc_tc)

            xs = x.reshape(b, n, chunk, -1).transpose(1, 0, 2, 3)
            ts = targets.reshape(b, n, chunk).transpose(1, 0, 2)
            _, nll = jax.lax.scan(body, None, (xs, ts))
            nll = nll.transpose(1, 0, 2).reshape(b, s)
        else:
            logits = _head_logits(cfg, x, params["lm_head"])
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

        total = jnp.sum(nll * m)
        count = jnp.maximum(jnp.sum(m), 1.0)
        ce = total / count
    loss = ce
    metrics = {"loss": ce, "tokens": count,
               "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}
    if cfg.n_experts:
        aux = moe_aux / cfg.n_layers
        loss = ce + cfg.moe_aux_weight * aux
        metrics["moe_aux"] = aux
    return loss, metrics


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token (fwd+bwd = 6*N_active + attention).

    For MoE, only top_k of n_experts FFNs touch each token, so inactive
    expert params are excluded from the 6N term.
    """
    n = cfg.num_params()
    if cfg.n_experts:
        n -= (3 * cfg.hidden * cfg.ffn * cfg.n_layers
              * max(cfg.n_experts - cfg.moe_top_k, 0))
    attn = 12 * cfg.n_layers * cfg.hidden * seq_len  # causal attn matmuls
    return 6.0 * n + attn
