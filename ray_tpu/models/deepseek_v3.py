"""DeepSeek-V3 for serving: latent (MLA) attention over a latent paged
cache, leading dense layers, then expert layers with sigmoid
group-limited routing, a shared expert and the routed experts HELD HERE.

The layer equations are those of the published `config.json` and
modelling code (https://huggingface.co/deepseek-ai/DeepSeek-V3):

- Latent attention. c_q = RMSNorm(x W_qa); q = c_q W_qb, per head
  [q_nope | q_pe], q_pe roped. [c_kv | k_pe] = x W_kva; c_kv =
  RMSNorm(c_kv); k_pe roped, one for all heads. The cache row of a token
  in a layer is (c_kv, k_pe). Attention runs in the ABSORBED form over
  that row: q_lat = q_nope W_kb (per head, nope -> latent), scores
  (q_lat . c_kv + q_pe . k_pe) * s, o_lat = softmax . c_kv, o = o_lat
  W_vb (per head, latent -> v), then W_o. One absorbed path serves
  decode ticks and ragged ticks (ops/mla_attention.py).
- YaRN rope on the rope dims (`yarn_inv_freq`); the cos/sin factor
  mscale / mscale_all_dim is 1 and s = (nope + rope)^-1/2 * m^2 with
  m = 0.1 * mscale_all_dim * ln(factor) + 1.
- Dense layers: SwiGLU. Expert layers: ops/moe.sigmoid_group_routing
  over ALL published experts, the shared expert's SwiGLU, and
  ops/moe.held_experts_ffn over `experts_held`, a contiguous range of
  the routed experts: the chip's share of an expert-parallel
  deployment, as one grouped product a projection over the tick's
  assignments sorted by held expert (what the router sent here is what
  is computed and read: an expert without a token is not touched). What
  absent experts would add is left out and the partial sum goes on;
  nothing stands in for the absent chips.

Departures from the published code: rotate-half rope pairing (the
published pairing is interleaved; a checkpoint's rope columns of W_qb
and W_kva would be permuted on load, the scores are the same); W_kvb is
stored split into its key part `wkb` and value part `wvb`; the
multi-token-prediction module is not run (`num_nextn_predict_layers`
adds nothing to next-token logits). Weights are created and stored in
`param_dtype` (bfloat16) and used as stored; norm weights, the router's
bias, its scores and the softmax statistics are float32.

The stack is not uniform, so it is not one `lax.scan` over one stacked
tree: `params["layers"]` is a list of one tree a layer (a dense layer
has `wg/wi/wd`, an expert layer `router/router_bias/shared/experts`) and
the forward is a loop over it. Every matrix is then a buffer of its own
that a program reads where it lies; a layer sliced out of a stacked
array is copied first (the compiler's `squeeze` fusions: a copy of every
weight every tick).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import mla_attention as mla_ops
from ..ops.moe import (held_experts_ffn, held_gates, platform_impl,
                       sigmoid_group_routing)
from .llama import rms_norm
from .paged_common import one_token_tick, refuse, rope, swiglu


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280         # rows of the vocabulary held here
    hidden: int = 7168
    n_layers: int = 61
    first_k_dense: int = 3
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn: int = 18432                 # dense layers' SwiGLU width
    moe_ffn: int = 2048              # one expert's SwiGLU width
    n_routed_experts: int = 256      # the router's width, as published
    # the routed experts this chip holds, [lo, hi): None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    moe_top_k: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_original_max: int = 4096
    norm_eps: float = 1e-6
    max_seq: int = 163840
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held
        return hi - lo

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token writes to the cache in one layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def yarn_mscale(self, scale: float) -> float:
        """YaRN's magnitude factor 0.1 * scale * ln(factor) + 1 (1 with
        no scaling or a zero `scale`)."""
        if self.rope_factor <= 1 or not scale:
            return 1.0
        return 0.1 * scale * math.log(self.rope_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        m = self.yarn_mscale(self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    def num_params(self) -> int:
        """Parameters held here (the experts' share, the vocabulary's
        slice)."""
        h = self.hidden
        attn = (h * self.q_lora_rank
                + self.q_lora_rank * self.n_heads * self.qk_head_dim
                + h * self.latent_width
                + self.kv_lora_rank * self.n_heads
                * (self.qk_nope_head_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * h)
        expert = 3 * h * self.moe_ffn
        moe = (h * self.n_routed_experts
               + (self.n_shared_experts + self.n_held) * expert)
        return (2 * self.vocab_size * h
                + self.first_k_dense * (attn + 3 * h * self.ffn)
                + self.n_moe_layers * (attn + moe))

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes from a configuration that
        is not the dense decoder's: matrix-product FLOPs a token through
        the stack (the absorbed attention's projections; the router, the
        shared expert and, of a token's `moe_top_k` picks, the share
        that falls on the experts held here), the head's, the absorbed
        attention's per (query, key) pair, and the bytes of weights a
        dispatch reads (all of them: at a serving batch every held
        expert receives a token)."""
        h, nh = self.hidden, self.n_heads
        attn = 2 * (h * self.q_lora_rank
                    + self.q_lora_rank * nh * self.qk_head_dim
                    + h * self.latent_width
                    + nh * self.qk_nope_head_dim * self.kv_lora_rank
                    + nh * self.kv_lora_rank * self.v_head_dim
                    + nh * self.v_head_dim * h)
        expert = 3 * 2 * h * self.moe_ffn
        here = self.moe_top_k * self.n_held / self.n_routed_experts
        moe = (2 * h * self.n_routed_experts
               + (self.n_shared_experts + here) * expert)
        return {
            "gemm_flops_per_token": (
                self.first_k_dense * (attn + 3 * 2 * h * self.ffn)
                + self.n_moe_layers * (attn + moe)),
            "head_flops": 2 * h * self.vocab_size,
            # scores at the row's width, values at the latent's, 2 a MAC
            "attn_flops_per_pair": 2 * self.n_layers * nh * (
                self.latent_width + self.kv_lora_rank),
            "weight_bytes": self.num_params() * jnp.dtype(
                self.param_dtype).itemsize,
        }

    def __post_init__(self):
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} "
                             "routed experts")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense outside the stack")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")


PRESETS: Dict[str, DeepseekV3Config] = {
    # the CPU tests' size: every mechanism at toy widths
    "debug": DeepseekV3Config(
        vocab_size=256, hidden=64, n_layers=3, first_k_dense=1,
        n_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, ffn=96, moe_ffn=32,
        n_routed_experts=16, n_group=4, topk_group=2, moe_top_k=4,
        rope_original_max=32, max_seq=256),
}


def config(name_or_cfg, **overrides) -> DeepseekV3Config:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


# --------------------------------------------------------------------- params

def _attn_shapes(cfg: DeepseekV3Config) -> Dict[str, Tuple[tuple, int]]:
    """name -> (shape, fan_in) of one layer's attention matrices."""
    h, nh = cfg.hidden, cfg.n_heads
    return {
        "wqa": ((h, cfg.q_lora_rank), h),
        "wqb": ((cfg.q_lora_rank, nh * cfg.qk_head_dim), cfg.q_lora_rank),
        "wkva": ((h, cfg.latent_width), h),
        "wkb": ((cfg.kv_lora_rank, nh, cfg.qk_nope_head_dim),
                cfg.kv_lora_rank),
        "wvb": ((cfg.kv_lora_rank, nh, cfg.v_head_dim), cfg.kv_lora_rank),
        "wo": ((nh * cfg.v_head_dim, h), nh * cfg.v_head_dim),
    }


def init_params(cfg: DeepseekV3Config, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters, each drawn in float32 and stored in
    `param_dtype`: {"embed", "layers": [one tree a layer], "final_norm",
    "lm_head"}."""
    pd, f32 = cfg.param_dtype, jnp.float32
    h = cfg.hidden
    counter = iter(range(1 << 20))

    def dense(shape, fan_in):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, f32)
                / math.sqrt(fan_in)).astype(pd)

    def attn():
        out = {name: dense(shape, fan)
               for name, (shape, fan) in _attn_shapes(cfg).items()}
        out.update(ln1=jnp.ones((h,), f32), ln2=jnp.ones((h,), f32),
                   q_norm=jnp.ones((cfg.q_lora_rank,), f32),
                   kv_norm=jnp.ones((cfg.kv_lora_rank,), f32))
        return out

    def swiglu_w(lead, width):
        return {"wg": dense(lead + (h, width), h),
                "wi": dense(lead + (h, width), h),
                "wd": dense(lead + (width, h), width)}

    layers = []
    for i in range(cfg.n_layers):
        if i < cfg.first_k_dense:
            layers.append({**attn(), **swiglu_w((), cfg.ffn)})
            continue
        kb = jax.random.fold_in(key, next(counter))
        layers.append({
            **attn(),
            "router": dense((h, cfg.n_routed_experts), h),
            # e_score_correction_bias: a tenth of the scores' spread,
            # enough to change some picks
            "router_bias": 0.05 * jax.random.normal(
                kb, (cfg.n_routed_experts,), f32),
            "shared": swiglu_w((), cfg.n_shared_experts * cfg.moe_ffn),
            "experts": swiglu_w((cfg.n_held,), cfg.moe_ffn),
        })
    return {"embed": dense((cfg.vocab_size, h), h), "layers": layers,
            "final_norm": jnp.ones((h,), f32),
            "lm_head": dense((h, cfg.vocab_size), h)}


# ----------------------------------------------------------------------- rope

def yarn_inv_freq(cfg: DeepseekV3Config) -> jax.Array:
    """The rope dims' inverse frequencies, [rope_dim / 2] float32:
    theta^(-2i/d), and that over `rope_factor`, blended by the linear
    ramp between the correction dims of beta_fast and beta_slow at the
    original context."""
    d = cfg.qk_rope_head_dim
    extra = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if cfg.rope_factor <= 1:
        return extra

    def correction_dim(rotations):
        return (d * math.log(cfg.rope_original_max
                             / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def rope_cos_sin(cfg: DeepseekV3Config, positions: jax.Array):
    """positions [T] -> cos, sin [T, rope_dim / 2] float32. The factor
    mscale / mscale_all_dim on both is 1 for this model and applied for
    any other."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)
    m = (cfg.yarn_mscale(cfg.rope_mscale)
         / cfg.yarn_mscale(cfg.rope_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


# --------------------------------------------------------------------- layers

def mla_project(cfg: DeepseekV3Config, layer, x, cos, sin):
    """x: [T, H] -> (absorbed queries [T, heads, latent_width], the
    tick's cache rows [T, latent_width]), both in the compute type."""
    dt = cfg.dtype
    t = x.shape[0]
    y = rms_norm(x, layer["ln1"], cfg.norm_eps)
    cq = rms_norm(y @ layer["wqa"], layer["q_norm"], cfg.norm_eps)
    q = (cq @ layer["wqb"]).reshape(t, cfg.n_heads, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_pe = rope(q[..., cfg.qk_nope_head_dim:], cos, sin)
    kv = y @ layer["wkva"]
    c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], layer["kv_norm"],
                    cfg.norm_eps)
    k_pe = rope(kv[:, cfg.kv_lora_rank:], cos, sin)
    q_lat = jnp.einsum("thn,chn->thc", q_nope, layer["wkb"],
                       preferred_element_type=jnp.float32).astype(dt)
    return (jnp.concatenate([q_lat, q_pe], axis=-1),
            jnp.concatenate([c_kv, k_pe], axis=-1).astype(dt))


def mla_output(cfg: DeepseekV3Config, layer, o_lat):
    """o_lat: [T, heads, kv_lora_rank] -> the attention block's output
    [T, H]."""
    o = jnp.einsum("thc,chv->thv", o_lat, layer["wvb"],
                   preferred_element_type=jnp.float32).astype(cfg.dtype)
    return o.reshape(o.shape[0], -1) @ layer["wo"]


def moe_block(cfg: DeepseekV3Config, layer, y, valid=None,
              impl: Optional[str] = None):
    """y: [T, H] normalised -> (the expert layer's output [T, H]: the
    shared expert plus the held experts' part of the routed sum; the
    assignments of `valid` rows landed on each held expert [n_held]
    int32). `impl` is the forward's (`_stack` passes
    the engine's); a caller with no engine (a check of one block)
    leaves it out and gets `ops/moe.platform_impl()`."""
    lo, hi = cfg.held
    # inside the `mlp` scope of `_stack`: the span tables' split of a
    # layer is attn / mlp, these names split the expert layer further
    with jax.named_scope("moe_router"):
        w, idx = sigmoid_group_routing(
            y, layer["router"], layer["router_bias"],
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            top_k=cfg.moe_top_k, scale=cfg.routed_scaling_factor,
            normalize=cfg.norm_topk_prob)
        gates, took, counts = held_gates(idx, w, lo, hi, valid)
    with jax.named_scope("moe_shared"):
        out = swiglu(layer["shared"], y)
    with jax.named_scope("moe_experts"):
        ex = layer["experts"]
        routed = held_experts_ffn(y, gates, took, (ex["wg"], ex["wi"]),
                                  ex["wd"], act="swiglu",
                                  picks=cfg.moe_top_k,
                                  impl=impl or platform_impl())
    return out + routed.astype(out.dtype), counts


# ------------------------------------------------------------------- forwards

def _stack(cfg: DeepseekV3Config, params, x, positions, valid, attend,
           impl: str):
    """Every layer in turn. attend(q, new_rows, layer_index) -> o_lat;
    `impl` is the held experts' as it is the attention's.
    Returns (x, cache rows [L, T, latent_width], expert counts
    [n_moe_layers, n_held])."""
    cos, sin = rope_cos_sin(cfg, positions)
    rows, counts = [], []
    for li, layer in enumerate(params["layers"]):
        # `attn` is the span tables' name for a layer's attention
        # block, whatever its kind; `mla` names this kind inside it
        with jax.named_scope("attn"), jax.named_scope("mla"):
            q, new = mla_project(cfg, layer, x, cos, sin)
            x = x + mla_output(cfg, layer, attend(q, new, li))
        rows.append(new)
        with jax.named_scope("mlp"):
            y = rms_norm(x, layer["ln2"], cfg.norm_eps)
            if "router" in layer:
                out, landed = moe_block(cfg, layer, y, valid, impl)
                counts.append(landed)
            else:
                out = swiglu(layer, y)
            x = x + out
    return (x, jnp.stack(rows),
            jnp.stack(counts) if counts
            else jnp.zeros((0, cfg.n_held), jnp.int32))


def cache_attention(cfg: DeepseekV3Config, impl: str, pool: jax.Array,
                    page_tables: jax.Array, slot_ids: jax.Array,
                    positions: jax.Array, valid: jax.Array,
                    start: jax.Array, ctx_pages: int = -1):
    """attend(q, rows, layer index) -> o_lat [T, heads, kv_lora_rank]
    for one tick: the absorbed queries of `mla_project` against the
    pool's cached rows of that layer and the tick's own `rows`, by the
    kernel or by the dense gather as `impl` says. The kernel's work
    list is built once here, for every layer."""
    dv, scale = cfg.kv_lora_rank, cfg.softmax_scale
    if impl in ("pallas", "pallas_interpret"):
        work = mla_ops.mla_work_list(slot_ids, valid, start, cfg.n_heads)

        def attend(q, rows, li):
            return mla_ops.mla_ragged_attention_pallas(
                q, pool, li, page_tables, slot_ids, positions, valid,
                start, rows, dv=dv, scale=scale, ctx_pages=ctx_pages,
                work=work, interpret=(impl == "pallas_interpret"))
    else:
        tables = (page_tables if ctx_pages < 0
                  else page_tables[:, :ctx_pages])

        def attend(q, rows, li):
            return mla_ops.mla_attention_gather_paged(
                q, pool, li, tables, rows, slot_ids, positions, valid,
                start, width=cfg.latent_width, dv=dv, scale=scale)
    return attend


def ragged_forward(cfg: DeepseekV3Config, params: Dict[str, Any],
                   tokens: jax.Array, slot_ids: jax.Array,
                   positions: jax.Array, valid: jax.Array,
                   start: jax.Array, last_idx: jax.Array,
                   k_pages: jax.Array, v_pages, page_tables: jax.Array,
                   ctx_pages: int = -1, lora=None, lora_idx=None,
                   impl: str = "gather", mesh=None,
                   kv_kind: str = "f32", k_scales=None, v_scales=None):
    """The unified ragged tick, with the contract of
    `llama_infer.ragged_forward` for a model of this family: `k_pages`
    is the ONE latent pool [L, pages, page, 1, row] and `v_pages` is
    None (there is no second pool) and comes back as given. Returns
    (last-token logits per slot [B, V] float32, pool, None, expert
    counts [n_moe_layers, n_held] int32)."""
    refuse("DeepSeek-V3", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    pool = k_pages
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    attend = cache_attention(cfg, impl, pool, page_tables, slot_ids,
                             positions, valid, start, ctx_pages)
    x, rows, counts = _stack(cfg, params, x, positions, valid, attend,
                             impl)
    pool = mla_ops.scatter_latent(pool, rows, page_tables[slot_ids],
                                  positions, valid)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.dot(x[last_idx], params["lm_head"],
                         preferred_element_type=jnp.float32)
    return logits, pool, v_pages, counts


decode_step = one_token_tick(ragged_forward)
