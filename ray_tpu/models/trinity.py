"""Trinity (arcee-ai, `model_type` "afmoe") for serving: sliding-window
and full-attention layers over TWO page groups, gated QK-normed GQA
attention, leading dense layers, then expert layers with sigmoid top-k
routing, a shared expert and the routed experts HELD HERE.

The layer equations are those of the published `config.json` and
`modeling_afmoe.py` (https://huggingface.co/arcee-ai/Trinity-Large-Preview),
for layer l of kind `layer_types[l]`:

- Embedding: x = E[ids] * sqrt(hidden) (`mup_enabled`).
- Attention block: y = RMSNorm_in(x); q = y W_q, k = y W_k, v = y W_v,
  g = y W_gate; q and k take a per-head RMS norm (one learned weight of
  head_dim each); on `sliding_attention` layers ONLY, rotate-half rope
  on q and k, on `full_attention` layers none; scores q.k / sqrt(d),
  softmax in float32 over the keys j that query i may see: j <= i on a
  full layer, i - window < j <= i on a window layer; o = softmax . v;
  a = (o * sigmoid(g)) W_o; x = x + RMSNorm_post_attn(a).
- Feed-forward block: y = RMSNorm_pre_mlp(x); f = FFN(y);
  x = x + RMSNorm_post_mlp(f). Dense layers: SwiGLU. Expert layers:
  ops/moe.sigmoid_group_routing over ALL published experts (one group),
  the shared expert's SwiGLU, and ops/moe.held_experts_ffn over
  `experts_held`, a contiguous range of the routed experts: the chip's
  share of an expert-parallel deployment, as one grouped product a
  projection over the tick's assignments sorted by held expert (an
  expert without a token is not touched). What absent experts would
  add is left out and the partial sum goes on; nothing stands in for
  the absent chips.
- Final RMSNorm, then the head over the vocabulary rows held.

Departures from the published code: W_q / W_k / W_v / W_gate / W_o are
stored transposed ([in, out]); the depth-scaled initialisation is not
reproduced (weights are normal(0, 1/fan_in) from the seed);
`load_balance_coeff` (a training loss) is unused. Weights are created
and stored in `param_dtype` (bfloat16) and used as stored; norm
weights, the router's bias, its scores, the gate's sigmoid and the
softmax statistics are float32.

The cache is two GROUPS (`cache_groups`): `full` (the full-attention
layers, whole contexts) and `window` (the sliding layers, the last
`sliding_window` tokens), the same K/V row. The forwards take a pool
pair and a page table a group, as tuples in that order.

The stack is a list of one tree a layer (`params["layers"]`, as the
latent family's is) and the forward a loop over it, not a `lax.scan`
over whole periods of stacked trees: scanned, every tick copies each
layer's held experts out of the stack before the expert layer may take
them (24 x 302 MB by the TPU compiler's analysis of PR 31's 9-layer
cut's 512-token program, 1.1 GB of temporaries against 0.2; a kernel
wants its operand whole as much as that tree's `lax.cond` did), while
the unrolled program compiles in 15 to 19 s against 13 to 14 (XLA:TPU
and Mosaic, for a v5e without the chip): depth costs the compile little
here. The pools go whole
to the attention kernel with the layer's index folded into the page
table (a pool sliced by layer is copied first), and a tick's rows are
scattered once, after the stack.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.moe import (held_experts_ffn, held_gates, platform_impl,
                       sigmoid_group_routing)
from .cache_row import CacheGroup, CacheRow
from .llama import rms_norm
from .paged_common import (FULL, SLIDING, attend_fn, one_token_tick,
                           refuse, rope, rope_cos_sin, swiglu)
from .paged_common import scatter_token_rows as scatter_rows
from .paged_common import window_span_counts as span_counts  # noqa: F401


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    vocab_size: int = 200192         # rows of the vocabulary held here
    hidden: int = 3072
    n_layers: int = 60
    n_dense_layers: int = 6
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn: int = 12288                 # dense layers' SwiGLU width
    moe_ffn: int = 3072              # one expert's SwiGLU width
    n_routed_experts: int = 256      # the router's width, as published
    # the routed experts this chip holds, [lo, hi): None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    n_shared_experts: int = 1
    moe_top_k: int = 4
    route_scale: float = 2.448
    route_norm: bool = True
    # one kind a layer; None = (period - 1) sliding then one full
    layer_types: Optional[Tuple[str, ...]] = None
    period: int = 4                  # `global_attn_every_n_layers`
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    mup_enabled: bool = True
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    @property
    def kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(FULL if (i + 1) % self.period == 0 else SLIDING
                     for i in range(self.n_layers))

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held
        return hi - lo

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    def group_index(self, layer: int) -> int:
        """Where `layer` lies among the layers of its kind: its index
        in its cache group's pools."""
        kinds = self.kinds
        return sum(1 for k in kinds[:layer] if k == kinds[layer])

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.hidden) if self.mup_enabled else 1.0

    def num_params(self) -> int:
        """Matrix parameters held here (the experts' share, the
        vocabulary's slice; norm weights and the router's bias, a few
        thousand a layer, are left out as the issue's arithmetic does)."""
        h = self.hidden
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        attn = h * q + 2 * h * kv + q * h + h * q
        expert = 3 * h * self.moe_ffn
        moe = (h * self.n_routed_experts
               + (self.n_shared_experts + self.n_held) * expert)
        return (2 * self.vocab_size * h
                + self.n_dense_layers * (attn + 3 * h * self.ffn)
                + self.n_moe_layers * (attn + moe))

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes from a configuration that
        is not the dense decoder's (see `DeepseekV3Config`): matrix
        products a token through the stack, the head's, attention's per
        kept (query, key) pair over every layer (the cost model cuts a
        window layer's pairs to its window), and the weights' bytes."""
        h = self.hidden
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        attn = 2 * (h * q + 2 * h * kv + q * h + h * q)
        expert = 3 * 2 * h * self.moe_ffn
        here = self.moe_top_k * self.n_held / self.n_routed_experts
        moe = (2 * h * self.n_routed_experts
               + (self.n_shared_experts + here) * expert)
        return {
            "gemm_flops_per_token": (
                self.n_dense_layers * (attn + 3 * 2 * h * self.ffn)
                + self.n_moe_layers * (attn + moe)),
            "head_flops": 2 * h * self.vocab_size,
            "attn_flops_per_pair": 4 * self.n_layers * self.n_heads
            * self.head_dim,
            "weight_bytes": self.num_params() * jnp.dtype(
                self.param_dtype).itemsize,
        }

    def __post_init__(self):
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} "
                             "routed experts")
        kinds = self.kinds
        if len(kinds) != self.n_layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {self.n_layers} "
                             f"layers as {SLIDING!r} or {FULL!r}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers outside the stack")
        if FULL not in kinds:
            raise ValueError("the engine's first cache group holds whole "
                             "contexts: a stack needs a full-attention "
                             "layer")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")


PRESETS: Dict[str, TrinityConfig] = {
    # the CPU tests' size: every mechanism at toy widths; one dense
    # window layer, then 8 expert layers (s s f s s s f s), a window of 8
    "debug": TrinityConfig(
        vocab_size=256, hidden=64, n_layers=9, n_dense_layers=1,
        n_heads=4, n_kv_heads=2, head_dim=16, ffn=96, moe_ffn=32,
        n_routed_experts=16, moe_top_k=4, sliding_window=8, max_seq=256),
}


def config(name_or_cfg, **overrides) -> TrinityConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def cache_groups(cfg: TrinityConfig, impl: str, kv_kind: str = "f32"
                 ) -> Tuple[CacheGroup, ...]:
    """`full` first (whole contexts: the engine's `slot.pages`), then
    `window`; the same row."""
    from ..ops.paged_attention import pool_head_dim
    if kv_kind != "f32":
        raise ValueError(TRINITY_REFUSES["kv_dtype"])
    row = CacheRow(kind="kv", pools=2, heads=cfg.n_kv_heads,
                   width=cfg.head_dim,
                   padded_width=pool_head_dim(cfg.head_dim, impl),
                   dtype=cfg.dtype)
    groups = (CacheGroup("full", row, cfg.layers_of(FULL)),)
    if cfg.layers_of(SLIDING):
        groups += (CacheGroup("window", row, cfg.layers_of(SLIDING),
                              cfg.sliding_window),)
    return groups


TRINITY_REFUSES = {
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo inside "
            "its layer scan; this family's gated attention has no "
            "adapter path",
    "kv_dtype": "int8/fp8 KV pages keep per-(row, kv head) scale pools "
                "beside ONE pair of pools; this family has a pair a "
                "cache group and no quantized write or read path",
    "enable_kv_offload": "the host KV tier spills and restores one "
                         "group's pages by a slot's page list; a window "
                         "group holds a moving part of a sequence",
    "mesh": "GSPMD tensor parallelism is the dense family's layout; the "
            "expert layer has no exchange across chips",
    "mesh_shape": "the explicit-tp shard_map programs are the dense "
                  "family's (Megatron layout of wq/wk/wv/wo)",
    "checkpoint": "no checkpoint loader for this family's tree yet",
    "session_shipping": "session and prefix export/import move one "
                        "group's pages; a window group's pages behind "
                        "the window are gone",
}


# --------------------------------------------------------------------- params

def _attn_shapes(cfg: TrinityConfig) -> Dict[str, Tuple[tuple, int]]:
    """name -> (shape, fan_in) of one layer's attention matrices."""
    h = cfg.hidden
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {"wq": ((h, q), h), "wk": ((h, kv), h), "wv": ((h, kv), h),
            "wgate": ((h, q), h), "wo": ((q, h), q)}


def init_params(cfg: TrinityConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters, each drawn in float32 and stored in
    `param_dtype`: {"embed", "layers": [one tree a layer], "final_norm",
    "lm_head"}."""
    pd, f32 = cfg.param_dtype, jnp.float32
    h, d = cfg.hidden, cfg.head_dim
    counter = iter(range(1 << 20))

    def dense(shape, fan_in):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, f32)
                / math.sqrt(fan_in)).astype(pd)

    def attn():
        out = {name: dense(shape, fan)
               for name, (shape, fan) in _attn_shapes(cfg).items()}
        ones = lambda n: jnp.ones((n,), f32)
        out.update(q_norm=ones(d), k_norm=ones(d), ln_in=ones(h),
                   ln_post_attn=ones(h), ln_pre_mlp=ones(h),
                   ln_post_mlp=ones(h))
        return out

    def swiglu_w(lead, width):
        return {"wg": dense(lead + (h, width), h),
                "wi": dense(lead + (h, width), h),
                "wd": dense(lead + (width, h), width)}

    def expert_layer():
        kb = jax.random.fold_in(key, next(counter))
        return {
            **attn(),
            "router": dense((h, cfg.n_routed_experts), h),
            # `expert_bias`: a tenth of the scores' spread, enough to
            # change some picks
            "router_bias": 0.05 * jax.random.normal(
                kb, (cfg.n_routed_experts,), f32),
            "shared": swiglu_w((), cfg.n_shared_experts * cfg.moe_ffn),
            "experts": swiglu_w((cfg.n_held,), cfg.moe_ffn),
        }

    layers = [{**attn(), **swiglu_w((), cfg.ffn)}
              if i < cfg.n_dense_layers else expert_layer()
              for i in range(cfg.n_layers)]
    return {"embed": dense((cfg.vocab_size, h), h), "layers": layers,
            "final_norm": jnp.ones((h,), f32),
            "lm_head": dense((h, cfg.vocab_size), h)}


# --------------------------------------------------------------------- layers

def attn_project(cfg: TrinityConfig, layer, x, kind: str, cos, sin):
    """x: [T, H] -> (q [T, heads, d], k, v [T, kv heads, d], the output
    gate's logits [T, heads * d]), q and k normed and, on a window
    layer, roped."""
    t = x.shape[0]
    y = rms_norm(x, layer["ln_in"], cfg.norm_eps)
    q = (y @ layer["wq"]).reshape(t, cfg.n_heads, cfg.head_dim)
    k = (y @ layer["wk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    v = (y @ layer["wv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    g = y @ layer["wgate"]
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if kind == SLIDING:
        q, k = rope(q, cos, sin), rope(k, cos, sin)
    return q, k, v, g


def attn_output(cfg: TrinityConfig, layer, o, g):
    """o: [T, heads, d], g: the gate's logits -> the block's output
    [T, H] (before the post-attention norm)."""
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(g.astype(jnp.float32))
        o = (o.reshape(o.shape[0], -1).astype(jnp.float32)
             * gate).astype(cfg.dtype)
    return o @ layer["wo"]


def moe_block(cfg: TrinityConfig, layer, y, valid=None,
              impl: Optional[str] = None):
    """y: [T, H] normalised -> (the expert layer's output [T, H]: the
    shared expert plus the held experts' part of the routed sum; the
    assignments of `valid` rows landed on each held expert [n_held]
    int32). One routing group of all the experts. `impl` is the
    forward's (`_stack` passes the engine's); a caller with no engine (a
    check of one block) leaves it out and gets
    `ops/moe.platform_impl()`."""
    lo, hi = cfg.held
    with jax.named_scope("moe_router"):
        w, idx = sigmoid_group_routing(
            y, layer["router"], layer["router_bias"], n_group=1,
            topk_group=1, top_k=cfg.moe_top_k, scale=cfg.route_scale,
            normalize=cfg.route_norm)
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


def _layer(cfg: TrinityConfig, layer, x, kind: str, cos, sin, valid,
           attend, gi, impl: str):
    """One layer. attend(q, k, v, kind, index in the kind's group) -> o.
    Returns (x, the tick's k rows, v rows, expert counts or None)."""
    with jax.named_scope("attn"), jax.named_scope(
            "swa" if kind == SLIDING else "full"):
        q, k, v, g = attn_project(cfg, layer, x, kind, cos, sin)
        a = attn_output(cfg, layer, attend(q, k, v, kind, gi), g)
        x = x + rms_norm(a, layer["ln_post_attn"], cfg.norm_eps)
    counts = None
    with jax.named_scope("mlp"):
        y = rms_norm(x, layer["ln_pre_mlp"], cfg.norm_eps)
        if "router" in layer:
            f, counts = moe_block(cfg, layer, y, valid, impl)
        else:
            f = swiglu(layer, y)
        x = x + rms_norm(f, layer["ln_post_mlp"], cfg.norm_eps)
    return x, k, v, counts


def _stack(cfg: TrinityConfig, params, x, positions, valid, attend,
           impl: str):
    """Every layer in turn. Returns (x, k rows [L, T, kv heads, d], v
    rows, expert counts [n_moe_layers, n_held])."""
    cos, sin = rope_cos_sin(cfg, positions)
    ks, vs, counts = [], [], []
    for li, (layer, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        x, k, v, landed = _layer(cfg, layer, x, kind, cos, sin, valid,
                                 attend, cfg.group_index(li), impl)
        ks.append(k)
        vs.append(v)
        if landed is not None:
            counts.append(landed)
    return (x, jnp.stack(ks), jnp.stack(vs),
            jnp.stack(counts) if counts
            else jnp.zeros((0, cfg.n_held), jnp.int32))


def cache_attention(cfg: TrinityConfig, impl: str, k_pools, v_pools,
                    page_tables, slot_ids: jax.Array,
                    positions: jax.Array, valid: jax.Array,
                    start: jax.Array, ctx_pages: int = -1):
    """attend(q, k, v, kind, index in the kind's group) -> o [T, heads,
    d] for one tick: `paged_common.attend_fn` over this family's
    token-layout pools, a layer's kind named to its group (full 0,
    window 1) and its window."""
    attend = attend_fn(impl, tuple(zip(k_pools, v_pools)), page_tables,
                       slot_ids, positions, valid, start, ctx_pages,
                       merged_rows=False)
    group_of = {FULL: 0, SLIDING: 1}
    window_of = {FULL: None, SLIDING: cfg.sliding_window}
    return lambda q, k, v, kind, gi: attend(
        q, k, v, group_of[kind], gi, window_of[kind])


def ragged_forward(cfg: TrinityConfig, params: Dict[str, Any],
                   tokens: jax.Array, slot_ids: jax.Array,
                   positions: jax.Array, valid: jax.Array,
                   start: jax.Array, last_idx: jax.Array,
                   k_pages, v_pages, page_tables,
                   ctx_pages: int = -1, lora=None, lora_idx=None,
                   impl: str = "gather", mesh=None,
                   kv_kind: str = "f32", k_scales=None, v_scales=None):
    """The unified ragged tick, with the contract of
    `llama_infer.ragged_forward` for a model of this family: `k_pages`,
    `v_pages` and `page_tables` are TUPLES, one entry a cache group in
    `cache_groups`' order (full, window): pools [the group's layers,
    its pages, page, kv heads, row], tables [B, max_pages] (the engine
    hands the tables stacked [groups, B, max_pages]: indexed alike). Returns
    (last-token logits per slot [B, V] float32, k pools, v pools,
    expert counts [n_moe_layers, n_held] int32)."""
    refuse("Trinity", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    with jax.named_scope("embed"):
        x = (params["embed"][tokens].astype(jnp.float32)
             * cfg.embed_scale).astype(cfg.dtype)
    attend = cache_attention(cfg, impl, k_pages, v_pages, page_tables,
                             slot_ids, positions, valid, start, ctx_pages)
    x, ks, vs, counts = _stack(cfg, params, x, positions, valid, attend,
                               impl)
    new_k, new_v = [], []
    for g, kind in enumerate((FULL, SLIDING)[:len(k_pages)]):
        of = np.asarray(cfg.layers_of(kind))         # static
        own = page_tables[g][slot_ids]
        new_k.append(scatter_rows(k_pages[g], ks[of], own, positions,
                                  valid))
        new_v.append(scatter_rows(v_pages[g], vs[of], own, positions,
                                  valid))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.dot(x[last_idx], params["lm_head"],
                         preferred_element_type=jnp.float32)
    return logits, tuple(new_k), tuple(new_v), counts


decode_step = one_token_tick(ragged_forward)
