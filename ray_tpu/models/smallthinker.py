"""SmallThinker (PowerInfer, `model_name` "smallthinker_21b_instruct")
for serving: a ROUTER THAT READS THE LAYER'S INPUT, ahead of attention;
plain pre-norm attention of 28 query heads over 4 K/V heads on
sliding-window and full layers over TWO page groups; and gated-ReLU
(ReGLU) experts with no shared expert and no dense layer, every routed
expert of every layer HELD HERE in one stack.

The layer equations are those of the published `config.json`
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct) and the
family's description ("router placed before attention", "sparse ReGLU",
"SWA(4096); NoPE global"). Layer l, input x [T, hidden]:

1. Router, FIRST: r = x W_r in float32 at the highest precision;
   idx = the `moe_top_k` largest of r (ties to the lower index);
   w = softmax over those logits alone (`moe_primary_router_apply_softmax`
   with `norm_topk_prob`: the softmax over all experts, the picks
   renormalised). The router reads x ITSELF, ahead of the input norm
   (`route`: the one line that holds this choice; the published config
   does not say on which side of the norm it sits).
2. Attention: y = RMSNorm_in(x); q = y W_q, k = y W_k, v = y W_v, no
   bias, no QK norm, no output gate. `rope_layout[l]` 1: rotate-half
   rope (theta 1.5e6) on q and k; 0: no positional encoding.
   `sliding_window_layout[l]` 1: query i sees keys j with
   i - window < j <= i; 0: j <= i. Scores q.k / sqrt(head_dim), softmax
   in float32; x = x + (o W_o).
3. Experts: y = RMSNorm_post(x);
   f = sum_{k} w_k W_down[e_k]( relu(W_gate[e_k] y) * (W_up[e_k] y) );
   x = x + f. The picks are step 1's. The down-projection is computed
   whole (a row that ReLU zeroed is multiplied all the same). This chip
   computes the picks that fall on `experts_held`; what absent experts
   would add is left out and the partial sum goes on. No shared expert,
   no dense layer, no secondary experts (the config has none).
4. After the last layer RMSNorm, then the untied head.

How it runs here:

- Routing is split from the expert product (`route` / `experts`): the
  weights, the picks, the gate matrix, the assignments' rows sorted by
  expert and the grouped kernels' tile visits depend on the layer's
  input alone, so `_layer` makes them BEFORE it calls attention (scope
  `moe_router`, outside and ahead of `attn`) and hands them to
  `ops/moe.held_experts_ffn` after. Whether the compiler then runs them
  under attention is its business.
- The cache is two GROUPS (`cache_groups`): `full` FIRST (layer 0 is a
  full layer; whole contexts: the engine's `slot.pages`), then `window`
  (the last `sliding_window` tokens), the same K/V row of 4 heads of
  128. 4 heads are no multiple of the 8-row tile, so a page is
  [page x 4 rows, 128] (`CacheRow.layout` "rows", as the phi4flash and
  nemotron_h families' pages): a [16, 4, 128] page would be padded to
  [16, 8, 128] in device memory. So the attention over the cache and
  the write of a tick's rows are the merged-rows ones of
  `paged_common` (`attend_fn`, `scatter_merged_rows`: one scatter of
  single 128-lane rows a pool, scope `kv_write`); rope is rotate-half
  (`paged_common.rope_cos_sin`, `rope`).
- The stack is a list of one tree a layer and the forward a loop over
  it (Trinity's way, not a `lax.scan` over periods): a layer's
  attention matrices are taken whole with no slice out of a stack, the
  layouts may be any list of 0 and 1, and the unrolled 12-layer program
  compiles in about the time Trinity's 9-layer one does (CHANGES.md,
  PR 43). The EXPERTS of every layer lie in ONE array a projection
  ([layers x held, hidden, moe_ffn]) all the same, and the grouped
  kernels take it whole with the layer's first expert as a scalar
  (`base`): no layer's experts are ever sliced out.

Departures from the published code: matrices are stored transposed
([in, out]); weights are normal(0, 1/fan_in) from the seed, norm
weights 1.0. Weights are created and stored in `param_dtype` (bfloat16)
and used as stored; norm weights, the router's logits, its softmax and
attention's softmax statistics are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.moe import (held_experts_ffn, held_gates, held_plan,
                       platform_impl, softmax_pick_routing)
from ..ops.paged_attention import pool_head_dim
from .cache_row import CacheGroup, CacheRow
from .llama import rms_norm
from .paged_common import (FULL, SLIDING, attend_fn, one_token_tick,
                           refuse, rope, rope_cos_sin)
from .paged_common import scatter_merged_rows as scatter_rows
from .paged_common import window_span_counts as span_counts  # noqa: F401

PERIOD = (0, 1, 1, 1)     # the published layouts: full, then three window


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_ffn: int = 768               # `moe_ffn_hidden_size`
    n_routed_experts: int = 64       # `moe_num_primary_experts`
    # the routed experts this chip holds, [lo, hi): None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    moe_top_k: int = 6               # `moe_num_active_primary_experts`
    # one 0 / 1 a layer; None = the published period (0, 1, 1, 1)
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window: int = 4096       # `sliding_window_size`
    rope_theta: float = 1500000.0
    norm_eps: float = 1e-6
    max_seq: int = 16384
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    def _layout(self, given) -> Tuple[int, ...]:
        if given is not None:
            return tuple(int(v) for v in given)
        return tuple(PERIOD[i % len(PERIOD)] for i in range(self.n_layers))

    @property
    def windowed(self) -> Tuple[int, ...]:
        return self._layout(self.sliding_window_layout)

    @property
    def roped(self) -> Tuple[int, ...]:
        return self._layout(self.rope_layout)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(SLIDING if w else FULL for w in self.windowed)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held
        return hi - lo

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    def group_index(self, layer: int) -> int:
        """Where `layer` lies among the layers of its kind: its index
        in its cache group's pools."""
        kinds = self.kinds
        return sum(1 for k in kinds[:layer] if k == kinds[layer])

    def layer_params(self) -> Dict[str, int]:
        """One layer's parameters held here, leaf for leaf."""
        h = self.hidden
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        expert = self.n_held * h * self.moe_ffn
        return {"wq": h * q, "wk": h * kv, "wv": h * kv, "wo": q * h,
                "router": h * self.n_routed_experts, "ln_in": h,
                "ln_post": h, "experts.wg": expert, "experts.wi": expert,
                "experts.wd": expert}

    def num_params(self) -> int:
        """Every parameter held, leaf for leaf: the embedding, the untied
        head, the final norm, each layer (21,506,562,560 whole;
        5,561,448,960 with the first 12 layers)."""
        return (2 * self.vocab_size * self.hidden + self.hidden
                + self.n_layers * sum(self.layer_params().values()))

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes (see `DeepseekV3Config`):
        matrix products a token through the stack (the routed part at
        the share of a token's picks that lands here), the head's,
        attention's per kept (query, key) pair over every layer (the
        cost model cuts a window layer's pairs to its window), and the
        weights' bytes. The cache's bytes a token come from
        `cache_groups`, a group each."""
        h = self.hidden
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        here = self.moe_top_k * self.n_held / self.n_routed_experts
        layer = 2 * (2 * h * q + 2 * h * kv + h * self.n_routed_experts
                     + here * 3 * h * self.moe_ffn)
        return {
            "gemm_flops_per_token": self.n_layers * layer,
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
        for name, layout in (("sliding_window_layout", self.windowed),
                             ("rope_layout", self.roped)):
            if len(layout) != self.n_layers or set(layout) - {0, 1}:
                raise ValueError(f"{name} must give {self.n_layers} "
                                 "layers a 0 or a 1")
        if 0 not in self.windowed:
            raise ValueError("the engine's first cache group holds whole "
                             "contexts: a stack needs a full-attention "
                             "layer")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if not 0 < self.moe_top_k <= self.n_routed_experts:
            raise ValueError("moe_top_k outside the router's width")


PRESETS: Dict[str, SmallThinkerConfig] = {
    # the CPU tests' size: two whole periods (f s s s f s s s), 7 query
    # heads a K/V head as published, 8 experts all held, 3 picks, a
    # window of 8
    "tiny": SmallThinkerConfig(
        vocab_size=256, hidden=64, n_layers=8, n_heads=14, n_kv_heads=2,
        head_dim=16, moe_ffn=32, n_routed_experts=8, moe_top_k=3,
        sliding_window=8, max_seq=256),
}


def config(name_or_cfg, **overrides) -> SmallThinkerConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def cache_groups(cfg: SmallThinkerConfig, impl: str, kv_kind: str = "f32"
                 ) -> Tuple[CacheGroup, ...]:
    """`full` first (whole contexts: the engine's `slot.pages`), then
    `window`; the same row, a page [page x kv heads rows, 128]."""
    if kv_kind != "f32":
        raise ValueError(SMALLTHINKER_REFUSES["kv_dtype"])
    row = CacheRow(kind="kv", pools=2, heads=cfg.n_kv_heads,
                   width=cfg.head_dim,
                   padded_width=pool_head_dim(cfg.head_dim, impl),
                   dtype=cfg.dtype, layout="rows")
    groups = (CacheGroup("full", row, cfg.layers_of(FULL)),)
    if cfg.layers_of(SLIDING):
        groups += (CacheGroup("window", row, cfg.layers_of(SLIDING),
                              cfg.sliding_window),)
    return groups


# Trinity's seven reasons; the first reworded (no gated attention here)
SMALLTHINKER_REFUSES = {
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo inside "
            "its layer scan; this family's layer loop and its expert "
            "projections have no adapter path",
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

def _layer_shapes(cfg: SmallThinkerConfig) -> Dict[str, Tuple[tuple, int]]:
    """name -> (shape, fan_in) of one layer's matrices outside the
    experts."""
    h = cfg.hidden
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {"wq": ((h, q), h), "wk": ((h, kv), h), "wv": ((h, kv), h),
            "wo": ((q, h), q), "router": ((h, cfg.n_routed_experts), h)}


def init_params(cfg: SmallThinkerConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters, each drawn in float32 and stored in
    `param_dtype`: {"embed", "layers": [one tree a layer: wq, wk, wv,
    wo, router, ln_in, ln_post], "experts": {"wg", "wi": [layers x held,
    hidden, moe_ffn], "wd": [layers x held, moe_ffn, hidden]},
    "final_norm", "lm_head"}. The experts' stacks are filled in place, a
    layer's held experts at a time (drawn whole, 9 GB of float32 would
    pass through the chip)."""
    pd, f32 = cfg.param_dtype, jnp.float32
    h, f, held = cfg.hidden, cfg.moe_ffn, cfg.n_held
    counter = iter(range(1 << 20))

    def nkey():
        return jax.random.fold_in(key, next(counter))

    def dense(shape, fan_in):
        return (jax.random.normal(nkey(), shape, f32)
                * jax.lax.rsqrt(jnp.float32(fan_in))).astype(pd)

    layers = [{**{name: dense(shape, fan)
                  for name, (shape, fan) in _layer_shapes(cfg).items()},
               "ln_in": jnp.ones((h,), f32), "ln_post": jnp.ones((h,), f32)}
              for _ in range(cfg.n_layers)]

    draw = jax.jit(
        lambda k, fan_in, shape: (
            jax.random.normal(k, shape, f32)
            * jax.lax.rsqrt(fan_in)).astype(pd), static_argnums=2)
    fill = jax.jit(
        lambda buf, blk, i: jax.lax.dynamic_update_slice_in_dim(
            buf, blk, i * held, 0), donate_argnums=0)
    experts = {}
    for name, shape, fan_in in (("wg", (h, f), h), ("wi", (h, f), h),
                                ("wd", (f, h), f)):
        buf = jnp.zeros((cfg.n_layers * held,) + shape, pd)
        for i in range(cfg.n_layers):
            buf = fill(buf, draw(nkey(), jnp.float32(fan_in),
                                 (held,) + shape), jnp.int32(i))
        experts[name] = buf
    return {"embed": dense((cfg.vocab_size, h), h), "layers": layers,
            "experts": experts, "final_norm": jnp.ones((h,), f32),
            "lm_head": dense((h, cfg.vocab_size), h)}


def layer_experts(cfg: SmallThinkerConfig, params: Dict[str, Any],
                  layer: int) -> Dict[str, jax.Array]:
    """Layer `layer`'s held experts cut out of the stacks ({"wg", "wi",
    "wd"} [held, ...]): for whoever walks the layers one by one (the
    benchmark's reference, a test), never for a tick's program."""
    return {name: jax.lax.dynamic_slice_in_dim(
        a, jnp.int32(layer * cfg.n_held), cfg.n_held, 0)
        for name, a in params["experts"].items()}


# --------------------------------------------------------------------- layers

class Routing(NamedTuple):
    """What a layer's router decides, all of it from the layer's input:
    the picks' weights and indices [T, top_k], the logits [T, E]
    float32, the held experts' gate matrix and assignment mask [T,
    held], the assignments landed on each held expert [held] int32, and
    the expert product's plan (`ops/moe.held_plan`: each
    assignment's row, the groups' offsets, the kernels' tile visits)."""
    w: jax.Array
    idx: jax.Array
    logits: jax.Array
    gates: jax.Array
    took: jax.Array
    counts: jax.Array
    plan: tuple


def route(cfg: SmallThinkerConfig, layer, x, valid=None,
          impl: Optional[str] = None) -> Routing:
    """The router of one layer on that layer's INPUT x [T, H] (the
    residual stream as it enters the layer, ahead of the input norm and
    of attention), and everything the expert product needs of the picks.
    `impl` as `experts`'."""
    # the router's input: x itself, not RMSNorm_in(x)
    w, idx, logits = softmax_pick_routing(x, layer["router"],
                                          top_k=cfg.moe_top_k)
    return plan_picks(cfg, w, idx, logits, valid, impl)


def plan_picks(cfg: SmallThinkerConfig, w, idx, logits, valid=None,
               impl: Optional[str] = None) -> Routing:
    """`route` from given picks (w, idx [T, top_k]): the gates, the
    assignments and the expert product's plan."""
    lo, hi = cfg.held
    gates, took, counts = held_gates(idx, w, lo, hi, valid)
    plan = held_plan(took, picks=cfg.moe_top_k,
                     impl=impl or platform_impl())
    return Routing(w, idx, logits, gates, took, counts, plan)


def experts(cfg: SmallThinkerConfig, stacks, y, routing: Routing, base=0,
            impl: Optional[str] = None) -> jax.Array:
    """y: [T, H] normalised -> the held experts' part of the routed sum
    [T, H] float32, by the picks `routing` holds. `stacks`: {"wg", "wi",
    "wd"} of which [base, base + n_held) are this layer's. `impl` is the
    forward's; a caller with no engine (a check of one block) leaves it
    out and gets `ops/moe.platform_impl()`."""
    return held_experts_ffn(y, routing.gates, routing.took,
                            (stacks["wg"], stacks["wi"]), stacks["wd"],
                            act="reglu", picks=cfg.moe_top_k,
                            impl=impl or platform_impl(), base=base,
                            plan=routing.plan)


def attn_project(cfg: SmallThinkerConfig, layer, x, roped: int, cos, sin):
    """x: [T, H] -> (q [T, heads, d], k, v [T, kv heads, d]), q and k
    roped where the layer's `rope_layout` says so."""
    t = x.shape[0]
    y = rms_norm(x, layer["ln_in"], cfg.norm_eps)
    q = (y @ layer["wq"]).reshape(t, cfg.n_heads, cfg.head_dim)
    k = (y @ layer["wk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    v = (y @ layer["wv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    if roped:
        q, k = rope(q, cos, sin), rope(k, cos, sin)
    return q, k, v


def kernel_group(cfg: SmallThinkerConfig) -> int:
    """Query heads a K/V head as the attention kernels are handed them:
    the kernels move a tick's queries [T, heads, 128] in tiles of 8
    heads, and the TPU compiler refuses 28 (`tests/test_tpu_aot_compile
    .py`), so each K/V head's 7 query heads go in as 8, the last one
    zeros (32 heads: the scores' and values' products grow by a
    seventh, the K and V bytes read do not), and its output is dropped.
    The gather path takes the heads as they are."""
    g = cfg.n_heads // cfg.n_kv_heads
    while (g * cfg.n_kv_heads) % 8:
        g += 1
    return g


def _attend_padded(cfg: SmallThinkerConfig, attend, q, k, v, *where):
    """`attend` with each K/V head's query heads padded to
    `kernel_group` and the padding's output dropped."""
    t, kvh, d = q.shape[0], cfg.n_kv_heads, cfg.head_dim
    g, gk = cfg.n_heads // kvh, kernel_group(cfg)
    q = jnp.pad(q.reshape(t, kvh, g, d),
                ((0, 0), (0, 0), (0, gk - g), (0, 0)))
    o = attend(q.reshape(t, kvh * gk, d), k, v, *where)
    return o.reshape(t, kvh, gk, d)[:, :, :g].reshape(t, kvh * g, d)


def _layer(cfg: SmallThinkerConfig, params, li: int, x, cos, sin, valid,
           attend, impl: str):
    """Layer `li`. attend(q, k, v, group, index in the group, window) ->
    o. Returns (x, the tick's k rows, v rows, the layer's `Routing`)."""
    layer = params["layers"][li]
    windowed = cfg.windowed[li]
    # 1. the router, on the layer's input, before attention starts
    with jax.named_scope("moe_router"):
        routing = route(cfg, layer, x, valid, impl)
    # 2. attention
    with jax.named_scope("attn"), jax.named_scope(
            "swa" if windowed else "full"):
        q, k, v = attn_project(cfg, layer, x, cfg.roped[li], cos, sin)
        where = (windowed, cfg.group_index(li),
                 cfg.sliding_window if windowed else None)
        if impl != "gather" and kernel_group(cfg) * cfg.n_kv_heads \
                != cfg.n_heads:
            o = _attend_padded(cfg, attend, q, k, v, *where)
        else:
            o = attend(q, k, v, *where)
        x = x + o.reshape(o.shape[0], -1).astype(cfg.dtype) @ layer["wo"]
    # 3. the experts, by step 1's picks
    with jax.named_scope("mlp"):
        y = rms_norm(x, layer["ln_post"], cfg.norm_eps)
        with jax.named_scope("moe_experts"):
            f = experts(cfg, params["experts"], y, routing,
                        base=li * cfg.n_held, impl=impl)
        x = x + f.astype(x.dtype)
    return x, k, v, routing


def ragged_forward(cfg: SmallThinkerConfig, params: Dict[str, Any],
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
    `cache_groups`' order (full, window): pools [the group's layers, its
    pages, page x kv heads, row], tables [B, max_pages] (the engine
    hands the tables stacked [groups, B, max_pages]: indexed alike).
    Returns (last-token logits per slot [B, V] float32, k pools, v
    pools, expert counts [n_layers, n_held] int32)."""
    refuse("SmallThinker", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    attend = attend_fn(impl, tuple(zip(k_pages, v_pages)), page_tables,
                       slot_ids, positions, valid, start, ctx_pages,
                       merged_rows=True)
    cos, sin = rope_cos_sin(cfg, positions)
    ks, vs, counts = [], [], []
    for li in range(cfg.n_layers):
        x, k, v, routing = _layer(cfg, params, li, x, cos, sin, valid,
                                  attend, impl)
        ks.append(k)
        vs.append(v)
        counts.append(routing.counts)
    ks, vs = jnp.stack(ks), jnp.stack(vs)
    # a tick's rows go into the pools once, after the stack: one scatter
    # of single rows a pool (scope `kv_write`)
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
    return logits, tuple(new_k), tuple(new_v), jnp.stack(counts)


decode_step = one_token_tick(ragged_forward)
