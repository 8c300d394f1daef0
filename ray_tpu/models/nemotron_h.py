"""Nemotron-H with experts (nvidia, `model_type` "nemotron_h": the
NVIDIA-Nemotron-3-Nano-30B-A3B layout) for serving: Mamba-2 (SSD) layers
whose state is kept a SLOT beside ONE small page group, attention
layers of 32 query heads over 2 K/V heads with no positional encoding,
expert layers of UNGATED relu^2 experts with the routed experts HELD
HERE, and layers that are a mixer OR a feed-forward part alone.

The model, for layer l of kind `hybrid_override_pattern[l]`:

    h <- h + Mixer_l(RMSNorm_l(h));  then RMSNorm_f, then
    logits = h W_head^T   (untied)

RMSNorm with a weight, eps 1e-5, in float32. No bias in any linear map;
the conv has one. ONE mixer a layer, of three kinds:

- `M`, MAMBA-2 (H = 64 heads of P = 64, d_inner = H P = 4096; G = 8
  groups, N = 128; conv of K = 4 taps). [z, xBC, dt] = u W_in (2688 ->
  4096 + 6144 + 64). xBC <- silu(conv(xBC) + b_c), causal, depthwise,
  over all 6144 channels; split x [H, P], B [G, N], C [G, N].
  Delta_t[h] = softplus(dt_t[h] + dt_bias[h]) (no clamp);
  A[h] = -exp(A_log[h]), a scalar a head; g(h) = h // 8:
      S_t[h] = exp(Delta_t[h] A[h]) S_{t-1}[h]
               + Delta_t[h] x_t[h] (outer) B_t[g(h)]        (P x N)
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
  Gated GROUP norm, gate first: y <- y * silu(z), then RMSNorm over each
  of the 8 groups of 512 channels separately, times a weight [4096].
  out = y W_out (4096 -> 2688). State, Delta, the decay and its
  exponential in float32 (`ops/ssd_scan.py` has the chunked form).
- `E`, EXPERTS. Router in float32: s = sigmoid(u W_r) over all 128;
  picks = the 6 largest of s + b (`e_score_correction_bias`; `n_group`
  1, `topk_group` 1 limit nothing); weights = the picked s over their
  sum (`norm_topk_prob`), times 2.5 (`routed_scaling_factor`). An
  expert: relu(u W_up)^2 W_down (2688 -> 1856 -> 2688, `relu2`, NO gate
  matrix). out = sum_picks w_e Expert_e(u) + Shared(u), the shared
  expert the same form at width 3712. This chip computes the picks that
  fall on `experts_held` and the shared expert; what absent experts
  would add is left out and the partial sum goes on.
- `*`, ATTENTION. 32 query heads over 2 K/V heads of 128 (q 2688 ->
  4096, k and v -> 256, o 4096 -> 2688), scale 1/sqrt(128), causal over
  the whole context, NO rotary embedding and no other positional
  encoding (the published `nemotron_h` modeling code applies none in its
  attention; the Mamba layers carry order).

How it runs here:

- The cache is two GROUPS (`cache_groups`): `full`, the `*` layers' K
  and V (2 heads of 128: a page is [16 x 2 rows, 128], `layout` "rows",
  because 2 heads are no multiple of the 8-row tile), and `state`, the
  `M` layers' conv inputs (the last 3 of 6144 channels, bfloat16) and
  scan state ([64, 64, 128] float32: 2 MB) a SLOT a layer. The forwards
  take (K pool, conv inputs) / (V pool, scan state) in `k_pages` /
  `v_pages`, and ONE page table.
- The stack is UNITS of `M`, an optional `*`, `E` (the published
  pattern is nothing else: every Mamba layer is followed by an expert
  layer, six times with an attention layer between), each kind's layers
  stacked along a leading axis and the forward ONE `lax.scan` over the
  units with a `lax.cond` on the attention layer, so that a tick's
  program holds each kind's body once, not 16 times. The held experts of
  every expert layer lie in ONE array [layers x held, F, H] that the
  grouped kernels take whole with the layer's first expert as an index
  (`ops/moe.held_experts_ffn`, `base`): a scan that sliced a layer's
  experts out would copy 0.64 GB a layer a tick.
- W_up of the experts is stored out by in ([F, H], as `nn.Linear` keeps
  it): the expert width 1856 is no whole number of 128-lane vectors,
  and as an array's minor dim XLA would pad it in a copy of the stack
  (`tests/test_tpu_aot_compile.py`).

Departures from the published code: the other matrices are stored
transposed ([in, out]); weights are normal(0, 1/fan_in) from the seed
(the depth-rescaled initialisation is not reproduced), A_log =
log(1 .. H) a head, D = 1, dt_bias by Mamba's inverse-softplus draw of
Delta log-uniform in [`time_step_min`, `time_step_max`] floored at
`time_step_floor`, the selection bias normal(0, 0.05); `rope_theta`
and `partial_rotary_factor` are keys the published attention does not
read. Weights are created and stored in `param_dtype` (bfloat16) and
used as stored; norm weights, the conv's taps and bias, dt_bias, A_log,
D and the selection bias are float32.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import selective_scan as ssm
from ..ops.moe import (held_experts_ffn, held_gates, platform_impl,
                       sigmoid_group_routing)
from ..ops.paged_attention import pool_head_dim
from .cache_row import CacheGroup, CacheRow, StateRow
from .llama import rms_norm
from .paged_common import attend_fn
from .paged_common import mamba2_mixer as mamba_mixer
from .paged_common import one_token_tick, refuse, state_span_counts
from .paged_common import scatter_merged_rows as scatter_rows

MAMBA, EXPERTS, ATTN = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072         # rows of the vocabulary held here
    hidden: int = 2688
    pattern: str = PUBLISHED_PATTERN  # `hybrid_override_pattern`
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64            # `mamba_num_heads`
    mamba_head_dim: int = 64
    ssm_state: int = 128             # `ssm_state_size`
    n_groups: int = 8                # the scan's groups (B and C)
    d_conv: int = 4                  # `conv_kernel`
    chunk_size: int = 128
    moe_ffn: int = 1856              # `moe_intermediate_size`
    shared_ffn: int = 3712           # `moe_shared_expert_intermediate_size`
    n_routed_experts: int = 128      # the router's width, as published
    # the routed experts this chip holds, [lo, hi): None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    moe_top_k: int = 6               # `num_experts_per_tok`
    route_scale: float = 2.5         # `routed_scaling_factor`
    route_norm: bool = True          # `norm_topk_prob`
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(self.pattern)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.pattern) if k == kind)

    @property
    def units(self) -> Tuple[Tuple[int, Optional[int], int], ...]:
        """The stack as (Mamba layer, attention layer or None, expert
        layer) triples, in order."""
        out, l = [], 0
        for m in re.finditer(r"M(\*?)E", self.pattern):
            star = bool(m.group(1))
            out.append((l, l + 1 if star else None, l + 1 + star))
            l += 2 + star
        return tuple(out)

    @property
    def d_inner(self) -> int:
        """`mamba_num_heads` x `mamba_head_dim` (the key `expand` is not
        what sizes it: 2 x 2688 is not 4096)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_heads

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held
        return hi - lo

    @property
    def n_moe_layers(self) -> int:
        return len(self.layers_of(EXPERTS))

    def layer_params(self, kind: str) -> int:
        """Every parameter of one layer of `kind`, its norm with it."""
        h = self.hidden
        if kind == MAMBA:
            return (h * self.in_width + self.d_inner * h
                    + self.conv_dim * (self.d_conv + 1)
                    + 3 * self.mamba_heads + self.d_inner + h)
        if kind == ATTN:
            q = self.n_heads * self.head_dim
            kv = self.n_kv_heads * self.head_dim
            return 2 * h * q + 2 * h * kv + h
        return (h * self.n_routed_experts + self.n_routed_experts
                + 2 * h * self.shared_ffn
                + self.n_held * 2 * h * self.moe_ffn + h)

    def num_params(self) -> int:
        """Every parameter held, leaf for leaf: the embedding, the
        untied head, the final norm, each layer (31,577,940,288 whole;
        5,282,534,208 with 64 of 128 experts, half the vocabulary and
        the first 16 layers)."""
        return (2 * self.vocab_size * self.hidden + self.hidden
                + sum(self.layer_params(k) for k in self.pattern))

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes (see `DeepseekV3Config`):
        matrix products a token through the stack (an expert layer's
        routed part at the share of a token's picks that lands here),
        the head's, attention's per kept (query, key) pair over the
        attention layers, and the weights' bytes. The scan's own
        products (scores and states over chunks) are left out: they are
        a few percent of a Mamba layer's projections."""
        h = self.hidden
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        here = self.moe_top_k * self.n_held / self.n_routed_experts
        per = {MAMBA: 2 * (h * self.in_width + self.d_inner * h),
               ATTN: 2 * (2 * h * q + 2 * h * kv),
               EXPERTS: 2 * (h * self.n_routed_experts
                             + 2 * h * self.shared_ffn
                             + here * 2 * h * self.moe_ffn)}
        return {
            "gemm_flops_per_token": sum(per[k] for k in self.pattern),
            "head_flops": 2 * h * self.vocab_size,
            "attn_flops_per_pair": 4 * self.n_heads * self.head_dim
            * len(self.layers_of(ATTN)),
            "weight_bytes": self.num_params() * jnp.dtype(
                self.param_dtype).itemsize,
        }

    def __post_init__(self):
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} "
                             "routed experts")
        if not re.fullmatch(r"(M\*?E)+", self.pattern):
            raise ValueError(
                f"pattern {self.pattern!r}: the stack is written for units "
                "of M, an optional *, E (a Mamba layer, an attention layer "
                "or none, an expert layer), which the published pattern is")
        if ATTN not in self.pattern:
            raise ValueError("the engine's first cache group holds pages: "
                             "a stack needs an attention layer")
        if self.mamba_heads % self.n_groups \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into their groups")


PRESETS: Dict[str, NemotronHConfig] = {
    # the CPU tests' size: all three kinds (a unit with attention and one
    # without, twice), 2 scan groups of 2 heads, 8 experts of which 4 are
    # held, 3 picks
    "tiny": NemotronHConfig(
        vocab_size=256, hidden=64, pattern="MEM*EMEM*E", n_heads=4,
        n_kv_heads=2, head_dim=16, mamba_heads=4, mamba_head_dim=16,
        ssm_state=16, n_groups=2, moe_ffn=32, shared_ffn=48,
        n_routed_experts=8, experts_held=(0, 4), moe_top_k=3, max_seq=256),
}


def config(name_or_cfg, **overrides) -> NemotronHConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def cache_groups(cfg: NemotronHConfig, impl: str, kv_kind: str = "f32"
                 ) -> Tuple[CacheGroup, ...]:
    """`full` (the attention layers' K and V, whole contexts: the
    engine's `slot.pages`), then the Mamba layers' `state`: a slot's last
    K - 1 conv inputs over all `conv_dim` channels and its scan state
    [H, P, N] float32, a layer. A page is [page * 2 rows, 128]."""
    if kv_kind != "f32":
        raise ValueError(NEMOTRON_H_REFUSES["kv_dtype"])
    row = CacheRow(kind="kv", pools=2, heads=cfg.n_kv_heads,
                   width=cfg.head_dim,
                   padded_width=pool_head_dim(cfg.head_dim, impl),
                   dtype=cfg.dtype, layout="rows")
    state = StateRow(kind="ssd", parts=(
        ("conv", ((cfg.d_conv - 1) * cfg.conv_dim,), cfg.dtype),
        ("ssm", (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
         jnp.float32)))
    return (CacheGroup("full", row, cfg.layers_of(ATTN)),
            CacheGroup("state", None, cfg.layers_of(MAMBA), state=state))


NEMOTRON_H_REFUSES = {
    "prefix_cache": "a resume at token m needs the recurrent state as it "
                    "stood at m; one state a slot is kept, the newest, and "
                    "no snapshot is taken at page boundaries: the cache "
                    "matches nothing (`stats()['prefix_cache']` says so)",
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo inside "
            "its layer scan; this family's Mamba and expert projections "
            "have no adapter path",
    "kv_dtype": "int8/fp8 KV pages keep per-(row, kv head) scale pools "
                "beside ONE pair of pools; this family has a float32 "
                "state beside its pools and no quantized write or read "
                "path",
    "enable_kv_offload": "the host KV tier spills and restores a slot's "
                         "pages; a sequence here is also its recurrent "
                         "state, which holds no pages and has no spill",
    "mesh": "GSPMD tensor parallelism is the dense family's layout; the "
            "scan's heads and the expert layer have no sharding and no "
            "exchange across chips here",
    "mesh_shape": "the explicit-tp shard_map programs are the dense "
                  "family's (Megatron layout of wq/wk/wv/wo)",
    "checkpoint": "no checkpoint loader for this family's tree yet",
    "session_shipping": "session and prefix export/import move a slot's "
                        "pages; a sequence here is also its recurrent "
                        "state, and nothing snapshots or ships that",
}


# --------------------------------------------------------------------- params

def _shapes(cfg: NemotronHConfig) -> Dict[str, Dict[str, tuple]]:
    """kind -> leaf -> (shape a layer, how it is drawn): a fan-in for a
    matrix stored in `param_dtype`, or the name of a float32 rule."""
    h, e, hm = cfg.hidden, cfg.d_inner, cfg.mamba_heads
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {
        "mamba": {
            "ln": ((h,), "ones"), "in_proj": ((h, cfg.in_width), h),
            "conv_w": ((cfg.d_conv, cfg.conv_dim), "taps"),
            "conv_b": ((cfg.conv_dim,), "small"),
            "dt_bias": ((hm,), "dt"), "a_log": ((hm,), "a_log"),
            "d_skip": ((hm,), "ones"), "norm": ((e,), "ones"),
            "out_proj": ((e, h), e)},
        "attn": {
            "ln": ((h,), "ones"), "wq": ((h, q), h), "wk": ((h, kv), h),
            "wv": ((h, kv), h), "wo": ((q, h), q)},
        "moe": {
            "ln": ((h,), "ones"),
            "router": ((h, cfg.n_routed_experts), h),
            "router_bias": ((cfg.n_routed_experts,), "bias"),
            "shared_up": ((h, cfg.shared_ffn), h),
            "shared_down": ((cfg.shared_ffn, h), cfg.shared_ffn)},
    }


def init_params(cfg: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters as the forwards take them and the engine keeps
    them: {"embed", "lm_head", "final_norm", "mamba", "attn", "moe":
    each kind's leaves stacked along a leading axis over its layers,
    "experts": {"up", "down"} [expert layers x held, F, H]}. A matrix is
    drawn in float32, a layer at a time, and stored in `param_dtype`;
    the experts' stacks are filled in place, a layer's 64 experts at a
    time (drawn whole they would pass 9 GB of float32 through the
    chip)."""
    pd, f32 = cfg.param_dtype, jnp.float32
    counter = iter(range(1 << 20))

    def nkey():
        return jax.random.fold_in(key, next(counter))

    def dense(shape, fan_in):
        return (jax.random.normal(nkey(), shape, f32)
                / math.sqrt(fan_in)).astype(pd)

    def leaf(shape, how):
        if not isinstance(how, str):
            return dense(shape, how)
        if how == "ones":
            return jnp.ones(shape, f32)
        if how == "a_log":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
        if how == "dt":
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.exp(jax.random.uniform(nkey(), shape, f32) * (hi - lo)
                         + lo).clip(cfg.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))   # softplus(dt_bias) = dt
        scale = {"taps": 1.0 / math.sqrt(cfg.d_conv), "small": 0.02,
                 # a tenth of the scores' spread: enough to change picks
                 "bias": 0.05}[how]
        return scale * jax.random.normal(nkey(), shape, f32)

    counts = {"mamba": len(cfg.layers_of(MAMBA)),
              "attn": len(cfg.layers_of(ATTN)), "moe": cfg.n_moe_layers}
    out: Dict[str, Any] = {
        kind: {name: jnp.stack([leaf(shape, how)
                                for _ in range(counts[kind])])
               for name, (shape, how) in leaves.items()}
        for kind, leaves in _shapes(cfg).items()}
    h, f, held = cfg.hidden, cfg.moe_ffn, cfg.n_held

    @jax.jit
    def draw(k, fan_in):
        return (jax.random.normal(k, (held, f, h), f32)
                * jax.lax.rsqrt(fan_in)).astype(pd)

    fill = jax.jit(
        lambda buf, blk, i: jax.lax.dynamic_update_slice_in_dim(
            buf, blk, i * held, 0), donate_argnums=0)
    experts = {}
    for name, fan_in in (("up", h), ("down", f)):
        buf = jnp.zeros((cfg.n_moe_layers * held, f, h), pd)
        for i in range(cfg.n_moe_layers):
            buf = fill(buf, draw(nkey(), jnp.float32(fan_in)), jnp.int32(i))
        experts[name] = buf
    out["experts"] = experts
    out["embed"] = dense((cfg.vocab_size, h), h)
    out["lm_head"] = dense((h, cfg.vocab_size), h)
    out["final_norm"] = jnp.ones((h,), f32)
    return out


class _Layers:
    """A stacked tree's layers as a sequence of one tree a layer, each
    cut out of its stack when it is asked for."""

    def __init__(self, cfg, params):
        self._cfg, self._params = cfg, params
        seen = {MAMBA: 0, ATTN: 0, EXPERTS: 0}
        self._where = []
        for k in cfg.pattern:
            self._where.append((k, seen[k]))
            seen[k] += 1

    def __len__(self):
        return len(self._where)

    def __getitem__(self, l):
        kind, n = self._where[range(len(self))[l]]
        key = {MAMBA: "mamba", ATTN: "attn", EXPERTS: "moe"}[kind]
        # the index as an operand: one program a leaf shape, not one an
        # index
        tree = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.int32(n), 0, keepdims=False), self._params[key])
        if kind == EXPERTS:
            held = self._cfg.n_held
            tree.update({name: jax.lax.dynamic_slice_in_dim(
                a, jnp.int32(n * held), held, 0)
                for name, a in self._params["experts"].items()})
        return tree

    def __iter__(self):
        return (self[l] for l in range(len(self)))


def layer_trees(cfg: NemotronHConfig, params: Dict[str, Any]
                ) -> Dict[str, Any]:
    """The stacked tree -> one tree a layer, in layer order, for whoever
    walks the layers one by one (the benchmark's reference): "layers" is
    a sequence whose items are made when taken; an expert layer's tree
    holds its own experts' "up" and "down" [held, F, H]."""
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": _Layers(cfg, params)}


def storage_dtypes(cfg: NemotronHConfig) -> Dict[str, Any]:
    """The type each leaf is stored in: as `init_params` makes it (the
    tick's programs use every leaf as stored)."""
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(lambda s: s.dtype, shapes)


# --------------------------------------------------------------------- layers

def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def moe_block(cfg: NemotronHConfig, layer, y, valid=None,
              impl: Optional[str] = None, experts=None, base=0):
    """y: [T, H] normalised -> (the expert layer's output [T, H]: the
    shared expert plus the held experts' part of the routed sum; the
    assignments of `valid` rows landed on each held expert [n_held]
    int32). One routing group of all the experts. `experts`: {"up",
    "down"} stacks of which [base, base + n_held) are this layer's
    (default: the layer's own "up" and "down"). `impl` is the forward's;
    a caller with no engine (a check of one block) leaves it out and
    gets `ops/moe.platform_impl()`."""
    lo, hi = cfg.held
    with jax.named_scope("moe_router"):
        w, idx = sigmoid_group_routing(
            y, layer["router"], layer["router_bias"], n_group=1,
            topk_group=1, top_k=cfg.moe_top_k, scale=cfg.route_scale,
            normalize=cfg.route_norm)
        gates, took, counts = held_gates(idx, w, lo, hi, valid)
    with jax.named_scope("moe_shared"):
        mid = relu2((y @ layer["shared_up"]).astype(jnp.float32))
        out = mid.astype(cfg.dtype) @ layer["shared_down"]
    with jax.named_scope("moe_experts"):
        ex = experts or layer
        routed = held_experts_ffn(y, gates, took, (ex["up"],), ex["down"],
                                  act="relu2", picks=cfg.moe_top_k,
                                  impl=impl or platform_impl(), base=base)
    return out + routed.astype(out.dtype), counts


def attention_mixer(cfg: NemotronHConfig, layer, u: jax.Array, attend, gi):
    """u: [T, H] normalised -> (the mixer's output, this tick's K rows
    and V rows [T, kv heads, d]). No positional encoding."""
    t = u.shape[0]
    q = (u @ layer["wq"]).reshape(t, cfg.n_heads, cfg.head_dim)
    k = (u @ layer["wk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    v = (u @ layer["wv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    o = attend(q, k, v, 0, gi, None)
    return o.reshape(t, -1).astype(cfg.dtype) @ layer["wo"], k, v


def ragged_forward(cfg: NemotronHConfig, params: Dict[str, Any],
                   tokens: jax.Array, slot_ids: jax.Array,
                   positions: jax.Array, valid: jax.Array,
                   start: jax.Array, last_idx: jax.Array,
                   k_pages, v_pages, page_tables,
                   ctx_pages: int = -1, lora=None, lora_idx=None,
                   impl: str = "gather", mesh=None,
                   kv_kind: str = "f32", k_scales=None, v_scales=None):
    """The unified ragged tick, with the contract of
    `llama_infer.ragged_forward` for a model of this family: `k_pages`
    and `v_pages` are TUPLES of one entry a cache group in
    `cache_groups`' order: (K pool, the Mamba layers' conv inputs
    [layers, B, 3 x conv_dim]) and (V pool, their scan state [layers, B,
    H, P, N] float32); `page_tables` the page group's, [B, max_pages]. A
    row whose `start` is 0 begins from zero state. Returns (last-token
    logits per slot [B, V] float32, the k tuple, the v tuple, expert
    counts [expert layers, n_held] int32), the state of the slots that
    had tokens advanced to their runs' ends."""
    refuse("NemotronH", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    (pool_k, conv), (pool_v, scan) = k_pages, v_pages
    t = tokens.shape[0]
    marks = ssm.segment_marks(slot_ids, positions, valid, start, last_idx)
    tick = (slot_ids, valid, last_idx)
    attend = attend_fn(impl, ((pool_k, pool_v),), (page_tables,), slot_ids,
                       positions, valid, start, ctx_pages, merged_rows=True)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    units = cfg.units
    kv_shape = (t, cfg.n_kv_heads, cfg.head_dim)

    def with_attention(x, a_idx):
        layer = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, a_idx, 0, False),
            params["attn"])
        with jax.named_scope("attn"):
            out, k, v = attention_mixer(
                cfg, layer, rms_norm(x, layer["ln"], cfg.norm_eps), attend,
                a_idx)
        return x + out, k.astype(cfg.dtype), v.astype(cfg.dtype)

    def without(x, a_idx):
        zero = jnp.zeros(kv_shape, cfg.dtype)
        return x, zero, zero

    def unit(carry, step):
        x, conv, scan = carry
        mamba, moe, i, a_idx, has_attn = step
        with jax.named_scope("mamba2"):
            out, conv, scan = mamba_mixer(
                cfg, mamba, rms_norm(x, mamba["ln"], cfg.norm_eps), marks,
                tick, conv, scan, i, impl)
        x = x + out
        x, k, v = jax.lax.cond(has_attn, with_attention, without, x, a_idx)
        with jax.named_scope("mlp"):
            out, counts = moe_block(
                cfg, moe, rms_norm(x, moe["ln"], cfg.norm_eps), valid, impl,
                experts=params["experts"], base=i * cfg.n_held)
        return (x + out, conv, scan), (k, v, counts)

    starred = [n for n, (_, a, _) in enumerate(units) if a is not None]
    a_idx = np.cumsum([a is not None for _, a, _ in units]) - 1
    (x, conv, scan), (ks, vs, counts) = jax.lax.scan(
        unit, (x, conv, scan),
        (params["mamba"], params["moe"],
         jnp.arange(len(units), dtype=jnp.int32),
         jnp.asarray(np.maximum(a_idx, 0), jnp.int32),
         jnp.asarray([a is not None for _, a, _ in units])))
    # the tick's K and V rows go into the pool once, after the stack: one
    # scatter of single rows a pool (scope `kv_write`)
    own = page_tables[slot_ids]
    at = np.asarray(starred)                         # static
    pool_k = scatter_rows(pool_k, ks[at], own, positions, valid)
    pool_v = scatter_rows(pool_v, vs[at], own, positions, valid)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.dot(x[last_idx], params["lm_head"],
                         preferred_element_type=jnp.float32)
    return logits, (pool_k, conv), (pool_v, scan), counts


decode_step = one_token_tick(ragged_forward)


# what the dispatch span carries besides the usual counts: `ssm_tokens`
# and `ssm_rows`
span_counts = state_span_counts
