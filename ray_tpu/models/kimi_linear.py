"""Kimi Linear (moonshotai, `model_type` "kimi_linear": the
Kimi-Linear-48B-A3B layout; arXiv:2510.26692) for serving: Kimi Delta
Attention (KDA) layers whose state is kept a SLOT beside ONE latent page
group that only a quarter of the layers write, latent attention (MLA)
with NO rotation, a leading dense layer, then expert layers with sigmoid
routing, a shared expert and the routed experts HELD HERE.

The model, for every layer l:

    h <- h + Mixer_l(RMSNorm(h));  h <- h + FF_l(RMSNorm(h))

and after the last RMSNorm and the untied head. RMSNorm with a weight,
eps 1e-5, in float32. No bias in any linear map. A mixer of two kinds
(`kda_layers` / `full_attn_layers`, 1-based in the published file):

- `K`, KDA (H = 32 heads, K = V = 128). With x [T, 2304] normalised:
  q, k, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v)):
  causal depthwise convs of 4 taps, no bias, along each sequence's own
  tokens; heads [T, 32, 128]. q <- q / |q|, k <- k / |k| a head (eps
  1e-6 under the root), q <- q 128^-1/2. The decay, a CHANNEL:
  g_t = -exp(A_log[h]) softplus((x W_f1 W_f2)_t + dt_bias), W_f1
  2304 x 128, W_f2 128 x 4096; beta_t = sigmoid(x W_beta) [T, 32]; all
  float32. The recurrence a head, S [128, 128] float32, zero at a
  sequence's first token:
      S <- Diag(e^{g_t}) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
      o_t = S^T q_t
  out = (RMSNorm_head(o_t) * sigmoid((x W_g1 W_g2)_t)) W_o: the norm
  over each head's 128 values with ONE weight [128].
  `ops/kda_scan.py` has the chunked form and the kernel.
- `M`, MLA, unroped (32 heads). q = x W_q [T, 32, 192] = [q_nope 128 |
  q_pe 64]; [c | k_pe] = x W_kva (512 + 64); c <- RMSNorm(c); [k_nope_h
  | v_h] = c W_kvb; the key of head h is [k_nope_h | k_pe], k_pe shared
  by the heads and NOT rotated, nor q_pe (`mla_use_nope`); scores
  q . k / sqrt(192), causal over the whole context, softmax float32; out
  = concat_h(P v_h) W_o. The cache row is [c | k_pe]; the program runs
  the ABSORBED form of `ops/mla_attention.py` (W_kvb's key half folded
  into q, its value half applied after the softmax).
- Feed-forward. Layer 1: SwiGLU at 9216. The others: s = sigmoid(x W_r)
  over all 256 in float32; picks = the 8 largest of s + b
  (`e_score_correction_bias`; one group of which one stays limits
  nothing); weights = the picked s over their sum, times 2.446; out =
  sum_picks w_e SwiGLU_e(x) + SwiGLU_shared(x) at width 1024. This chip
  computes the picks that fall on `experts_held` and the shared expert;
  what absent experts would add is left out and the partial sum goes on.

How it runs here:

- The cache is two GROUPS (`cache_groups`): `latent` FIRST (the MLA
  layers' rows, whole contexts: the engine's `slot.pages`), then
  `state`, the KDA layers' conv inputs (the last 3 of 3 x 4096 channels,
  bfloat16) and recurrent state ([32, 128, 128] float32: 2 MB) a SLOT a
  layer. The forwards take (latent pool, conv inputs) in `k_pages` and
  (None, recurrent state) in `v_pages`, and ONE page table. The latent
  pool is indexed by a layer's place in its GROUP.
- The stack is UNITS of some KDA layers and one MLA layer (K K K M six
  times, then K K M), each kind's layers stacked along a leading axis,
  and the forward ONE `lax.scan` over the units with a loop over the
  unit's KDA layers inside, so that a tick's program holds each mixer's
  body once and the feed-forward's twice, not 27 times. The dense
  feed-forward of the first layer is a `lax.cond` on the layer's index.
  The held experts of every expert layer lie in ONE array a projection
  [layers x held, ...] that the grouped kernels take whole with the
  layer's first expert as an index (`ops/moe.held_experts_ffn`, `base`).
- W_q, W_k, W_v of a KDA layer are ONE matrix [2304, 3 x 4096] and its
  three convs one conv over 12,288 channels; W_f1 and W_g1 one matrix
  [2304, 256]. W_kvb is stored split into its key part `wkb` and value
  part `wvb`. The sums are the published ones.

Departures from the published code: matrices are stored transposed
([in, out]); weights are normal(0, 1/fan_in) from the seed, A_log =
log U(1, 16) a head, dt_bias by Mamba's inverse-softplus draw of a step
log-uniform in [`time_step_min`, `time_step_max`], the selection bias
normal(0, 0.05); `head_dim` 72, `rope_theta` and `rope_scaling` are keys
no published layer reads; the multi-token-prediction module does not
exist (`num_nextn_predict_layers` 0). Weights are created and stored in
`param_dtype` (bfloat16) and used as stored; norm weights, the conv's
taps, dt_bias, A_log and the selection bias are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kda_scan
from ..ops import selective_scan as ssm
from ..ops.mla_attention import (MLA_Q_MOST, latent_row_width,
                                 mla_work_counts, scatter_latent)
from ..ops.moe import (held_experts_ffn, held_gates, platform_impl,
                       sigmoid_group_routing)
from .cache_row import CacheGroup, CacheRow, StateRow
from .llama import rms_norm
from .paged_common import (latent_attend_fn, one_token_tick, refuse,
                           state_span_counts, swiglu)

KDA, MLA = "K", "M"
PUBLISHED_KDA = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
                 23, 25, 26)
PUBLISHED_MLA = (4, 8, 12, 16, 20, 24, 27)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840         # rows of the vocabulary held here
    hidden: int = 2304
    kda_layers: Tuple[int, ...] = PUBLISHED_KDA      # 1-based, as published
    full_attn_layers: Tuple[int, ...] = PUBLISHED_MLA
    first_k_dense: int = 1
    ffn: int = 9216                  # the dense layer's SwiGLU width
    n_heads: int = 32                # the MLA layers'
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64       # carried unrotated (`mla_use_nope`)
    v_head_dim: int = 128
    kda_heads: int = 32              # `linear_attn_config.num_heads`
    kda_head_dim: int = 128          # `linear_attn_config.head_dim`
    d_conv: int = 4                  # `short_conv_kernel_size`
    gate_rank: int = 128             # the decay's and the gate's low rank
    moe_ffn: int = 1024              # `moe_intermediate_size`
    n_routed_experts: int = 256      # the router's width, as published
    # the routed experts this chip holds, [lo, hi): None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    n_shared_experts: int = 1
    moe_top_k: int = 8               # `num_experts_per_token`
    route_scale: float = 2.446       # `routed_scaling_factor`
    route_norm: bool = True          # `moe_renormalize`
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    max_seq: int = 1048576
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    @property
    def n_layers(self) -> int:
        return len(self.kda_layers) + len(self.full_attn_layers)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, in order (0-based)."""
        kda = set(self.kda_layers)
        return tuple(KDA if l + 1 in kda else MLA
                     for l in range(self.n_layers))

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    @property
    def units(self) -> Tuple[Tuple[int, int], ...]:
        """The stack as (first layer, KDA layers) of each unit of some
        KDA layers and the MLA layer behind them, in order."""
        out, l = [], 0
        for m in re.finditer(r"K*M", "".join(self.kinds)):
            out.append((l, len(m.group()) - 1))
            l += len(m.group())
        return tuple(out)

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token writes to the cache in one MLA layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

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

    def mixer_params(self, kind: str) -> int:
        """Every parameter of one mixer of `kind` (39,514,272 a KDA
        layer's, 29,114,880 an MLA layer's at the published widths)."""
        h, e, r = self.hidden, self.kda_width, self.gate_rank
        if kind == KDA:
            return (3 * h * e + e * h + 2 * (h * r + r * e)
                    + h * self.kda_heads + 3 * e * self.d_conv
                    + self.kda_heads + e + self.kda_head_dim)
        nh = self.n_heads
        return (h * nh * self.qk_head_dim + h * self.latent_width
                + self.kv_lora_rank + self.kv_lora_rank * nh
                * (self.qk_nope_head_dim + self.v_head_dim)
                + nh * self.v_head_dim * h)

    def num_params(self) -> int:
        """Every parameter held, leaf for leaf: the embedding, the
        untied head, the final norm, each layer's two norms, mixer and
        feed-forward (49,122,681,728 whole; 4,296,057,728 with 16 of 256
        experts and an eighth of the vocabulary)."""
        h = self.hidden
        expert = 3 * h * self.moe_ffn
        moe = (h * self.n_routed_experts + self.n_routed_experts
               + (self.n_shared_experts + self.n_held) * expert)
        return (2 * self.vocab_size * h + h + self.n_layers * 2 * h
                + sum(self.mixer_params(k) for k in self.kinds)
                + self.first_k_dense * 3 * h * self.ffn
                + self.n_moe_layers * moe)

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes (see `DeepseekV3Config`):
        matrix products a token through the stack (an expert layer's
        routed part at the share of a token's picks that lands here; a
        KDA layer's projections and the recurrence's 6 K V a head), the
        head's, the absorbed attention's per kept (query, key) pair over
        the MLA layers, and the weights' bytes."""
        h, e, r, nh = (self.hidden, self.kda_width, self.gate_rank,
                       self.n_heads)
        kda = 2 * (3 * h * e + e * h + 2 * (h * r + r * e)
                   + h * self.kda_heads) \
            + 6 * self.kda_heads * self.kda_head_dim ** 2
        mla = 2 * (h * nh * self.qk_head_dim + h * self.latent_width
                   + nh * self.qk_nope_head_dim * self.kv_lora_rank
                   + nh * self.kv_lora_rank * self.v_head_dim
                   + nh * self.v_head_dim * h)
        expert = 3 * 2 * h * self.moe_ffn
        here = self.moe_top_k * self.n_held / self.n_routed_experts
        moe = (2 * h * self.n_routed_experts
               + (self.n_shared_experts + here) * expert)
        n_kda, n_mla = len(self.kda_layers), len(self.full_attn_layers)
        return {
            "gemm_flops_per_token": (
                n_kda * kda + n_mla * mla
                + self.first_k_dense * 3 * 2 * h * self.ffn
                + self.n_moe_layers * moe),
            "head_flops": 2 * h * self.vocab_size,
            "attn_flops_per_pair": 2 * n_mla * nh * (
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
        both = sorted(self.kda_layers + self.full_attn_layers)
        if both != list(range(1, len(both) + 1)):
            raise ValueError("kda_layers and full_attn_layers together are "
                             "the layers 1..n, each once")
        if not re.fullmatch(r"(K*M)+", "".join(self.kinds)):
            raise ValueError(
                f"layers {''.join(self.kinds)!r}: the stack is written for "
                "units of KDA layers and the MLA layer behind them, and "
                "ends on an MLA layer, as the published lists do")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense outside the stack")
        if self.n_heads > MLA_Q_MOST:
            raise ValueError(
                f"{self.n_heads} MLA heads: `work_counts` counts the latent "
                f"kernel's items at {MLA_Q_MOST} tokens, which it takes at "
                f"{MLA_Q_MOST} heads and fewer")


PRESETS: Dict[str, KimiLinearConfig] = {
    # the CPU tests' size: a unit of two KDA layers and one of one, the
    # first layer dense, 8 experts of which 4 are held, 3 picks
    "tiny": KimiLinearConfig(
        vocab_size=256, hidden=64, kda_layers=(1, 2, 4),
        full_attn_layers=(3, 5), ffn=96, n_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, kda_heads=2,
        kda_head_dim=16, gate_rank=8, moe_ffn=32, n_routed_experts=8,
        experts_held=(0, 4), moe_top_k=3, max_seq=256),
}


def config(name_or_cfg, **overrides) -> KimiLinearConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def cache_groups(cfg: KimiLinearConfig, impl: str, kv_kind: str = "f32"
                 ) -> Tuple[CacheGroup, ...]:
    """`latent` (the MLA layers' rows [c | k_pe], whole contexts: the
    engine's `slot.pages`), then the KDA layers' `state`: a slot's last
    3 conv inputs over the 3 x 4096 channels of q, k and v, and its
    recurrent state [H, K, V] float32, a layer."""
    if kv_kind != "f32":
        raise ValueError(KIMI_LINEAR_REFUSES["kv_dtype"])
    row = CacheRow(
        kind="latent", pools=1, heads=1, width=cfg.latent_width,
        padded_width=latent_row_width(cfg.kv_lora_rank,
                                      cfg.qk_rope_head_dim, impl),
        dtype=cfg.dtype, value_width=cfg.kv_lora_rank)
    state = StateRow(kind="kda", parts=(
        ("conv", ((cfg.d_conv - 1) * 3 * cfg.kda_width,), cfg.dtype),
        ("kda", (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
         jnp.float32)))
    return (CacheGroup("latent", row, cfg.layers_of(MLA)),
            CacheGroup("state", None, cfg.layers_of(KDA), state=state))


KIMI_LINEAR_REFUSES = {
    "prefix_cache": "a resume at token m needs the delta-rule state as it "
                    "stood at m; one state a slot is kept, the newest, and "
                    "no snapshot is taken at page boundaries: the cache "
                    "matches nothing (`stats()['prefix_cache']` says so)",
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo "
            "projections; latent attention has none of them, and the KDA "
            "and expert projections have no adapter path",
    "kv_dtype": "int8/fp8 KV pages keep per-(row, kv head) scales for a "
                "K pool and a V pool; the latent pool has one row that "
                "is both, a float32 state lies beside it, and there is "
                "no quantized write or read path",
    "enable_kv_offload": "the host KV tier spills and restores K and V "
                         "pages; the latent pool is one pool, and a "
                         "sequence here is also its recurrent state, "
                         "which holds no pages and has no spill",
    "mesh": "GSPMD tensor parallelism shards heads and kv heads; the "
            "latent cache has one head, and the recurrence's heads and "
            "the expert layer have no sharding and no exchange across "
            "chips here",
    "mesh_shape": "the explicit-tp shard_map programs are the dense "
                  "family's (Megatron layout of wq/wk/wv/wo)",
    "checkpoint": "no checkpoint loader for this family's tree yet",
    "session_shipping": "session and prefix export/import move K and V "
                        "pages; the latent pool is one pool, a sequence "
                        "here is also its recurrent state, and nothing "
                        "snapshots or ships that",
}


# --------------------------------------------------------------------- params

def _shapes(cfg: KimiLinearConfig) -> Dict[str, Dict[str, tuple]]:
    """kind -> leaf -> (shape a layer, how it is drawn): a fan-in for a
    matrix stored in `param_dtype`, or the name of a float32 rule."""
    h, e, r, nh = cfg.hidden, cfg.kda_width, cfg.gate_rank, cfg.n_heads
    c = cfg.kv_lora_rank
    return {
        "kda": {
            "ln": ((h,), "ones"), "wqkv": ((h, 3 * e), h),
            "conv_w": ((cfg.d_conv, 3 * e), "taps"),
            "w_down": ((h, 2 * r), h), "f_up": ((r, e), r),
            "g_up": ((r, e), r), "w_beta": ((h, cfg.kda_heads), h),
            "a_log": ((cfg.kda_heads,), "a_log"), "dt_bias": ((e,), "dt"),
            "norm": ((cfg.kda_head_dim,), "ones"), "wo": ((e, h), e)},
        "mla": {
            "ln": ((h,), "ones"), "wq": ((h, nh * cfg.qk_head_dim), h),
            "wkva": ((h, cfg.latent_width), h), "kv_norm": ((c,), "ones"),
            "wkb": ((c, nh, cfg.qk_nope_head_dim), c),
            "wvb": ((c, nh, cfg.v_head_dim), c),
            "wo": ((nh * cfg.v_head_dim, h), nh * cfg.v_head_dim)},
        "dense": {
            "ln": ((h,), "ones"), "wg": ((h, cfg.ffn), h),
            "wi": ((h, cfg.ffn), h), "wd": ((cfg.ffn, h), cfg.ffn)},
        "moe": {
            "ln": ((h,), "ones"),
            "router": ((h, cfg.n_routed_experts), h),
            "router_bias": ((cfg.n_routed_experts,), "bias"),
            "shared_wg": ((h, cfg.n_shared_experts * cfg.moe_ffn), h),
            "shared_wi": ((h, cfg.n_shared_experts * cfg.moe_ffn), h),
            "shared_wd": ((cfg.n_shared_experts * cfg.moe_ffn, h),
                          cfg.n_shared_experts * cfg.moe_ffn)},
    }


def _counts(cfg: KimiLinearConfig) -> Dict[str, int]:
    return {"kda": len(cfg.kda_layers), "mla": len(cfg.full_attn_layers),
            "dense": cfg.first_k_dense, "moe": cfg.n_moe_layers}


def init_params(cfg: KimiLinearConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters as the forwards take them and the engine keeps
    them: {"embed", "lm_head", "final_norm", "kda", "mla", "dense",
    "moe": each kind's leaves stacked along a leading axis over its
    layers, "experts": {"wg", "wi" [expert layers x held, H, F], "wd"
    [expert layers x held, F, H]}}. A matrix is drawn in float32, a
    layer at a time, and stored in `param_dtype`; the experts' stacks
    are filled in place, a layer's held experts at a time."""
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
            return jnp.log(jax.random.uniform(nkey(), shape, f32, 1.0, 16.0))
        if how == "dt":
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.exp(jax.random.uniform(nkey(), shape, f32) * (hi - lo)
                         + lo)
            return dt + jnp.log(-jnp.expm1(-dt))   # softplus(dt_bias) = dt
        scale = {"taps": 1.0 / math.sqrt(cfg.d_conv),
                 # a tenth of the scores' spread: enough to change picks
                 "bias": 0.05}[how]
        return scale * jax.random.normal(nkey(), shape, f32)

    counts = _counts(cfg)
    out: Dict[str, Any] = {
        kind: {name: jnp.stack([leaf(shape, how)
                                for _ in range(counts[kind])])
               if counts[kind] else jnp.zeros((0,) + shape, f32 if
                                              isinstance(how, str) else pd)
               for name, (shape, how) in leaves.items()}
        for kind, leaves in _shapes(cfg).items()}
    h, f, held = cfg.hidden, cfg.moe_ffn, cfg.n_held

    @functools.partial(jax.jit, static_argnums=1)
    def draw(k, shape, fan_in):
        return (jax.random.normal(k, shape, f32)
                * jax.lax.rsqrt(fan_in)).astype(pd)

    fill = jax.jit(
        lambda buf, blk, i: jax.lax.dynamic_update_slice_in_dim(
            buf, blk, i * held, 0), donate_argnums=0)
    experts = {}
    for name, shape, fan_in in (("wg", (h, f), h), ("wi", (h, f), h),
                                ("wd", (f, h), f)):
        buf = jnp.zeros((cfg.n_moe_layers * held,) + shape, pd)
        for i in range(cfg.n_moe_layers):
            buf = fill(buf, draw(nkey(), (held,) + shape,
                                 jnp.float32(fan_in)), jnp.int32(i))
        experts[name] = buf
    out["experts"] = experts
    out["embed"] = dense((cfg.vocab_size, h), h)
    out["lm_head"] = dense((h, cfg.vocab_size), h)
    out["final_norm"] = jnp.ones((h,), f32)
    return out


class _Layers:
    """A stacked tree's layers as a sequence of one tree a layer ({"kind",
    "mixer", "ff"}), each cut out of its stack when it is asked for."""

    def __init__(self, cfg, params):
        self._cfg, self._params = cfg, params
        seen = {KDA: 0, MLA: 0}
        self._where = []
        for k in cfg.kinds:
            self._where.append((k, seen[k]))
            seen[k] += 1

    def __len__(self):
        return len(self._where)

    def __getitem__(self, l):
        l = range(len(self))[l]
        kind, n = self._where[l]
        cfg = self._cfg
        # the index as an operand: one program a leaf shape, not one an
        # index
        cut = lambda tree, i: jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.int32(i), 0, keepdims=False), tree)
        mixer = cut(self._params["kda" if kind == KDA else "mla"], n)
        if l < cfg.first_k_dense:
            ff = cut(self._params["dense"], l)
        else:
            i = l - cfg.first_k_dense
            ff = cut(self._params["moe"], i)
            ff.update({name: jax.lax.dynamic_slice_in_dim(
                a, jnp.int32(i * cfg.n_held), cfg.n_held, 0)
                for name, a in self._params["experts"].items()})
        return {"kind": kind, "mixer": mixer, "ff": ff}

    def __iter__(self):
        return (self[l] for l in range(len(self)))


def layer_trees(cfg: KimiLinearConfig, params: Dict[str, Any]
                ) -> Dict[str, Any]:
    """The stacked tree -> one tree a layer, in layer order, for whoever
    walks the layers one by one (the benchmark's reference): "layers" is
    a sequence whose items are made when taken; an expert layer's "ff"
    holds its own experts' "wg", "wi" and "wd" [held, ...]."""
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"],
            "layers": _Layers(cfg, params)}


def storage_dtypes(cfg: KimiLinearConfig) -> Dict[str, Any]:
    """The type each leaf is stored in: as `init_params` makes it (the
    tick's programs use every leaf as stored)."""
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(lambda s: s.dtype, shapes)


# --------------------------------------------------------------------- layers

def l2_normalise(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def kda_gates(cfg: KimiLinearConfig, layer, u: jax.Array):
    """u: [T, H] normalised -> (the decay's log g [T, heads, K] float32,
    <= 0; beta [T, heads] float32; the output gate's low-rank input
    [T, gate_rank])."""
    f32 = jnp.float32
    t = u.shape[0]
    f_low, g_low = jnp.split(u @ layer["w_down"], 2, axis=-1)
    pre = (f_low @ layer["f_up"]).astype(f32) + layer["dt_bias"]
    g = -jnp.exp(layer["a_log"])[None, :, None] * jax.nn.softplus(
        pre).reshape(t, cfg.kda_heads, cfg.kda_head_dim)
    beta = jax.nn.sigmoid((u @ layer["w_beta"]).astype(f32))
    return g, beta, g_low


def gated_head_norm(cfg: KimiLinearConfig, layer, o: jax.Array,
                    g_low: jax.Array) -> jax.Array:
    """o: [T, heads, V] float32 -> RMSNorm over each head's values with
    the one weight [V], times sigmoid of the gate; `cfg.dtype` out,
    [T, heads x V]."""
    f32 = jnp.float32
    t = o.shape[0]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg.norm_eps) * layer["norm"]
    gate = jax.nn.sigmoid((g_low @ layer["g_up"]).astype(f32))
    return (o.reshape(t, -1) * gate).astype(cfg.dtype)


def kda_mixer(cfg: KimiLinearConfig, layer, u: jax.Array, marks, tick,
              conv_all: jax.Array, kda_all: jax.Array, gi, impl: str):
    """u: [T, H] normalised -> (the mixer's output [T, H], the conv
    inputs and the recurrent state with layer `gi`'s rows of this tick's
    slots replaced)."""
    slot_ids, valid, last_idx = tick
    t, b = u.shape[0], conv_all.shape[1]
    e, k = cfg.kda_width, cfg.d_conv
    heads = lambda m: m.reshape(t, cfg.kda_heads, cfg.kda_head_dim)
    qkv = u @ layer["wqkv"]
    with jax.named_scope("conv"):
        stored = jax.lax.dynamic_index_in_dim(conv_all, gi, 0, False)
        xc, conv_new = ssm.causal_conv_ragged(
            qkv, layer["conv_w"], jnp.zeros((), jnp.float32), slot_ids,
            last_idx, marks, stored.reshape(b, k - 1, 3 * e))
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, conv_new.reshape(b, -1), gi, 0)
        q, key, v = jnp.split(jax.nn.silu(xc), 3, axis=-1)
    with jax.named_scope("kda_gate"):
        g, beta, g_low = kda_gates(cfg, layer, u)
        q = l2_normalise(heads(q)) * cfg.kda_head_dim ** -0.5
        key = l2_normalise(heads(key))
    with jax.named_scope("kda_scan"):
        o, kda_all = kda_scan.kda_ragged_scan(
            q, key, heads(v), g, beta, marks, slot_ids, valid, last_idx,
            kda_all, gi, impl=impl)
    with jax.named_scope("out_gate"):
        o = gated_head_norm(cfg, layer, o, g_low)
    return o @ layer["wo"], conv_all, kda_all


def mla_project(cfg: KimiLinearConfig, layer, y: jax.Array):
    """y: [T, H] normalised -> (absorbed queries [T, heads,
    latent_width], the tick's cache rows [T, latent_width]), both in the
    compute type. No low-rank query and no rotation."""
    dt = cfg.dtype
    t = y.shape[0]
    q = (y @ layer["wq"]).reshape(t, cfg.n_heads, cfg.qk_head_dim)
    kv = y @ layer["wkva"]
    c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], layer["kv_norm"],
                    cfg.norm_eps)
    q_lat = jnp.einsum("thn,chn->thc", q[..., :cfg.qk_nope_head_dim],
                       layer["wkb"],
                       preferred_element_type=jnp.float32).astype(dt)
    return (jnp.concatenate([q_lat, q[..., cfg.qk_nope_head_dim:]], -1),
            jnp.concatenate([c_kv, kv[:, cfg.kv_lora_rank:]], -1).astype(dt))


def mla_output(cfg: KimiLinearConfig, layer, o_lat: jax.Array) -> jax.Array:
    """o_lat: [T, heads, kv_lora_rank] -> the mixer's output [T, H]."""
    o = jnp.einsum("thc,chv->thv", o_lat, layer["wvb"],
                   preferred_element_type=jnp.float32).astype(cfg.dtype)
    return o.reshape(o.shape[0], -1) @ layer["wo"]


def moe_block(cfg: KimiLinearConfig, layer, y, valid=None,
              impl: Optional[str] = None, experts=None, base=0):
    """y: [T, H] normalised -> (the expert layer's output [T, H]: the
    shared expert plus the held experts' part of the routed sum; the
    assignments of `valid` rows landed on each held expert [n_held]
    int32). One routing group of all the experts. `experts`: {"wg",
    "wi", "wd"} stacks of which [base, base + n_held) are this layer's
    (default: the layer's own). `impl` is the forward's; a caller with
    no engine (a check of one block) leaves it out and gets
    `ops/moe.platform_impl()`."""
    lo, hi = cfg.held
    impl = impl or platform_impl()
    with jax.named_scope("moe_router"):
        w, idx = sigmoid_group_routing(
            y, layer["router"], layer["router_bias"], n_group=1,
            topk_group=1, top_k=cfg.moe_top_k, scale=cfg.route_scale,
            normalize=cfg.route_norm)
        gates, took, counts = held_gates(idx, w, lo, hi, valid)
    with jax.named_scope("moe_shared"):
        out = swiglu({"wg": layer["shared_wg"], "wi": layer["shared_wi"],
                      "wd": layer["shared_wd"]}, y)
    with jax.named_scope("moe_experts"):
        ex = experts or layer
        if impl == "gather":
            # the reference product (`lax.ragged_dot`) takes ONE layer's
            # experts, cut out of the stack here (a copy; the kernels
            # take the stack), in the rows' type: its TPU lowering
            # refuses float32 rows against weights stored in bfloat16,
            # which a check in float32 activations hands it
            ex = {n: jax.lax.dynamic_slice_in_dim(
                a, base, cfg.n_held, 0).astype(y.dtype)
                for n, a in ex.items() if n in ("wg", "wi", "wd")}
            base = 0
        routed = held_experts_ffn(y, gates, took, (ex["wg"], ex["wi"]),
                                  ex["wd"], act="swiglu",
                                  picks=cfg.moe_top_k, impl=impl, base=base)
    return out + routed.astype(out.dtype), counts


# ------------------------------------------------------------------- forwards

def ragged_forward(cfg: KimiLinearConfig, params: Dict[str, Any],
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
    `cache_groups`' order: (the latent pool [MLA layers, pages, page, 1,
    row], the KDA layers' conv inputs [layers, B, 3 x 3 x 4096]) and
    (None: a latent group has one pool; their recurrent state [layers,
    B, H, K, V] float32); `page_tables` the latent group's, [B,
    max_pages]. A row whose `start` is 0 begins from zero state. Returns
    (last-token logits per slot [B, V] float32, the k tuple, the v
    tuple, expert counts [expert layers, n_held] int32), the state of
    the slots that had tokens advanced to their runs' ends."""
    refuse("Kimi Linear", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    (pool, conv), (_, state) = k_pages, v_pages
    marks = ssm.segment_marks(slot_ids, positions, valid, start, last_idx)
    tick = (slot_ids, valid, last_idx)
    attend = latent_attend_fn(
        impl, pool, page_tables, slot_ids, positions, valid, start,
        ctx_pages, heads=cfg.n_heads, width=cfg.latent_width,
        dv=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    at = lambda tree, i: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)

    def feed_forward(x, counts, l):
        """Layer l's (a traced index): the dense SwiGLU of the leading
        layers, or the expert layer and its row of `counts`."""
        def dense(x, counts):
            w = at(params["dense"], jnp.minimum(l, cfg.first_k_dense - 1))
            return x + swiglu(w, rms_norm(x, w["ln"], cfg.norm_eps)), counts

        def experts(x, counts):
            i = jnp.maximum(l - cfg.first_k_dense, 0)
            w = at(params["moe"], i)
            out, landed = moe_block(
                cfg, w, rms_norm(x, w["ln"], cfg.norm_eps), valid, impl,
                experts=params["experts"], base=i * cfg.n_held)
            return x + out, jax.lax.dynamic_update_index_in_dim(
                counts, landed, i, 0)

        with jax.named_scope("mlp"):
            if not cfg.first_k_dense:
                return experts(x, counts)
            if not cfg.n_moe_layers:
                return dense(x, counts)
            return jax.lax.cond(l < cfg.first_k_dense, dense, experts, x,
                                counts)

    def unit(carry, step):
        first_layer, n_kda, kda_base, u = step

        def kda_layer(j, carry):
            x, conv, state, counts = carry
            w = at(params["kda"], kda_base + j)
            with jax.named_scope("attn"), jax.named_scope("kda"):
                out, conv, state = kda_mixer(
                    cfg, w, rms_norm(x, w["ln"], cfg.norm_eps), marks, tick,
                    conv, state, kda_base + j, impl)
            x, counts = feed_forward(x + out, counts, first_layer + j)
            return x, conv, state, counts

        x, conv, state, counts = jax.lax.fori_loop(0, n_kda, kda_layer,
                                                   carry)
        w = at(params["mla"], u)
        with jax.named_scope("attn"), jax.named_scope("mla"):
            q, rows = mla_project(cfg, w, rms_norm(x, w["ln"], cfg.norm_eps))
            x = x + mla_output(cfg, w, attend(q, rows, u))
        x, counts = feed_forward(x, counts, first_layer + n_kda)
        return (x, conv, state, counts), rows

    units = np.asarray(cfg.units, np.int32)                   # static
    counts = jnp.zeros((cfg.n_moe_layers, cfg.n_held), jnp.int32)
    (x, conv, state, counts), rows = jax.lax.scan(
        unit, (x, conv, state, counts),
        (jnp.asarray(units[:, 0]), jnp.asarray(units[:, 1]),
         jnp.asarray(np.cumsum(units[:, 1]) - units[:, 1]),
         jnp.arange(len(units), dtype=jnp.int32)))
    # the tick's latent rows go into the pool once, after the stack
    pool = scatter_latent(pool, rows, page_tables[slot_ids], positions,
                          valid)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.dot(x[last_idx], params["lm_head"],
                         preferred_element_type=jnp.float32)
    return logits, (pool, conv), (None, state), counts


decode_step = one_token_tick(ragged_forward)


def work_counts(segs, t, page_size, n_ctx_pages, geometry):
    """`ModelFamily.work_counts`: the latent kernel's (live items, KV
    blocks) for a tick, at the 32 tokens an item takes with this
    family's 32 heads or fewer (`KimiLinearConfig.__post_init__`)."""
    del geometry
    return mla_work_counts(segs, t, page_size, n_ctx_pages,
                           heads=MLA_Q_MOST)


# what the dispatch span carries besides the usual counts: `ssm_tokens`
# (the tokens through each KDA layer's scan) and `ssm_rows`
span_counts = state_span_counts
