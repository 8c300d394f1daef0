"""Granite 4.0-H dense (ibm-granite, `model_type` "granitemoehybrid" with
`num_local_experts` 0: the granite-4.0-h-micro layout) for serving:
Mamba-2 (SSD) layers of ONE group whose state is kept a SLOT beside one
small page group, a few attention layers of 32 query heads over 8 K/V
heads of 64 with no positional encoding, a SwiGLU block in EVERY layer,
and the four muP multipliers.

The model, for layer l of kind `layer_types[l]`:

    h_0 = embedding_multiplier E[token]
    u = RMSNorm(h);  h <- h + residual_multiplier Mixer_l(u)
    u = RMSNorm(h);  h <- h + residual_multiplier SwiGLU_l(u)
    logits = RMSNorm(h) E^T / logits_scaling        (the head is TIED)

RMSNorm with a weight, eps 1e-5, in float32. No bias in any linear map;
the conv has one. SwiGLU: (silu(u W_g) * (u W_i)) W_d, hidden -> `ffn`
-> hidden (`shared_intermediate_size`; there is no routed expert). The
mixers:

- `mamba` (H = 64 heads of P = 64, d_inner 4096; G = 1 group, N = 128;
  K = 4 taps): `paged_common.mamba2_mixer`, the recurrence of
  `ops/ssd_scan.py` with B and C shared by ALL heads, the gate before
  the norm and the norm over all d_inner channels, Delta not clamped.
- `attention`: q (hidden -> 32 x 64), k and v (-> 8 x 64), o; scores
  q.k x `attention_multiplier` (1/64, NOT 64^-1/2), causal over the
  whole context, no rotation and no other positional encoding.

How it runs here:

- The cache is two GROUPS (`cache_groups`): `full`, the attention
  layers' K and V, and `state`, the Mamba layers' conv inputs (the last
  3 of 4352 channels, bfloat16) and scan state ([64, 64, 128] float32: 2
  MB) a SLOT a layer: 76.4 MB a slot over 36 layers. A pool row is a
  PAIR of K/V heads, [k_2j | k_2j+1], 128 lanes wide with no padding (a
  row of one 64-wide head would be padded to 128 lanes and cost twice
  the bytes); a page is [16 x 4 rows, 128], `layout` "rows", because 4
  rows are no multiple of the 8-row tile. A query head goes in 128 wide
  with zeros in the half that is not its K/V head's (`wide_queries`), so
  its scores are its own head's, and of the output it gets back, V's
  pair side by side, its own half is kept (`own_half`). The kernels
  divide scores by sqrt(128): the queries carry `attention_multiplier`
  x sqrt(128).
- The stack is UNITS of an optional attention layer and a Mamba layer
  (the published pattern is nothing else: every attention layer is
  followed by a Mamba layer), each kind's layers stacked along a leading
  axis and the forward ONE `lax.scan` over the units with a `lax.cond`
  on the attention layer, so that a tick's program holds the Mamba body
  and the attention body once each, not 40 times (the SwiGLU block
  twice: a Mamba layer's, and an attention layer's inside the cond). The
  cond takes and returns the residual stream ALONE. The scan state must
  not pass through it: a branch that hands a buffer through unchanged
  COPIES it (compiled for a v5e: 3.67 GB, ~17 ms, an attention layer).
  The state is the scan's carry and goes to `ssd_ragged_scan` whole,
  the layer as an index; the kernel aliases it and writes the live rows
  in place.

Departures from the published code: matrices are stored transposed
([in, out]) and the SwiGLU's input matrix as its two halves; weights
are normal(0, 1/fan_in) from the seed, A_log = log(1 .. H) a head, D =
1, dt_bias by Mamba's inverse-softplus draw of Delta log-uniform in
[`time_step_min`, `time_step_max`] floored at `time_step_floor`;
`rope_theta` is a key nothing reads. Weights are created and stored in
`param_dtype` (bfloat16) and used as stored; norm weights, the conv's
taps and bias, dt_bias, A_log and D are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import selective_scan as ssm
from ..ops.paged_attention import pool_head_dim
from .cache_row import CacheGroup, CacheRow, StateRow
from .llama import rms_norm
from .paged_common import (attend_fn, mamba2_mixer, one_token_tick, refuse,
                           state_span_counts, swiglu)
from .paged_common import scatter_merged_rows as scatter_rows

MAMBA, ATTN = "mamba", "attention"
PAIR = 2                 # K/V heads a pool row
# the published stack: attention at 5, 15, 25, 35 of 40
PUBLISHED_LAYERS = ((MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden: int = 2048
    layer_types: Tuple[str, ...] = PUBLISHED_LAYERS
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn: int = 8192                  # `shared_intermediate_size`
    mamba_heads: int = 64            # `mamba_n_heads`
    mamba_head_dim: int = 64         # `mamba_d_head`
    ssm_state: int = 128             # `mamba_d_state`
    n_groups: int = 1                # `mamba_n_groups`
    d_conv: int = 4                  # `mamba_d_conv`
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @property
    def units(self) -> Tuple[Tuple[Optional[int], int], ...]:
        """The stack as (attention layer or None, Mamba layer) pairs, in
        order."""
        out, before = [], None
        for l, kind in enumerate(self.layer_types):
            if kind == ATTN:
                before = l
            else:
                out.append((before, l))
                before = None
        return tuple(out)

    @property
    def d_inner(self) -> int:
        """`mamba_n_heads` x `mamba_d_head` (= `mamba_expand` x hidden at
        the published sizes)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_heads

    def mixer_params(self, kind: str) -> int:
        """Every parameter of one mixer of `kind`, without a norm."""
        h = self.hidden
        if kind == MAMBA:
            return (h * self.in_width + self.d_inner * h
                    + self.conv_dim * (self.d_conv + 1)
                    + 3 * self.mamba_heads + self.d_inner)
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        return 2 * h * q + 2 * h * kv

    def num_params(self) -> int:
        """Every parameter held, leaf for leaf: the tied embedding once,
        the final norm, each layer's mixer, SwiGLU block and two norms
        (3,191,396,096 at the published sizes)."""
        h = self.hidden
        return (self.vocab_size * h + h
                + sum(self.mixer_params(k) for k in self.layer_types)
                + self.n_layers * (3 * h * self.ffn + 2 * h))

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes (see `DeepseekV3Config`):
        matrix products a token through the stack, the head's,
        attention's per kept (query, key) pair over the attention
        layers, and the weights' bytes. The scan's own products are left
        out: a few percent of a Mamba layer's projections."""
        h = self.hidden
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        per = {MAMBA: 2 * (h * self.in_width + self.d_inner * h),
               ATTN: 2 * (2 * h * q + 2 * h * kv)}
        return {
            "gemm_flops_per_token": sum(per[k] for k in self.layer_types)
            + self.n_layers * 6 * h * self.ffn,
            "head_flops": 2 * h * self.vocab_size,
            "attn_flops_per_pair": 4 * self.n_heads * self.head_dim
            * len(self.layers_of(ATTN)),
            "weight_bytes": self.num_params() * jnp.dtype(
                self.param_dtype).itemsize,
        }

    def __post_init__(self):
        if set(self.layer_types) - {MAMBA, ATTN}:
            raise ValueError(f"layer_types {self.layer_types}: a layer is "
                             f"{MAMBA!r} or {ATTN!r}")
        if ATTN not in self.layer_types:
            raise ValueError("the engine's first cache group holds pages: "
                             "a stack needs an attention layer")
        if any(a == ATTN and b != MAMBA for a, b in zip(
                self.layer_types, self.layer_types[1:] + (None,))):
            raise ValueError(
                f"layer_types {self.layer_types}: the stack is written "
                "for units of an optional attention layer and a Mamba "
                "layer, which the published pattern is: an attention "
                "layer is followed by a Mamba layer")
        if self.mamba_heads % self.n_groups \
                or self.n_heads % self.n_kv_heads \
                or self.n_kv_heads % PAIR or self.hidden % self.n_heads:
            raise ValueError("heads must divide into their groups, and "
                             "K/V heads into pairs")


PRESETS: Dict[str, GraniteHybridConfig] = {
    # the CPU tests' size: 2 periods of a shortened pattern with both
    # kinds of layer, 8 scan heads in ONE group
    "tiny": GraniteHybridConfig(
        vocab_size=512, hidden=64,
        layer_types=(MAMBA, MAMBA, ATTN, MAMBA) * 2, n_heads=4,
        n_kv_heads=2, ffn=96, mamba_heads=8, mamba_head_dim=16,
        ssm_state=16, max_seq=256),
}


def config(name_or_cfg, **overrides) -> GraniteHybridConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def cache_groups(cfg: GraniteHybridConfig, impl: str, kv_kind: str = "f32"
                 ) -> Tuple[CacheGroup, ...]:
    """`full` (the attention layers' K and V, whole contexts: the
    engine's `slot.pages`), then the Mamba layers' `state`: a slot's last
    K - 1 conv inputs over all `conv_dim` channels and its scan state
    [H, P, N] float32, a layer. A pool row is a pair of K/V heads; a
    page is [page * kv heads / 2 rows, 2 * head_dim]."""
    if kv_kind != "f32":
        raise ValueError(GRANITE_HYBRID_REFUSES["kv_dtype"])
    width = PAIR * cfg.head_dim
    row = CacheRow(kind="kv", pools=2, heads=cfg.n_kv_heads // PAIR,
                   width=width, padded_width=pool_head_dim(width, impl),
                   dtype=cfg.dtype, layout="rows")
    state = StateRow(kind="ssd", parts=(
        ("conv", ((cfg.d_conv - 1) * cfg.conv_dim,), cfg.dtype),
        ("ssm", (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
         jnp.float32)))
    return (CacheGroup("full", row, cfg.layers_of(ATTN)),
            CacheGroup("state", None, cfg.layers_of(MAMBA), state=state))


_STATE = ("a sequence here is also its recurrent state, which holds no "
          "pages")
GRANITE_HYBRID_REFUSES = {
    "prefix_cache": "a resume at token m needs the recurrent state as it "
                    "stood at m; one state a slot is kept, the newest, and "
                    "no snapshot is taken at page boundaries: the cache "
                    "matches nothing (`stats()['prefix_cache']` says so)",
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo inside "
            "its layer scan; this family's Mamba projections have no "
            "adapter path",
    "kv_dtype": "int8/fp8 KV pages keep per-(row, kv head) scale pools "
                "beside ONE pair of pools; this family has a float32 "
                "state beside its pools and no quantized write or read "
                "path",
    "enable_kv_offload": "the host KV tier spills and restores a slot's "
                         f"pages; {_STATE} and has no spill",
    "mesh": "GSPMD tensor parallelism is the dense family's layout; the "
            "scan's heads have no sharding here",
    "mesh_shape": "the explicit-tp shard_map programs are the dense "
                  "family's (Megatron layout of wq/wk/wv/wo)",
    "checkpoint": "no checkpoint loader for this family's tree yet",
    "session_shipping": "session and prefix export/import move a slot's "
                        f"pages; {_STATE}, and nothing snapshots or ships "
                        "that",
}


# --------------------------------------------------------------------- params

def _shapes(cfg: GraniteHybridConfig) -> Dict[str, Dict[str, tuple]]:
    """kind -> leaf -> (shape a layer, how it is drawn): a fan-in for a
    matrix stored in `param_dtype`, or the name of a float32 rule."""
    h, e, hm, f = cfg.hidden, cfg.d_inner, cfg.mamba_heads, cfg.ffn
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
        "mlp": {
            "ln": ((h,), "ones"), "wg": ((h, f), h), "wi": ((h, f), h),
            "wd": ((f, h), f)},
    }


def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters as the forwards take them and the engine keeps
    them: {"embed", "final_norm", "mamba", "attn": each kind's layers
    stacked along a leading axis, a layer its mixer's leaves and "mlp",
    its SwiGLU block's}. No head: it is the embedding. A matrix is drawn
    in float32, a layer at a time, and stored in `param_dtype`."""
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
        scale = {"taps": 1.0 / math.sqrt(cfg.d_conv), "small": 0.02}[how]
        return scale * jax.random.normal(nkey(), shape, f32)

    shapes = _shapes(cfg)

    def stack(kind, count):
        one = lambda leaves: {name: leaf(shape, how)
                              for name, (shape, how) in leaves.items()}
        layers = [{**one(shapes[kind]), "mlp": one(shapes["mlp"])}
                  for _ in range(count)]
        return jax.tree.map(lambda *a: jnp.stack(a), *layers)

    return {"mamba": stack("mamba", len(cfg.layers_of(MAMBA))),
            "attn": stack("attn", len(cfg.layers_of(ATTN))),
            "embed": dense((cfg.vocab_size, cfg.hidden), cfg.hidden),
            "final_norm": jnp.ones((cfg.hidden,), f32)}


class _Layers:
    """A stacked tree's layers as a sequence of one tree a layer, each
    cut out of its stack when it is asked for (all of them at once would
    be a second copy of the weights)."""

    def __init__(self, cfg, params):
        self._params = params
        seen = {MAMBA: 0, ATTN: 0}
        self._where = []
        for kind in cfg.layer_types:
            self._where.append((kind, seen[kind]))
            seen[kind] += 1

    def __len__(self):
        return len(self._where)

    def __getitem__(self, l):
        kind, n = self._where[range(len(self))[l]]
        # the index as an operand: one program a leaf shape, not one an
        # index
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.int32(n), 0, keepdims=False),
            self._params["mamba" if kind == MAMBA else "attn"])

    def __iter__(self):
        return (self[l] for l in range(len(self)))


def layer_trees(cfg: GraniteHybridConfig, params: Dict[str, Any]
                ) -> Dict[str, Any]:
    """The stacked tree -> one tree a layer, in layer order, for whoever
    walks the layers one by one (the benchmark's reference): "layers" is
    a sequence whose items are made when taken, a layer its mixer's
    leaves and "mlp", its SwiGLU block's."""
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "layers": _Layers(cfg, params)}


def storage_dtypes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """The type each leaf is stored in: as `init_params` makes it (the
    tick's programs use every leaf as stored)."""
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(lambda s: s.dtype, shapes)


# --------------------------------------------------------------------- layers

def _second_of_pair(cfg: GraniteHybridConfig) -> np.ndarray:
    """[heads, 1] bool: is a query head's K/V head the second of its
    pool row?"""
    per = cfg.n_heads // cfg.n_kv_heads
    return (np.arange(cfg.n_heads) // per % PAIR).astype(bool)[:, None]


def wide_queries(cfg: GraniteHybridConfig, q: jax.Array) -> jax.Array:
    """q: [T, heads, d] float32 -> [T, heads, 2 d] in `cfg.dtype`: a
    head whose K/V head is the first of its pool row is [q | 0], the
    second's [0 | q], times `attention_multiplier` x sqrt(2 d) (the
    kernels divide scores by sqrt(2 d))."""
    q = (q * (cfg.attention_multiplier * math.sqrt(PAIR * cfg.head_dim))
         ).astype(cfg.dtype)
    zero = jnp.zeros_like(q)
    second = _second_of_pair(cfg)
    return jnp.concatenate([jnp.where(second, zero, q),
                            jnp.where(second, q, zero)], axis=-1)


def own_half(cfg: GraniteHybridConfig, o: jax.Array) -> jax.Array:
    """o: [T, heads, 2 d], attention over V's pool rows -> [T, heads, d],
    each head's own V head's half."""
    d = cfg.head_dim
    return jnp.where(_second_of_pair(cfg), o[..., d:], o[..., :d])


def attention_mixer(cfg: GraniteHybridConfig, layer, u: jax.Array, attend,
                    gi):
    """u: [T, H] normalised -> (the mixer's output, this tick's K rows
    and V rows [T, kv heads / 2, 2 d]). No positional encoding."""
    t = u.shape[0]
    q = jnp.dot(u, layer["wq"], preferred_element_type=jnp.float32)
    q = wide_queries(cfg, q.reshape(t, cfg.n_heads, cfg.head_dim))
    rows = (t, cfg.n_kv_heads // PAIR, PAIR * cfg.head_dim)
    k = (u @ layer["wk"]).reshape(rows)
    v = (u @ layer["wv"]).reshape(rows)
    o = own_half(cfg, attend(q, k, v, 0, gi, None))
    return o.reshape(t, -1).astype(cfg.dtype) @ layer["wo"], k, v


def ragged_forward(cfg: GraniteHybridConfig, params: Dict[str, Any],
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
    logits per slot [B, V] float32, the k tuple, the v tuple), the state
    of the slots that had tokens advanced to their runs' ends."""
    refuse("GraniteHybrid", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    (pool_k, conv), (pool_v, scan) = k_pages, v_pages
    t = tokens.shape[0]
    marks = ssm.segment_marks(slot_ids, positions, valid, start, last_idx)
    tick = (slot_ids, valid, last_idx)
    attend = attend_fn(impl, ((pool_k, pool_v),), (page_tables,), slot_ids,
                       positions, valid, start, ctx_pages, merged_rows=True)
    res = cfg.residual_multiplier
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)
        x = (x * cfg.embedding_multiplier).astype(cfg.dtype)
    kv_shape = (t, cfg.n_kv_heads // PAIR, PAIR * cfg.head_dim)

    def add(x, out):
        return x + (res * out.astype(jnp.float32)).astype(cfg.dtype)

    def mlp(x, w):
        with jax.named_scope("mlp"):
            return add(x, swiglu(w, rms_norm(x, w["ln"], cfg.norm_eps)))

    def with_attention(x, a_idx):
        layer = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, a_idx, 0, False),
            params["attn"])
        with jax.named_scope("attn_mixer"):
            out, k, v = attention_mixer(
                cfg, layer, rms_norm(x, layer["ln"], cfg.norm_eps), attend,
                a_idx)
        return (mlp(add(x, out), layer["mlp"]), k.astype(cfg.dtype),
                v.astype(cfg.dtype))

    def without(x, a_idx):
        zero = jnp.zeros(kv_shape, cfg.dtype)
        return x, zero, zero

    def unit(carry, step):
        x, conv, scan = carry
        layer, i, a_idx, has_attn = step
        x, k, v = jax.lax.cond(has_attn, with_attention, without, x, a_idx)
        with jax.named_scope("mamba_mixer"):
            out, conv, scan = mamba2_mixer(
                cfg, layer, rms_norm(x, layer["ln"], cfg.norm_eps), marks,
                tick, conv, scan, i, impl, conv_scope="ssm_conv")
        return (mlp(add(x, out), layer["mlp"]), conv, scan), (k, v)

    has_attn = np.asarray([a is not None for a, _ in cfg.units])
    (x, conv, scan), (ks, vs) = jax.lax.scan(
        unit, (x, conv, scan),
        (params["mamba"], jnp.arange(len(has_attn), dtype=jnp.int32),
         jnp.asarray(np.maximum(np.cumsum(has_attn) - 1, 0), jnp.int32),
         jnp.asarray(has_attn)))
    # the tick's K and V rows go into the pool once, after the stack: one
    # scatter of single rows a pool (scope `kv_write`)
    own = page_tables[slot_ids]
    at = np.flatnonzero(has_attn)                    # static
    pool_k = scatter_rows(pool_k, ks[at], own, positions, valid)
    pool_v = scatter_rows(pool_v, vs[at], own, positions, valid)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jax.lax.dot_general(
            x[last_idx], params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / cfg.logits_scaling
    return logits, (pool_k, conv), (pool_v, scan)


decode_step = one_token_tick(ragged_forward)


# what the dispatch span carries besides the usual counts: `ssm_tokens`
# and `ssm_rows`
span_counts = state_span_counts
