"""Phi-4-mini-flash-reasoning (microsoft, `model_type` "phi4flash": the
SambaY decoder-hybrid-decoder of arXiv:2507.06607) for serving: Mamba-1
layers whose state is kept a SLOT beside two page groups, sliding-window
and full differential attention, and a cross-decoder of gated memory
units and cross-attention layers that reads ONE layer's K and V and one
layer's scan output, and that only the rows that sample run.

The model, for layer l of `n_layers` = 32 (half = 16):

    h <- h + Mixer_l(LN(h));  h <- h + MLP_l(LN(h));  then LN, then
    logits = h E^T   (E the embedding: the head is TIED)

LN is LayerNorm with weight and bias, eps 1e-5. No positional encoding
anywhere. MLP(u) = (up * silu(gate)) W2 with [gate, up] = u W1
(2560 -> 2 x 10240 -> 2560, no bias). The mixer by layer index:

- l even, l <= half: MAMBA-1. [x, z] = u W_in (2560 -> 2 x 5120);
  x <- silu(conv(x) + b_c), a causal depthwise convolution of 4 taps;
  [r, B, C] = x W_x (5120 -> 160 + 16 + 16); delta = softplus(r W_dt +
  b_dt); A = -exp(A_log); s_t = exp(delta_t A) s_{t-1} + (delta_t x_t)
  outer B_t; y_t = s_t C_t + D x_t; out = (y * silu(z)) W_out. State,
  delta, A and the exponential in float32. Layer `half` also keeps
  m := y (before the gate) as this token's memory.
- l odd, l < half: WINDOW attention, query i sees keys j with
  i - 512 < j <= i. l = half + 1: the same with every key j <= i; its K
  and V of every token are the ONE shared cache. Differential attention
  (arXiv:2410.05258, its flash form): the 40 query heads of 64 are 20
  pairs (q1_i, q2_i), the 20 key heads 10 pairs (k1_j, k2_j), the 20
  value heads 10 rows V_j = [v1_j | v2_j] of 128, j = i // 2;
  O_i = (1 - lam0) RMSNorm_128((softmax(q1_i k1_j^T / 8)
        - lam softmax(q2_i k2_j^T / 8)) V_j), each softmax under the
  layer's mask; lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
  lam0 = 0.8 - 0.6 exp(-0.3 l), four learned 64-vectors a layer;
  out = [O_0 ... O_19] W_o + b_o; [q, k, v] = u W_qkv + b.
- l even, l >= half + 2: GATED MEMORY UNIT. out = (m * silu(u W1)) W2
  (2560 -> 5120 -> 2560), m the memory layer `half` made for the SAME
  token in the same forward: an activation, nothing cached.
- l odd, l >= half + 3: CROSS attention. q = u W_q + b_q only; K and V
  are layer half + 1's; every key j <= i; differential as above with this
  layer's lam; out projection.

How it runs here:

- Differential attention as ordinary GQA: a query head [q1 | 0] or
  [0 | q2] of 128 against the key row [k1_j | k2_j] and the value row
  V_j is attention of 40 query heads over 10 K/V heads of 128 (scores
  q1 . k1 and q2 . k2 exactly: the zeros add nothing), so the pool row
  needs no padding (5,120 B a token a layer) and the two ragged kernels
  serve as they are; the subtraction, the norm and W_o come after them.
  The kernels scale scores by 128^-1/2, so q carries sqrt(2).
- The cache is three GROUPS (`cache_groups`): `full` (layer half + 1's
  K and V, whole contexts, READ by the cross layers too), `window` (the
  window layers, the last 512 tokens) and `state` (the Mamba layers: a
  slot's scan state [16, 5120] float32 and its last 3 conv inputs,
  bfloat16, a layer). The forwards take one entry a group, in that
  order, in `k_pages` / `v_pages`: a page group's K pool / V pool, the
  state group's conv inputs / scan state; `page_tables` has the two
  page groups'.
- The cross-decoder runs on sampling rows only. Layers half + 2 ... 31
  write nothing to any cache (they read layer half + 1's pages and layer
  half's m of their own token), so a prompt token that samples nothing
  needs only layers 0 ... half + 1: the stack scatters the full group's
  rows after layer half + 1, gathers `last_idx` and runs the layers above
  on [rows, d], each row one query at its position against the pool.
  This is the architecture's published prefill saving; it leaves no
  output out. No option chooses it.

Departures from the published `modeling_phi4flash.py`: matrices are
stored transposed ([in, out]); A_log is stored [N, E] (E in the lanes,
as the scan's state); weights are normal(0, 1/fan_in) from the seed,
the attention biases normal(0, 0.02), the lam vectors normal(0, 0.1),
A_log = log(1 ... N), b_dt by Mamba's inverse-softplus draw; which
heads pair is adjacent heads (with seeded weights any fixed pairing is
the same model); dropout (`embd_pdrop`, `resid_pdrop`, 0 as published)
is not there. Weights are created and stored in `param_dtype`
(bfloat16) and used as stored; norm weights and biases, lam vectors,
A_log, D, the conv's taps and b_dt are float32.

The stack as the engine keeps it (`stack_layers`) is the eight (Mamba,
window) pairs and the seven (memory unit, cross) pairs each stacked
along a leading axis, with layer `half` and layer `half + 1` as trees of
their own between them, and the forward a `lax.scan` over each run of
pairs: a tick's program holds a pair's body once, not eight times (an
unrolled 32-layer program took 5-6 s to trace and lower on the chip's
host, 56 programs a warm-up; `models/trinity.py` says why ITS stack is
no scan: a `lax.cond` on held experts, which no layer here has).
`init_params` draws a list of one tree a layer, the form the reference
takes; `layer_trees` gives that form back from the stacked one, a layer
at a time.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import selective_scan as ssm
from ..ops.paged_attention import pool_head_dim
from .cache_row import CacheGroup, CacheRow, StateRow
from .paged_common import (attend_fn, one_token_tick, refuse,
                           state_span_counts, window_span_counts)
from .paged_common import scatter_merged_rows as scatter_rows

MAMBA, SWA, FULL, GMU, CROSS = "mamba", "swa", "full", "gmu", "cross"
PAIR = 2            # heads a differential pair; K/V heads a pool row


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    ffn: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2            # every other layer of the first half
    norm_eps: float = 1e-5
    d_state: int = 16                # `mamba_d_state`
    d_conv: int = 4                  # `mamba_d_conv`
    expand: int = 2                  # `mamba_expand`
    dt_rank: int = 160               # `mamba_dt_rank`: ceil(hidden / 16)
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16        # compute type
    param_dtype: Any = jnp.bfloat16  # storage type: used as stored

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden

    @property
    def half(self) -> int:
        return self.n_layers // 2

    @property
    def kinds(self) -> Tuple[str, ...]:
        half = self.half

        def kind(l):
            if l % 2 == 0:
                return MAMBA if l <= half else GMU
            return SWA if l < half else FULL if l == half + 1 else CROSS
        return tuple(kind(l) for l in range(self.n_layers))

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    @property
    def n_self(self) -> int:
        """Layers every token runs: up to the one whose K and V are the
        shared cache. The rest is the cross-decoder."""
        return self.half + 2

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def _mixer_params(self, kind: str) -> int:
        h, e, n, r = self.hidden, self.d_inner, self.d_state, self.dt_rank
        kv = self.n_kv_heads * self.head_dim
        d = self.head_dim
        lam = 4 * d + PAIR * d                       # lam vectors, sub-norm
        return {
            MAMBA: (h * 2 * e + self.d_conv * e + e + e * (r + 2 * n)
                    + r * e + e + e * n + e + e * h),
            SWA: h * (h + 2 * kv) + (h + 2 * kv) + h * h + h + lam,
            GMU: 2 * h * e,
            CROSS: 2 * (h * h + h) + lam,
        }[SWA if kind == FULL else kind]

    def num_params(self) -> int:
        """Every parameter held: the tied embedding once, each layer's
        mixer and MLP, norms and biases with them (3,852M at the
        published sizes; the norms, biases and lam vectors are 0.4M of
        it)."""
        h = self.hidden
        per_layer = 3 * h * self.ffn + 4 * h          # MLP, two LNs
        return (self.vocab_size * h + 2 * h
                + sum(self._mixer_params(k) + per_layer
                      for k in self.kinds))

    def serving_costs(self) -> Dict[str, float]:
        """What `perfmodel.CostModel` takes (see `DeepseekV3Config`):
        matrix products a token through the layers EVERY token runs, a
        sampled row's through the cross-decoder besides, the head's,
        attention's per kept (query, key) pair over the 16 attention
        layers at the model's own head size, and the weights' bytes."""
        h, e = self.hidden, self.d_inner
        kv = self.n_kv_heads * self.head_dim
        mlp = 3 * 2 * h * self.ffn
        mixer = {
            MAMBA: 2 * (h * 2 * e + e * (self.dt_rank + 2 * self.d_state)
                        + self.dt_rank * e + e * h),
            SWA: 2 * (h * (h + 2 * kv) + h * h),
            GMU: 2 * 2 * h * e, CROSS: 2 * 2 * h * h}
        mixer[FULL] = mixer[SWA]
        kinds = self.kinds
        return {
            "gemm_flops_per_token": sum(
                mixer[k] + mlp for k in kinds[:self.n_self]),
            "gemm_flops_per_sampled_row": sum(
                mixer[k] + mlp for k in kinds[self.n_self:]),
            "head_flops": 2 * h * self.vocab_size,
            "attn_flops_per_pair": 4 * self.n_heads * self.head_dim * sum(
                k in (SWA, FULL, CROSS) for k in kinds),
            "weight_bytes": self.num_params() * jnp.dtype(
                self.param_dtype).itemsize,
        }

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(
                "n_layers must be a multiple of 4, at least 8: layer "
                "n/2 is the Mamba layer whose output is the memory, "
                "layer n/2 + 1 the full-attention layer whose K and V "
                "the cross layers read")
        if self.mb_per_layer != 2:
            raise ValueError("the layer schedule is mb_per_layer 2")
        if self.hidden % self.n_heads or self.n_heads % PAIR \
                or self.n_kv_heads % PAIR \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must pair, and group over kv heads")


PRESETS: Dict[str, Phi4FlashConfig] = {
    # the CPU tests' size: every kind of layer at toy widths: mamba 0 2
    # 4, window 1 3, full 5, gmu 6, cross 7; a window of 8; E = 128 (one
    # lane vector, for the interpreted kernel)
    "debug": Phi4FlashConfig(
        vocab_size=256, hidden=64, n_layers=8, n_heads=8, n_kv_heads=4,
        ffn=96, sliding_window=8, d_state=16, dt_rank=4, max_seq=256),
}


def config(name_or_cfg, **overrides) -> Phi4FlashConfig:
    cfg = PRESETS[name_or_cfg] if isinstance(name_or_cfg, str) \
        else name_or_cfg
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def cache_groups(cfg: Phi4FlashConfig, impl: str, kv_kind: str = "f32"
                 ) -> Tuple[CacheGroup, ...]:
    """`full` first (whole contexts: the engine's `slot.pages`; ONE
    layer writes it, the cross layers read it), then `window`, then the
    Mamba layers' `state`. The page groups' row is a pair of K/V heads:
    10 heads of 128, which is the model's 20 heads of 64 and no lane
    padding; a page is [page * 10 rows, 128] (`layout` "rows": the
    token-major bytes in one axis), because 10 heads are no multiple of
    the 8-row tile: a [page, 10, 128] page is padded to 16 heads in
    device memory (1.6 x the bytes) and the TPU compiler refuses to
    slice it for a page's DMA (`tests/test_tpu_aot_compile.py`)."""
    if kv_kind != "f32":
        raise ValueError(PHI4FLASH_REFUSES["kv_dtype"])
    width = PAIR * cfg.head_dim
    row = CacheRow(kind="kv", pools=2, heads=cfg.n_kv_heads // PAIR,
                   width=width, padded_width=pool_head_dim(width, impl),
                   dtype=cfg.dtype, layout="rows")
    e = cfg.d_inner
    state = StateRow(kind="ssm", parts=(
        ("conv_inputs", ((cfg.d_conv - 1) * e,), cfg.dtype),
        ("scan_state", (cfg.d_state, e), jnp.float32)))
    return (CacheGroup("full", row, cfg.layers_of(FULL),
                       readers=cfg.layers_of(CROSS)),
            CacheGroup("window", row, cfg.layers_of(SWA),
                       cfg.sliding_window),
            CacheGroup("state", None, cfg.layers_of(MAMBA), state=state))


PHI4FLASH_REFUSES = {
    "prefix_cache": "a resume at token m needs the recurrent state as it "
                    "stood at m and the window group's pages over "
                    "(m - window, m]; one state a slot is kept and the "
                    "pages behind a window are gone: the cache matches "
                    "nothing (`stats()['prefix_cache']` says so)",
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo inside "
            "its layer scan; this family's fused W_qkv, its Mamba and "
            "its memory-unit projections have no adapter path",
    "kv_dtype": "int8/fp8 KV pages keep per-(row, kv head) scale pools "
                "beside ONE pair of pools; this family has a pair a "
                "page group, a float32 state, and no quantized write or "
                "read path",
    "enable_kv_offload": "the host KV tier spills and restores one "
                         "group's pages by a slot's page list; a window "
                         "group holds a moving part of a sequence and "
                         "the state group holds no pages at all",
    "mesh": "GSPMD tensor parallelism is the dense family's layout; the "
            "scan's channels and the paired heads have no sharding here",
    "mesh_shape": "the explicit-tp shard_map programs are the dense "
                  "family's (Megatron layout of wq/wk/wv/wo)",
    "checkpoint": "no checkpoint loader for this family's tree yet",
    "session_shipping": "session and prefix export/import move one "
                        "group's pages; a sequence here is also its "
                        "window pages and its recurrent state, and "
                        "nothing snapshots or ships those",
}


# --------------------------------------------------------------------- params

def init_params(cfg: Phi4FlashConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded parameters, each matrix drawn in float32 and stored in
    `param_dtype`: {"embed", "layers": [one tree a layer], "final_norm":
    {"w", "b"}}. No head: it is the embedding."""
    pd, f32 = cfg.param_dtype, jnp.float32
    h, e, d = cfg.hidden, cfg.d_inner, cfg.head_dim
    n, r = cfg.d_state, cfg.dt_rank
    q, kv = cfg.n_heads * d, cfg.n_kv_heads * d
    counter = iter(range(1 << 20))

    def draw(shape, scale, dtype=f32):
        k = jax.random.fold_in(key, next(counter))
        return (scale * jax.random.normal(k, shape, f32)).astype(dtype)

    def dense(shape, fan_in):
        return draw(shape, 1.0 / math.sqrt(fan_in), pd)

    def norm():
        return {"w": jnp.ones((h,), f32), "b": jnp.zeros((h,), f32)}

    def lam():
        return {"lam_q1": draw((d,), 0.1), "lam_k1": draw((d,), 0.1),
                "lam_q2": draw((d,), 0.1), "lam_k2": draw((d,), 0.1),
                "subln": jnp.ones((PAIR * d,), f32)}

    def mamba():
        ku = jax.random.fold_in(key, next(counter))
        dt = jnp.exp(jax.random.uniform(ku, (e,), f32)
                     * (math.log(0.1) - math.log(0.001))
                     + math.log(0.001)).clip(1e-4)
        return {
            "in_proj": dense((h, 2 * e), h),
            "conv_w": draw((cfg.d_conv, e), 1.0 / math.sqrt(cfg.d_conv)),
            "conv_b": draw((e,), 0.02),
            "x_proj": dense((e, r + 2 * n), e),
            "dt_proj": dense((r, e), r),
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=f32))[:, None], (n, e)),
            "d_skip": jnp.ones((e,), f32),
            "out_proj": dense((e, h), e)}

    def attention():
        return {"wqkv": dense((h, q + 2 * kv), h),
                "bqkv": draw((q + 2 * kv,), 0.02),
                "wo": dense((q, h), q), "bo": draw((h,), 0.02), **lam()}

    def cross():
        return {"wq": dense((h, q), h), "bq": draw((q,), 0.02),
                "wo": dense((q, h), q), "bo": draw((h,), 0.02), **lam()}

    def gmu():
        return {"gmu_in": dense((h, e), h), "gmu_out": dense((e, h), e)}

    mixer = {MAMBA: mamba, SWA: attention, FULL: attention, GMU: gmu,
             CROSS: cross}
    layers = [{"ln1": norm(), "ln2": norm(), **mixer[kind](),
               "w1": dense((h, 2 * cfg.ffn), h),
               "w2": dense((cfg.ffn, h), cfg.ffn)}
              for kind in cfg.kinds]
    return {"embed": dense((cfg.vocab_size, h), h), "layers": layers,
            "final_norm": norm()}


def _runs(cfg: Phi4FlashConfig):
    """The stack's four parts in layer order: (key, the kinds a step,
    the layers of each kind). A stacked part's step is a PAIR of layers;
    `memory` and `shared` are one layer each."""
    half = cfg.half
    return (("self_pairs", (MAMBA, SWA),
             (tuple(range(0, half, 2)), tuple(range(1, half, 2)))),
            ("memory", (MAMBA,), ((half,),)),
            ("shared", (FULL,), ((half + 1,),)),
            ("cross_pairs", (GMU, CROSS),
             (cfg.layers_of(GMU), cfg.layers_of(CROSS))))


def stack_layers(cfg: Phi4FlashConfig, params: Dict[str, Any],
                 spend: bool = False) -> Dict[str, Any]:
    """`init_params`' tree -> the tree the forwards take and the engine
    keeps: {"embed", "final_norm", "self_pairs": {"mamba", "swa"},
    "memory", "shared", "cross_pairs": {"gmu", "cross"}}, a pair's leaves
    stacked along a leading axis over the pairs. spend: the caller
    hands its tree over, and the layers' dicts are emptied as their
    leaves are stacked (a leaf at a time: the tree that came in plus one
    stacked leaf is held, never two trees)."""
    layers = params["layers"]
    out = {k: v for k, v in params.items() if k != "layers"}

    take = dict.pop if spend else dict.__getitem__

    def stacked(idx):
        trees = [layers[i] for i in idx]
        return {name: jax.tree.map(lambda *a: jnp.stack(a),
                                   *[take(t, name) for t in trees])
                for name in list(trees[0])}

    for key, kinds, idx in _runs(cfg):
        out[key] = (layers[idx[0][0]] if len(kinds) == 1 else
                    {kind: stacked(i) for kind, i in zip(kinds, idx)})
    return out


class _Layers:
    """A stacked tree's layers as a sequence of one tree a layer, each
    cut out of its stack when it is asked for (a second copy of ONE
    layer at a time)."""

    def __init__(self, cfg, params):
        where = {}
        for key, kinds, idx in _runs(cfg):
            for kind, layers in zip(kinds, idx):
                for n, l in enumerate(layers):
                    where[l] = (key, kind if len(kinds) > 1 else None, n)
        self._where, self._params = where, params

    def __len__(self):
        return len(self._where)

    def __getitem__(self, l):
        key, kind, n = self._where[range(len(self))[l]]
        if kind is None:
            return self._params[key]
        # the index as an operand: one program a leaf shape, not one an
        # index
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.int32(n), 0, keepdims=False),
            self._params[key][kind])

    def __iter__(self):
        return (self[l] for l in range(len(self)))


def layer_trees(cfg: Phi4FlashConfig, params: Dict[str, Any]
                ) -> Dict[str, Any]:
    """A stacked tree -> `init_params`' form, for whoever walks the
    layers one by one (the benchmark's reference): "layers" is a
    sequence whose items are made when taken."""
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "layers": _Layers(cfg, params)}


def init_stacked(cfg: Phi4FlashConfig, key: jax.Array) -> Dict[str, Any]:
    """The family's `init_params`: the seeded draw, stacked."""
    return stack_layers(cfg, init_params(cfg, key), spend=True)


def storage_dtypes(cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """The type each leaf is stored in: as `init_stacked` makes it (the
    tick's programs use every leaf as stored)."""
    shapes = jax.eval_shape(lambda k: init_stacked(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(lambda s: s.dtype, shapes)


# --------------------------------------------------------------------- layers

def layer_norm(x: jax.Array, p, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["w"]
            + p["b"]).astype(x.dtype)


def mlp(cfg: Phi4FlashConfig, layer, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        u = layer_norm(x, layer["ln2"], cfg.norm_eps)
        gate, up = jnp.split(u @ layer["w1"], 2, axis=-1)
        act = (up.astype(jnp.float32)
               * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
        return x + act @ layer["w2"]


def wide_queries(cfg: Phi4FlashConfig, q: jax.Array) -> jax.Array:
    """q: [T, heads * 64] -> [T, heads, 128]: head 2i is [q1_i | 0],
    head 2i + 1 is [0 | q2_i], times sqrt(2) (the kernels divide scores
    by sqrt(128); the model by sqrt(64))."""
    t, d = q.shape[0], cfg.head_dim
    q = (q.astype(jnp.float32) * math.sqrt(PAIR)).astype(cfg.dtype)
    q = q.reshape(t, cfg.n_heads // PAIR, PAIR, d)
    zero = jnp.zeros_like(q[:, :, 0])
    return jnp.stack(
        [jnp.concatenate([q[:, :, 0], zero], axis=-1),
         jnp.concatenate([zero, q[:, :, 1]], axis=-1)],
        axis=2).reshape(t, cfg.n_heads, PAIR * d)


def lam_consts(cfg: Phi4FlashConfig, layers) -> jax.Array:
    """[len(layers), 2] float32: (lam0, 1 - lam0) of each layer index,
    reckoned on the host: what a scanned layer is handed in place of
    its index."""
    return jnp.asarray([(cfg.lambda_init(l), 1.0 - cfg.lambda_init(l))
                        for l in layers], jnp.float32)


def diff_output(cfg: Phi4FlashConfig, layer, o: jax.Array,
                lam0: jax.Array) -> jax.Array:
    """o: [T, heads, 128], the GQA attention of `wide_queries` -> the
    mixer's output [T, H]: the pairs' difference, the sub-norm, (1 -
    lam0) and the output projection. lam0: the layer's row of
    `lam_consts`."""
    with jax.named_scope("diff"):
        t = o.shape[0]
        o = o.astype(jnp.float32).reshape(t, cfg.n_heads // PAIR, PAIR, -1)
        lam = (jnp.exp(jnp.sum(layer["lam_q1"] * layer["lam_k1"]))
               - jnp.exp(jnp.sum(layer["lam_q2"] * layer["lam_k2"]))
               + lam0[0])
        d = o[:, :, 0] - lam * o[:, :, 1]
        d = d * jax.lax.rsqrt(
            jnp.mean(jnp.square(d), axis=-1, keepdims=True) + cfg.norm_eps)
        d = d * layer["subln"] * lam0[1]
        d = d.reshape(t, -1).astype(cfg.dtype)
    return d @ layer["wo"] + layer["bo"].astype(cfg.dtype)


def mamba_mixer(cfg: Phi4FlashConfig, layer, u: jax.Array, marks, tick,
                conv_all: jax.Array, scan_all: jax.Array, gi: int,
                impl: str):
    """u: [T, H] normalised -> (the mixer's output [T, H], y [T, E]
    before the gate, the conv inputs and the scan state with layer
    `gi`'s rows of this tick's slots replaced)."""
    slot_ids, valid, last_idx = tick
    e, k = cfg.d_inner, cfg.d_conv
    b = conv_all.shape[1]
    x, z = jnp.split(u @ layer["in_proj"], 2, axis=-1)
    with jax.named_scope("conv"):
        xc, conv_new = ssm.causal_conv_ragged(
            x, layer["conv_w"], layer["conv_b"], slot_ids, last_idx, marks,
            conv_all[gi].reshape(b, k - 1, e))
        conv_all = conv_all.at[gi].set(conv_new.reshape(b, -1))
        x = jax.nn.silu(xc).astype(cfg.dtype)
    rbc = jnp.dot(x, layer["x_proj"], preferred_element_type=jnp.float32)
    r, bm, cm = jnp.split(
        rbc, [cfg.dt_rank, cfg.dt_rank + cfg.d_state], axis=-1)
    delta = jax.nn.softplus(
        jnp.dot(r.astype(cfg.dtype), layer["dt_proj"],
                preferred_element_type=jnp.float32) + layer["dt_bias"])
    with jax.named_scope("scan"):
        y, scan_all = ssm.selective_scan_ragged(
            x, delta, -jnp.exp(layer["a_log"]), bm, cm, layer["d_skip"],
            slot_ids, valid, last_idx, marks, scan_all, gi, impl=impl)
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
    return gated @ layer["out_proj"], y.astype(cfg.dtype), conv_all, \
        scan_all


def _attend_fn(cfg: Phi4FlashConfig, impl: str, *tick):
    """`paged_common.attend_fn` over merged-rows pools (`tick`: pools,
    tables, slot_ids, positions, valid, start, ctx_pages), under the
    signature this family's callers know."""
    del cfg
    return attend_fn(impl, *tick, merged_rows=True)


def ragged_forward(cfg: Phi4FlashConfig, params: Dict[str, Any],
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
    `cache_groups`' order: (full K pool, window K pool, the Mamba
    layers' conv inputs [9, B, 3 E]) and (full V pool, window V pool,
    their scan state [9, B, N, E] float32); `page_tables` the page
    groups' two, [B, max_pages] each. A row whose `start` is 0 begins
    from zero state. `params`: the stacked tree (`stack_layers`).
    Returns (last-token logits per slot [B, V] float32,
    the k tuple, the v tuple), the state of the slots that had tokens
    advanced to their runs' ends."""
    refuse("Phi4Flash", lora=lora, mesh=mesh, kv_kind=kv_kind,
           k_scales=k_scales, v_scales=v_scales)
    del lora_idx
    (full_k, win_k, conv), (full_v, win_v, scan) = k_pages, v_pages
    t, b = tokens.shape[0], start.shape[0]
    page = full_k.shape[2] // (cfg.n_kv_heads // PAIR)
    kvr, width = cfg.n_kv_heads // PAIR, PAIR * cfg.head_dim
    q_dim = cfg.n_heads * cfg.head_dim
    marks = ssm.segment_marks(slot_ids, positions, valid, start, last_idx)
    tick = (slot_ids, valid, last_idx)
    pools = ((full_k, full_v), (win_k, win_v))
    attend = _attend_fn(cfg, impl, pools, page_tables, slot_ids,
                        positions, valid, start, ctx_pages)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)

    def mamba_layer(x, layer, conv, scan, gi):
        u = layer_norm(x, layer["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"), jax.named_scope(MAMBA):
            out, y, conv, scan = mamba_mixer(
                cfg, layer, u, marks, tick, conv, scan, gi, impl)
        return mlp(cfg, layer, x + out), y, conv, scan

    def attention_layer(x, layer, kind, gi, lam0):
        """A window layer (its group's layer `gi`) or the full one ->
        (x, this tick's K rows, V rows)."""
        u = layer_norm(x, layer["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"), jax.named_scope(kind):
            qkv = u @ layer["wqkv"] + layer["bqkv"].astype(cfg.dtype)
            q = wide_queries(cfg, qkv[:, :q_dim])
            k, v = (a.reshape(t, kvr, width) for a in jnp.split(
                qkv[:, q_dim:], 2, axis=-1))
            g, window = (1, cfg.sliding_window) if kind == SWA else (0, None)
            out = diff_output(cfg, layer, attend(q, k, v, g, gi, window),
                              lam0)
        return mlp(cfg, layer, x + out), k, v

    def self_pair(carry, step):
        layers, gi, lam0 = step
        x, conv, scan = carry
        x, _, conv, scan = mamba_layer(x, layers[MAMBA], conv, scan, gi)
        x, k, v = attention_layer(x, layers[SWA], SWA, gi, lam0)
        return (x, conv, scan), (k, v)

    runs = {key: idx for key, _, idx in _runs(cfg)}
    n_pairs = len(runs["self_pairs"][1])
    (x, conv, scan), (win_rows_k, win_rows_v) = jax.lax.scan(
        self_pair, (x, conv, scan),
        (params["self_pairs"], jnp.arange(n_pairs, dtype=jnp.int32),
         lam_consts(cfg, runs["self_pairs"][1])))
    x, memory, conv, scan = mamba_layer(x, params["memory"], conv, scan,
                                        n_pairs)
    x, k, v = attention_layer(x, params["shared"], FULL, 0,
                              lam_consts(cfg, runs["shared"][0])[0])
    # the tick's rows go into the pools: the window group's after its
    # last layer, the shared layer's K and V BEFORE the cross-decoder,
    # which reads its own token there too
    own = (page_tables[0][slot_ids], page_tables[1][slot_ids])
    full_k = scatter_rows(full_k, k[None], own[0], positions, valid)
    full_v = scatter_rows(full_v, v[None], own[0], positions, valid)
    win_k = scatter_rows(win_k, win_rows_k, own[1], positions, valid)
    win_v = scatter_rows(win_v, win_rows_v, own[1], positions, valid)
    # -- the cross-decoder, on the rows that sample -------------------
    rows = jnp.arange(b, dtype=jnp.int32)
    row_valid = marks.has
    row_pos = positions[last_idx]
    xr, mr = x[last_idx], memory[last_idx]
    kr, vr = k[last_idx], v[last_idx]
    # a row's one query at its position: the keys before it are in the
    # pool (this tick's too, by the scatter above), its own rides along
    reach = (-1 if ctx_pages < 0 else
             min(ctx_pages + -(-t // page) + 1, page_tables[0].shape[1]))
    attend = _attend_fn(cfg, impl, ((full_k, full_v),), page_tables[:1],
                        rows, row_pos, row_valid, row_pos, reach)

    def cross_pair(xr, step):
        layers, lam0 = step
        layer = layers[GMU]
        u = layer_norm(xr, layer["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"), jax.named_scope(GMU):
            gate = jax.nn.silu((u @ layer["gmu_in"]).astype(jnp.float32))
            out = ((mr.astype(jnp.float32) * gate).astype(cfg.dtype)
                   @ layer["gmu_out"])
        xr = mlp(cfg, layer, xr + out)
        layer = layers[CROSS]
        u = layer_norm(xr, layer["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"), jax.named_scope(CROSS):
            q = wide_queries(
                cfg, u @ layer["wq"] + layer["bq"].astype(cfg.dtype))
            out = diff_output(cfg, layer, attend(q, kr, vr, 0, 0, None),
                              lam0)
        return mlp(cfg, layer, xr + out), None

    xr, _ = jax.lax.scan(
        cross_pair, xr,
        (params["cross_pairs"], lam_consts(cfg, runs["cross_pairs"][1])))
    with jax.named_scope("lm_head"):
        xr = layer_norm(xr, params["final_norm"], cfg.norm_eps)
        logits = jax.lax.dot_general(
            xr, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return logits, (full_k, win_k, conv), (full_v, win_v, scan)


decode_step = one_token_tick(ragged_forward)


def span_counts(cfg: Phi4FlashConfig, segs, decode) -> Dict[str, int]:
    """What the dispatch span carries besides the usual counts, from
    the plan (`segs` = [(cached tokens, tokens this tick)] a row):
    `ssm_tokens`, the tokens through each Mamba layer's scan;
    `ssm_rows`, the rows whose state a layer reads and writes;
    `cross_tokens`, the tokens the cross-decoder ran on (one a row);
    and the window layers' `win_kv_tokens`, `win_attn_pairs` and
    `win_decode_pairs` (`paged_common.window_span_counts`)."""
    return {**state_span_counts(cfg, segs, decode),
            "cross_tokens": len(segs),
            **window_span_counts(cfg, segs, decode)}
