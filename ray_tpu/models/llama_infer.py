"""Cache-aware Llama forward passes for inference.

Net-new (reference inference = external vLLM; SURVEY.md §7 hard part #1).
Two forwards, both designed to jit once per shape and stay compiled:

- ragged_forward: a flat ragged token batch (decode rows of one token,
  prefill chunks of many) attending over the shared page pool
  (ops/paged_attention.py layout: [n_layers, num_pages, page_size,
  n_kv_heads, pool_head_dim]) plus the batch itself, every token's K/V
  scattered into the pool.
- decode_step: one token per active sequence, new KV scattered in-place
  (donate the pools for true in-place HBM updates under jit). On the
  kernel path it is ragged_forward of one token a slot; the gather path
  keeps a dense forward of its own.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..ops.paged_attention import (gather_kv, gather_kv_quant,
                                   paged_attention_on_gathered, scatter_kv,
                                   scatter_kv_quant)
from .llama import (LlamaConfig, param_logical_axes, rms_norm,
                    rope_frequencies)
from .paged_common import one_token_tick


def _rope_single(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, H, D) one token per sequence; cos/sin: (B, D//2)."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    cos = cos[:, None, :]
    sin = sin[:, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


# ------------------------------------------------------------------ storage

# the leaves both forwards multiply in cfg.dtype (`_proj`, the MLP, the
# embedding's rows); lm_head and the norms are used in float32
_COMPUTE_LEAVES = frozenset(
    ("embed", "wq", "wk", "wv", "wo", "wg", "wi", "wd"))


def storage_dtypes(cfg: LlamaConfig) -> Dict[str, Any]:
    """The type a serving engine stores each leaf of init_params' tree
    in: the type `ragged_forward` and `decode_step` use it in, so that
    a tick's program converts no weight (models/family.store_params
    casts once, checkpoint_io reads straight into these). The forwards
    still take a tree in any type: a cast to the type a leaf has is
    free. Training keeps cfg.param_dtype masters and shares none of
    this."""
    compute, f32 = jnp.dtype(cfg.dtype), jnp.dtype(jnp.float32)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: compute if path[-1].key in _COMPUTE_LEAVES
        else f32,
        param_logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------- layer body

def _layer_body(cfg: LlamaConfig, dt, x, layer, lora_l, lora_idx,
                lead_shape: tuple, rope_fn, attn_fn,
                psum_axis: Optional[str] = None):
    """ONE transformer layer, shared by both forwards (ragged step,
    decode) — they differ only in
    the leading activation shape, the rope application, and the
    attention call. Returns (x, (k, v)) with k/v rope'd, ready for the
    KV scatter.

    psum_axis: inside an explicit-tp shard_map (Megatron layout:
    wq/wk/wv/wg/wi column-parallel, wo/wd row-parallel, cfg a shard-
    local view with n_heads/n_kv_heads divided by tp) the two residual
    projections produce PARTIAL sums — all-reduce them over the named
    axis before the residual add so activations stay replicated."""
    # the scope names are what the benchmark's span tables key device
    # time on (benchmarks/lib/span_reduce.py); they change HLO metadata
    # only
    with jax.named_scope("attn"):
        y = rms_norm(x, layer["ln1"], cfg.norm_eps)
        q = _proj(y, layer["wq"], lora_l, "wq", lora_idx, dt).reshape(
            *lead_shape, cfg.n_heads, cfg.head_dim)
        k = _proj(y, layer["wk"], lora_l, "wk", lora_idx, dt).reshape(
            *lead_shape, cfg.n_kv_heads, cfg.head_dim)
        v = _proj(y, layer["wv"], lora_l, "wv", lora_idx, dt).reshape(
            *lead_shape, cfg.n_kv_heads, cfg.head_dim)
        q = rope_fn(q)
        k = rope_fn(k)
        attn = attn_fn(q, k, v)
        attn_out = _proj(attn.reshape(*lead_shape, cfg.q_dim),
                         layer["wo"], lora_l, "wo", lora_idx, dt)
        if psum_axis is not None:
            attn_out = jax.lax.psum(attn_out, psum_axis)
        x = x + attn_out
    with jax.named_scope("mlp"):
        y = rms_norm(x, layer["ln2"], cfg.norm_eps)
        gate = jax.nn.silu(y @ layer["wg"].astype(dt))
        up = y @ layer["wi"].astype(dt)
        mlp_out = (gate * up) @ layer["wd"].astype(dt)
        if psum_axis is not None:
            mlp_out = jax.lax.psum(mlp_out, psum_axis)
        x = x + mlp_out
    return x, (k, v)


# ----------------------------------------------------- explicit tp (shard_map)

def tp_local_config(cfg: LlamaConfig, tp: int) -> LlamaConfig:
    """Shard-local view of *cfg* for the explicit-tp forwards: inside
    the engine's shard_map each shard sees 1/tp of the heads, so the
    reshape arithmetic in _layer_body must use divided head counts
    (q_dim follows automatically — it is a property of n_heads)."""
    if tp <= 1:
        return cfg
    if cfg.n_experts:
        raise ValueError("explicit tp (mesh_shape) does not support MoE "
                         "models; use the GSPMD mesh= path")
    for name, dim in (("n_heads", cfg.n_heads),
                      ("n_kv_heads", cfg.n_kv_heads),
                      ("hidden", cfg.hidden), ("ffn", cfg.ffn)):
        if dim % tp:
            raise ValueError(
                f"model {name}={dim} not divisible by tp={tp}")
    return dataclasses.replace(cfg, n_heads=cfg.n_heads // tp,
                               n_kv_heads=cfg.n_kv_heads // tp)


def tp_param_specs(cfg: LlamaConfig, tp_axis: str = "tp"):
    """PartitionSpec tree for init_params' dense llama tree under the
    Megatron layout: column-parallel wq/wk/wv/wg/wi (shard the output
    feature dim), row-parallel wo/wd (shard the input dim, psum in
    _layer_body), lm_head row-parallel over hidden (psum'd logits in
    _tp_head_logits), everything norm/embed replicated. Used both for
    device placement and as shard_map in_specs so dispatch never
    reshards."""
    if cfg.n_experts:
        raise ValueError("explicit tp (mesh_shape) does not support MoE "
                         "models; use the GSPMD mesh= path")
    P = PartitionSpec
    col = P(None, None, tp_axis)
    row = P(None, tp_axis, None)
    return {
        "embed": P(),
        "layers": {"wq": col, "wk": col, "wv": col, "wg": col,
                   "wi": col, "wo": row, "wd": row,
                   "ln1": P(), "ln2": P()},
        "final_norm": P(),
        "lm_head": P(tp_axis, None),
    }


def _tp_head_logits(last, lm_head, psum_axis, logits_psum=None):
    """Row-parallel lm_head: each shard holds an (H/tp, V) slice over
    hidden. Slice the replicated activations down to the shard's rows,
    take the partial product, and all-reduce. logits_psum lets the
    engine route the reduction through ops/quantized_collectives when
    EngineConfig.quantized_collectives is armed."""
    h_loc = lm_head.shape[0]
    shard = jax.lax.axis_index(psum_axis)
    loc = jax.lax.dynamic_slice_in_dim(last, shard * h_loc, h_loc,
                                       axis=-1)
    part = loc.astype(jnp.float32) @ lm_head.astype(jnp.float32)
    if logits_psum is None:
        return jax.lax.psum(part, psum_axis)
    return logits_psum(part, psum_axis)


# ---------------------------------------------------------------------- lora

def lora_delta(y, stack, idx):
    """Per-slot low-rank delta for one projection at one layer.

    y: (B, H) or (B, S, H) activations; stack: (A, Ha, r) down / up pair
    packed as {"a": (Adapters, H, r), "b": (Adapters, r, O)} already
    sliced to this layer; idx: (B,) adapter index per slot (0 = the
    zero adapter -> exact no-op). Multi-LoRA batching the vLLM way:
    gather each slot's adapter then two tiny einsums.
    """
    a = stack["a"][idx]          # (B, H, r)
    b = stack["b"][idx]          # (B, r, O)
    if y.ndim == 2:
        mid = jnp.einsum("bh,bhr->br", y, a)
        return jnp.einsum("br,bro->bo", mid, b)
    mid = jnp.einsum("bsh,bhr->bsr", y, a)
    return jnp.einsum("bsr,bro->bso", mid, b)


def _proj(y, w, lora_layer, key, idx, dt):
    """y @ w (+ the slot's LoRA delta for projection `key`, if any).

    lora_layer: THIS layer's slice of the adapter stacks (rides the
    layer scan as xs): {key: {"a": (A, H, r), "b": (A, r, O)}},
    already in compute dtype."""
    out = y @ w.astype(dt)
    if lora_layer is not None and key in lora_layer:
        out = out + lora_delta(y, lora_layer[key], idx).astype(out.dtype)
    return out


def lora_scan_xs(lora: Optional[dict]):
    """Adapter stacks are stored LAYER-MAJOR ((L, A, ...)) in compute
    dtype at registration — they ride the layer scan as xs directly
    (the old form relayouted + cast inside every compiled step)."""
    return lora if lora else None


def _whole_pools(*pools):
    """What the kernel path of `ragged_forward` (and so of a decode
    tick) gives the layer scan in place of the pools: each pool ([L,
    pages, page, KVH(, D)]; the scale pools of quantized values too)
    viewed as [L * pages, ...], a
    reshape of the two leading axes that moves no data, and every
    layer's first page in that view ([L], an xs of the scan). A layer's
    call gets the pools WHOLE and `page_tables + first page`: the
    kernels keep their pools in HBM and reach pages only through the
    table, whereas a pool sliced by layer in the scan is copied out
    before a Pallas call — twice the pool's bytes a tick."""
    n_layers, pages = pools[0].shape[:2]
    return (tuple(p.reshape((-1,) + p.shape[2:]) for p in pools),
            jnp.arange(n_layers, dtype=jnp.int32) * pages)


# -------------------------------------------------------------- ragged step

def ragged_forward(cfg: LlamaConfig, params: Dict[str, Any],
                   tokens: jax.Array, slot_ids: jax.Array,
                   positions: jax.Array, valid: jax.Array,
                   start: jax.Array, last_idx: jax.Array,
                   k_pages: jax.Array, v_pages: jax.Array,
                   page_tables: jax.Array, ctx_pages: int = -1,
                   lora: Optional[dict] = None,
                   lora_idx: Optional[jax.Array] = None,
                   impl: str = "gather", mesh=None,
                   max_seg_len: int = -1, kv_kind: str = "f32",
                   k_scales: Optional[jax.Array] = None,
                   v_scales: Optional[jax.Array] = None,
                   psum_axis: Optional[str] = None,
                   logits_psum=None) -> Tuple[jax.Array, ...]:
    """Unified ragged prefill+decode forward: ONE program per engine
    tick consumes a FLAT token batch where each active slot contributes
    between 1 token (decoding) and C tokens (prefilling), packed by the
    engine's token-budget scheduler. Decode is the n_tokens == 1 case
    of chunked prefill, so one program serves both.

    tokens: (T,) flat ragged batch (slot segments contiguous, position
    order); slot_ids: (T,) owning slot; positions: (T,) absolute
    position per token; valid: (T,) bool (padding excluded from
    attention, KV scatter, and seen updates); start: (B,) tokens
    already cached per slot; last_idx: (B,) flat index of each slot's
    last valid token (logits source; 0 for slots with no tokens this
    tick — callers mask); lora_idx: per-TOKEN adapter index (T,).

    impl (decode_step takes the same three):
      "gather"            dense XLA fallback — gathers each token's
                          [ctx] context up front; O(T*ctx*KVH*D)
                          transient per layer.
      "pallas"            Pallas ragged kernel: stream each slot's KV
                          pages through VMEM with online softmax, no
                          gathered-context transient.
      "pallas_interpret"  same kernel, interpreter mode (CPU tests).

    mesh: optional tp Mesh — the gather impl partitions via GSPMD as
    before; the kernel impl is wrapped in shard_map over 'tp'
    (attention is per-head: no collectives inside). The kernel's work
    list (`ragged_work_list`: one (slot, query block) item per grid
    step) is built here once for all layers. max_seg_len bounds
    nothing since the kernel stages nothing per slot; it is accepted
    and ignored because benchmarks/lib/checks.py, which this tree may
    not edit, still passes it.

    kv_kind/k_scales/v_scales: quantized pools (ISSUE 16). With
    kv_kind in ("int8", "fp8") the pools hold narrow values and
    k_scales/v_scales carry the [L, P, page, KVH] f32 row scales; the
    gather impl dequantizes up front (gather_kv_quant), the kernel impl
    streams scale blocks beside the pages and fuses the dequant
    multiply, and the return grows to (logits, k_pages, v_pages,
    k_scales, v_scales) with the tick's fresh KV quantized at append.

    psum_axis/logits_psum: explicit-tp mode (ISSUE 17) — the CALLER is
    already inside a shard_map over psum_axis, cfg is the shard-local
    view (tp_local_config), params/pools are the local shards
    (tp_param_specs layout), and mesh must be None (no nested
    shard_map). _layer_body all-reduces the row-parallel residual
    projections and the lm_head goes row-parallel over hidden with the
    partial logits reduced via logits_psum (default lax.psum).

    Returns (last-token logits per slot (B, V) f32, k_pages, v_pages)
    with every valid token's KV scattered into the pool at its
    position.
    """
    from ..ops.ragged_paged_attention import (
        ragged_paged_attention_pallas, ragged_prefill_decode_attention,
        ragged_q_block, ragged_work_list)

    del max_seg_len
    (t,) = tokens.shape
    dt = cfg.dtype
    quantized = kv_kind != "f32"
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]          # (T, H)
        cos, sin = rope_frequencies(cfg, positions)     # (T, D/2)
    use_kernel = impl in ("pallas", "pallas_interpret")
    kernel_quant = use_kernel and quantized
    if use_kernel:
        # the scan carries each layer's first page, not its pages
        pools, first_pages = _whole_pools(
            k_pages, v_pages, *((k_scales, v_scales) if quantized else ()))
        kv_xs = (first_pages,)
        # the kernel's grid: the same list for every layer
        work = ragged_work_list(slot_ids, valid, start,
                                ragged_q_block(t))
    else:
        ctx_tables = (page_tables if ctx_pages < 0
                      else page_tables[:, :ctx_pages])
        if quantized:
            kv_xs = gather_kv_quant(
                k_pages, v_pages, k_scales, v_scales, ctx_tables,
                cfg.head_dim)
        else:
            kv_xs = gather_kv(k_pages, v_pages, ctx_tables, cfg.head_dim)

    def layer_fn(x, inp):
        layer, *kv_l, lora_l = inp

        def attn_fn(q, k, v):
            if not use_kernel:
                k_l, v_l = kv_l
                return ragged_prefill_decode_attention(
                    q, k_l, v_l, k, v, slot_ids, positions, valid,
                    start)
            (first_page,) = kv_l
            # positional wrapper so shard_map's in_specs line up
            def kernel(q_, kp, vp, tb, si, po, va, st, kn, vn, items,
                       segs, ksl=None, vsl=None):
                return ragged_paged_attention_pallas(
                    q_, kp, vp, tb, si, po, va, st, kn, vn,
                    ctx_pages=ctx_pages, k_scales=ksl, v_scales=vsl,
                    work=(items, segs),
                    interpret=(impl == "pallas_interpret"))
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                # per-head attention: each tp shard streams pages for
                # its local kv heads, no cross-shard comms
                from jax.sharding import PartitionSpec as P
                in_specs = [P(None, "tp", None),          # q (T,H,D)
                            P(None, None, "tp", None),    # k pool
                            P(None, None, "tp", None),    # v pool
                            P(None, None),                # tables
                            P(None),                      # slot_ids
                            P(None),                      # positions
                            P(None),                      # valid
                            P(None),                      # start
                            P(None, "tp", None),          # new k
                            P(None, "tp", None),          # new v
                            P(None, None),                # work items
                            P(None, None)]                # slot segments
                if kernel_quant:
                    # scale blocks shard on kv heads like their pages
                    in_specs += [P(None, None, "tp"),     # k scales
                                 P(None, None, "tp")]     # v scales
                kernel = jax.shard_map(
                    kernel, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=P(None, "tp", None), check_vma=False)
            return kernel(q, *pools[:2], page_tables + first_page,
                          slot_ids, positions, valid, start, k, v, *work,
                          *pools[2:])

        return _layer_body(
            cfg, dt, x, layer, lora_l, lora_idx, (t,),
            lambda a: _rope_single(a, cos, sin), attn_fn,
            psum_axis=psum_axis)

    x, (ks, vs) = jax.lax.scan(
        layer_fn, x, (params["layers"], *kv_xs, lora_scan_xs(lora)))
    # ks/vs: (L, T, KVH, D) -> token-major (T, L, KVH, D)
    k_rows = jnp.swapaxes(ks, 0, 1)
    v_rows = jnp.swapaxes(vs, 0, 1)
    if quantized:
        k_pages, v_pages, k_scales, v_scales = scatter_kv_quant(
            k_pages, v_pages, k_scales, v_scales, k_rows, v_rows,
            page_tables[slot_ids], positions, valid, kv_kind)
    else:
        k_pages, v_pages = scatter_kv(k_pages, v_pages, k_rows, v_rows,
                                      page_tables[slot_ids], positions,
                                      valid)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = x[last_idx]                              # (B, H)
        if psum_axis is not None:
            logits = _tp_head_logits(last, params["lm_head"],
                                     psum_axis, logits_psum)
        else:
            logits = last.astype(jnp.float32) @ params[
                "lm_head"].astype(jnp.float32)
    if quantized:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages


# -------------------------------------------------------------------- decode

_one_token_tick = one_token_tick(ragged_forward)


def decode_step(cfg: LlamaConfig, params: Dict[str, Any],
                tokens: jax.Array, positions: jax.Array,
                k_pages: jax.Array, v_pages: jax.Array,
                page_tables: jax.Array, active: jax.Array,
                impl: str = "gather", mesh=None,
                lora: Optional[dict] = None,
                lora_idx: Optional[jax.Array] = None,
                kv_kind: str = "f32",
                k_scales: Optional[jax.Array] = None,
                v_scales: Optional[jax.Array] = None,
                psum_axis: Optional[str] = None,
                logits_psum=None) -> Tuple[jax.Array, ...]:
    """One decode step for the whole running batch.

    tokens: (B,) last sampled token per slot; positions: (B,) its
    absolute position (== number of cached tokens); active: (B,) bool.
    Returns (logits (B, V) f32, k_pages, v_pages) with the new token's KV
    scattered in.

    impl:
      "pallas"            the decode tick IS the ragged tick of one token
                          a slot (`paged_common.one_token_tick`, as in
                          every other family): `ragged_forward` with
                          slot b's token at positions[b] and inactive
                          slots invalid, so the work-list kernel steps
                          over live rows only and a row costs the keys it
                          has. Every other argument goes to
                          `ragged_forward` under its contract there.
      "pallas_interpret"  same, kernel in interpreter mode (CPU tests).
      "gather"            dense XLA path, below: gathers [B, max_ctx] KV
                          up front; cost scales with max_pages. What a
                          CPU engine resolves "auto" to, and the side the
                          kernel path is checked against.

    mesh: a jax Mesh with a 'tp' axis for tensor-parallel serving
    (params sharded on heads/mlp/vocab, KV pool on kv_heads — the
    reference places external vLLM TP workers via PGs,
    vllm_models.py:123-159; here TP is in-program GSPMD, which
    partitions the gather path end-to-end).

    kv_kind/k_scales/v_scales: quantized pools (ISSUE 16) — same
    contract as ragged_forward: dequant-on-gather on the read side,
    quantize-at-append on the write side, and a (logits, k_pages,
    v_pages, k_scales, v_scales) return.

    psum_axis/logits_psum: explicit-tp mode — same contract as
    ragged_forward (caller already inside the shard_map, shard-local
    cfg/params/pools, mesh=None).
    """
    if impl in ("pallas", "pallas_interpret"):
        return _one_token_tick(
            cfg, params, tokens, positions, k_pages, v_pages, page_tables,
            active, impl=impl, mesh=mesh, lora=lora, lora_idx=lora_idx,
            kv_kind=kv_kind, k_scales=k_scales, v_scales=v_scales,
            psum_axis=psum_axis, logits_psum=logits_psum)
    b = tokens.shape[0]
    dt = cfg.dtype
    quantized = kv_kind != "f32"
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]       # (B, H)
        cos, sin = rope_frequencies(cfg, positions)  # (B, D/2)

    # One gather of the whole context for all layers, layer-major.
    if quantized:
        kv_xs = gather_kv_quant(
            k_pages, v_pages, k_scales, v_scales, page_tables,
            cfg.head_dim)
    else:
        kv_xs = gather_kv(k_pages, v_pages, page_tables, cfg.head_dim)

    def layer_fn(x, inp):
        layer, k_l, v_l, lora_l = inp

        def attn_fn(q, k, v):
            # The just-computed token's KV is not yet in the pages: it
            # is appended to the dense context (append_len=1).
            k_full = jnp.concatenate([k_l, k[:, None]], axis=1)
            v_full = jnp.concatenate([v_l, v[:, None]], axis=1)
            return paged_attention_on_gathered(
                q, k_full, v_full, positions, append_len=1)

        return _layer_body(cfg, dt, x, layer, lora_l, lora_idx, (b,),
                           lambda a: _rope_single(a, cos, sin),
                           attn_fn, psum_axis=psum_axis)

    x, (ks, vs) = jax.lax.scan(
        layer_fn, x, (params["layers"], *kv_xs, lora_scan_xs(lora)))
    k_rows = jnp.transpose(ks, (1, 0, 2, 3))        # (B, L, KVH, D)
    v_rows = jnp.transpose(vs, (1, 0, 2, 3))
    if quantized:
        k_pages, v_pages, k_scales, v_scales = scatter_kv_quant(
            k_pages, v_pages, k_scales, v_scales, k_rows, v_rows,
            page_tables, positions, active, kv_kind)
    else:
        k_pages, v_pages = scatter_kv(k_pages, v_pages, k_rows, v_rows,
                                      page_tables, positions, active)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if psum_axis is not None:
            logits = _tp_head_logits(x, params["lm_head"], psum_axis,
                                     logits_psum)
        else:
            logits = x.astype(jnp.float32) @ params["lm_head"].astype(
                jnp.float32)
    if quantized:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages
