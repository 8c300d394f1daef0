"""Paged attention: decode-time attention over a block-paged KV cache.

Net-new for the TPU build (the reference delegates paged attention to
external vLLM CUDA kernels; SURVEY.md §7 step 10). Layout decision
(TPU-first): one page pool shared by ALL layers, layer-major + head-major —

    k_pages, v_pages: [n_layers, num_pages, page_size, n_kv_heads, head_dim]

chosen for the hot paths at once: a layer's pages are contiguous, so
the scan over layers hands the kernels the pool whole, viewed as
[n_layers * num_pages, ...], and a page table shifted by
layer * num_pages (no slice, which XLA would copy out before a Pallas
call, and no transpose of the pool); (page, token) are adjacent so
KV writes flatten the pool to [L, P*page_size, KVH, D] and scatter on a
SINGLE index dim (row = page*page_size + offset) — the
two-index-dim form (.at[:, page_idx, :, offset]) lowers to a
pathologically slow XLA scatter on TPU; and the kernel block's last two
dims stay (KVH, head_dim). The kernels' DMAs need that minor dim
lane-aligned, so pools the kernels read are allocated with head_dim
padded to 128 lanes (`pool_head_dim`); every function here takes the
row width from the pool it is handed.

What serving programs use of this module: the pool layout, the gather
path (`gather_kv`, `paged_attention_on_gathered`: the dense decode tick
of a CPU engine and the side every kernel is checked against) and the
row writes (`scatter_kv`, `scatter_kv_quant`). A decode tick on the
kernel path is the ragged tick of one token a slot in every family
(`models/paged_common.one_token_tick`, `ops/ragged_paged_attention.py`).

The two Pallas decode kernels (`paged_decode_attention`: `paged_decode`
a page a grid step, `paged_decode_mp` a block of pages;
`paged_decode_with_new_token` around them) have no caller in a serving
program and are kept with their kernel tests: they step a grid of slots
x page-table width and pay for every step, live slot or not. Page
indices come from the scalar-prefetched page table, so the BlockSpec DMAs
exactly the pages a sequence owns; grid steps past the end of a sequence
re-map to the same page (Pallas elides the repeat DMA) and skip compute.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kv_quant

# TPU vector lanes. The kernels' manual DMAs move whole
# [page, KVH, D] slabs out of the HBM pool, and Mosaic refuses a slab
# whose minor dim is not lane-aligned ("Slice shape along dimension 3
# must be aligned to tiling (128)").
LANES = 128


def pool_head_dim(head_dim: int, impl: str) -> int:
    """Minor dim of the KV pool's rows — THE pool layout rule, applied
    where the engine allocates its pools. The kernel impls store each
    head's row zero-padded up to a whole number of 128-lane vectors
    (head_dim 64 -> 128: the model keeps its published width, the
    cache pays the padding); the gather impl stores head_dim as is.
    Every reader/writer below takes the width from the pool it is
    handed: writers zero-pad fresh rows (`_fit_lanes`), gather readers
    slice back to head_dim, and the kernels pad q so the padded lanes
    contribute exact zeros to every score and output."""
    if impl in ("pallas", "pallas_interpret"):
        return -(-head_dim // LANES) * LANES
    return head_dim


def kernel_layout_error(kv_kind: str, local_kv_heads: int,
                        pool_dtype) -> "str | None":
    """Why the compiled kernels cannot serve this cache geometry, in
    the compiler's words (None = they can). Checked at engine
    construction on a TPU so a refused geometry fails there, not at
    the first tick's Mosaic compile; tests/test_tpu_aot_compile.py
    holds each rule against the real compiler."""
    if kv_kind != "f32":
        return ("int8/fp8 KV pages keep per-(row, head) f32 scales as "
                "[pages, page, KVH]; the kernels' scale DMA is refused: "
                "'Slice shape along dimension 2 must be aligned to "
                "tiling (128), but is KVH'")
    packing = 4 // jnp.dtype(pool_dtype).itemsize
    if local_kv_heads % packing:
        return (f"{local_kv_heads} kv head(s) per shard in a "
                f"{jnp.dtype(pool_dtype).name} pool: 'Slice shape along "
                f"dimension 2 must be aligned to tiling ({packing}), "
                f"but is {local_kv_heads}'")
    return None


def _fit_lanes(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad (or slice) x's minor dim to `width` — the one
    conversion between a model's head_dim and a pool's row width."""
    d = x.shape[-1]
    if d == width:
        return x
    if d > width:
        return x[..., :width]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - d)])


def gather_kv(k_pages: jax.Array, v_pages: jax.Array,
              page_tables: jax.Array, head_dim: int = -1
              ) -> Tuple[jax.Array, jax.Array]:
    """page_tables: [B, max_pages] int32 →
    k/v: [n_layers, B, max_pages*page_size, n_kv_heads, head_dim]
    (layer-major, ready for a scan over layers). head_dim slices a
    lane-padded pool (pool_head_dim) back to the model's width."""
    def one(pages):
        g = pages[:, page_tables]          # [L, B, P, page, KVH, D]
        l, b, p, s, h, d = g.shape
        g = g.reshape(l, b, p * s, h, d)
        return g if head_dim < 0 else _fit_lanes(g, head_dim)
    return one(k_pages), one(v_pages)


def gather_kv_quant(k_pages: jax.Array, v_pages: jax.Array,
                    k_scales: jax.Array, v_scales: jax.Array,
                    page_tables: jax.Array, head_dim: int = -1
                    ) -> Tuple[jax.Array, jax.Array]:
    """Quantized-pool gather (ISSUE 16): pools hold int8/fp8 values
    with per-(row, head) f32 scales ([L, P, page, KVH],
    ops/kv_quant.py layout). Gathers values AND scales by the table,
    dequantizes, and returns the same dense f32 layout as gather_kv —
    the XLA fallback paths stay byte-for-byte identical downstream of
    this call."""
    def one(pages, scales):
        g = pages[:, page_tables]          # [L, B, P, page, KVH, D]
        s = scales[:, page_tables]         # [L, B, P, page, KVH]
        l, b, p, sz, h, d = g.shape
        deq = g.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
        deq = deq.reshape(l, b, p * sz, h, d)
        return deq if head_dim < 0 else _fit_lanes(deq, head_dim)
    return one(k_pages, k_scales), one(v_pages, v_scales)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_tables: jax.Array, seq_lens: jax.Array,
                    layer: int) -> jax.Array:
    """Single-layer decode attention (dense-gather path).

    q: [B, n_heads, head_dim] (one new token per sequence)
    seq_lens: [B] number of valid cached tokens (including the new one)
    Returns [B, n_heads, head_dim].
    """
    g_k = k_pages[layer][page_tables]      # [B, P, page, KVH, D]
    g_v = v_pages[layer][page_tables]
    b, p, s, h, d = g_k.shape
    k = _fit_lanes(g_k.reshape(b, p * s, h, d), q.shape[-1])
    v = _fit_lanes(g_v.reshape(b, p * s, h, d), q.shape[-1])
    return paged_attention_on_gathered(q, k, v, seq_lens)


def paged_attention_on_gathered(q: jax.Array, k: jax.Array, v: jax.Array,
                                seq_lens: jax.Array,
                                append_len: int = 0) -> jax.Array:
    """q: [B, H, D]; k/v: [B, ctx, KVH, D]; seq_lens: [B] → [B, H, D].

    Valid positions: the first seq_lens[b] cached entries plus the last
    `append_len` entries (decode appends the current token's KV at the
    tail before it has been scattered into the pool). GQA: H query heads
    share H//KVH groups. Softmax in float32, invalid positions -> -inf.
    """
    b, h, d = q.shape
    ctx, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    qf = q.reshape(b, kvh, group, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bckd->bkgc", qf, kf) / jnp.sqrt(d)
    idx = jnp.arange(ctx)[None, :]
    mask = idx < seq_lens[:, None]                        # [B, ctx]
    if append_len:
        mask = mask | (idx >= ctx - append_len)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", probs, vf)
    return out.reshape(b, h, d).astype(q.dtype)


def chunk_attention_on_gathered(q: jax.Array, k_ctx: jax.Array,
                                v_ctx: jax.Array, k_chunk: jax.Array,
                                v_chunk: jax.Array, start: jax.Array,
                                chunk_lens: jax.Array) -> jax.Array:
    """Multi-token-query attention over cached context + the chunk itself
    (chunked prefill / prefix-cache suffix prefill).

    q: [B, C, H, D] queries at absolute positions start[b]+i;
    k_ctx/v_ctx: [B, ctx, KVH, D] gathered pool (valid: pos < start[b]);
    k_chunk/v_chunk: [B, C, KVH, D] the chunk's own KV;
    chunk_lens: [B] valid tokens in the chunk.
    Query i attends ctx positions < start[b] and chunk positions j <= i
    (j < chunk_lens[b]). Softmax in float32. Returns [B, C, H, D].
    """
    b, c, h, d = q.shape
    ctx, kvh = k_ctx.shape[1], k_ctx.shape[2]
    group = h // kvh
    qf = q.reshape(b, c, kvh, group, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(d)
    s_ctx = jnp.einsum("bikgd,bckd->bkgic", qf, k_ctx.astype(jnp.float32))
    s_chk = jnp.einsum("bikgd,bjkd->bkgij", qf,
                       k_chunk.astype(jnp.float32))
    ctx_mask = (jnp.arange(ctx)[None, :] < start[:, None])     # [B, ctx]
    i_idx = jnp.arange(c)[:, None]
    j_idx = jnp.arange(c)[None, :]
    chk_mask = ((j_idx <= i_idx)[None]
                & (j_idx[None] < chunk_lens[:, None, None]))   # [B, C, C]
    s_ctx = jnp.where(ctx_mask[:, None, None, None, :],
                      s_ctx * scale, -jnp.inf)
    s_chk = jnp.where(chk_mask[:, None, None, :, :],
                      s_chk * scale, -jnp.inf)
    scores = jnp.concatenate([s_ctx, s_chk], axis=-1)  # [B,KVH,G,C,ctx+C]
    probs = jax.nn.softmax(scores, axis=-1)
    p_ctx, p_chk = probs[..., :ctx], probs[..., ctx:]
    out = (jnp.einsum("bkgic,bckd->bikgd", p_ctx,
                      v_ctx.astype(jnp.float32))
           + jnp.einsum("bkgij,bjkd->bikgd", p_chk,
                        v_chunk.astype(jnp.float32)))
    return out.reshape(b, c, h, d).astype(q.dtype)


def _paged_decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref,
                         *rest, page_size: int, scale: float, kvh: int,
                         quantized: bool = False):
    """Grid (B, max_pages): each step consumes one page for ALL kv heads
    (the per-head loop is unrolled — kvh is small and static), keeping the
    grid shallow so dispatch overhead doesn't dominate decode.

    quantized=True (ISSUE 16): two extra refs after v_ref carry the
    page's per-(row, head) f32 scales; dequant folds into the f32
    upcast of each head's page slice."""
    if quantized:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref,
         m_scr, l_scr, acc_scr) = rest
    else:
        (o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr) = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]
    live = j * page_size < seq_len

    @pl.when(live)
    def _step():
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < seq_len                          # (1, page)
        group = q_ref.shape[2]
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)        # (group, D)
            k = k_ref[0, :, h].astype(jnp.float32)     # (page, D)
            v = v_ref[0, :, h].astype(jnp.float32)     # (page, D)
            if quantized:
                k = k * ks_ref[0, :, h][:, None]
                v = v * vs_ref[0, :, h][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (group, page)
            s = jnp.where(valid, s, -1e30)
            rows = slice(h * group, (h + 1) * group)
            m_prev = m_scr[rows]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[rows] = (l_scr[rows] * corr
                           + jnp.sum(p, axis=1, keepdims=True))
            acc_scr[rows] = acc_scr[rows] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        group = q_ref.shape[2]
        safe_l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).reshape(
            kvh, group, -1).astype(o_ref.dtype)
        m_ref[0] = m_scr[:].reshape(kvh, group, 1)
        l_ref[0] = l_scr[:].reshape(kvh, group, 1)


def _paged_decode_kernel_mp(tables_ref, lens_ref, q_ref, k_hbm, v_hbm,
                            *rest, page_size: int, ppb: int,
                            scale: float, kvh: int,
                            quantized: bool = False):
    """Multi-page variant: grid (B, max_pages // ppb); each step manually
    DMAs its block's ppb pages (all kv heads per page — our pool layout
    keeps heads together) into VMEM and runs one online-softmax update
    over ppb*page_size keys. 8x fewer grid steps and 8x larger matmuls
    than the one-page-per-step BlockSpec kernel, whose per-step dispatch
    overhead dominated decode (~5us x B x max_pages).

    quantized=True (ISSUE 16): two extra HBM refs carry the per-(row,
    head) f32 scale pools; each block DMAs its pages' scale rows in
    the same wave and fuses the dequant multiply into the f32 upcast —
    the streamed context bytes drop to ~1/4 of f32."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, m_ref, l_ref, k_vmem, v_vmem,
         ks_vmem, vs_vmem, sem, m_scr, l_scr, acc_scr) = rest
    else:
        (o_ref, m_ref, l_ref, k_vmem, v_vmem, sem,
         m_scr, l_scr, acc_scr) = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    bk = page_size * ppb
    length = jnp.maximum(lens_ref[b], 1)   # inactive rows attend 1 page

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(i * bk < length)
    def _step():
        last = jnp.maximum((length - 1) // page_size, 0)

        def copies():
            out = []
            for t in range(ppb):
                idx = tables_ref[b, jnp.minimum(i * ppb + t, last)]
                out.append(pltpu.make_async_copy(
                    k_hbm.at[idx], k_vmem.at[t], sem))
                out.append(pltpu.make_async_copy(
                    v_hbm.at[idx], v_vmem.at[t], sem))
                if quantized:
                    out.append(pltpu.make_async_copy(
                        ks_hbm.at[idx], ks_vmem.at[t], sem))
                    out.append(pltpu.make_async_copy(
                        vs_hbm.at[idx], vs_vmem.at[t], sem))
            return out

        for c in copies():
            c.start()
        for c in copies():
            c.wait()

        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = pos < length                           # (1, bk)
        group = q_ref.shape[2]
        d = q_ref.shape[3]
        # [ppb, page, kvh, D] -> per-head [bk, D]
        kb = k_vmem[...].astype(jnp.float32)
        vb = v_vmem[...].astype(jnp.float32)
        if quantized:
            # fused dequant against the scale rows from the same wave
            kb = kb * ks_vmem[...][..., None]
            vb = vb * vs_vmem[...][..., None]
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)        # (group, D)
            k = kb[:, :, h].reshape(bk, d)
            v = vb[:, :, h].reshape(bk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (group, bk)
            s = jnp.where(valid, s, -1e30)
            rows = slice(h * group, (h + 1) * group)
            m_prev = m_scr[rows]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[rows] = (l_scr[rows] * corr
                           + jnp.sum(p, axis=1, keepdims=True))
            acc_scr[rows] = acc_scr[rows] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows] = m_new

    @pl.when(i == n_blocks - 1)
    def _finish():
        group = q_ref.shape[2]
        safe_l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).reshape(
            kvh, group, -1).astype(o_ref.dtype)
        m_ref[0] = m_scr[:].reshape(kvh, group, 1)
        l_ref[0] = l_scr[:].reshape(kvh, group, 1)


def _paged_decode_multipage(q, k_pages, v_pages, page_tables, seq_lens,
                            ppb: int, interpret: bool = False,
                            k_scales=None, v_scales=None,
                            scale: "float | None" = None):
    b, h, d = q.shape                       # d: the POOL's row width
    _, page_size, kvh, _ = k_pages.shape
    max_pages = page_tables.shape[1]
    group = h // kvh
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, kvh, group, d)
    n_blocks = max(-(-max_pages // ppb), 1)
    quantized = k_scales is not None

    fixed = lambda bi, i, tables, lens: (bi, 0, 0, 0)
    out_spec = pl.BlockSpec((1, kvh, group, d), fixed)
    stat_spec = pl.BlockSpec((1, kvh, group, 1), fixed)
    in_specs = [
        pl.BlockSpec((1, kvh, group, d), fixed),
        pl.BlockSpec(memory_space=pl.ANY),   # k pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # v pool stays in HBM
    ]
    inputs = [qg, k_pages, v_pages]
    scratch = [
        pltpu.VMEM((ppb, page_size, kvh, d), k_pages.dtype),
        pltpu.VMEM((ppb, page_size, kvh, d), v_pages.dtype),
    ]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]
        scratch += [pltpu.VMEM((ppb, page_size, kvh), jnp.float32),
                    pltpu.VMEM((ppb, page_size, kvh), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel_mp, page_size=page_size,
                          ppb=ppb, scale=scale, kvh=kvh,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=in_specs,
            out_specs=(out_spec, stat_spec, stat_spec),
            scratch_shapes=scratch + [
                pltpu.SemaphoreType.DMA,
                pltpu.VMEM((kvh * group, 1), jnp.float32),
                pltpu.VMEM((kvh * group, 1), jnp.float32),
                pltpu.VMEM((kvh * group, d), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
            jax.ShapeDtypeStruct((b, kvh, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, group, 1), jnp.float32),
        ),
        interpret=interpret,
        name="paged_decode_mp",
    )(page_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      *inputs)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           seq_lens: jax.Array, *,
                           return_stats: bool = False,
                           pages_per_block: int = 16,
                           k_scales: jax.Array = None,
                           v_scales: jax.Array = None,
                           interpret: bool = False):
    """Pallas paged decode attention for one layer.

    q: [B, H, D]; k_pages/v_pages: [num_pages, page_size, KVH, D]
    (one layer's pages, or the pool of all layers flattened over them
    with the table shifted to the layer's pages);
    page_tables: [B, max_pages] int32;
    seq_lens: [B] int32. Returns [B, H, D], or with return_stats=True
    (out, m, l) where m/l are the [B, H] online-softmax row max /
    denominator — callers merge extra not-yet-paged KV (the token being
    decoded) with one more online-softmax step.

    The page-table BlockSpec index map clamps the page index for grid
    steps past a sequence's last page to the sequence's final page:
    consecutive identical block indices make Pallas skip the DMA, and
    `pl.when` skips the compute, so per-sequence work is proportional to
    ceil(seq_len / page_size), not max_pages.
    """
    b, h, head_dim = q.shape
    _, page_size, kvh, d = k_pages.shape
    max_pages = page_tables.shape[1]
    quantized = k_scales is not None
    scale = head_dim ** -0.5
    # a lane-padded pool (pool_head_dim): zero-padded q lanes score 0
    # against any k lanes, and the pool's own pad lanes are zeros, so
    # the padded output lanes are exact zeros — sliced off below
    q = _fit_lanes(q, d)
    # interpret mode stays on the one-page kernel: the engine-level CPU
    # suites assert bf16 token-exactness against gather, which the
    # multi-page kernel's accumulation order breaks by rounding alone
    # (f32 logits agree to 2e-6). The multi-page kernel is held by
    # test_multipage_kernel_matches_dense_gather (interpret), by
    # tests/test_tpu_aot_compile.py (the real compiler) and by
    # chip_smoke.py's on-chip logits check against gather.
    if not interpret and max_pages >= pages_per_block > 1:
        out, m, l = _paged_decode_multipage(
            q, k_pages, v_pages, page_tables, seq_lens, pages_per_block,
            k_scales=k_scales, v_scales=v_scales, scale=scale)
        out = _fit_lanes(out.reshape(b, h, d), head_dim)
        if return_stats:
            return out, m.reshape(b, h), l.reshape(b, h)
        return out
    group = h // kvh
    qg = q.reshape(b, kvh, group, d)

    def page_index(bi, j, tables, lens):
        last = jnp.maximum((lens[bi] - 1) // page_size, 0)
        return (tables[bi, jnp.minimum(j, last)], 0, 0, 0)

    def scale_index(bi, j, tables, lens):
        last = jnp.maximum((lens[bi] - 1) // page_size, 0)
        return (tables[bi, jnp.minimum(j, last)], 0, 0)

    grid = (b, max_pages)
    out_spec = pl.BlockSpec(
        (1, kvh, group, d), lambda bi, j, tables, lens: (bi, 0, 0, 0))
    stat_spec = pl.BlockSpec(
        (1, kvh, group, 1), lambda bi, j, tables, lens: (bi, 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, kvh, group, d),
                     lambda bi, j, tables, lens: (bi, 0, 0, 0)),
        pl.BlockSpec((1, page_size, kvh, d), page_index),
        pl.BlockSpec((1, page_size, kvh, d), page_index),
    ]
    inputs = [qg, k_pages, v_pages]
    if quantized:
        # scale blocks ride the same clamped page-index map as their
        # pages, so the DMA-elision for past-the-end steps holds
        in_specs += [pl.BlockSpec((1, page_size, kvh), scale_index),
                     pl.BlockSpec((1, page_size, kvh), scale_index)]
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]
    out, m, l = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=page_size,
                          scale=scale, kvh=kvh, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=(out_spec, stat_spec, stat_spec),
            scratch_shapes=[
                pltpu.VMEM((kvh * group, 1), jnp.float32),
                pltpu.VMEM((kvh * group, 1), jnp.float32),
                pltpu.VMEM((kvh * group, d), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
            jax.ShapeDtypeStruct((b, kvh, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, group, 1), jnp.float32),
        ),
        interpret=interpret,
        name="paged_decode",
    )(page_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      *inputs)
    out = _fit_lanes(out.reshape(b, h, d), head_dim)
    if return_stats:
        return out, m.reshape(b, h), l.reshape(b, h)
    return out


def paged_decode_with_new_token(q: jax.Array, k_pages: jax.Array,
                                v_pages: jax.Array, page_tables: jax.Array,
                                seq_lens: jax.Array, k_new: jax.Array,
                                v_new: jax.Array, *,
                                k_scales: jax.Array = None,
                                v_scales: jax.Array = None,
                                interpret: bool = False) -> jax.Array:
    """Kernel decode over cached pages + one online-softmax merge step for
    the current token's KV (not yet scattered into the pool).

    q/k_new/v_new: [B, H, D] / [B, KVH, D] / [B, KVH, D];
    seq_lens counts CACHED tokens only. Returns [B, H, D].
    k_scales/v_scales: per-(row, head) f32 scales when the pools are
    int8/fp8 (ISSUE 16); the new token's KV stays full-precision.
    """
    b, h, d = q.shape
    kvh = k_new.shape[1]
    group = h // kvh
    scale = d ** -0.5
    out, m, l = paged_decode_attention(
        q, k_pages, v_pages, page_tables, seq_lens,
        return_stats=True, k_scales=k_scales, v_scales=v_scales,
        interpret=interpret)
    # score of the new token against itself (always attendable)
    qf = q.reshape(b, kvh, group, d).astype(jnp.float32)
    kf = k_new.astype(jnp.float32)
    s_new = jnp.einsum("bkgd,bkd->bkg", qf, kf).reshape(b, h) * scale
    m_tot = jnp.maximum(m, s_new)
    c_old = jnp.exp(m - m_tot)
    c_new = jnp.exp(s_new - m_tot)
    l_tot = l * c_old + c_new
    vf = jnp.repeat(v_new.astype(jnp.float32), group, axis=1)  # [B, H, D]
    num = (out.astype(jnp.float32) * (l * c_old)[..., None]
           + vf * c_new[..., None])
    return (num / jnp.maximum(l_tot, 1e-30)[..., None]).astype(q.dtype)


def scatter_kv(k_pages: jax.Array, v_pages: jax.Array,
               k_new: jax.Array, v_new: jax.Array,
               page_tables: jax.Array, positions: jax.Array,
               valid: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write new KV rows into the page pool.

    k_new/v_new: [N, n_layers, n_kv_heads, head_dim] (N tokens, any mix of
    sequences); page_tables: [N, max_pages] each token's OWN sequence
    table; positions: [N] absolute position of each token; valid: [N]
    bool — invalid rows write to a scratch page (the last page, which the
    allocator never hands out) instead of branching.

    Single-index-dim scatter over the flattened [L, P*page_size, KVH, D]
    view: row = page*page_size + offset. (The earlier two-index-dim form
    .at[:, page_idx, :, offset] lowered to an XLA scatter that took
    SECONDS per call on TPU.)
    """
    l, num_pages, page_size, kvh, d = k_pages.shape
    scratch = num_pages - 1
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(valid, page_idx, scratch)
    rows = page_idx * page_size + positions % page_size          # [N]
    flat = lambda p: p.reshape(l, num_pages * page_size, kvh, d)
    # a single advanced index keeps its position: the updated view is
    # [L, N, KVH, D], so swap k_new's leading dims to match (rows
    # zero-padded to a lane-padded pool's width)
    k_rows = jnp.swapaxes(_fit_lanes(k_new, d), 0, 1)
    v_rows = jnp.swapaxes(_fit_lanes(v_new, d), 0, 1)
    k_pages = flat(k_pages).at[:, rows].set(k_rows).reshape(k_pages.shape)
    v_pages = flat(v_pages).at[:, rows].set(v_rows).reshape(v_pages.shape)
    return k_pages, v_pages


def scatter_kv_quant(k_pages: jax.Array, v_pages: jax.Array,
                     k_scales: jax.Array, v_scales: jax.Array,
                     k_new: jax.Array, v_new: jax.Array,
                     page_tables: jax.Array, positions: jax.Array,
                     valid: jax.Array, kind: str
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """scatter_kv for quantized pools: quantize-at-append (ISSUE 16).

    Same row math as scatter_kv, but the fresh f32 rows are quantized
    to `kind` (int8/fp8) with per-(row, head) scales before the write,
    and the scale rows land in the [L, P, page, KVH] scale pools at the
    same flat rows. Append stays write-only: each row carries its own
    scale, so no neighbour rows are re-read. Invalid rows hit the
    scratch page in both the value and scale pools.
    """
    l, num_pages, page_size, kvh, d = k_pages.shape
    scratch = num_pages - 1
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(valid, page_idx, scratch)
    rows = page_idx * page_size + positions % page_size          # [N]
    kq, ks = kv_quant.quantize_rows(k_new, kind)   # [N,L,KVH,D]/[N,L,KVH]
    vq, vs = kv_quant.quantize_rows(v_new, kind)
    kq, vq = _fit_lanes(kq, d), _fit_lanes(vq, d)
    flat = lambda p: p.reshape(l, num_pages * page_size, kvh, d)
    flat_s = lambda s: s.reshape(l, num_pages * page_size, kvh)
    k_pages = flat(k_pages).at[:, rows].set(
        jnp.swapaxes(kq, 0, 1)).reshape(k_pages.shape)
    v_pages = flat(v_pages).at[:, rows].set(
        jnp.swapaxes(vq, 0, 1)).reshape(v_pages.shape)
    k_scales = flat_s(k_scales).at[:, rows].set(
        jnp.swapaxes(ks, 0, 1)).reshape(k_scales.shape)
    v_scales = flat_s(v_scales).at[:, rows].set(
        jnp.swapaxes(vs, 0, 1)).reshape(v_scales.shape)
    return k_pages, v_pages, k_scales, v_scales
