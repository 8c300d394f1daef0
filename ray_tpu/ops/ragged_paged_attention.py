"""Ragged paged attention: ONE attention program for mixed
prefill+decode batches over the paged KV cache.

"Ragged Paged Attention" (PAPERS.md) is the key TPU-serving kernel:
instead of dispatching a chunked-prefill program per prompt AND a
separate whole-batch decode program per engine tick, a single program
consumes a flat ("ragged") token batch where each active slot
contributes between 1 token (decoding) and C tokens (prefilling).
Decode is just the n_tokens == 1 degenerate case of chunked prefill, so
one causal-masking rule covers both:

    token t of slot s at absolute position p attends
      - cached context of s:   pool positions c with c < start[s]
      - batch tokens of s:     tokens u with positions[u] <= p

The flat packing (not a padded [B, C] grid) is the point: a tick with 7
decode slots and one 64-token chunk costs 71 token-positions of
compute, not 8 x 64. Pool layout matches ops/paged_attention.py
([n_layers, num_pages, page_size, n_kv_heads, head_dim]).

Two implementations:
- dense gather (`ragged_prefill_decode_attention` /
  `ragged_paged_prefill_decode_attention`): the CPU/XLA reference —
  materializes each token's gathered context, O(T * ctx * KVH * D)
  transient per layer.
- Pallas kernel (`ragged_paged_attention_pallas`): flash-style online
  softmax that STREAMS each slot's KV pages through VMEM (manual DMA
  off the scalar-prefetched page table, the
  `_paged_decode_kernel_mp` scaffolding) and applies the per-slot
  causal rule blockwise — no [T, ctx] score or gathered-context
  tensor ever exists. Decode rows (1 token) and prefill chunks
  (C tokens) share the one program.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _fit_lanes

# default flash block sizes for the Pallas ragged kernel (shared with
# the benches' analytic staging-size math — keep in one place)
DEFAULT_Q_BLOCK = 8
DEFAULT_PAGES_PER_BLOCK = 8


def ragged_prefill_decode_attention(
        q: jax.Array, k_ctx: jax.Array, v_ctx: jax.Array,
        k_new: jax.Array, v_new: jax.Array, slot_ids: jax.Array,
        positions: jax.Array, valid: jax.Array, start: jax.Array
) -> jax.Array:
    """Core ragged attention over gathered context + the batch's own KV.

    q: [T, H, D] queries of the flat ragged token batch; k_ctx/v_ctx:
    [B, ctx, KVH, D] gathered pool context per slot (position-major —
    row c holds the KV cached at absolute position c); k_new/v_new:
    [T, KVH, D] the batch's own (not yet scattered) KV; slot_ids: [T]
    owning slot per token; positions: [T] absolute position per token;
    valid: [T] bool (padding rows excluded everywhere); start: [B]
    cached tokens per slot (the per-slot causal boundary).

    Token t attends its slot's context positions c < start[slot] plus
    batch tokens u of the same slot with positions[u] <= positions[t].
    GQA (H // KVH query heads per kv head), softmax in float32.
    Returns [T, H, D].

    Every token also attends ITSELF unconditionally — a no-op for
    valid tokens (the causal rule already includes them) that keeps
    padding rows finite: an all-masked row softmaxes to NaN, the NaN
    poisons that row's K/V projection at the next layer, and a
    0-probability x NaN-value product then contaminates every real
    row of the batch (IEEE 0*NaN=NaN).

    Memory note: k_ctx[slot_ids] duplicates each slot's gathered
    context per token — O(T * ctx * KVH * D) f32 transient per layer.
    Fine at the engine's default budgets; at Sarathi-scale budgets
    over multi-thousand-token contexts this is the term the future
    Pallas ragged kernel removes (it streams pages per slot instead).
    Size the token budget accordingly until then.
    """
    t, h, d = q.shape
    ctx, kvh = k_ctx.shape[1], k_ctx.shape[2]
    group = h // kvh
    scale = 1.0 / jnp.sqrt(d)
    qf = q.reshape(t, kvh, group, d).astype(jnp.float32)
    kc = k_ctx[slot_ids].astype(jnp.float32)          # [T, ctx, KVH, D]
    vc = v_ctx[slot_ids].astype(jnp.float32)
    s_ctx = jnp.einsum("tkgd,tckd->tkgc", qf, kc)
    s_new = jnp.einsum("tkgd,ukd->tkgu", qf, k_new.astype(jnp.float32))
    ctx_mask = (jnp.arange(ctx)[None, :]
                < start[slot_ids][:, None])            # [T, ctx]
    new_mask = ((slot_ids[:, None] == slot_ids[None, :])
                & (positions[None, :] <= positions[:, None])
                & valid[None, :]) | jnp.eye(t, dtype=bool)  # [T, T]
    s_ctx = jnp.where(ctx_mask[:, None, None, :], s_ctx * scale,
                      -jnp.inf)
    s_new = jnp.where(new_mask[:, None, None, :], s_new * scale,
                      -jnp.inf)
    scores = jnp.concatenate([s_ctx, s_new], axis=-1)  # [T,KVH,G,ctx+T]
    probs = jax.nn.softmax(scores, axis=-1)
    p_ctx, p_new = probs[..., :ctx], probs[..., ctx:]
    out = (jnp.einsum("tkgc,tckd->tkgd", p_ctx, vc)
           + jnp.einsum("tkgu,ukd->tkgd", p_new,
                        v_new.astype(jnp.float32)))
    return out.reshape(t, h, d).astype(q.dtype)


def ragged_paged_prefill_decode_attention(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        page_tables: jax.Array, slot_ids: jax.Array,
        positions: jax.Array, valid: jax.Array, start: jax.Array,
        k_new: jax.Array, v_new: jax.Array,
        ctx_pages: int = -1) -> jax.Array:
    """Single-layer convenience: gather each slot's pages then run the
    ragged attention (what the model forward does once for all layers).

    k_pages/v_pages: [num_pages, page_size, KVH, D] (already sliced to
    the layer); page_tables: [B, max_pages]; ctx_pages (static) bounds
    the gather to the context that exists (-1 = the whole table).
    """
    tables = (page_tables if ctx_pages < 0
              else page_tables[:, :ctx_pages])
    g_k = k_pages[tables]                   # [B, P, page, KVH, D]
    g_v = v_pages[tables]
    b, p, s, kvh, d = g_k.shape
    hd = q.shape[-1]                        # pool may be lane-padded
    return ragged_prefill_decode_attention(
        q, _fit_lanes(g_k.reshape(b, p * s, kvh, d), hd),
        _fit_lanes(g_v.reshape(b, p * s, kvh, d), hd),
        k_new, v_new, slot_ids, positions, valid, start)


def ragged_attention_dense_oracle(
        q, dense_k, dense_v, k_new, v_new, slot_ids, positions, valid,
        start) -> np.ndarray:
    """CPU-exact dense reference for the ragged op (numpy, per-token
    loops — slow and obviously correct; the property tests' ground
    truth).

    dense_k/dense_v: [B, max_ctx, KVH, D] each slot's cached KV in
    position order (row p = the KV written at absolute position p);
    everything else as in ragged_prefill_decode_attention. Output rows
    for invalid tokens are zero.
    """
    q = np.asarray(q, np.float32)
    dense_k = np.asarray(dense_k, np.float32)
    dense_v = np.asarray(dense_v, np.float32)
    k_new = np.asarray(k_new, np.float32)
    v_new = np.asarray(v_new, np.float32)
    slot_ids = np.asarray(slot_ids)
    positions = np.asarray(positions)
    valid = np.asarray(valid)
    start = np.asarray(start)
    t, h, d = q.shape
    kvh = k_new.shape[1]
    group = h // kvh
    out = np.zeros_like(q)
    for i in range(t):
        if not valid[i]:
            continue
        s = int(slot_ids[i])
        keys = [dense_k[s, :start[s]]]                 # [n_ctx, KVH, D]
        vals = [dense_v[s, :start[s]]]
        mates = [j for j in range(t)
                 if valid[j] and slot_ids[j] == s
                 and positions[j] <= positions[i]]
        keys.append(k_new[mates])
        vals.append(v_new[mates])
        kk = np.repeat(np.concatenate(keys), group, axis=1)  # [n, H, D]
        vv = np.repeat(np.concatenate(vals), group, axis=1)
        sc = np.einsum("hd,nhd->hn", q[i], kk) / np.sqrt(d)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hn,nhd->hd", p, vv)
    return out


# ----------------------------------------------------- Pallas ragged kernel

def _ragged_paged_kernel(tables_ref, start_ref, qlen_ref, q_ref, k_hbm,
                         v_hbm, *rest, page_size: int,
                         ppb: int, n_ctx_blocks: int, q_blk: int,
                         scale: float, kvh: int, group: int,
                         quantized: bool = False):
    """Grid (B, NQ, NK): slot b x query block qb x kv block i.

    kv blocks [0, n_ctx_blocks) stream the slot's CACHED context pages
    (ppb pages manually DMA'd per step off the scalar-prefetched page
    table, exactly the `_paged_decode_kernel_mp` pattern); blocks
    [n_ctx_blocks, NK) are the slot's own IN-BATCH KV, block-diagonal
    causal (new block jb only feeds query blocks qb >= jb since both
    use the same q_blk tokens). Online-softmax state (m/l/acc) lives in
    scratch across the NK sweep of one (b, qb) block; compute for
    blocks past the slot's context/segment is skipped via pl.when, so
    per-slot cost scales with the KV that EXISTS — a decode row pays
    one q block over ceil(start/page_size) pages, never a [T, ctx]
    score tensor.

    Per-slot causal rule, blockwise: context position c attends iff
    c < start[b]; in-batch key offset j attends query offset i iff
    j <= i and j < q_len[b] (the engine packs each slot's tokens
    contiguously at positions start[b] + rank, so offset order IS
    position order).

    quantized=True (ISSUE 16): the pools hold int8/fp8 values and two
    extra HBM refs carry the per-(row, head) f32 scales
    ([num_pages, page, KVH], ops/kv_quant.py layout). Each context
    step DMAs the scale rows of its ppb pages alongside the pages
    themselves and folds the dequant — one broadcast multiply — into
    the existing f32 upcast of the VMEM block, so the quantized
    kernel streams ~1/4 the context bytes with no extra pass. The
    fresh in-batch KV (kn/vn) is never quantized.
    """
    if quantized:
        (ks_hbm, vs_hbm, kn_ref, vn_ref, o_ref, k_vmem, v_vmem,
         ks_vmem, vs_vmem, sem, m_scr, l_scr, acc_scr) = rest
    else:
        (kn_ref, vn_ref, o_ref, k_vmem, v_vmem,
         sem, m_scr, l_scr, acc_scr) = rest
    b = pl.program_id(0)
    qb = pl.program_id(1)
    i = pl.program_id(2)
    nk = pl.num_programs(2)
    bk = page_size * ppb
    r = q_blk * group                      # score rows per kv head

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx_len = start_ref[b]
    qlen = qlen_ref[b]
    live_q = qb * q_blk < qlen
    d = q_ref.shape[3]

    def online_update(h, s, v):
        """One flash step for kv head h: s (r, n) masked scores,
        v (n, D) values."""
        rows = slice(h * r, (h + 1) * r)
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

    @pl.when(live_q & (i < n_ctx_blocks) & (i * bk < ctx_len))
    def _ctx_step():
        last = jnp.maximum((ctx_len - 1) // page_size, 0)

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
        keep = pos < ctx_len                           # (1, bk)
        kb = k_vmem[...].astype(jnp.float32)           # (ppb, page, kvh, D)
        vb = v_vmem[...].astype(jnp.float32)
        if quantized:
            # the fused dequant: one multiply against the scale rows
            # that rode the same DMA wave as their pages
            kb = kb * ks_vmem[...][..., None]
            vb = vb * vs_vmem[...][..., None]
        for h in range(kvh):
            q = q_ref[0, :, h * group:(h + 1) * group, :].reshape(
                r, d).astype(jnp.float32)
            k = kb[:, :, h].reshape(bk, d)
            v = vb[:, :, h].reshape(bk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (r, bk)
            online_update(h, jnp.where(keep, s, -1e30), v)

    jb = i - n_ctx_blocks
    @pl.when(live_q & (i >= n_ctx_blocks) & (jb <= qb)
             & (jb * q_blk < qlen))
    def _new_step():
        # query offset per score row / key offset per column, in the
        # slot's segment (offset order == position order)
        i_tok = (qb * q_blk + jax.lax.broadcasted_iota(
            jnp.int32, (r, q_blk), 0) // group)
        j_tok = jb * q_blk + jax.lax.broadcasted_iota(
            jnp.int32, (r, q_blk), 1)
        keep = (j_tok <= i_tok) & (j_tok < qlen)       # (r, q_blk)
        for h in range(kvh):
            q = q_ref[0, :, h * group:(h + 1) * group, :].reshape(
                r, d).astype(jnp.float32)
            k = kn_ref[0, :, h].astype(jnp.float32)    # (q_blk, D)
            v = vn_ref[0, :, h].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (r, q_blk)
            online_update(h, jnp.where(keep, s, -1e30), v)

    @pl.when(i == nk - 1)
    def _finish():
        # all-masked rows (query padding / empty slots) have l == 0 and
        # acc == 0: the epsilon floor makes them exact zeros, keeping
        # every output row finite (the caller re-masks by `valid`)
        safe_l = jnp.maximum(l_scr[:], 1e-30)
        out = acc_scr[:] / safe_l                      # (kvh*r, D)
        for h in range(kvh):
            rows = slice(h * r, (h + 1) * r)
            o_ref[0, :, h * group:(h + 1) * group, :] = out[rows].reshape(
                q_blk, group, d).astype(o_ref.dtype)


def ragged_paged_attention_pallas(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        page_tables: jax.Array, slot_ids: jax.Array,
        positions: jax.Array, valid: jax.Array, start: jax.Array,
        k_new: jax.Array, v_new: jax.Array, *, ctx_pages: int = -1,
        max_seg_len: int = -1, q_block: int = DEFAULT_Q_BLOCK,
        pages_per_block: int = DEFAULT_PAGES_PER_BLOCK,
        k_scales: jax.Array = None, v_scales: jax.Array = None,
        interpret: bool = False) -> jax.Array:
    """TPU Pallas ragged paged attention: same contract as
    `ragged_paged_prefill_decode_attention`, but each slot's KV pages
    are STREAMED through VMEM with online softmax — no [T, ctx] score
    and no gathered [T, ctx, KVH, D] context is ever materialized.

    q: [T, H, D] flat ragged batch (kv-major head order);
    k_pages/v_pages: [num_pages, page_size, KVH, D] (layer slice,
    stays in HBM); page_tables: [B, max_pages]; slot_ids/positions/
    valid: [T]; start: [B]; k_new/v_new: [T, KVH, D].

    Packing contract (what the engine's `_ragged_step` produces, and
    what the kernel's segment formulation requires): each slot's valid
    tokens form ONE run in position order with
    positions[t] == start[slot_ids[t]] + rank-within-slot (flat order
    of the run is irrelevant — tokens are re-packed per slot here).
    Invalid rows are ignored on input and zero on output.

    Static knobs: ctx_pages bounds the context sweep (-1 = whole
    table); max_seg_len bounds any single slot's token count
    (-1 = T) — the engine passes its chunk cap so decode-heavy ticks
    don't pad to T; q_block / pages_per_block are the flash block
    sizes. The per-slot padded Q/O/new-KV staging arrays are
    [B, ceil(max_seg_len/q_block)*q_block, ...] — O(B * C * H * D),
    vs the gather path's O(T * ctx * KVH * D) context transient.

    Quantized KV (ISSUE 16): pass k_scales/v_scales
    ([num_pages, page_size, KVH] f32, ops/kv_quant.py layout) when
    the pools hold int8/fp8 values; the kernel DMAs the scale rows
    beside their pages and fuses the dequant multiply into the
    streaming loop. k_new/v_new stay full-precision either way.
    """
    t, h, head_dim = q.shape
    _, page_size, kvh, d = k_pages.shape
    b = page_tables.shape[0]
    group = h // kvh
    scale = head_dim ** -0.5
    # a lane-padded pool (paged_attention.pool_head_dim): the kernel
    # runs at the pool's row width; zero-padded q/new-KV lanes add
    # exact zeros to every score and output, sliced off at the end
    q = _fit_lanes(q, d)
    k_new = _fit_lanes(k_new, d)
    v_new = _fit_lanes(v_new, d)
    tables = (page_tables if ctx_pages < 0
              else page_tables[:, :max(ctx_pages, 1)])
    n_ctx_pages = tables.shape[1] if ctx_pages != 0 else 0
    ppb = max(min(pages_per_block, n_ctx_pages), 1)
    n_ctx_blocks = -(-n_ctx_pages // ppb) if n_ctx_pages else 0

    q_max = t if max_seg_len < 0 else max(min(max_seg_len, t), 1)
    q_blk = max(min(q_block, q_max), 1)
    nq = -(-q_max // q_blk)
    qp = nq * q_blk
    nk = n_ctx_blocks + nq

    # per-slot repack: token -> (slot, offset-within-segment); invalid
    # rows land in a dummy slot row b that the grid never reads
    off = jnp.clip(positions - start[slot_ids], 0, qp - 1)
    row = jnp.where(valid, slot_ids, b)
    q_pad = jnp.zeros((b + 1, qp, h, d), q.dtype).at[row, off].set(q)
    kn_pad = jnp.zeros((b + 1, qp, kvh, d),
                       k_new.dtype).at[row, off].set(k_new)
    vn_pad = jnp.zeros((b + 1, qp, kvh, d),
                       v_new.dtype).at[row, off].set(v_new)
    qlen = jnp.zeros((b,), jnp.int32).at[
        jnp.where(valid, slot_ids, 0)].add(valid.astype(jnp.int32))

    io_spec = pl.BlockSpec(
        (1, q_blk, h, d),
        lambda bi, qb, i, tables, start, qlen: (bi, qb, 0, 0))

    def new_kv_index(bi, qb, i, tables, start, qlen):
        # clamp to the causal diagonal: blocks past qb are fully
        # masked, re-mapping them to qb elides the DMA entirely
        jb = jnp.clip(i - n_ctx_blocks, 0, nq - 1)
        return (bi, jnp.minimum(jb, qb), 0, 0)

    new_spec = pl.BlockSpec((1, q_blk, kvh, d), new_kv_index)

    quantized = k_scales is not None
    if quantized and v_scales is None:
        raise ValueError("k_scales and v_scales must come together")
    in_specs = [
        io_spec,                             # padded queries
        pl.BlockSpec(memory_space=pl.ANY),   # k pool in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # v pool in HBM
    ]
    scratch = [
        pltpu.VMEM((ppb, page_size, kvh, d), k_pages.dtype),
        pltpu.VMEM((ppb, page_size, kvh, d), v_pages.dtype),
    ]
    inputs = [q_pad, k_pages, v_pages]
    if quantized:
        # scale pools ride beside the page pools: HBM-resident, DMA'd
        # per context block into their own VMEM scratch rows
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((ppb, page_size, kvh), jnp.float32),
                    pltpu.VMEM((ppb, page_size, kvh), jnp.float32)]
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]
    in_specs += [new_spec, new_spec]         # padded new k / new v
    inputs += [kn_pad, vn_pad]

    out = pl.pallas_call(
        functools.partial(
            _ragged_paged_kernel, page_size=page_size, ppb=ppb,
            n_ctx_blocks=n_ctx_blocks, q_blk=q_blk, scale=scale,
            kvh=kvh, group=group, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nq, nk),
            in_specs=in_specs,
            out_specs=io_spec,
            scratch_shapes=scratch + [
                pltpu.SemaphoreType.DMA,
                pltpu.VMEM((kvh * q_blk * group, 1), jnp.float32),
                pltpu.VMEM((kvh * q_blk * group, 1), jnp.float32),
                pltpu.VMEM((kvh * q_blk * group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, qp, h, d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(tables.astype(jnp.int32), start.astype(jnp.int32), qlen,
      *inputs)

    flat = _fit_lanes(out[jnp.where(valid, slot_ids, 0), off],
                      head_dim)                        # [T, H, D]
    return jnp.where(valid[:, None, None], flat,
                     jnp.zeros_like(flat)).astype(q.dtype)
