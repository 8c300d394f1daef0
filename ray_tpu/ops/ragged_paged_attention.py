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
  off the scalar-prefetched page table, double-buffered) and applies
  the per-slot causal rule blockwise — no [T, ctx] score or
  gathered-context tensor ever exists. Its grid is a WORK LIST: one
  step per (slot, block of up to 128 of the slot's tokens) that the
  tick holds, built on the device from the token counts the tick
  uploads (`ragged_work_list`) and padded to a static bound of
  ceil(T / 128) + slots; a step loops over the context blocks that
  exist for its slot and the in-batch blocks up to the causal
  diagonal, so a tick costs its tokens and their contexts, not its
  bucket. Decode rows (1 token) and prefill chunks (C tokens) share
  the one program; q, new K/V and the output are read and written in
  place in the flat [T, ...] arrays.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _fit_lanes


def ragged_prefill_decode_attention(
        q: jax.Array, k_ctx: jax.Array, v_ctx: jax.Array,
        k_new: jax.Array, v_new: jax.Array, slot_ids: jax.Array,
        positions: jax.Array, valid: jax.Array, start: jax.Array,
        window: Optional[int] = None) -> jax.Array:
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
    With `window` w (a sliding-window layer) a key at position j is
    kept only if j > positions[t] - w: w keys, the token's own among
    them. GQA (H // KVH query heads per kv head), softmax in float32.
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
                & valid[None, :])
    if window is not None:
        floor = positions[:, None] - window                # keys above it
        ctx_mask = ctx_mask & (jnp.arange(ctx)[None, :] > floor)
        new_mask = new_mask & (positions[None, :] > floor)
    new_mask = new_mask | jnp.eye(t, dtype=bool)           # [T, T]
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
        ctx_pages: int = -1, window: Optional[int] = None) -> jax.Array:
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
        k_new, v_new, slot_ids, positions, valid, start, window)


# gathered context rows (one token's slot's cached keys, every kv head)
# that a block of tokens may hold at once: x 8 heads x 128 lanes x 2 B
# x (K and V) = 0.5 GB at 2**17; a 512-token tick over 16k-token tables
# runs in 64 blocks of 8 tokens, a tick of the CPU tests in one
GATHER_ROWS = 1 << 17


def ragged_gather_paged_blocked(
        q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, layer,
        page_tables: jax.Array, slot_ids: jax.Array,
        positions: jax.Array, valid: jax.Array, start: jax.Array,
        k_new: jax.Array, v_new: jax.Array, *,
        window: Optional[int] = None,
        gather_rows: int = GATHER_ROWS,
        merged_rows: bool = False) -> jax.Array:
    """The ragged attention rule of `ragged_prefill_decode_attention`
    (window included) straight off a WHOLE pool, in blocks of tokens
    that each gather their own slots' pages of `layer` (a traced index
    is fine), at most `gather_rows` context rows at a time: the same
    sums whatever the block, so a 512-token tick over 16k-token tables
    fits beside the weights. Operands as stored, scores, softmax and
    accumulation in float32. The oracle the window kernel is held to:
    it reads every table entry, also those of pages a window group has
    handed back, and masks them.

    q: [T, H, D]; k_pool/v_pool: [L, P, page, KVH, Dp] (merged_rows:
    [L, P, page * KVH, Dp], `CacheRow.layout` "rows"); page_tables:
    [B, n]; k_new/v_new: [T, KVH, D]. Returns [T, H, D] in q's type."""
    t, h, d = q.shape
    n, kvh = page_tables.shape[1], k_new.shape[1]
    page = k_pool.shape[2] // kvh if merged_rows else k_pool.shape[2]
    ctx, group = n * page, h // kvh
    f32 = jnp.float32
    scale = d ** -0.5
    kn, vn = k_new.astype(q.dtype), v_new.astype(q.dtype)

    def block(q_b, slot_b, pos_b, idx_b):
        b = q_b.shape[0]
        own = page_tables[slot_b]                        # [b, n]
        rows = (b, ctx, kvh, k_pool.shape[-1])
        kc = _fit_lanes(k_pool[layer, own].reshape(rows), d)
        vc = _fit_lanes(v_pool[layer, own].reshape(rows), d)
        qg = q_b.reshape(b, kvh, group, d)
        s_ctx = jnp.einsum("tkgd,tckd->tkgc", qg, kc.astype(q.dtype),
                           preferred_element_type=f32) * scale
        s_new = jnp.einsum("tkgd,ukd->tkgu", qg, kn,
                           preferred_element_type=f32) * scale
        col = jnp.arange(ctx)[None, :]
        ctx_mask = col < start[slot_b][:, None]
        new_mask = ((slot_b[:, None] == slot_ids[None, :])
                    & (positions[None, :] <= pos_b[:, None])
                    & valid[None, :])
        if window is not None:
            floor = pos_b[:, None] - window
            ctx_mask = ctx_mask & (col > floor)
            new_mask = new_mask & (positions[None, :] > floor)
        # every token attends itself: padding rows stay finite
        new_mask = new_mask | (idx_b[:, None] == jnp.arange(t)[None, :])
        s_all = jnp.concatenate(
            [jnp.where(ctx_mask[:, None, None, :], s_ctx, -jnp.inf),
             jnp.where(new_mask[:, None, None, :], s_new, -jnp.inf)],
            axis=-1)
        p = jax.nn.softmax(s_all, axis=-1).astype(q.dtype)
        out = (jnp.einsum("tkgc,tckd->tkgd", p[..., :ctx],
                          vc.astype(q.dtype), preferred_element_type=f32)
               + jnp.einsum("tkgu,ukd->tkgd", p[..., ctx:], vn,
                            preferred_element_type=f32))
        return out.reshape(b, h, d).astype(q.dtype)

    idx = jnp.arange(t)
    blk = max(gather_rows // max(ctx, 1), 1)
    if blk >= t:
        return block(q, slot_ids, positions, idx)
    blk = 1 << (blk.bit_length() - 1)
    pad = -t % blk
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                            ).reshape((-1, blk) + a.shape[1:])
    # padding tokens repeat token 0's slot and index: finite, cut off
    out = jax.lax.map(lambda a: block(*a),
                      (cut(q), cut(slot_ids), cut(positions), cut(idx)))
    return out.reshape((-1,) + out.shape[2:])[:t]


def ragged_attention_dense_oracle(
        q, dense_k, dense_v, k_new, v_new, slot_ids, positions, valid,
        start, window: Optional[int] = None) -> np.ndarray:
    """CPU-exact dense reference for the ragged op (numpy, per-token
    loops — slow and obviously correct; the property tests' ground
    truth).

    dense_k/dense_v: [B, max_ctx, KVH, D] each slot's cached KV in
    position order (row p = the KV written at absolute position p);
    everything else as in ragged_prefill_decode_attention, `window`
    too. Output rows for invalid tokens are zero.
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
        # the first position a window still covers
        lo = 0 if window is None else max(int(positions[i]) - window + 1, 0)
        keys = [dense_k[s, lo:start[s]]]               # [n_ctx, KVH, D]
        vals = [dense_v[s, lo:start[s]]]
        mates = [j for j in range(t)
                 if valid[j] and slot_ids[j] == s
                 and lo <= positions[j] <= positions[i]]
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

# One work item is (slot, block of up to Q_BLOCK of its tokens), and an
# item sweeps its KV KV_BLOCK keys at a time: both the MXU's width.
# `ragged_block_sizes` shrinks them to what a small tick or a narrow
# table holds; nothing else sets them.
Q_BLOCK = 128
KV_BLOCK = 128
# an item with at most this many tokens (a decode row, the tail of a
# chunk) runs its flash steps on this many query rows instead of q_blk
SMALL_ROWS = 8
# VMEM the two double-buffered K and V page blocks may take
_KV_VMEM_BYTES = 4 << 20


def ragged_q_block(t: int) -> int:
    """Query rows per work item for a tick of `t` flat tokens."""
    return max(min(Q_BLOCK, t), 1)


def ragged_block_sizes(t: int, page_size: int, n_ctx_pages: int,
                       kvh: int = 1, row_width: int = 128,
                       itemsize: int = 2) -> Tuple[int, int]:
    """(query rows per item, pages per context block) for a tick of
    `t` flat tokens over a table `n_ctx_pages` wide: 128 rows and 128
    keys where the tick and the table hold that many, fewer keys where
    `kvh` heads of `row_width` lanes would outgrow the kernel's VMEM."""
    keys = min(KV_BLOCK, _KV_VMEM_BYTES // (4 * kvh * row_width * itemsize))
    ppb = max(min(keys // page_size, n_ctx_pages), 1)
    return ragged_q_block(t), ppb


def ragged_item_bound(t: int, n_slots: int, q_blk: int) -> int:
    """Static length of the work list: every slot with tokens adds at
    most one partial block to the ceil(t / q_blk) full ones."""
    return -(-t // q_blk) + n_slots


def ragged_work_counts(segs, t: int, page_size: int,
                       n_ctx_pages: int, kvh: int = 1,
                       row_width: int = 128,
                       itemsize: int = 2,
                       window: Optional[int] = None) -> Tuple[int, int]:
    """Host-side count of what the kernel does for a tick whose slots
    hold `segs` = [(cached tokens, tokens this tick)]: (live items,
    KV blocks those items visit, context plus in-batch). Plain ints —
    the engine's dispatch span and the tests' hand counts share it.
    With `window` an item's sweep starts at the first context block its
    first query's window still covers."""
    q_blk, ppb = ragged_block_sizes(t, page_size, n_ctx_pages, kvh,
                                    row_width, itemsize)
    bk = ppb * page_size
    items = kv_blocks = 0
    for start, n in segs:
        n_blk = -(-n // q_blk)
        items += n_blk
        # item qb sweeps the slot's whole cached context, then the
        # in-batch blocks 0..qb (the causal diagonal)
        n_ctx = -(-start // bk) if n_ctx_pages else 0
        kv_blocks += n_blk * n_ctx + n_blk * (n_blk + 1) // 2
        if window is not None:
            # ... less the context blocks wholly behind its first query
            kv_blocks -= sum(
                min(max(start + qb * q_blk - (window - 1), 0) // bk, n_ctx)
                for qb in range(n_blk))
    return items, kv_blocks


def ragged_work_list(slot_ids: jax.Array, valid: jax.Array,
                     start: jax.Array, q_blk: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """The kernel's grid, built on the device from what a tick already
    uploads. Returns (items, segs), both int32:

    items [3, n]: per work item its slot (-1 = no item: the list is
      padded to `ragged_item_bound`), the offset of its first token in
      the slot's segment, and that token's row in the flat batch;
      items run in flat order (the kernel's output writes rely on it).
    segs [3, B]: per slot its cached tokens (`start`), its tokens this
      tick, and the flat row of the first of them.

    Loop-invariant over layers: `ragged_forward` builds it once.
    """
    (t,) = slot_ids.shape
    (b,) = start.shape
    n = ragged_item_bound(t, b, q_blk)
    # invalid rows fall into a dummy slot b
    sid = jnp.where(valid, slot_ids, b)
    qlen = jnp.zeros((b + 1,), jnp.int32).at[sid].add(1)[:b]
    first = jnp.full((b + 1,), t, jnp.int32).at[sid].min(
        jnp.arange(t, dtype=jnp.int32))[:b]
    n_blk = -(-qlen // q_blk)
    order = jnp.argsort(first)             # flat order, empty slots last
    ends = jnp.cumsum(n_blk[order])
    i = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.minimum(jnp.searchsorted(ends, i, side="right"), b - 1)
    slot = order[rank]
    qoff = (i - (ends[rank] - n_blk[slot])) * q_blk
    live = i < ends[-1]
    items = jnp.stack([jnp.where(live, slot, -1),
                       jnp.where(live, qoff, 0),
                       jnp.where(live, first[slot] + qoff, 0)])
    segs = jnp.stack([start.astype(jnp.int32), qlen, first])
    return items.astype(jnp.int32), segs


def _ragged_paged_kernel(items_ref, segs_ref, tables_ref, q_hbm, k_hbm,
                         v_hbm, *rest, page_size: int, ppb: int,
                         n_ctx_pages: int, q_blk: int, small: int,
                         scale: float, kvh: int, group: int,
                         quantized: bool = False,
                         window: Optional[int] = None,
                         merged_rows: bool = False):
    """Grid (n_items,): one step per work item (slot, query block).

    A step reads its q_blk query rows from the flat batch where they
    lie (one DMA at the item's flat row), sweeps the slot's CACHED
    context — ceil(start / bk) blocks of ppb pages DMA'd off the
    scalar-prefetched page table, the next block in flight while this
    one is computed — then the slot's own IN-BATCH KV up to the causal
    diagonal (blocks 0..qb of the slot's run in the flat new-K/V), and
    writes its q_blk output rows back at the same flat row. The sweep
    is one in-kernel loop whose trip count comes from the prefetched
    scalars, so an item costs the KV that EXISTS for its slot and a
    tick costs its items: steps past the live count do nothing at all.

    Online-softmax state (m/l/acc, float32) lives in scratch for the
    item; m and l lie on all 128 lanes of their row, as wide as a score
    tile and an accumulator row, so that subtracting the maximum and
    rescaling the accumulator broadcast nothing across lanes (a
    statistic one lane wide cost a lane permute a vector register at
    each use, and the kernel ran at the pace of those). An item with at
    most `small` tokens (a decode row, a chunk's tail) runs every flash
    step on its first `small` rows only.

    What a KV block costs between its DMA and its two MXU products is
    kept small: the mask's query offsets are made once an item
    (`tok_scr`) and a block's mask is two compares against them, and
    bf16 pages reach the MXU's head-major operands through
    `split_heads` with no float32 copy of the block. The softmax scale
    stays a multiply of the float32 scores: 128 ** -0.5 is no power of
    two, so q x scale rounded to bf16 is another operand (and every
    logits check another draw of its near-ties), for a fiftieth of a
    head's flash step.

    A block's rows past the slot's segment belong to the next slots of
    the flat batch (or to the padding): they are computed under the
    key mask alone, stay finite, and are written too — the items run
    in flat order and each write is waited for, so the next item
    overwrites them with its own rows, and the wrapper zeroes every
    invalid row.

    Per-slot causal rule, blockwise: context position c attends iff
    c < start[slot]; in-batch key offset j attends query offset i iff
    j <= i and j < q_len[slot] (each slot's tokens are one contiguous
    run in position order, so offset order IS position order).

    window=w (static; a sliding-window layer): a key at position j is
    kept only if j > the query's position - w. The context sweep starts
    at the block that holds position (the item's first query - (w - 1)):
    earlier blocks lie behind every query of the item (their pages may
    have gone back to the allocator) and are never read; the lower edge
    is masked inside the boundary block, and the in-batch blocks obey
    the same rule by the mask alone.

    quantized=True (ISSUE 16): the pools hold int8/fp8 values and two
    extra HBM refs carry the per-(row, head) f32 scales
    ([num_pages, page, KVH], ops/kv_quant.py layout). Each context
    block DMAs the scale rows of its pages alongside the pages and
    folds the dequant — one broadcast multiply — into the upcast of
    the VMEM block. The fresh in-batch KV is never quantized.

    merged_rows=True: the pools are [num_pages, page * KVH, D], a
    page's (token, head) rows in one axis, token-major as ever: for a
    number of kv heads that is no multiple of the 8-row tile (10),
    which a [page, KVH, D] page pads to the next one in HBM (1.6 x the
    bytes) and which a page DMA cannot slice. A token's write is still
    KVH adjacent rows; a head's keys of a context block are every
    KVH-th row of it. The in-batch K and V stay [T, KVH, D], their
    heads padded to the tile by the wrapper.

    Either pool form is, in VMEM, a block of (token, head) rows, and a
    head's rows are a strided load off a reshaped view of the page
    buffer. Which load is a STATIC fact of the arguments: bf16 pools
    with an even number of heads (every cell) read 32-bit words, two
    adjacent heads each (`split_heads`); a quantized pool needs
    float32 for its dequant and casts the block whole; anything else
    (float32 pools, an odd head count: the CPU tests) loads a head's
    rows in the pool's own type, the tile form by way of the block's
    float32 value.
    """
    if quantized:
        (ks_hbm, vs_hbm, kn_hbm, vn_hbm, o_hbm, q_vmem, kn_vmem,
         vn_vmem, o_vmem, k_vmem, v_vmem, ks_vmem, vs_vmem, kv_sem,
         io_sem, qh_scr, kh_scr, vh_scr, m_scr, l_scr, acc_scr,
         tok_scr) = rest
    else:
        (kn_hbm, vn_hbm, o_hbm, q_vmem, kn_vmem, vn_vmem, o_vmem,
         k_vmem, v_vmem, kv_sem, io_sem, qh_scr, kh_scr, vh_scr, m_scr,
         l_scr, acc_scr, tok_scr) = rest
    it = pl.program_id(0)
    slot = items_ref[0, it]
    bk = page_size * ppb
    d = q_vmem.shape[-1]
    cdt = q_vmem.dtype                     # the MXU's operand type
    bf16 = jnp.dtype(jnp.bfloat16)

    def lanes(x, n):
        """A statistic (rows, 128), the row's value on every lane, as
        (rows, n): itself beside a score tile of 128 keys or an
        accumulator row of 128 lanes, which is every shape on the chip."""
        return x if x.shape[1] == n else jnp.broadcast_to(
            x[:, :1], (x.shape[0], n))

    def paired(ref):
        """Whether split_heads reads `ref`'s rows: bf16 all the way to
        the MXU and an even number of heads a token (the in-batch
        rows' heads are kvh or kvh padded to the 8-row tile)."""
        return (not quantized and cdt == bf16 and ref.dtype == bf16
                and kvh % 2 == 0)

    def split_heads(rows, n_tok, dst):
        """rows: a ref of bf16 (token, head) rows, two adjacent heads
        of a token in one 32-bit word of its bitcast view. Head h's
        n_tok rows go to dst[h, :n_tok] with no float32 copy of the
        block: one strided load of words a PAIR of heads, the low half
        shifted up and the high half masked out (a bf16 value is the
        upper half of the float32 of the same value, so the cast back
        is exact and is the pack the MXU's operand wants)."""
        heads = rows.shape[0] // n_tok
        words = rows.bitcast(jnp.uint32)   # (n_tok * heads // 2, d)
        for pair in range(kvh // 2):
            w = words[pl.ds(pair, n_tok, stride=heads // 2), :]
            lo = pltpu.bitcast(w << 16, jnp.float32)
            hi = pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)
            dst[2 * pair, :n_tok] = lo.astype(cdt)
            dst[2 * pair + 1, :n_tok] = hi.astype(cdt)

    @pl.when(slot >= 0)
    def _item():
        qoff = items_ref[1, it]
        tok0 = items_ref[2, it]
        ctx_len = segs_ref[0, slot]
        qlen = segs_ref[1, slot]
        first = segs_ref[2, slot]
        n_ctx = (ctx_len + bk - 1) // bk if n_ctx_pages else 0
        # the first context block the item's first query still sees
        lo_blk = 0 if window is None else jnp.minimum(
            jnp.maximum(ctx_len + qoff - (window - 1), 0) // bk, n_ctx)
        last_page = jnp.minimum(jnp.maximum((ctx_len - 1) // page_size, 0),
                                max(n_ctx_pages - 1, 0))

        def page_dma(blk, buf, go):
            """Start (go) or await the DMAs of context block blk's ppb
            pages into half buf of the double buffer. A loop over the
            pages, not ppb unrolled copies: what a program pays to
            trace and lower the kernel grows with its ref operations."""
            def page(t, carry):
                idx = tables_ref[slot,
                                 jnp.minimum(blk * ppb + t, last_page)]
                pairs = [(k_hbm, k_vmem), (v_hbm, v_vmem)]
                if quantized:
                    pairs += [(ks_hbm, ks_vmem), (vs_hbm, vs_vmem)]
                for src, dst in pairs:
                    c = pltpu.make_async_copy(
                        src.at[idx], dst.at[buf, t], kv_sem.at[buf])
                    c.start() if go else c.wait()
                return carry

            jax.lax.fori_loop(0, ppb, page, 0)

        q_copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(tok0, q_blk)], q_vmem, io_sem)
        q_copy.start()
        if n_ctx_pages:
            pl.when(n_ctx > lo_blk)(
                lambda: page_dma(lo_blk, lo_blk % 2, True))
        q_copy.wait()

        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # per kv head its group's queries as one (q_blk * group, D)
        # matrix, rows in (token, group) order
        for h in range(kvh):
            qh_scr[h] = q_vmem[:, h * group:(h + 1) * group, :].reshape(
                q_blk * group, d)
        # the mask's query offsets, once an item: score row i is the
        # query at segment offset qoff + i // group, whatever the block
        # (on every lane, so a block's mask is compares alone)
        tok_scr[...] = qoff + jax.lax.broadcasted_iota(
            jnp.int32, tok_scr.shape, 0) // group

        def flash_heads(r, keep):
            """One flash step per kv head on its first r score rows,
            against the keys and values of kh_scr / vh_scr, masked by
            keep(r). The heads are a loop, not unrolled: the kernel's
            code (what every program traces, lowers and compiles) is
            one head's."""
            mask = keep(r)

            def head(h, carry):
                s = jax.lax.dot_general(
                    qh_scr[h, :r], kh_scr[h], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(mask, s, -1e30)
                m_prev = m_scr[h, :r]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - lanes(m_new, s.shape[1]))
                corr = jnp.exp(m_prev - m_new)
                l_scr[h, :r] = (l_scr[h, :r] * corr
                                + jnp.sum(p, axis=1, keepdims=True))
                acc_scr[h, :r] = (
                    acc_scr[h, :r] * lanes(corr, d) + jax.lax.dot_general(
                        p.astype(cdt), vh_scr[h],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                m_scr[h, :r] = m_new
                return carry

            jax.lax.fori_loop(0, kvh, head, 0)

        if bk != q_blk:
            # the narrower kind of block leaves the rest of the key
            # rows as they were: masked, but they must be finite
            kh_scr[...] = jnp.zeros_like(kh_scr)
            vh_scr[...] = jnp.zeros_like(vh_scr)

        def load_ctx(blk):
            buf = blk % 2
            pl.when(blk + 1 < n_ctx)(
                lambda: page_dma(blk + 1, 1 - buf, True))
            page_dma(blk, buf, False)
            # pages keep a token's heads together; the MXU wants one
            # head's keys together. Either pool form is (bk * kvh, D)
            # rows in (token, head) order
            if paired(k_vmem) or merged_rows:
                k_rows = k_vmem.at[buf].reshape(bk * kvh, d)
                v_rows = v_vmem.at[buf].reshape(bk * kvh, d)
                if paired(k_vmem):
                    split_heads(k_rows, bk, kh_scr)
                    split_heads(v_rows, bk, vh_scr)
                    return
                # head h's keys are rows h, h + kvh, ... of the block
                for h in range(kvh):
                    every = pl.ds(h, bk, stride=kvh)
                    kh_scr[h, :bk] = k_rows[every, :].astype(cdt)
                    vh_scr[h, :bk] = v_rows[every, :].astype(cdt)
                return
            kb = k_vmem[buf].astype(jnp.float32)   # (ppb, page, kvh, D)
            vb = v_vmem[buf].astype(jnp.float32)
            if quantized:
                # the fused dequant: one multiply against the scale
                # rows that rode the same DMA wave as their pages
                kb = kb * ks_vmem[buf][..., None]
                vb = vb * vs_vmem[buf][..., None]
            for h in range(kvh):
                kh_scr[h, :bk] = kb[:, :, h].reshape(bk, d).astype(cdt)
                vh_scr[h, :bk] = vb[:, :, h].reshape(bk, d).astype(cdt)

        def load_new(jb):
            base = first + jb * q_blk
            copies = (
                pltpu.make_async_copy(
                    kn_hbm.at[pl.ds(base, q_blk)], kn_vmem, io_sem),
                pltpu.make_async_copy(
                    vn_hbm.at[pl.ds(base, q_blk)], vn_vmem, io_sem))
            for c in copies:
                c.start()
            for c in copies:
                c.wait()
            if paired(kn_vmem):
                rows = q_blk * kn_vmem.shape[1]
                split_heads(kn_vmem.reshape(rows, d), q_blk, kh_scr)
                split_heads(vn_vmem.reshape(rows, d), q_blk, vh_scr)
                return
            kn = kn_vmem[...].astype(jnp.float32)      # (q_blk, kvh, D)
            vn = vn_vmem[...].astype(jnp.float32)
            for h in range(kvh):
                kh_scr[h, :q_blk] = kn[:, h].astype(cdt)
                vh_scr[h, :q_blk] = vn[:, h].astype(cdt)

        few = qlen - qoff <= small

        def kv_block(blk, carry):
            """Blocks [0, n_ctx) are the slot's cached context, the
            rest its in-batch KV; one flash step serves both, under a
            mask whose bounds the kind of block sets."""
            is_ctx = blk < n_ctx
            jb = blk - n_ctx
            if n_ctx_pages:
                pl.when(is_ctx)(lambda: load_ctx(blk))
            pl.when(jnp.logical_not(is_ctx))(lambda: load_new(jb))
            first_key = jnp.where(is_ctx, blk * bk, jb * q_blk)
            # the block's columns that hold keys of the slot: none past
            # the kind of block's width, none past the context's or the
            # segment's end
            width = jnp.minimum(jnp.where(is_ctx, bk, q_blk),
                                jnp.where(is_ctx, ctx_len, qlen)
                                - first_key)
            # context keys precede every query; in-batch keys are
            # causal: column j holds segment offset first_key + j
            ahead = jnp.where(is_ctx, 1 << 30, 1 - first_key)
            # a query at segment offset i sits ctx_len + i into the
            # sequence; a context key's offset is its position
            behind = jnp.where(is_ctx, ctx_len, 0) - first_key

            def keep(r):
                # tok_scr: query offset per score row; a column is a
                # key offset less first_key
                tok = tok_scr[:r]
                col = jax.lax.broadcasted_iota(jnp.int32, tok.shape, 1)
                kept = col < jnp.minimum(tok + ahead, width)
                if window is not None:
                    kept = kept & (col > tok + (behind - window))
                return kept

            # the rows the item holds: its first `small` tokens' or
            # all q_blk's
            if small < q_blk:
                pl.when(few)(lambda: flash_heads(small * group, keep))
                pl.when(jnp.logical_not(few))(
                    lambda: flash_heads(q_blk * group, keep))
            else:
                flash_heads(q_blk * group, keep)
            return carry

        jax.lax.fori_loop(lo_blk, n_ctx + qoff // q_blk + 1, kv_block, 0)

        # rows the sweep left alone (past `small`) have l == 0 and
        # acc == 0: the epsilon floor makes them exact zeros
        for h in range(kvh):
            out = acc_scr[h] / jnp.maximum(lanes(l_scr[h], d), 1e-30)
            o_vmem[:, h * group:(h + 1) * group, :] = out.reshape(
                q_blk, group, d).astype(o_vmem.dtype)
        o_copy = pltpu.make_async_copy(
            o_vmem, o_hbm.at[pl.ds(tok0, q_blk)], io_sem)
        o_copy.start()
        o_copy.wait()


def ragged_paged_attention_pallas(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        page_tables: jax.Array, slot_ids: jax.Array,
        positions: jax.Array, valid: jax.Array, start: jax.Array,
        k_new: jax.Array, v_new: jax.Array, *, ctx_pages: int = -1,
        k_scales: jax.Array = None, v_scales: jax.Array = None,
        work: Tuple[jax.Array, jax.Array] = None,
        window: Optional[int] = None,
        interpret: bool = False, merged_rows: bool = False) -> jax.Array:
    """TPU Pallas ragged paged attention: same contract as
    `ragged_paged_prefill_decode_attention`, but each slot's KV pages
    are STREAMED through VMEM with online softmax — no [T, ctx] score
    and no gathered [T, ctx, KVH, D] context is ever materialized.

    q: [T, H, D] flat ragged batch (kv-major head order);
    k_pages/v_pages: [num_pages, page_size, KVH, D] (stays in HBM and
    is reached only through the table: the forwards pass the pool of
    ALL layers, flattened over them, and a table shifted to the
    layer's pages, since a slice of the pool would be copied first);
    page_tables: [B, max_pages]; slot_ids/positions/valid: [T];
    start: [B]; k_new/v_new: [T, KVH, D].

    Packing contract (what the engine's `_ragged_step` produces): each
    slot's valid tokens are ONE contiguous run of the flat batch, in
    position order, with positions[t] == start[slot_ids[t]] + rank in
    the run. Invalid rows are ignored on input and zero on output.

    The grid follows the work: one step per (slot, query block) item
    of `ragged_work_list` — at most ceil(T / q_blk) + B of them, the
    live ones first — and inside a step one loop over the KV blocks:
    the context blocks that exist for the slot, then its in-batch
    blocks up to the causal diagonal. q, new K/V and the output are read and
    written in place in the flat [T, ...] arrays at each item's row
    (padded by one block so the last item's block stays in bounds):
    nothing is staged per slot. Block sizes come from
    `ragged_block_sizes`; ctx_pages (static) says only whether any
    slot has a context (0 = none: no context sweep is built) — the
    kernel reads the whole page table and stops at the context that
    exists. `work` takes a list built once for all layers.

    Quantized KV (ISSUE 16): pass k_scales/v_scales
    ([num_pages, page_size, KVH] f32, ops/kv_quant.py layout) when
    the pools hold int8/fp8 values; the kernel DMAs the scale rows
    beside their pages and fuses the dequant multiply into the
    streaming loop. k_new/v_new stay full-precision either way.

    window (static): None is a full-attention layer, the kernel named
    `ragged_paged_attention`; w tokens is a sliding-window layer, the
    same kernel with its sweep started at the window's first block and
    its lower edge masked, named `ragged_window_attention` so that a
    device trace tells the two kinds of layer apart.
    """
    quantized = k_scales is not None
    if quantized and v_scales is None:
        raise ValueError("k_scales and v_scales must come together")
    items, segs = (
        ragged_work_list(slot_ids, valid, start, ragged_q_block(q.shape[0]))
        if work is None else work)
    flat = _ragged_call(
        items, segs, page_tables.astype(jnp.int32), q, k_pages, v_pages,
        k_new, v_new, k_scales, v_scales, has_ctx=ctx_pages != 0,
        window=window, interpret=interpret, merged_rows=merged_rows)
    return jnp.where(valid[:, None, None], flat,
                     jnp.zeros_like(flat)).astype(q.dtype)


# Mosaic's default scoped VMEM, and what a kernel may ask of the 128
# MiB a v5e core has
_VMEM_DEFAULT, _VMEM_MOST = 16 << 20, 96 << 20


def _vmem_limit(q_blk: int, h: int, kvh: int, group: int, d: int,
                n_keys: int, itemsize: int) -> dict:
    """`vmem_limit_bytes` for a geometry whose scratch outgrows the
    default: the q and output blocks, a kv head's queries, the
    accumulator, the two statistics (a row's on all 128 lanes) and the
    mask's query offsets grow with q_blk x heads, so 48 query heads
    over 8 kv heads (a group of 6) want ~15 MiB where a group of 2
    wants ~6. Nothing for a geometry inside the default: its compiler
    parameters are what they were."""
    r = q_blk * group
    need = (2 * q_blk * h * d * itemsize            # q, output blocks
            + kvh * r * d * itemsize                # q per kv head
            + kvh * r * d * 4 + 2 * kvh * r * 128 * 4   # acc, m, l
            + r * n_keys * 4                        # query offsets
            + 2 * kvh * n_keys * d * itemsize       # k, v per kv head
            + _KV_VMEM_BYTES + 2 * q_blk * kvh * d * itemsize)
    if need <= _VMEM_DEFAULT * 3 // 4:
        return {}
    return {"vmem_limit_bytes": min(2 * need + (8 << 20), _VMEM_MOST)}


@functools.partial(jax.jit,
                   static_argnames=("has_ctx", "window", "interpret",
                                    "merged_rows"))
def _ragged_call(items, segs, tables, q, k_pages, v_pages, k_new, v_new,
                 k_scales, v_scales, *, has_ctx: bool,
                 window: Optional[int] = None, interpret: bool,
                 merged_rows: bool = False):  # jaxlint: disable=JL002 -- the pools are read, never written: an inner jit that shares the kernel's trace, inlined into the engine's program, which donates them
    """The pallas_call of `ragged_paged_attention_pallas`, [T, H, D]
    out (invalid rows not yet zeroed). A jit of its own, so that its
    trace is shared: a serving engine builds one program per (token
    bucket, context bucket) and each would trace the kernel anew, but
    the kernel sees the whole page table whatever the context bucket
    (its sweep stops at the context that exists), so a token bucket's
    programs share one trace — what a warm start pays per program is
    mostly that trace."""
    t, h, head_dim = q.shape
    quantized = k_scales is not None
    if merged_rows:
        if quantized:
            raise ValueError("a merged-rows pool has no quantized read "
                             "path")
        kvh, d = k_new.shape[1], k_pages.shape[-1]
        page_size = k_pages.shape[1] // kvh
        # the in-batch K and V of a head count off the 8-row tile ride
        # with their heads padded to it (the kernel reads the first kvh)
        kvh_new = -(-kvh // 8) * 8
    else:
        _, page_size, kvh, d = k_pages.shape
        kvh_new = kvh
    group = h // kvh
    n_ctx_pages = tables.shape[1] if has_ctx else 0
    q_blk, ppb = ragged_block_sizes(
        t, page_size, n_ctx_pages, kvh, d, k_pages.dtype.itemsize)
    small = min(SMALL_ROWS, q_blk)
    # a lane-padded pool (paged_attention.pool_head_dim): the kernel
    # runs at the pool's row width; zero-padded q/new-KV lanes add
    # exact zeros to every score and output, sliced off at the end.
    # One block of rows past T keeps the last item's block in bounds.
    tail = lambda x: jnp.pad(_fit_lanes(x, d),
                             ((0, q_blk), (0, 0), (0, 0)))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    inputs = [tail(q), k_pages, v_pages]
    page_block = ((2, ppb, page_size * kvh, d) if merged_rows
                  else (2, ppb, page_size, kvh, d))
    scratch = [
        pltpu.VMEM((q_blk, h, d), q.dtype),            # q block
        pltpu.VMEM((q_blk, kvh_new, d), k_new.dtype),  # in-batch k / v
        pltpu.VMEM((q_blk, kvh_new, d), v_new.dtype),
        pltpu.VMEM((q_blk, h, d), q.dtype),            # output block
        pltpu.VMEM(page_block, k_pages.dtype),
        pltpu.VMEM(page_block, v_pages.dtype),
    ]
    if quantized:
        # scale pools ride beside the page pools: HBM-resident, DMA'd
        # per context block into their own VMEM scratch rows
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, ppb, page_size, kvh), jnp.float32),
                    pltpu.VMEM((2, ppb, page_size, kvh), jnp.float32)]
    heads = lambda x: (x if kvh_new == kvh else jnp.pad(
        x, ((0, 0), (0, kvh_new - kvh), (0, 0))))
    inputs += [tail(heads(k_new)), tail(heads(v_new))]
    r = q_blk * group
    n_keys = max(ppb * page_size, q_blk)
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),                 # page blocks
        pltpu.SemaphoreType.DMA,                       # q / new kv / out
        pltpu.VMEM((kvh, r, d), q.dtype),              # q per kv head
        pltpu.VMEM((kvh, n_keys, d), q.dtype),         # k, v per kv head
        pltpu.VMEM((kvh, n_keys, d), q.dtype),
        pltpu.VMEM((kvh, r, 128), jnp.float32),        # m, l: a row's
        pltpu.VMEM((kvh, r, 128), jnp.float32),        # on all 128 lanes
        pltpu.VMEM((kvh, r, d), jnp.float32),          # acc
        pltpu.VMEM((r, n_keys), jnp.int32),            # query offsets
    ]

    out = pl.pallas_call(
        functools.partial(
            _ragged_paged_kernel, page_size=page_size, ppb=ppb,
            n_ctx_pages=n_ctx_pages, q_blk=q_blk, small=small,
            scale=head_dim ** -0.5, kvh=kvh, group=group,
            quantized=quantized, window=window,
            **({"merged_rows": True} if merged_rows else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(items.shape[1],),
            in_specs=[hbm] * len(inputs),
            out_specs=hbm,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((t + q_blk, h, d), q.dtype),
        # the items' output writes overlap and rely on their order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            **_vmem_limit(q_blk, h, kvh, group, d, n_keys,
                          q.dtype.itemsize)),
        interpret=interpret,
        name=("ragged_paged_attention" if window is None
              else "ragged_window_attention"),
    )(items, segs, tables, *inputs)
    return _fit_lanes(out[:t], head_dim)               # [T, H, D]
