"""Attention over a LATENT paged cache (multi-head latent attention,
DeepSeek-V2/V3), in the absorbed form.

The cache holds one row a token a layer: `[c_kv | k_pe]`, the
normalised compressed key/value (`kv_lora_rank` lanes) and the one roped
key all heads share (`qk_rope_head_dim` lanes), zero-padded to whole
128-lane vectors in a kernel pool. With the up-projection of the keys
absorbed into the queries and that of the values applied after the
softmax, attention over this cache is multi-QUERY attention: every head
of a token scores against the same row at its full width and takes its
values from the row's first `dv` lanes,

    s[t, h, c] = q[t, h, :] . row[c, :] * scale
    o[t, h, :] = softmax_c(s[t, h, :]) @ row[:, :dv]

so per-head keys and values of a context are never written out. The
causal rule, the flat packing and the work list are those of
ops/ragged_paged_attention.py: token t of slot s at position p attends
the slot's cached rows c < start[s] and the tick's own rows u of s with
positions[u] <= p. A decode tick is the case of one token a slot.

Two implementations:
- `mla_attention_gather`: dense XLA over gathered pages, and
  `mla_attention_gather_paged`, the same straight off the pool in
  blocks of tokens: the CPU path, and the other side of the kernel's
  comparison on the chip at the cell's own sizes.
- `mla_ragged_attention_pallas`: flash-style kernel, `name=`
  `mla_ragged_attention`. One grid step per (slot, block of
  `MLA_Q_BLOCK` of its tokens); ALL heads of those tokens are the query
  rows of the step (8 tokens x 128 heads = 1,024 rows of real work, a
  decode row 128), the slot's pages stream through VMEM double-buffered
  and each is read once for scores and values alike.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import LANES, _fit_lanes
from .ragged_paged_attention import (KV_BLOCK, ragged_item_bound,
                                     ragged_work_list)

# tokens per work item: with every head of a token a query row, 8 tokens
# are 1,024 rows against each 128-key block, far enough past the chip's
# ridge (about 133 rows at 1,280 bytes a key) that a prefill item is
# bound by the MXU; the q block, the f32 accumulator and the scores stay
# under 6 MB of VMEM
MLA_Q_BLOCK = 8
# ... at the 128 heads the kernel was sized for. With fewer heads an item
# takes more tokens, up to the same 1,024 rows and at most 32 tokens: at
# 32 heads 8 tokens are 256 rows, and a 512-token chunk swept its
# context 64 times where 16 do (a block-step costs ~1.4 us whatever its
# rows: PERF.md section 6, PR 48)
MLA_Q_ROWS, MLA_Q_MOST = 1024, 32
# the tick's own rows are a 2-D [T, W] array whose row dim is tiled (16
# bf16 rows a tile): a slot's blocks of them start at the aligned row at
# or before its segment's first, the rows before the segment masked
_ROW_ALIGN = 16


def latent_row_width(kv_lora_rank: int, rope_dim: int, impl: str) -> int:
    """Minor dim of the latent pool's rows: `kv_lora_rank + rope_dim`
    as published for the gather impl, padded to whole 128-lane vectors
    for the kernel impls (576 -> 640)."""
    width = kv_lora_rank + rope_dim
    if impl in ("pallas", "pallas_interpret"):
        return -(-width // LANES) * LANES
    return width


def scatter_latent(pool: jax.Array, rows: jax.Array,
                   page_tables: jax.Array, positions: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """Write a tick's rows into the pool. rows: [L, N, width]; each
    token's OWN table in page_tables [N, max_pages]; invalid rows go to
    the scratch page (the last, which the allocator never hands out).
    One scatter with ONE index dim over the pool flattened to
    [L * pages * page, W] (a bitcast: the pool is row-major), so the
    pool is updated where it lies; with the layer as a second, sliced
    dim the compiler transposes the whole pool to scatter and back."""
    l, num_pages, page, one, w = pool.shape
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(valid, page_idx, num_pages - 1)
    at = page_idx * page + positions % page                       # [N]
    at = (jnp.arange(l, dtype=at.dtype)[:, None] * (num_pages * page)
          + at[None, :]).reshape(-1)                              # [L*N]
    new = _fit_lanes(rows, w).reshape(-1, w).astype(pool.dtype)
    return pool.reshape(-1, w).at[at].set(new).reshape(pool.shape)


# gathered context rows a block of tokens may hold at once (x 576 lanes
# x 2 B = 300 MB): a 512-token tick over 8k-token tables runs in 16
# blocks of 32 tokens, a tick of the CPU tests in one
GATHER_ROWS = 1 << 18


def _gather_block(q, kc, new_rows, slot_blk, pos_blk, idx_blk, slot_ids,
                  positions, valid, start, *, dv: int, scale: float):
    """Attention of a block of the tick's tokens. q: [n, H, W]; kc:
    [n, ctx, W] each token's slot's cached rows; slot_blk / pos_blk /
    idx_blk: [n] the tokens' slots, positions and flat indices; the
    rest describes the whole tick. Returns [n, H, dv] in q's type."""
    ctx = kc.shape[1]
    f32 = jnp.float32
    s_ctx = jnp.einsum("thw,tcw->thc", q, kc,
                       preferred_element_type=f32) * scale
    s_new = jnp.einsum("thw,uw->thu", q, new_rows,
                       preferred_element_type=f32) * scale
    ctx_mask = jnp.arange(ctx)[None, :] < start[slot_blk][:, None]
    new_mask = ((slot_blk[:, None] == slot_ids[None, :])
                & (positions[None, :] <= pos_blk[:, None])
                & valid[None, :]) | (
        idx_blk[:, None] == jnp.arange(new_rows.shape[0])[None, :])
    s = jnp.concatenate(
        [jnp.where(ctx_mask[:, None, :], s_ctx, -jnp.inf),
         jnp.where(new_mask[:, None, :], s_new, -jnp.inf)], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = (jnp.einsum("thc,tcv->thv", p[..., :ctx], kc[..., :dv],
                      preferred_element_type=f32)
           + jnp.einsum("thu,uv->thv", p[..., ctx:], new_rows[:, :dv],
                        preferred_element_type=f32))
    return out.astype(q.dtype)


def mla_attention_gather(q: jax.Array, ctx_rows: jax.Array,
                         new_rows: jax.Array, slot_ids: jax.Array,
                         positions: jax.Array, valid: jax.Array,
                         start: jax.Array, *, dv: int,
                         scale: float) -> jax.Array:
    """q: [T, H, W] absorbed queries (`[q_lat | q_pe]`); ctx_rows:
    [B, ctx, W] each slot's cached rows in position order; new_rows:
    [T, W] the tick's own rows. Returns [T, H, dv] in q's type.
    Operands as stored, scores and softmax in float32. Every token also
    attends itself, which keeps padding rows finite (see
    ops/ragged_paged_attention.ragged_prefill_decode_attention)."""
    return _gather_block(
        q, ctx_rows[slot_ids], new_rows, slot_ids, positions,
        jnp.arange(q.shape[0]), slot_ids, positions, valid, start,
        dv=dv, scale=scale)


def mla_attention_gather_paged(q: jax.Array, pool: jax.Array, layer: int,
                               page_tables: jax.Array,
                               new_rows: jax.Array, slot_ids: jax.Array,
                               positions: jax.Array, valid: jax.Array,
                               start: jax.Array, *, width: int, dv: int,
                               scale: float) -> jax.Array:
    """`mla_attention_gather` straight off the pool, in blocks of tokens
    that each gather their own slots' pages of `layer`, at most
    GATHER_ROWS context rows at a time: the same sums whatever the
    block, and a 512-token tick over 8k-token tables fits beside the
    weights (whole, its gathered context is 4.8 GB and its scores 2.1).
    pool: [L, P, page, 1, W]; page_tables: [B, n]."""
    t = q.shape[0]
    n, page = page_tables.shape[1], pool.shape[2]
    ctx = n * page

    def block(q_b, slot_b, pos_b, idx_b):
        kc = pool[layer, page_tables[slot_b]]     # [b, n, page, 1, W]
        kc = kc.reshape(q_b.shape[0], ctx, pool.shape[-1])[..., :width]
        return _gather_block(q_b, kc, new_rows, slot_b, pos_b, idx_b,
                             slot_ids, positions, valid, start, dv=dv,
                             scale=scale)

    idx = jnp.arange(t)
    blk = max(GATHER_ROWS // max(ctx, 1), 1)
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


# ------------------------------------------------------------ Pallas kernel

def mla_q_block(t: int, heads: int = 128) -> int:
    """Tokens per work item for a tick of `t` flat tokens of `heads`
    query heads: MLA_Q_BLOCK at 128 heads, more with fewer heads (up to
    MLA_Q_ROWS query rows an item and MLA_Q_MOST tokens)."""
    blk = max(MLA_Q_BLOCK, min(MLA_Q_ROWS // max(heads, 1), MLA_Q_MOST))
    return max(min(blk, t), 1)


def mla_block_sizes(t: int, page_size: int, n_ctx_pages: int,
                    heads: int = 128) -> Tuple[int, int, int]:
    """(tokens per item, pages per context block, in-batch rows per
    block) for a tick of `t` flat tokens over a table `n_ctx_pages`
    wide."""
    ppb = max(min(KV_BLOCK // page_size, n_ctx_pages), 1)
    return (mla_q_block(t, heads), ppb,
            min(KV_BLOCK, -(-t // _ROW_ALIGN) * _ROW_ALIGN))


def mla_work_list(slot_ids: jax.Array, valid: jax.Array,
                  start: jax.Array, heads: int = 128
                  ) -> Tuple[jax.Array, jax.Array]:
    """The kernel's grid for a tick: `ragged_work_list` at this
    kernel's tokens per item. Built once a forward, for every layer."""
    return ragged_work_list(slot_ids, valid, start,
                            mla_q_block(slot_ids.shape[0], heads))


def mla_work_counts(segs, t: int, page_size: int, n_ctx_pages: int,
                    heads: int = 128) -> Tuple[int, int]:
    """Host-side count of what the kernel does for a tick whose slots
    hold `segs` = [(cached tokens, tokens this tick)] in packing order:
    (live items, KV blocks they visit). An in-batch block is bkn flat
    rows from the aligned row at or before the segment's first."""
    q_blk, ppb, bkn = mla_block_sizes(t, page_size, n_ctx_pages, heads)
    bk = ppb * page_size
    items = blocks = first = 0
    for start, n in segs:
        lead = first % _ROW_ALIGN
        for qoff in range(0, n, q_blk):
            items += 1
            blocks += (-(-start // bk)
                       + -(-(lead + min(qoff + q_blk, n)) // bkn))
        first += n
    return items, blocks


def _mla_kernel(items_ref, segs_ref, tables_ref, layer_ref, q_hbm,
                pool_hbm, new_hbm, o_hbm, q_vmem, o_vmem, kv_vmem, kn_vmem, kv_sem,
                io_sem, m_scr, l_scr, acc_scr, tok_scr, *, page_size: int,
                ppb: int, n_ctx_pages: int, q_blk: int, bkn: int,
                heads: int, dv: int, scale: float):
    """Grid (n_items,): one step per work item (slot, block of q_blk of
    its tokens). The step's query rows are every head of those tokens,
    (token, head) order; it sweeps the slot's cached rows in blocks of
    ppb pages (the next block in flight while this one is computed),
    then the tick's own rows of the slot up to the causal diagonal in
    blocks of bkn flat rows, with an online softmax in float32 scratch,
    and writes its q_blk x heads output rows at the item's flat row.
    Items run in flat order; rows a block holds past the slot's segment
    are computed under the key mask alone, stay finite, and are
    overwritten by the next item (the wrapper zeroes invalid rows). An
    item of one token (a decode row, a chunk's last token) runs on
    `heads` rows.

    What a block costs beside its two MXU products is kept small, as in
    ops/ragged_paged_attention.py: the running maximum and sum lie on
    all 128 lanes of their row (`lanes`), so no use of them broadcasts
    across lanes; the sum is kept a LANE (`l = l * corr + p`, one lane
    reduction at the item's end) where every block is whole lane tiles
    wide, which is every program of 128 tokens and more on the chip; a
    context block's mask is one compare against a scalar and an
    in-batch block's two against the rows' token offsets, made once an
    item (`tok_scr`). A second body that left the mask off whole context
    blocks was 17 bundles of 2,200 shorter and the kernel SLOWER with it
    (1-5%, PERF.md section 6: the kernel's code is a quarter larger)."""
    it = pl.program_id(0)
    slot = items_ref[0, it]
    bk = page_size * ppb
    w = q_vmem.shape[-1]
    cdt = q_vmem.dtype
    # the row's sum a lane: every block's scores are whole lane tiles
    lane_l = bk % LANES == 0 and bkn % LANES == 0

    def lanes(x, n):
        """A statistic (rows, 128), the row's value on every lane, as
        (rows, n): its first lanes, or copies of itself side by side
        beside whole lane tiles, which is every shape on the chip."""
        if n <= LANES:
            return x[:, :n]
        if n % LANES == 0:
            return jnp.concatenate([x] * (n // LANES), axis=1)
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))

    @pl.when(slot >= 0)
    def _item():
        qoff = items_ref[1, it]
        tok0 = items_ref[2, it]
        ctx_len = segs_ref[0, slot]
        qlen = segs_ref[1, slot]
        first = segs_ref[2, slot]
        n_ctx = (ctx_len + bk - 1) // bk if n_ctx_pages else 0
        last_page = jnp.minimum(
            jnp.maximum((ctx_len - 1) // page_size, 0),
            max(n_ctx_pages - 1, 0))

        def page_dma(blk, buf, go):
            def page(t, carry):
                idx = tables_ref[slot,
                                 jnp.minimum(blk * ppb + t, last_page)]
                c = pltpu.make_async_copy(
                    pool_hbm.at[layer_ref[0], idx], kv_vmem.at[buf, t],
                    kv_sem.at[buf])
                c.start() if go else c.wait()
                return carry

            jax.lax.fori_loop(0, ppb, page, 0)

        q_copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(tok0, q_blk)], q_vmem, io_sem)
        q_copy.start()
        if n_ctx_pages:
            pl.when(n_ctx > 0)(lambda: page_dma(0, 0, True))
        q_copy.wait()

        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the in-batch mask's query offsets, once an item: score row i
        # is the token at segment offset qoff + i // heads, which sees
        # no key past itself nor past the segment's last
        tok_scr[...] = jnp.minimum(
            qoff + jax.lax.broadcasted_iota(
                jnp.int32, tok_scr.shape, 0) // heads, qlen - 1)

        def flash(n_tok, keys, keep):
            """One flash step of the item's first n_tok tokens against
            `keys` [n, w]; keep(r, n) is the [r, n] mask of the scores
            that count."""
            r, n = n_tok * heads, keys.shape[0]
            q = q_vmem[:n_tok].reshape(r, w)
            s = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep(r, n), s, -1e30)
            m_prev = m_scr[:r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - lanes(m_new, n))
            corr = jnp.exp(m_prev - m_new)
            if lane_l:
                p_row = functools.reduce(jnp.add, [
                    p[:, c:c + LANES] for c in range(0, n, LANES)])
            else:
                p_row = jnp.sum(p, axis=1, keepdims=True)
            l_scr[:r] = l_scr[:r] * corr + p_row
            acc_scr[:r] = (
                acc_scr[:r] * lanes(corr, dv) + jax.lax.dot_general(
                    p.astype(cdt), keys[:, :dv], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_scr[:r] = m_new

        one = qlen - qoff <= 1

        def step(keys, keep):
            """The flash step on the rows the item holds: one token's
            or all q_blk's."""
            if q_blk > 1:
                pl.when(one)(lambda: flash(1, keys, keep))
                pl.when(jnp.logical_not(one))(
                    lambda: flash(q_blk, keys, keep))
            else:
                flash(1, keys, keep)

        def cols(r, n):
            return jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)

        if n_ctx_pages:
            def ctx_block(blk, carry):
                buf = blk % 2
                pl.when(blk + 1 < n_ctx)(
                    lambda: page_dma(blk + 1, 1 - buf, True))
                page_dma(blk, buf, False)
                # context keys precede every query of the tick: a
                # block's columns count up to the cached length
                left = ctx_len - blk * bk
                step(kv_vmem[buf].reshape(bk, w),
                     lambda r, n: cols(r, n) < left)
                return carry

            jax.lax.fori_loop(0, n_ctx, ctx_block, 0)

        # the tick's own rows are read in blocks of bkn FLAT rows from
        # the aligned row at or before the segment's first: a block is
        # one aligned read, and a key lies in one block
        lead = first % _ROW_ALIGN

        def new_block(jb, carry):
            c = pltpu.make_async_copy(
                new_hbm.at[pl.ds(pl.multiple_of(
                    first - lead + jb * bkn, _ROW_ALIGN), bkn)],
                kn_vmem, io_sem)
            c.start()
            c.wait()
            # column j holds segment offset j - before: none under 0
            # (the rows ahead of the segment), none past the row's token
            before = lead - jb * bkn

            def keep(r, n):
                col = cols(r, n)
                return (col >= before) & (
                    col <= lanes(tok_scr[:r], n) + before)

            step(kn_vmem[...], keep)
            return carry

        n_new = (lead + jnp.minimum(qoff + q_blk, qlen) + bkn - 1) // bkn
        jax.lax.fori_loop(0, n_new, new_block, 0)

        l = (jnp.sum(l_scr[...], axis=1, keepdims=True) if lane_l
             else l_scr[:, :1])
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_vmem[...] = out.reshape(q_blk, heads, dv).astype(o_vmem.dtype)
        o_copy = pltpu.make_async_copy(
            o_vmem, o_hbm.at[pl.ds(tok0, q_blk)], io_sem)
        o_copy.start()
        o_copy.wait()


def mla_ragged_attention_pallas(
        q: jax.Array, pool: jax.Array, layer: jax.Array,
        page_tables: jax.Array, slot_ids: jax.Array, positions: jax.Array, valid: jax.Array,
        start: jax.Array, new_rows: jax.Array, *, dv: int, scale: float,
        ctx_pages: int = -1,
        work: Tuple[jax.Array, jax.Array] = None,
        interpret: bool = False) -> jax.Array:
    """The kernel path of `mla_attention_gather`. q: [T, H, Wq]
    absorbed queries; pool: the WHOLE [L, num_pages, page, 1, W] pool
    and `layer` the (traced) index of the layer to read: the kernel
    DMAs pages out of the pool where it lies, so no layer's slice is
    ever copied out of it; new_rows: [T, Wq]; the rest as in
    `ragged_paged_attention_pallas`, whose packing contract holds (a
    slot's valid tokens are one contiguous run in position order).
    q and the new rows are zero-padded to the pool's row width, which
    adds exact zeros to every score. ctx_pages (static) says only
    whether any slot has a context (0 = none). Returns [T, H, dv]."""
    del positions
    items, segs = (mla_work_list(slot_ids, valid, start, q.shape[1])
                   if work is None else work)
    flat = _mla_call(items, segs, page_tables.astype(jnp.int32),
                     jnp.asarray(layer, jnp.int32).reshape(1), q, pool,
                     new_rows, dv=dv, scale=float(scale),
                     has_ctx=ctx_pages != 0, interpret=interpret)
    return jnp.where(valid[:, None, None], flat,
                     jnp.zeros_like(flat)).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("dv", "scale", "has_ctx",
                                             "interpret"))
def _mla_call(items, segs, tables, layer, q, pool, new_rows, *, dv: int,
              scale: float, has_ctx: bool, interpret: bool):  # jaxlint: disable=JL002 -- the pool is read, never written: an inner jit that shares the kernel's trace, inlined into the engine's program, which donates it
    """The pallas_call, [T, H, dv] out (invalid rows not yet zeroed);
    a jit of its own so that a token bucket's programs share its
    trace (see ragged_paged_attention._ragged_call)."""
    t, heads, _ = q.shape
    n_layers, num_pages, page_size, _, w = pool.shape
    n_ctx_pages = tables.shape[1] if has_ctx else 0
    q_blk, ppb, bkn = mla_block_sizes(t, page_size, n_ctx_pages, heads)
    assert items.shape[1] == ragged_item_bound(t, segs.shape[1], q_blk)
    # one block of rows past T keeps the last item's blocks in bounds
    qp = jnp.pad(_fit_lanes(q, w), ((0, q_blk), (0, 0), (0, 0)))
    newp = jnp.pad(_fit_lanes(new_rows, w).astype(pool.dtype),
                   ((0, bkn), (0, 0)))
    rows = q_blk * heads
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(
            _mla_kernel, page_size=page_size, ppb=ppb,
            n_ctx_pages=n_ctx_pages, q_blk=q_blk, bkn=bkn, heads=heads,
            dv=dv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(items.shape[1],),
            in_specs=[hbm, hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((q_blk, heads, w), q.dtype),        # q block
                pltpu.VMEM((q_blk, heads, dv), q.dtype),       # output
                pltpu.VMEM((2, ppb, page_size, w), pool.dtype),
                pltpu.VMEM((bkn, w), pool.dtype),              # new rows
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
                # m, a row's value on all 128 lanes, and l, the same or
                # the row's sum a lane (what a [rows, 1] array padded to
                # its tile takes anyway)
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, dv), jnp.float32),           # acc
                pltpu.VMEM((rows, LANES), jnp.int32),          # tok
            ]),
        out_shape=jax.ShapeDtypeStruct((t + q_blk, heads, dv), q.dtype),
        # the items' output writes overlap and rely on their order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_ragged_attention",
    )(items, segs, tables, layer, qp,
      pool.reshape(n_layers, num_pages, page_size, w), newp)
    return out[:t]
