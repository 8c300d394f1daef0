"""Mamba-2's recurrence (the state-space dual form, SSD) over a RAGGED
token axis, for continuous batching: a tick's flat batch holds one
contiguous run of tokens a slot (a prompt's chunk, or one decode token),
each run continues the state its slot stored at the end of the tick
before, and the end state of each run is written back
(`selective_scan.Marks` says where runs start and end; that module's
`causal_conv_ragged` is the convolution in front of this scan).

The recurrence, per head h of H (P channels a head), state index n of N,
group g(h) = h // (H / G), float32:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]

ONE decay a head a token (a scalar; Mamba-1 has one a state element), B
and C shared by the heads of a group. `S_{t-1}` at a run's first token
is the slot's stored state, or zeros where the run starts its sequence
(`first` 2). An invalid token passes the state by (dt = 0 there).

Over a piece of a run, with l_t = sum_{s <= t} dt_s A inside the piece:

    y_t = sum_{s <= t} e^{l_t - l_s} dt_s (C_t . B_s) x_s
          + e^{l_t} C_t S_in + D x_t
    S_out = e^{l_end} S_in + sum_s e^{l_end - l_s} dt_s x_s (outer) B_s

which is matrix products (scores C B^T under a decay mask, the state by
X^T B) and exponents that are never positive.

The state lives as `[layers, slots, H, P, N]` float32: N = 128 in the
lanes, 2 MB a slot a layer at the published sizes.

impl (the names the attention ops take):
- "gather": plain `jax.numpy`, the form above with the whole tick as
  one piece and a mask that keeps a run from seeing another, a head at
  a time. The oracle, and what runs off the chip.
- "pallas" / "pallas_interpret": `ssd_ragged_scan`. The tick is cut into
  SEGMENTS: the pieces of runs inside chunks of `T_CHUNK` tokens, in
  order (`segments`: a table on the device, read by scalar prefetch).
  The grid is (head tiles, segments), the second bound the tick's own
  count. A TILE is `head_tile(H, G)` heads of ONE group: a whole group
  where a group has at most `TILE_HEADS` = 8 heads (G = 8 over 64
  heads: tile t is group t, 8 tiles), and 8 heads of a larger group (G =
  1 over 64 heads: 8 tiles, every one reading the one group's B and C),
  so that a step's state block is [8, P, N] (256 KB at the published
  sizes) whatever G is. A step takes the segment's chunk of x, dt, B
  (its tile's group's alone: `[T, G, N]` is never broadcast a head) and
  C, and its SLOT's state of the tile's heads as a block picked by the
  prefetched slot id; the output state aliases the input, so the states
  of slots without a run are never read nor written, and a run's state
  block stays in VMEM from its first segment to its last (the output
  block is the carry). Two bodies: a segment of ONE token (a decode row)
  updates its state on the vector unit, the row's x turned to a column
  by a diagonal mask, and is bound by the state's way in and out; a
  longer segment runs the form above as matrix products over its chunk
  under row masks.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .selective_scan import Marks

SUBLANES = 8
T_CHUNK = 128                  # tokens a chunk: the published kernel's
TILE_HEADS = 8                 # the most heads a grid step's state holds
KERNEL_NAME = "ssd_ragged_scan"
_HI = lax.Precision.HIGHEST


def segments(marks: Marks, slot_ids: jax.Array, valid: jax.Array,
             chunk: int, most: int):
    """The tick's segments, in token order: a segment starts at a run's
    first token and at a chunk's first token inside a run. Returns int32
    arrays [most] (entries past the count are in range and never run):
    chunk of the segment, its first row in the chunk, its tokens, its
    slot, `first` of its first token (0: it continues the segment
    before); and the count, a scalar."""
    t = slot_ids.shape[0]
    at = jnp.arange(t, dtype=jnp.int32)
    head = valid & ((marks.first != 0) | (at % chunk == 0))
    n = jnp.sum(head).astype(jnp.int32)
    seg_of = jnp.cumsum(head.astype(jnp.int32)) - 1            # [T]
    start = jnp.nonzero(head, size=most, fill_value=0)[0].astype(jnp.int32)
    length = jnp.zeros((most,), jnp.int32).at[
        jnp.where(valid, seg_of, most)].add(1, mode="drop")
    return (start // chunk, start % chunk, length, slot_ids[start],
            marks.first[start], n)


def head_tile(h_all: int, groups: int) -> int:
    """Heads a grid step takes: a whole group, or `TILE_HEADS` of a
    group that has more (which then has to be whole tiles)."""
    heads = h_all // groups
    if heads <= TILE_HEADS:
        return heads
    if heads % TILE_HEADS:
        raise ValueError(f"a group of {heads} heads is no whole number of "
                         f"tiles of {TILE_HEADS}")
    return TILE_HEADS


def _column(a, j):
    """Column j of a [rows, width] value as [rows, 1]: a masked sum
    over lanes (a one-lane slice has no layout a broadcast takes)."""
    mine = lax.broadcasted_iota(jnp.int32, a.shape, 1) == j
    return jnp.sum(jnp.where(mine, a, 0.0), axis=1, keepdims=True)


def _ssd_kernel(chunk_ref, row_ref, len_ref, slot_ref, first_ref, lay_ref,
                x_ref, xt_ref, dt_ref, dtt_ref, cs_ref, cst_ref, b_ref,
                c_ref, a_ref, s_in_ref, y_ref, s_out_ref, *, heads: int,
                p: int):
    """One segment of one tile's heads. x: [Q, heads * P]; xt: its
    transpose; dt, cs (the chunk's running sum of dt A): [Q, heads] and
    transposed; b, c: [Q, N]; a: [1, heads]; the state blocks
    [heads, P, N]."""
    i = pl.program_id(1)
    r0, n, first = row_ref[i], len_ref[i], first_ref[i]
    q = x_ref.shape[0]
    f32 = jnp.float32

    @pl.when(first == 1)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(first == 2)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    @pl.when(n == 1)
    def _():
        # a decode row: the recurrence itself, a head at a time. The
        # row's x becomes a column (P down the sublanes, beside the
        # state's [P, N]) and y a row again by a diagonal mask over the
        # row's whole width: a row read at a traced sublane takes no
        # lane offset
        wide = (heads * p,)
        lane = lax.broadcasted_iota(jnp.int32, (p,) + wide, 1)
        chan = lax.broadcasted_iota(jnp.int32, (p,) + wide, 0)
        b_row = b_ref[pl.ds(r0, 1), :]                       # [1, N]
        c_row = c_ref[pl.ds(r0, 1), :]
        dt_row = dt_ref[pl.ds(r0, 1), :]                     # [1, heads]
        da_row = dt_row * a_ref[...]
        x_row = x_ref[pl.ds(r0, 1), :]                       # [1, heads P]
        y_row = jnp.zeros((1,) + wide, f32)
        for h in range(heads):
            diag = lane == chan + h * p
            x_col = _column(dt_row, h) * jnp.sum(
                jnp.where(diag, x_row, 0.0), axis=1, keepdims=True)
            s = (jnp.exp(_column(da_row, h)) * s_out_ref[h]
                 + x_col * b_row)                            # [P, N]
            s_out_ref[h] = s
            y_col = jnp.sum(s * c_row, axis=1, keepdims=True)
            y_row += jnp.sum(jnp.where(diag, y_col, 0.0), axis=0,
                             keepdims=True)
        y_ref[pl.ds(r0, 1), :] = y_row

    @pl.when(n > 1)
    def _():
        rows = lax.broadcasted_iota(jnp.int32, (q, 1), 0)
        cols = lax.broadcasted_iota(jnp.int32, (1, q), 1)
        in_r = (rows >= r0) & (rows < r0 + n)
        in_c = (cols >= r0) & (cols < r0 + n)
        keep = in_r & in_c & (cols <= rows)                  # [Q, Q]
        bm, cm = b_ref[...], c_ref[...]
        cb = lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             precision=_HI, preferred_element_type=f32)
        cs_all, dt_t, cs_t = cs_ref[...], dtt_ref[...], cst_ref[...]
        for h in range(heads):
            lanes = slice(h * p, (h + 1) * p)
            cs_col = _column(cs_all, h)                      # [Q, 1]
            cs_row, dt_row = cs_t[h:h + 1, :], dt_t[h:h + 1, :]
            # the sum up to the token before the segment, and at its end
            base = jnp.sum(jnp.where(rows == r0 - 1, cs_col, 0.0),
                           axis=0, keepdims=True)            # [1, 1]
            top = jnp.sum(jnp.where(rows == r0 + n - 1, cs_col, 0.0),
                          axis=0, keepdims=True)
            decay = jnp.where(
                keep, jnp.exp(jnp.minimum(cs_col - cs_row, 0.0)), 0.0)
            y = jnp.dot(cb * decay * dt_row, x_ref[:, lanes],
                        precision=_HI, preferred_element_type=f32)
            s_in = s_out_ref[h]                              # [P, N]
            into = jnp.where(in_r, jnp.exp(jnp.minimum(cs_col - base, 0.0)),
                             0.0)
            y += lax.dot_general(into * cm, s_in, (((1,), (1,)), ((), ())),
                                 precision=_HI, preferred_element_type=f32)
            out = jnp.where(in_c, jnp.exp(jnp.minimum(top - cs_row, 0.0))
                            * dt_row, 0.0)                   # [1, Q]
            s_out_ref[h] = (jnp.exp(top - base) * s_in + jnp.dot(
                xt_ref[lanes, :] * out, bm, precision=_HI,
                preferred_element_type=f32))
            y_ref[:, lanes] = jnp.where(in_r, y, y_ref[:, lanes])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_call(seg, layer, a, x, dt, cs, b, c, state, *, interpret: bool):  # jaxlint: disable=JL002 -- the state is aliased by the kernel, in place; an inner jit that shares the kernel's trace, inlined into the engine's program, which donates it
    """The pallas_call. x: [T, H * P] float32; dt, cs: [T, H]; b, c:
    [G, T, N]; state: [L, slots, H, P, N] whole, layer `layer`'s rows of
    the slots with a run read and written in place."""
    chunk_of, row_of, length, slot_of, first_of, n_seg = seg
    t, hp = x.shape
    groups, _, n = b.shape
    h_all = dt.shape[1]
    heads = head_tile(h_all, groups)
    tiles = h_all // heads
    per = tiles // groups                  # tiles a group
    # a tile's group: the tile itself where a group is one tile (the
    # index map then holds no division)
    grp = (lambda g: g) if per == 1 else (lambda g: g // per)
    p = hp // h_all
    q = min(T_CHUNK, t)
    # the layer's index rides with the prefetched scalars: a stack
    # that scans its layers hands a traced one
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    by_tile = lambda m: m.reshape(t, tiles, heads).transpose(1, 0, 2)
    dt_g, cs_g = by_tile(dt), by_tile(cs)                   # [tiles, T, heads]
    tok = lambda width: pl.BlockSpec(
        (None, q, width), lambda g, i, ch, *_: (g, ch[i], 0))
    tok_t = lambda depth: pl.BlockSpec(
        (None, depth, q), lambda g, i, ch, *_: (g, 0, ch[i]))
    of_group = pl.BlockSpec(
        (None, q, n), lambda g, i, ch, *_: (grp(g), ch[i], 0))
    rows = pl.BlockSpec(
        (None, None, heads, p, n),
        lambda g, i, ch, r, ln, sl, fi, lay: (lay[0], sl[i], g, 0, 0))
    kernel = functools.partial(_ssd_kernel, heads=heads, p=p)
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(tiles, n_seg),
            in_specs=[
                pl.BlockSpec((q, heads * p), lambda g, i, ch, *_: (ch[i], g)),
                pl.BlockSpec((heads * p, q), lambda g, i, ch, *_: (g, ch[i])),
                tok(heads), tok_t(heads), tok(heads), tok_t(heads),
                of_group, of_group,
                pl.BlockSpec((None, 1, heads), lambda g, i, *_: (g, 0, 0)),
                rows],
            out_specs=[
                pl.BlockSpec((q, heads * p), lambda g, i, ch, *_: (ch[i], g)),
                rows]),
        out_shape=[jax.ShapeDtypeStruct((t, hp), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 15 (6 prefetched scalars + 9 arrays before it)
        input_output_aliases={15: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=KERNEL_NAME,
    )(chunk_of, row_of, length, slot_of, first_of, lay,
      x, x.T, dt_g, dt_g.transpose(0, 2, 1), cs_g, cs_g.transpose(0, 2, 1),
      b, c, a.reshape(tiles, 1, heads), state)
    return y, state


def _gather_head(blk, *, b, c, heads, seg_id, offset, fresh, has, slots,
                 valid, last_idx):
    """One head by the whole-tick form. blk: its index, x [T, P], dt
    [T], a (a scalar), state [slots, P, N]; b, c: [G, T, N], of which
    the head's group's are read."""
    h, x, dt, a, s_old = blk
    b = lax.dynamic_index_in_dim(b, h // heads, 0, False)
    c = lax.dynamic_index_in_dim(c, h // heads, 0, False)
    t = x.shape[0]
    n_slots = s_old.shape[0]
    at = jnp.arange(t)
    dta = dt * a
    cs = jnp.cumsum(dta)                                     # [T]
    same = ((seg_id[:, None] == seg_id[None, :]) & (at[None, :] <= at[:, None])
            & valid[:, None] & valid[None, :])
    decay = jnp.where(same, jnp.exp(jnp.minimum(cs[:, None] - cs[None, :],
                                                0.0)), 0.0)  # [T, S]
    cb = jnp.einsum("tn,sn->ts", c, b, precision=_HI)
    y = jnp.dot(cb * decay * dt[None, :], x, precision=_HI)  # [T, P]
    # what the run came in with: the slot's state, or zeros
    s_in = jnp.where(fresh[:, None, None], 0.0, s_old)
    base = (cs - dta)[at - offset]                           # [T]
    y += (jnp.exp(jnp.minimum(cs - base, 0.0))[:, None]
          * jnp.einsum("tn,tpn->tp", c, s_in[slots], precision=_HI))
    top = cs[last_idx]                                       # [slots]
    out = jnp.where(valid, jnp.exp(jnp.minimum(top[slots] - cs, 0.0)) * dt,
                    0.0)
    add = jax.ops.segment_sum(
        (out[:, None] * x)[:, :, None] * b[:, None, :],
        jnp.where(valid, slots, n_slots), num_segments=n_slots + 1)[:-1]
    total = jnp.exp(top - base[last_idx])                    # [slots]
    new = total[:, None, None] * s_in + add
    return y, jnp.where(has[:, None, None], new, s_old)


def ssd_ragged_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array, d: jax.Array, marks: Marks,
                    slot_ids: jax.Array, valid: jax.Array,
                    last_idx: jax.Array, state: jax.Array, layer, *,
                    impl: str = "gather") -> Tuple[jax.Array, jax.Array]:
    """The recurrence of the module's docstring over one tick.

    x: [T, H, P] (the conv's activated output); dt: [T, H] float32,
    after softplus; a: [H] float32, negative; b, c: [T, G, N]; d: [H];
    marks: `selective_scan.segment_marks`'; slot_ids, valid: [T];
    last_idx: [B]; state: [L, B, H, P, N] float32, every layer's, of
    which `layer` (an int, or a traced one) is this one's.
    Returns (y [T, H, P] float32, state with layer `layer`'s rows of
    the slots that had tokens replaced by their runs' end states)."""
    f32 = jnp.float32
    t_given, h_all, p = x.shape
    groups = b.shape[1]
    heads = h_all // groups
    if h_all % groups:
        raise ValueError(f"{h_all} heads in {groups} groups")
    xf = x.astype(f32)
    dt = jnp.where(valid[:, None], dt.astype(f32), 0.0)
    a = a.astype(f32)
    slots = jnp.where(valid, slot_ids, 0).astype(jnp.int32)
    if impl in ("pallas", "pallas_interpret"):
        # whole sublane tiles, and whole chunks: the padding is invalid
        t = -(-t_given // SUBLANES) * SUBLANES
        t = t if t <= T_CHUNK else -(-t // T_CHUNK) * T_CHUNK
        pad = lambda m: jnp.pad(
            m, ((0, t - t_given),) + ((0, 0),) * (m.ndim - 1))
        q = min(T_CHUNK, t)
        padded = Marks(*(pad(m) for m in marks[:3]), marks.has)
        seg = segments(padded, pad(slots), pad(valid), q,
                       t // q + last_idx.shape[0])
        dtp = pad(dt)
        cs = jnp.cumsum((dtp * a).reshape(t // q, q, h_all),
                        axis=1).reshape(t, h_all)
        by_group = lambda m: pad(m.astype(f32)).transpose(1, 0, 2)
        y, state = _ssd_call(
            seg, layer, a, pad(xf).reshape(t, h_all * p), dtp, cs,
            by_group(b), by_group(c), state,
            interpret=(impl == "pallas_interpret"))
        # rows no segment wrote hold whatever the buffer held
        y = jnp.where(valid[:, None], y[:t_given], 0.0).reshape(xf.shape)
    else:
        first, has = marks.first, marks.has
        n_slots = state.shape[1]
        fresh = jnp.zeros((n_slots,), bool).at[slots].max(first == 2)
        y, new = lax.map(
            functools.partial(
                _gather_head, b=jnp.moveaxis(b.astype(f32), 1, 0),
                c=jnp.moveaxis(c.astype(f32), 1, 0), heads=heads,
                seg_id=jnp.cumsum(first != 0), offset=marks.offset,
                fresh=fresh, has=has, slots=slots, valid=valid,
                last_idx=last_idx),
            (jnp.arange(h_all), jnp.moveaxis(xf, 1, 0),
             jnp.moveaxis(dt, 1, 0), a,
             jnp.moveaxis(state[layer], 1, 0)))
        y = jnp.moveaxis(y, 0, 1)
        state = state.at[layer].set(jnp.moveaxis(new, 0, 1))
    return y + d.astype(f32)[None, :, None] * xf, state
