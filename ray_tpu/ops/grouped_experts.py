"""Grouped expert feed-forward over expert-sorted rows: the Pallas
kernels behind `ops/moe.held_experts_ffn` on the chip, one pair for
every form of expert (`FORMS`: SwiGLU, gated ReLU, ungated relu^2).

A tick's assignments (token t picked expert e, held here) are laid out
sorted by expert, then token (`assignment_rows`): group e is rows
[offsets[e], offsets[e + 1]). Cut into tiles of `tm` rows, the work is
the list of (row tile, expert) pairs that share at least one row
(`tile_visits`): a tile that two groups meet in is visited once for
each, and only the visits that exist are run. The grid's middle bound is
that count, read on the device, so a tick computes the row tiles that
hold assignments and fetches the weight tiles of the experts that got
any; an expert nobody picked is never read. (The layout and the masked
store of a shared tile are those of MegaBlocks' grouped product,
`jax.experimental.pallas.ops.tpu.megablox`; here the way from the tokens
to the rows, the activation and the way back are fused in, so nothing of
the row bound's size is gathered, scattered or kept outside the kernels.)

Two kernels a layer, named by the form (`moe_grouped_up`,
`moe_grouped_up_reglu`, `moe_grouped_up_relu2`, and `_down` alike: the
benchmark's readers key on the names):

- `moe_grouped_up*`: the tile's rows of x, fetched by a one-hot product
  against the expert's column of `assignment_rows` (exact), then the
  form's activation over its up products (SwiGLU: h = silu(xs W_g[e]) *
  (xs W_i[e])), one float32 accumulator a matrix over the hidden tiles,
  stored in the operands' type. The first visit of a row tile writes
  zeros to the rows that are not its own, so a visited tile never holds
  stale bytes.
- `moe_grouped_down*`: y = h W_d[e] in float32, the rows of other groups
  zeroed, then straight back to the tokens: out[t] += gate[t, e] *
  y[row of (t, e)], by the same one-hot the other way round (y split
  into three bfloat16 parts, so the product is exact in float32) and
  the gate applied in float32. `out` [T, tn] stays in VMEM across a
  column tile's visits.

The one-hot products cost T * tm a visit beside the tile's tm * H * F:
a sixth more at T = 512, nothing at a decode tick's T. A caller with
thousands of tokens a call would want them tiled over T.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128                 # rows of a visit: the MXU's height
_WEIGHT_TILE_BYTES = 2 << 20   # a weight tile in VMEM (two buffers each)
_OUT_TILE_BYTES = 4 << 20      # the down kernel's resident [T, tn] block
_VMEM_LIMIT = 48 << 20
_PARTS = 3    # bfloat16 parts a float32 row goes back in: 3 x 8 bits, exact


def _divisor(dim: int, most: int) -> int:
    """The largest whole-vector (128) tile of `dim` that is at most
    `most`; `dim` itself where it has none (a toy width)."""
    best = 0
    for d in range(128, min(dim, max(most, 128)) + 1, 128):
        if dim % d == 0:
            best = d
    return best or dim


def row_tile(rows: int) -> int:
    """Rows of a visit for a row bound: ROW_TILE, or the bound rounded
    up to the 16 rows of a bfloat16 vector where it is smaller."""
    return min(ROW_TILE, -(-rows // 16) * 16)


def assignment_rows(took: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """took: [T, E] bool, token t picked held expert e -> the tick's
    assignments sorted by expert, then token: (the row of each
    assignment [T, E] int32, -1 where there is none; offsets [E + 1]
    int32, group e is rows [offsets[e], offsets[e + 1])). No sort: an
    assignment's row is its expert's offset plus the running count down
    the expert's column."""
    upto = jnp.cumsum(took.astype(jnp.int32), axis=0)        # [T, E]
    ends = jnp.cumsum(upto[-1])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return jnp.where(took, offsets[:-1] + upto - 1, -1), offsets


def tile_visits(offsets: jax.Array, rows: int, tm: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The (row tile, expert) pairs that share a row, in row order:
    (expert of each visit [V] int32, row tile of each visit [V] int32,
    the number of visits, a scalar), V = tiles + E - 1 the most there
    can be. Entries past the count are in range and never run."""
    e = offsets.shape[0] - 1
    tiles = -(-rows // tm)
    starts, ends = offsets[:-1], offsets[1:]
    first = starts // tm
    n = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(n)
    v = jnp.arange(tiles + e - 1, dtype=jnp.int32)
    g = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1), e - 1)
    # first[g] - (upto - n)[g] without a gather: sixteen selects
    mine = g[:, None] == jnp.arange(e)
    base = jnp.sum(jnp.where(mine, first - (upto - n), 0), axis=1)
    return (g.astype(jnp.int32),
            jnp.clip(base + v, 0, tiles - 1).astype(jnp.int32), upto[-1])


def row_visits(offsets: jax.Array, rows: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`tile_visits` at `grouped_ffn`'s own row tile: offsets [E + 1]
    from `assignment_rows`, `rows` the most assignments there can be. A
    function of its own for a layer whose router runs ahead of its
    attention: the visits exist before the expert product does and are
    made where the picks are."""
    tm = row_tile(rows)
    return tile_visits(offsets, -(-rows // tm) * tm, tm)


def _own_rows(v, vis_g, vis_t, off, tm: int, shape):
    """Mask [tm, tn] of the visit's rows that belong to its expert."""
    g = vis_g[v]
    row = vis_t[v] * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= off[g]) & (row < off[g + 1])


class Form(NamedTuple):
    """One expert's feed-forward form: the matrices of its up
    projection (2: a gate and an up matrix, 1: an up matrix alone),
    whether they are stored out by in ([S, F, H], as `nn.Linear` keeps
    them: Nemotron-H's, whose expert width 1856 is no whole number of
    128-lane vectors and as an array's minor dim would be padded by XLA
    in a COPY of the stack before the kernel), the activation over the
    float32 products, and what the kernels' `name=` carries of it."""
    n_up: int
    out_by_in: bool
    activate: Callable[..., jax.Array]
    suffix: str


FORMS = {
    # silu(x W_g) * (x W_i): DeepSeek-V3, Trinity
    "swiglu": Form(2, False, lambda g, u: jax.nn.silu(g) * u, ""),
    # relu(x W_g) * (x W_i): SmallThinker
    "reglu": Form(2, False, lambda g, u: jnp.maximum(g, 0.0) * u, "_reglu"),
    # relu(x W_up)^2, no gate matrix (`mlp_hidden_act` "relu2"): Nemotron-H
    "relu2": Form(1, True, lambda u: jnp.square(jnp.maximum(u, 0.0)),
                  "_relu2"),
}


def _up_kernel(vis_g, vis_t, off, base, x_ref, row_ref, *refs, tm: int,
               exact, form: Form):
    """refs: the form's up matrices' tiles, the output tile, one float32
    accumulator a matrix."""
    del base                       # the index maps' alone
    w_refs, h_ref, accs = (refs[:form.n_up], refs[form.n_up],
                           refs[form.n_up + 1:])
    v, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)  # jaxlint: disable=JL004 -- a Pallas scratch ref: a store in the kernel, not a Python mutation

    # the tile's rows of x, fetched by a one-hot product: row i is the
    # token whose assignment to this expert sits at row i (exact: one 1
    # a row), zeros where the row is another expert's or nobody's
    t = x_ref.shape[0]
    here = (vis_t[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, t), 0)
            == row_ref[...]).astype(x_ref.dtype)
    xs = jnp.dot(here, x_ref[...], precision=exact,
                 preferred_element_type=jnp.float32).astype(x_ref.dtype)
    over = (((1,), (1 if form.out_by_in else 0,)), ((), ()))
    for w_ref, acc in zip(w_refs, accs):
        acc[...] += lax.dot_general(  # jaxlint: disable=JL004 -- a Pallas scratch ref: a store in the kernel, not a Python mutation
            xs, w_ref[...], over, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        h = form.activate(*(acc[...] for acc in accs)).astype(h_ref.dtype)
        first = (v == 0) | (vis_t[jnp.maximum(v - 1, 0)] != vis_t[v])
        kept = jnp.where(first, jnp.zeros_like(h), h_ref[...])
        h_ref[...] = jnp.where(
            _own_rows(v, vis_g, vis_t, off, tm, h.shape), h, kept)


def _column(ref, g):
    """Column g of a [T, E] block as [T, 1]: a masked sum over lanes."""
    a = ref[...]
    mine = lax.broadcasted_iota(jnp.int32, a.shape, 1) == g
    return jnp.sum(jnp.where(mine, a, 0), axis=1, keepdims=True)


def _down_kernel(vis_g, vis_t, off, base, h_ref, wd_ref, place_ref,
                 gates_ref, out_ref, acc, *, tm: int):
    del base                       # the index maps' alone
    v, k = pl.program_id(1), pl.program_id(2)

    @pl.when((v == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(h_ref[...], wd_ref[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        f32, bf16 = jnp.float32, jnp.bfloat16
        y = jnp.where(_own_rows(v, vis_g, vis_t, off, tm, acc.shape),
                      acc[...], 0.0)
        # the way back, by the same one-hot the other way round: token
        # t takes the row its assignment to this expert sits at
        t = out_ref.shape[0]
        at = (_column(place_ref, vis_g[v]) == vis_t[v] * tm
              + lax.broadcasted_iota(jnp.int32, (t, tm), 1)).astype(bf16)
        back = jnp.zeros(out_ref.shape, f32)
        for _ in range(_PARTS):
            part = y.astype(bf16)
            back += jnp.dot(at, part, preferred_element_type=f32)
            y = y - part.astype(f32)
        out_ref[...] += _column(gates_ref, vis_g[v]) * back


@functools.partial(jax.jit, static_argnames=("act", "rows", "interpret"))
def grouped_ffn(x: jax.Array, gates: jax.Array, place: jax.Array,
                offsets: jax.Array, visits, ups: Tuple[jax.Array, ...],
                wd: jax.Array, base: jax.Array, *, act: str, rows: int,
                interpret: bool = False) -> jax.Array:
    """x: [T, H]; gates: [T, E] float32; place: [T, E] and offsets:
    [E + 1] from `assignment_rows`; `visits`: `row_visits(offsets,
    rows)`; `act`: a key of `FORMS`; ups: that form's up matrices, [S,
    H, F] each (out by in: [S, F, H]), wd: [S, F, H], a stack of S >= E
    experts of which [base, base + E) are this layer's (base: an int32
    scalar, traced or not; it rides with the prefetched scalars and
    shifts the weights' block index alone, so a stack of several layers'
    experts is never sliced, which would copy it); `rows`: the most
    assignments there can be -> [T, H] float32: for each token the
    gate-weighted sum of its assignments' feed-forward. Tiles follow the
    operands' shapes; the kernels are named by the form
    (`moe_grouped_up` / `_down` + `Form.suffix`)."""
    form = FORMS[act]
    t, hid = x.shape
    e = offsets.shape[0] - 1
    ffn = wd.shape[1]
    tm = row_tile(rows)
    rows = -(-rows // tm) * tm
    vis_g, vis_t, n_visits = visits
    base = jnp.asarray(base, jnp.int32).reshape(1)
    item = jnp.dtype(ups[0].dtype).itemsize
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
    # block indices of a grid step (column tile n, visit v, depth tile k)
    # from the prefetched scalars: the visit's expert and row tile
    of_expert = lambda n, v, k, g, tl, off, b: (b[0] + g[v], k, n)
    of_expert_t = lambda n, v, k, g, tl, off, b: (b[0] + g[v], n, k)
    of_tile = lambda n, v, k, g, tl, off, b: (tl[v], n)
    whole = lambda n, v, k, g, tl, off, b: (0, 0)

    tn = _divisor(ffn, 2048)
    tk = _divisor(hid, _WEIGHT_TILE_BYTES // (tn * item))
    w_up = (pl.BlockSpec((None, tn, tk), of_expert_t) if form.out_by_in
            else pl.BlockSpec((None, tk, tn), of_expert))
    h = pl.pallas_call(
        functools.partial(
            _up_kernel, tm=tm, form=form,
            exact=lax.Precision.HIGHEST if x.dtype == jnp.float32 else None),
        out_shape=jax.ShapeDtypeStruct((rows, ffn), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ffn // tn, n_visits, hid // tk),
            in_specs=[
                pl.BlockSpec((t, tk),
                             lambda n, v, k, g, tl, off, b: (0, k)),
                pl.BlockSpec((None, 1, t),
                             lambda n, v, k, g, tl, off, b: (g[v], 0, 0)),
                *[w_up] * form.n_up],
            out_specs=pl.BlockSpec((tm, tn), of_tile),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * form.n_up),
        compiler_params=params, interpret=interpret,
        name="moe_grouped_up" + form.suffix,
    )(vis_g, vis_t, offsets, base, x, place.T[:, None, :], *ups)

    # the expert width whole where it fits: every step then ends in the
    # way back to the tokens, under the next weight tile's fetch (with
    # the width cut in four the last step's stood in the open: 0.58 ->
    # 0.46 ms at 512 tokens, my chip run, PR 32)
    tk = _divisor(ffn, 4096)
    tn = _divisor(hid, min(_WEIGHT_TILE_BYTES // (tk * item),
                           _OUT_TILE_BYTES // (t * 4)))
    out = pl.pallas_call(
        functools.partial(_down_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((t, hid), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(hid // tn, n_visits, ffn // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, v, k, g, tl, off, b: (tl[v], k)),
                pl.BlockSpec((None, tk, tn), of_expert),
                pl.BlockSpec((t, e), whole),
                pl.BlockSpec((t, e), whole)],
            out_specs=pl.BlockSpec(
                (t, tn), lambda n, v, k, g, tl, off, b: (0, n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=params, interpret=interpret,
        name="moe_grouped_down" + form.suffix,
    )(vis_g, vis_t, offsets, base, h, wd, place, gates)
    # no visit, no store: a tick that sent nothing here reads as zeros
    return jnp.where(n_visits > 0, out, 0.0)
