"""Kimi Delta Attention's recurrence (a gated DELTA rule with a decay a
CHANNEL) over a RAGGED token axis, for continuous batching: a tick's
flat batch holds one contiguous run of tokens a slot (a prompt's chunk,
or one decode token), each run continues the state its slot stored at
the end of the tick before, and the end state of each run is written
back (`selective_scan.Marks` says where runs start and end; that
module's `causal_conv_ragged` is the convolution in front of this scan).

The recurrence, per head h of H, state S [K, V] float32, with q_t, k_t
[K] (normalised by the caller), v_t [V], a decay g_t [K] <= 0 a CHANNEL
and a step beta_t in (0, 1):

    S <- Diag(e^{g_t}) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

so a token's write depends on what the state already holds along k_t
(Mamba's states are only decayed and added to). `S` at a run's first
token is the slot's stored state, or zeros where the run starts its
sequence (`first` 2). An invalid token passes the state by.

Over a piece of a run (rows of one chunk), with G_t = sum_{s <= t} g_s
inside the piece, kb = beta k and vb = beta v:

    A   = strict-lower[(kb_t . k_j e^{G_t - G_j})_tj]
    V'  = (I + A)^-1 (vb - (kb e^G) S_in)
    O   = (q e^G) S_in + lower[(q_t . k_j e^{G_t - G_j})_tj] V'
    S_out = Diag(e^{G_end}) S_in + (k e^{G_end - G})^T V'

Every exponent that is kept is <= 0. The products under `lower` are NOT
taken as (k e^G)(k e^-G)^T: e^-G overflows as soon as one channel decays
fast (g = -20 a token passes float32's range in five tokens). They are
taken LEVEL BY LEVEL: at level b the rows of the odd blocks of b tokens
meet the columns of the even block before them, both against the last
row of that even block, G_ref: (k_t e^{G_t - G_ref}) . (k_j e^{G_ref -
G_j}), two exponents <= 0 whatever g is. log2(chunk) levels of one
masked matrix product each cover the strict lower triangle exactly once.
(I + A)^-1 is taken by blocks: the 8-token diagonal blocks by the
finite Neumann product (I + X)(I + X^2)(I + X^4), X = -A (X^8 = 0),
then pairs of blocks merged, [[T1, 0], [-T2 A21 T1, T2]], up to the
chunk: forward substitution by blocks, which stays bounded where the
whole chunk's Neumann series cancels catastrophically.

The state lives as `[layers, slots, H, K, V]` float32: 2 MB a slot a
layer at the published sizes (32 heads of 128 x 128).

impl (the names the attention ops take):
- "gather": plain `jax.numpy`, the recurrence itself a token at a time
  over the tick (`lax.scan`), every head at once. The oracle, what runs
  off the chip, and the other side of the kernel's comparison on it.
- "pallas" / "pallas_interpret": `kda_ragged_scan`. The tick is cut
  into SEGMENTS, the pieces of runs inside chunks of `T_CHUNK` tokens
  (`ssd_scan.segments`: a table on the device, read by scalar
  prefetch). The grid is (blocks of heads, segments). A step takes the
  segment's chunk of q, k, kb, vb, g (head-major, `[H, T, 128]`) and its
  SLOT's state of those heads as a block picked by the prefetched slot
  id; the output state aliases the input, so the states of slots
  without a run are never read nor written, and a run's state stays in
  VMEM from its first segment to its last. Two bodies, each a loop over
  the block's heads: a segment of ONE token (a decode row) runs the
  recurrence on the vector unit and is bound by the state's way in and
  out; a longer one runs the chunked form above as matrix products
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
from .ssd_scan import segments

SUBLANES = 8
T_CHUNK = 64                   # tokens a chunk: log2 = 6 levels
SOLVE_BLOCK = 8                # the diagonal blocks solved by Neumann
HEAD_BLOCK = 32                # heads a grid step (a loop inside it)
KERNEL_NAME = "kda_ragged_scan"
_HI = lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    """a [M, K] . b [N, K]^T."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _eye(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _to_column(row, eye):
    """A [1, n] row as an [n, 1] column: a sum over lanes under the
    diagonal mask `eye` [n, n]."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kda_kernel(chunk_ref, row_ref, len_ref, slot_ref, first_ref, lay_ref,
                q_ref, k_ref, kb_ref, vb_ref, g_ref, s_in_ref, o_ref,
                s_out_ref):
    """One segment of one block of heads. q, k, kb, g: [heads, Q, K];
    vb: [heads, Q, V]; the state blocks [heads, K, V]."""
    i = pl.program_id(1)
    r0, n, first = row_ref[i], len_ref[i], first_ref[i]
    heads, q_len, dk = q_ref.shape
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
        # row's g, k, kb and q become columns (K down the sublanes,
        # beside the state's [K, V]) by a diagonal mask
        at = pl.ds(r0, 1)
        diag = _eye(dk)

        def head(h, carry):
            a_col = jnp.exp(_to_column(g_ref[h, at, :], diag))
            s = a_col * s_out_ref[h]
            vp = vb_ref[h, at, :] - jnp.sum(
                s * _to_column(kb_ref[h, at, :], diag), axis=0,
                keepdims=True)
            s = s + _to_column(k_ref[h, at, :], diag) * vp
            s_out_ref[h] = s
            o_ref[h, at, :] = jnp.sum(
                s * _to_column(q_ref[h, at, :], diag), axis=0,
                keepdims=True)
            return carry

        lax.fori_loop(0, heads, head, 0)

    @pl.when(n > 1)
    def _():
        rows = lax.broadcasted_iota(jnp.int32, (q_len, 1), 0)
        cols = lax.broadcasted_iota(jnp.int32, (1, q_len), 1)
        in_r = (rows >= r0) & (rows < r0 + n)
        in_c = (cols >= r0) & (cols < r0 + n)
        inside = in_r & in_c
        eye = (rows == cols).astype(f32)
        # the levels' masks and reference rows, once for every head
        levels = []
        b = q_len // 2
        while b >= 1:
            sh = b.bit_length() - 1
            blk_r, blk_c = rows >> sh, cols >> sh
            pair = inside & ((blk_r & 1) == 1) & (blk_c == blk_r - 1)
            ref = (cols == ((blk_r | 1) << sh) - 1).astype(f32)  # [Q, Q]
            levels.append((b, pair, ref, (blk_r & 1) == 1))
            b //= 2
        cum = (inside & (cols <= rows)).astype(f32)
        sb = SOLVE_BLOCK.bit_length() - 1
        diag_blk = (rows >> sb) == (cols >> sb)
        last = rows == r0 + n - 1
        diag = _eye(dk)

        def head(h, carry):
            qh, kh, kbh = q_ref[h], k_ref[h], kb_ref[h]
            big_g = _mm(cum, jnp.where(in_r, g_ref[h], 0.0))    # [Q, K]
            a = jnp.zeros((q_len, q_len), f32)
            aq = jnp.where(inside, eye * jnp.sum(qh * kh, axis=1,
                                                 keepdims=True), 0.0)
            offs = {}
            for b, pair, ref, odd in levels:
                d = big_g - _mm(ref, big_g)
                e = jnp.exp(jnp.minimum(jnp.where(odd, d, -d), 0.0))
                ke = kh * e
                lvl = jnp.where(pair, _mm_nt(kbh * e, ke), 0.0)
                aq += jnp.where(pair, _mm_nt(qh * e, ke), 0.0)
                a += lvl
                if b >= SOLVE_BLOCK:
                    offs[b] = lvl
            # (I + A)^-1 by blocks
            x = -jnp.where(diag_blk, a, 0.0)
            t = eye + x
            blk = 2
            while blk < min(SOLVE_BLOCK, q_len):
                x = _mm(x, x)
                t = t + _mm(t, x)
                blk *= 2
            b = SOLVE_BLOCK
            while b < q_len:
                t = t - _mm(t, _mm(offs[b], t))
                b *= 2
            e_g = jnp.exp(big_g)
            s_in = s_out_ref[h]                                  # [K, V]
            vp = (_mm(t, jnp.where(in_r, vb_ref[h], 0.0))
                  - _mm(_mm(t, jnp.where(in_r, kbh * e_g, 0.0)), s_in))
            vp = jnp.where(in_r, vp, 0.0)
            o = _mm(qh * e_g, s_in) + _mm(aq, vp)
            o_ref[h] = jnp.where(in_r, o, o_ref[h])
            g_end = jnp.sum(jnp.where(last, big_g, 0.0), axis=0,
                            keepdims=True)                       # [1, K]
            k_out = jnp.where(in_r, kh * jnp.exp(
                jnp.minimum(g_end - big_g, 0.0)), 0.0)
            s_out_ref[h] = (_to_column(jnp.exp(g_end), diag) * s_in
                            + lax.dot_general(
                                k_out, vp, (((0,), (0,)), ((), ())),
                                precision=_HI, preferred_element_type=f32))
            return carry

        lax.fori_loop(0, heads, head, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_call(seg, layer, q, k, kb, vb, g, state, *, interpret: bool):  # jaxlint: disable=JL002 -- the state is aliased by the kernel, in place; an inner jit that shares the kernel's trace, inlined into the engine's program, which donates it
    """The pallas_call. q, k, kb, g: [H, T, K] float32; vb: [H, T, V];
    state: [L, slots, H, K, V] whole, layer `layer`'s rows of the slots
    with a run read and written in place."""
    chunk_of, row_of, length, slot_of, first_of, n_seg = seg
    h_all, t, dk = q.shape
    dv = vb.shape[2]
    hb = min(HEAD_BLOCK, h_all)
    qn = min(T_CHUNK, t)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    tok = lambda width: pl.BlockSpec(
        (hb, qn, width), lambda hg, i, ch, *_: (hg, ch[i], 0))
    rows = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda hg, i, ch, r, ln, sl, fi, lay: (lay[0], sl[i], hg, 0, 0))
    o, state = pl.pallas_call(
        _kda_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(h_all // hb, n_seg),
            in_specs=[tok(dk), tok(dk), tok(dk), tok(dv), tok(dk), rows],
            out_specs=[tok(dv), rows]),
        out_shape=[jax.ShapeDtypeStruct((h_all, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 11 (6 prefetched scalars + 5 arrays before it)
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 << 20),
        interpret=interpret,
        name=KERNEL_NAME,
    )(chunk_of, row_of, length, slot_of, first_of, lay, q, k, kb, vb, g,
      state)
    return o, state


def _gather(q, k, v, g, beta, first, slots, valid, state, layer):
    """The recurrence a token at a time over the whole tick, every head
    at once, on layer `layer`'s rows of `state` [L, slots, H, K, V] where
    they lie (a layer's states cut out and put back are two copies of
    0.4 GB at the published sizes)."""

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t, first_t, slot, ok = x
        old = state[layer, slot]
        s = jnp.where(first_t == 2, 0.0, old)
        s = jnp.exp(g_t)[:, :, None] * s
        vp = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                              precision=_HI))
        s = s + k_t[:, :, None] * vp[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, q_t, precision=_HI)
        return state.at[layer, slot].set(jnp.where(ok, s, old)), o

    return lax.scan(step, state, (q, k, v, g, beta, first, slots, valid))


def padded_tokens(t: int) -> int:
    """Rows the kernel path pads a tick of `t` tokens to: whole chunks
    past one chunk, else the next power of two (the levels halve it)."""
    if t > T_CHUNK:
        return -(-t // T_CHUNK) * T_CHUNK
    return max(SUBLANES, 1 << (t - 1).bit_length())


def kda_ragged_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                    beta: jax.Array, marks: Marks, slot_ids: jax.Array,
                    valid: jax.Array, last_idx: jax.Array, state: jax.Array,
                    layer, *, impl: str = "gather"
                    ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence of the module's docstring over one tick.

    q, k: [T, H, K], normalised and scaled by the caller; v: [T, H, V];
    g: [T, H, K] float32, the log of the decay, <= 0; beta: [T, H];
    marks: `selective_scan.segment_marks`'; slot_ids, valid: [T];
    last_idx: [B]; state: [L, B, H, K, V] float32, every layer's, of
    which `layer` (an int, or a traced one) is this one's.
    Returns (o [T, H, V] float32, state with layer `layer`'s rows of
    the slots that had tokens replaced by their runs' end states)."""
    f32 = jnp.float32
    t_given, h_all, _ = q.shape
    q, k, v, g = (m.astype(f32) for m in (q, k, v, g))
    ok = valid[:, None]
    beta = jnp.where(ok, beta.astype(f32), 0.0)
    g = jnp.where(ok[:, :, None], g, 0.0)
    slots = jnp.where(valid, slot_ids, 0).astype(jnp.int32)
    if impl in ("pallas", "pallas_interpret"):
        if h_all % min(HEAD_BLOCK, h_all):
            raise ValueError(f"{h_all} heads in blocks of {HEAD_BLOCK}")
        t = padded_tokens(t_given)
        pad = lambda m: jnp.pad(
            m, ((0, t - t_given),) + ((0, 0),) * (m.ndim - 1))
        padded = Marks(*(pad(m) for m in marks[:3]), marks.has)
        qn = min(T_CHUNK, t)
        seg = segments(padded, pad(slots), pad(valid), qn,
                       t // qn + last_idx.shape[0])
        by_head = lambda m: pad(m).transpose(1, 0, 2)            # [H, T, .]
        o, state = _kda_call(
            seg, layer, by_head(q), by_head(k),
            by_head(k * beta[:, :, None]), by_head(v * beta[:, :, None]),
            by_head(g), state, interpret=(impl == "pallas_interpret"))
        # rows no segment wrote hold whatever the buffer held
        o = jnp.where(valid[:, None, None],
                      o.transpose(1, 0, 2)[:t_given], 0.0)
    else:
        state, o = _gather(q, k, v, g, beta, marks.first, slots, valid,
                           state, layer)
        o = jnp.where(valid[:, None, None], o, 0.0)
    return o, state
