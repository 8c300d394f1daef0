"""A selective scan (Mamba-1's recurrence) and its causal convolution
over a RAGGED token axis, for continuous batching: a tick's flat batch
holds one contiguous run of tokens a slot (a prompt's chunk, or one
decode token), each run continues the state its slot stored at the end
of the tick before, and the end state of each run is written back.

The recurrence, per channel e of E and state index n of N, float32:

    s_t = exp(delta_t[e] * A[e, n]) * s_{t-1} + (delta_t[e] x_t[e]) * B_t[n]
    y_t[e] = sum_n s_t[e, n] * C_t[n] + D[e] * x_t[e]

`s_{t-1}` at a run's FIRST token is the slot's stored state, or zeros
where the run starts its sequence (`start` 0: a slot reused after
`vacate` needs no clearing). An invalid token (the padding behind the
tick's runs) passes the state by: delta = 0 there, so exp(0) = 1 and
nothing is added.

The state lives as `[slots, N, E]` (E in the lanes: N = 16 is two
sublane tiles, where `[E, N]` would fill an eighth of every vector).

impl (the names the attention ops take):
- "gather": plain `jax.numpy`, an associative scan over the token axis
  whose elements (a_t, b_t) compose as (a2 a1, a2 b1 + b2); a run's first
  token takes a_t = 0 and b_t = a_t s_in + b_t, which cuts it off from
  whatever precedes it in the flat batch. The oracle, and what runs off
  the chip.
- "pallas" / "pallas_interpret": `ssm_ragged_scan`, a chunked scan. The
  grid is (blocks of E, chunks of tokens); a block's state is carried in
  VMEM from chunk to chunk, one token at a time, eight to a loop step
  (the recurrence is
  sequential in t: the work is vector multiply-adds and one exponential
  a state element, no matrix product). At a run's first token the carry
  is replaced by the slot's stored state (or zeros), at its last the
  carry is stored to the slot's row of the output state, which aliases
  the input: the rows of slots without a token this tick stay as they
  were. B_t and C_t arrive broadcast along the lanes, `[T, N, 128]`
  (16 KiB a token, written by XLA before the kernel): a column of N
  values down the sublanes is then one aligned load, where building it
  from `[T, N]` in the kernel wants a transpose a token.

`causal_conv_ragged` is plain `jax.numpy` under every impl: four taps
a channel, which XLA fuses into one pass over `[T, E]`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8
# channels a grid step carries ([N, E_BLOCK] float32 of state: 8 vector
# registers at N = 16) and tokens a chunk
E_BLOCK, T_CHUNK = 512, 128
KERNEL_NAME = "ssm_ragged_scan"


class Marks(NamedTuple):
    """What the scan and the conv need of a tick's packing. int32 [T]:
    offset, a token's place in its run (0 at the run's first token);
    first, 0 not a run's first token, 1 first and the slot's stored
    state continues, 2 first of a sequence (zero state); last, 1 at a
    run's last token (its state is written back). bool [B]: has, the
    slots with a run this tick."""
    offset: jax.Array
    first: jax.Array
    last: jax.Array
    has: jax.Array


def segment_marks(slot_ids: jax.Array, positions: jax.Array,
                  valid: jax.Array, start: jax.Array, last_idx: jax.Array
                  ) -> Marks:
    """The tick's `Marks`, once for every layer. slot_ids, positions,
    valid: [T]; start, last_idx: [B] (a slot's cached tokens, and the
    flat row of its last token this tick)."""
    t = slot_ids.shape[0]
    b = start.shape[0]
    s0 = start[slot_ids]
    offset = jnp.where(valid, positions - s0, 0).astype(jnp.int32)
    first = jnp.where(valid & (offset == 0),
                      jnp.where(s0 == 0, 2, 1), 0).astype(jnp.int32)
    rows = jnp.arange(b, dtype=jnp.int32)
    has = valid[last_idx] & (slot_ids[last_idx] == rows)
    # rows without a token point past the batch: dropped
    last = jnp.zeros((t,), jnp.int32).at[
        jnp.where(has, last_idx, t)].set(1, mode="drop")
    return Marks(offset, first, last, has)


def causal_conv_ragged(x: jax.Array, w: jax.Array, bias: jax.Array,
                       slot_ids: jax.Array, last_idx: jax.Array,
                       marks: Marks, conv_in: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """A causal depthwise convolution of K taps over each run, continued
    from the slot's last K - 1 inputs of the tick before.

    x: [T, E]; w: [K, E] (tap K - 1 multiplies the token itself);
    bias: [E]; conv_in: [B, K - 1, E], a slot's last K - 1 inputs,
    oldest first (read as zeros where the run starts its sequence).
    Returns (conv(x) + bias [T, E] float32, the slots' new last K - 1
    inputs [B, K - 1, E]: rows of slots without a token are returned as
    they came)."""
    offset, first, _, has = marks
    t, e = x.shape
    k = w.shape[0]
    b = conv_in.shape[0]
    f32 = jnp.float32
    # which slots' stored inputs are zeros: the run starts a sequence
    fresh = jnp.zeros((b,), bool).at[slot_ids].max(first == 2)
    stored = jnp.where(fresh[:, None, None], 0, conv_in).astype(x.dtype)
    acc = x.astype(f32) * w[k - 1].astype(f32)
    for back in range(1, k):
        # the input `back` tokens ago: in this tick's run, or among the
        # stored ones (index K - 1 + offset - back, oldest first)
        inside = offset >= back
        ago = jnp.roll(x, back, axis=0)
        at = jnp.clip(k - 1 + offset - back, 0, k - 2)
        old = stored[slot_ids, at]
        prev = jnp.where(inside[:, None], ago, old)
        acc = acc + prev.astype(f32) * w[k - 1 - back].astype(f32)
    # the new stored inputs: offsets n - (K - 1) .. n - 1 of each run
    rows = jnp.arange(b, dtype=jnp.int32)
    n_last = offset[last_idx]                       # the run's n - 1
    new = []
    for i in range(k - 1):
        back = k - 2 - i                            # tokens before last
        inside = n_last >= back
        tok = jnp.clip(last_idx - back, 0, t - 1)
        at = jnp.clip(k - 1 + n_last - back, 0, k - 2)
        new.append(jnp.where(inside[:, None], x[tok],
                             stored[rows, at]))
    new = jnp.stack(new, axis=1).astype(conv_in.dtype)
    return (acc + bias.astype(f32),
            jnp.where(has[:, None, None], new, conv_in))


def _scan_reference(delta, dx, a_t, b_mat, c_mat, first, slot_ids,
                    state_in):
    """The associative-scan form. delta, dx: [T, E] float32 (0 at
    invalid tokens); a_t: [N, E]; b_mat, c_mat: [T, N]; state_in:
    [B, N, E]. Returns (y [T, E], the state after every token
    [T, N, E])."""
    a = jnp.exp(delta[:, None, :] * a_t[None])              # [T, N, E]
    b = dx[:, None, :] * b_mat[:, :, None]
    s_in = jnp.where((first == 2)[:, None, None], 0.0,
                     state_in[slot_ids])
    head = (first != 0)[:, None, None]
    b = jnp.where(head, a * s_in + b, b)
    a = jnp.where(head, 0.0, a)

    def compose(lo, hi):
        return hi[0] * lo[0], hi[0] * lo[1] + hi[1]

    _, s = lax.associative_scan(compose, (a, b), axis=0)
    y = jnp.einsum("tne,tn->te", s, c_mat)
    return y, s


def _scan_kernel(slot_ref, first_ref, last_ref, layer_ref,  # prefetched
                 d_ref, dx_ref, a_ref, b_ref, c_ref, s_in_ref,
                 y_ref, s_out_ref, carry, *, t_chunk: int, e_block: int):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        # the rows of slots without a token this tick go out as they
        # came in; the carry before a tick's first run is never read
        s_out_ref[...] = s_in_ref[...]
        carry[...] = jnp.zeros_like(carry)

    n_blk = e_block // LANES
    row_of = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def tokens(g, _):
        # eight tokens a step: their delta and delta * x rows are one
        # aligned tile a lane block, each row a static slice of it (a
        # row read at a traced sublane has no layout a broadcast takes)
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        d8 = d_ref[pl.ds(base, SUBLANES), :]             # [8, E_BLOCK]
        dx8 = dx_ref[pl.ds(base, SUBLANES), :]
        ys = [jnp.zeros((SUBLANES, LANES), jnp.float32)] * n_blk
        for r in range(SUBLANES):
            t = chunk * t_chunk + base + r
            slot, head = slot_ref[t], first_ref[t]

            @pl.when(head == 1)
            def _():
                carry[...] = s_in_ref[slot]

            @pl.when(head == 2)
            def _():
                carry[...] = jnp.zeros_like(carry)

            b, c = b_ref[base + r], c_ref[base + r]      # [N, 128]
            for j in range(n_blk):
                lanes = slice(j * LANES, (j + 1) * LANES)
                s = (jnp.exp(d8[r:r + 1, lanes] * a_ref[:, lanes])
                     * carry[:, lanes] + dx8[r:r + 1, lanes] * b)
                carry[:, lanes] = s
                y = jnp.sum(s * c, axis=0, keepdims=True)
                ys[j] = jnp.where(row_of == r, y, ys[j])

            @pl.when(last_ref[t] == 1)
            def _():
                s_out_ref[slot] = carry[...]
        for j in range(n_blk):
            y_ref[pl.ds(base, SUBLANES), j * LANES:(j + 1) * LANES] = ys[j]
        return 0

    lax.fori_loop(0, t_chunk // SUBLANES, tokens, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(slot_ids, first, last, layer, delta, dx, a_t, b_mat, c_mat,
               state, *, interpret: bool):  # jaxlint: disable=JL002 -- the state is aliased by the kernel, in place; an inner jit that shares the kernel's trace, inlined into the engine's program, which donates it
    """The pallas_call: state [L, B, N, E] whole, layer `layer`'s rows
    read and written in place (the output aliases it; the other layers'
    blocks are never visited)."""
    t_given, e = delta.shape
    _, n_slots, n, _ = state.shape
    # whole sublane tiles, and whole chunks: the padding is invalid
    # tokens (delta 0, no run starts or ends there)
    t = -(-t_given // SUBLANES) * SUBLANES
    t = t if t <= T_CHUNK else -(-t // T_CHUNK) * T_CHUNK
    pad = lambda m: jnp.pad(m, ((0, t - t_given),) + ((0, 0),) * (m.ndim - 1))
    slot_ids, first, last, delta, dx, b_mat, c_mat = (
        pad(m) for m in (slot_ids, first, last, delta, dx, b_mat, c_mat))
    t_chunk = min(T_CHUNK, t)
    e_block = min(E_BLOCK, e)
    if t % t_chunk or t_chunk % SUBLANES or e % e_block \
            or e_block % LANES:
        raise ValueError(
            f"ssm_ragged_scan wants T {t} a multiple of {t_chunk} and of "
            f"{SUBLANES}, and E {e} a multiple of {e_block} (whole "
            f"{LANES}-lane vectors)")
    wide = lambda m: jnp.broadcast_to(m[:, :, None], (t, n, LANES))
    tok = pl.BlockSpec((t_chunk, e_block), lambda eb, c, *_: (c, eb))
    col = pl.BlockSpec((t_chunk, n, LANES), lambda eb, c, *_: (c, 0, 0))
    # the layer's index rides with the prefetched scalars: a stack that
    # scans its layers hands a traced one
    rows = pl.BlockSpec((None, n_slots, n, e_block),
                        lambda eb, c, s_, f_, l_, lay: (lay[0], 0, 0, eb))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, t_chunk=t_chunk, e_block=e_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(e // e_block, t // t_chunk),
            in_specs=[tok, tok,
                      pl.BlockSpec((n, e_block), lambda eb, c, *_: (0, eb)),
                      col, col, rows],
            out_specs=[tok, rows],
            scratch_shapes=[pltpu.VMEM((n, e_block), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((t, e), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 9 (4 prefetched scalars + 5 arrays before it)
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slot_ids, first, last, jnp.asarray(layer, jnp.int32).reshape(1),
      delta, dx, a_t, wide(b_mat), wide(c_mat), state)
    return y[:t_given], state


def selective_scan_ragged(x: jax.Array, delta: jax.Array, a_t: jax.Array,
                          b_mat: jax.Array, c_mat: jax.Array,
                          d_skip: jax.Array, slot_ids: jax.Array,
                          valid: jax.Array, last_idx: jax.Array,
                          marks: Marks, state: jax.Array, layer: int, *,
                          impl: str = "gather"
                          ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence of the module's docstring over one tick.

    x: [T, E] (the conv's activated output); delta: [T, E] float32,
    after softplus; a_t: [N, E] float32 (A transposed, negative);
    b_mat, c_mat: [T, N]; d_skip: [E]; slot_ids, valid: [T]; last_idx:
    [B]; marks: `segment_marks`'; state: [L, B, N, E] float32,
    every layer's, of which `layer` (an int, or a traced one) is this one's.
    Returns (y [T, E] float32, state with layer `layer`'s rows of the
    slots that had tokens replaced by their runs' end states)."""
    f32 = jnp.float32
    _, first, last, has = marks
    live = valid[:, None]
    xf = x.astype(f32)
    delta = jnp.where(live, delta.astype(f32), 0.0)
    dx = delta * xf
    b_mat, c_mat = b_mat.astype(f32), c_mat.astype(f32)
    slots = jnp.where(valid, slot_ids, 0).astype(jnp.int32)
    if impl in ("pallas", "pallas_interpret"):
        y, state = _scan_call(
            slots, first, last, layer, delta, dx, a_t.astype(f32), b_mat,
            c_mat, state, interpret=(impl == "pallas_interpret"))
    else:
        def channels(blk):
            # [T, N, a block of E] at a time: the scan's elements are T x
            # N x E float32, 168 MB at the published sizes, several times
            # over inside the associative scan
            d_b, dx_b, a_b, s_b = blk
            y_b, s_all = _scan_reference(d_b, dx_b, a_b, b_mat, c_mat,
                                         first, slots, s_b)
            return y_b, jnp.where(has[:, None, None], s_all[last_idx], s_b)

        e = x.shape[1]
        n_blk = e // E_BLOCK if e % E_BLOCK == 0 else 1
        cut = lambda m: jnp.moveaxis(
            m.reshape(m.shape[:-1] + (n_blk, e // n_blk)), -2, 0)
        y, new = lax.map(channels, (cut(delta), cut(dx),
                                    cut(a_t.astype(f32)),
                                    cut(state[layer])))
        join = lambda m: jnp.moveaxis(m, 0, -2).reshape(
            m.shape[1:-1] + (e,))
        y, state = join(y), state.at[layer].set(join(new))
    return y + d_skip.astype(f32) * xf, state
