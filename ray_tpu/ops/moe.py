"""Mixture-of-Experts: top-k routing + capacity-based dispatch.

Net-new for the TPU build (EP is absent in the reference — SURVEY.md
§2.4; vLLM-internal only). GShard/Switch-style formulation chosen FOR the
hardware: dispatch/combine are einsums against a [tokens, experts,
capacity] one-hot — static shapes, MXU-friendly, and when the expert
dimension is sharded over the `ep` mesh axis XLA lowers the dispatch
einsum to the all-to-all over ICI (no hand-written collective).

Two dispatch modes:

- capacity (default-off via ``dropless=False``): tokens over an
  expert's capacity are dropped (residual passes through), the standard
  Switch behavior — einsum one-hot dispatch, shapes static under jit.
- dropless (``dropless=True``): sort-by-expert + ``lax.ragged_dot``
  grouped matmuls — every routed token computes, no capacity knob. With
  an ``ep`` mesh axis the token shards exchange assignments with the
  expert owners through ``ops/ragged_exchange.py`` (TPU: the real
  ``ragged_all_to_all`` ICI collective; CPU tests: semantics-exact
  emulation). SURVEY §2.4's EP target (`ragged_all_to_all`-style,
  VERDICT r4 weak #7).

Serving (below the two training paths): sigmoid group-limited routing
and `held_experts_ffn`, the expert layer of a chip that holds a range
of the routed experts; its kernels are in ``ops/grouped_experts.py``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import with_logical_constraint as wlc
from . import grouped_experts as ge
from .ragged_exchange import exchange_offsets, ragged_all_to_all


def router_probs(x: jax.Array, router_w: jax.Array
                 ) -> jax.Array:
    """x: [T, H]; router_w: [H, E] → probs [T, E] (float32 softmax)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def top_k_routing(probs: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """probs: [T, E] → (gates [T, k] renormalized, indices [T, k])."""
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx


def load_balancing_loss(probs: jax.Array, idx: jax.Array,
                        num_experts: int) -> jax.Array:
    """Switch aux loss: E * Σ_e fraction_e * mean_prob_e."""
    t = probs.shape[0]
    sel = jax.nn.one_hot(idx[:, 0], num_experts)      # top-1 assignment
    fraction = jnp.mean(sel, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(fraction * mean_prob)


def make_dispatch(probs: jax.Array, k: int, capacity: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build (dispatch [T, E, C] one-hot, combine [T, E, C] gate-weighted,
    aux_loss) for capacity C per expert."""
    t, num_experts = probs.shape
    gates, idx = top_k_routing(probs, k)
    aux = load_balancing_loss(probs, idx, num_experts)

    dispatch = jnp.zeros((t, num_experts, capacity), probs.dtype)
    combine = jnp.zeros((t, num_experts, capacity), probs.dtype)
    for slot in range(k):                      # k is tiny (1-2): unrolled
        e = idx[:, slot]                       # [T]
        onehot = jax.nn.one_hot(e, num_experts, dtype=probs.dtype)
        # position of each token within its expert's queue, counting
        # earlier slots' assignments too
        prior = dispatch.sum(axis=2)           # [T, E] taken so far
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot
                    + prior.sum(axis=0, keepdims=True))  # [T, E]
        pos = jnp.sum(pos_in_e * onehot, axis=1)          # [T]
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)
        contrib = (onehot * keep[:, None])[:, :, None] * pos_oh[:, None, :]
        dispatch = dispatch + contrib
        combine = combine + contrib * gates[:, slot, None, None]
    return dispatch, combine, aux


def _swiglu_ragged(xs: jax.Array, wg: jax.Array, wi: jax.Array,
                   wd: jax.Array, counts: jax.Array) -> jax.Array:
    """Grouped SwiGLU over expert-sorted rows: three ragged_dot calls
    (per-group matmuls tile onto the MXU without capacity padding)."""
    dt = xs.dtype
    gate = jax.nn.silu(lax.ragged_dot(xs, wg.astype(dt), counts))
    up = lax.ragged_dot(xs, wi.astype(dt), counts)
    return lax.ragged_dot(gate * up, wd.astype(dt), counts)


def _dropless_local(xt: jax.Array, gates: jax.Array, idx: jax.Array,
                    wi: jax.Array, wg: jax.Array, wd: jax.Array
                    ) -> jax.Array:
    """Single-shard dropless dispatch: stable-sort the T*k assignments
    by expert, grouped matmuls, gate-weighted scatter-add combine."""
    t, h = xt.shape
    k = idx.shape[1]
    num_experts = wi.shape[0]
    eid = idx.reshape(-1)                          # [T*k]
    tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(eid, stable=True)
    xs = xt[tok[order]]
    counts = jnp.bincount(eid, length=num_experts).astype(jnp.int32)
    ys = _swiglu_ragged(xs, wg, wi, wd, counts)
    gat = gates.reshape(-1)[order].astype(jnp.float32)
    out = jnp.zeros((t, h), jnp.float32).at[tok[order]].add(
        ys.astype(jnp.float32) * gat[:, None])
    return out.astype(xt.dtype)


def _dropless_ep_shard(xt: jax.Array, router_w: jax.Array,
                       wi: jax.Array, wg: jax.Array, wd: jax.Array,
                       *, top_k: int, num_experts: int,
                       axis_name: str) -> jax.Array:
    """Per-shard body (inside shard_map over the ep axis).

    Tokens arrive replicated w.r.t. ep; this shard owns the STATIC
    slice [me*Tl, (me+1)*Tl) of tokens and the experts
    [me*El, (me+1)*El). Assignments travel to their expert's owner via
    the ragged exchange, compute in grouped ragged_dot matmuls, travel
    back, and combine on the token's home shard — zero drops, compute
    proportional to each shard's routed load.
    """
    P = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    t, h = xt.shape                    # t is pre-padded to a multiple of P
    tl = t // P
    el = wi.shape[0]                   # local experts (= num_experts / P)
    k = top_k

    xs_tok = lax.dynamic_slice_in_dim(xt, me * tl, tl)
    probs = router_probs(xs_tok, router_w)
    gates, idx = top_k_routing(probs, k)           # [Tl, k]

    # ---- forward exchange: my assignments -> expert owners ----
    a = tl * k
    eid = idx.reshape(-1)
    tok = jnp.repeat(jnp.arange(tl), k)
    order = jnp.argsort(eid, stable=True)          # dest-shard-major
    xs = xs_tok[tok[order]]
    counts_e = jnp.bincount(eid, length=num_experts).astype(jnp.int32)
    send_sizes = counts_e.reshape(P, el).sum(axis=1)
    in_off, out_off, recv_sizes = exchange_offsets(send_sizes, axis_name)

    buf_rows = t * k                   # worst case: every assignment here
    buf = jnp.zeros((buf_rows, h), xt.dtype)
    buf = ragged_all_to_all(xs, buf, in_off, send_sizes, out_off,
                            recv_sizes, axis_name=axis_name)
    # ship the expert ids alongside (padding marker -1 survives in
    # unwritten rows and routes to the zero-weight trash group below)
    ebuf = jnp.full((buf_rows, 1), -1, jnp.int32)
    ebuf = ragged_all_to_all(eid[order][:, None].astype(jnp.int32), ebuf,
                             in_off, send_sizes, out_off, recv_sizes,
                             axis_name=axis_name)
    local_e = jnp.where(ebuf[:, 0] >= 0, ebuf[:, 0] - me * el, el)

    # ---- local grouped compute (El real groups + 1 zero trash group) --
    order2 = jnp.argsort(local_e, stable=True)
    xs2 = buf[order2]
    counts2 = jnp.bincount(local_e, length=el + 1).astype(jnp.int32)
    zeros = jnp.zeros((1,) + wi.shape[1:], wi.dtype)
    ys2 = _swiglu_ragged(xs2, jnp.concatenate([wg, zeros]),
                         jnp.concatenate([wi, zeros]),
                         jnp.concatenate([wd, jnp.zeros(
                             (1,) + wd.shape[1:], wd.dtype)]), counts2)
    ys = jnp.zeros_like(buf).at[order2].set(ys2)   # undo local sort

    # ---- return exchange: computed rows -> token owners ----
    in_off_r = jnp.cumsum(recv_sizes) - recv_sizes
    out_off_r = lax.all_to_all(in_off, axis_name, 0, 0)
    back = jnp.zeros((a, h), xt.dtype)
    back = ragged_all_to_all(ys, back, in_off_r, recv_sizes, out_off_r,
                             send_sizes, axis_name=axis_name)

    gat = gates.reshape(-1)[order].astype(jnp.float32)
    out_l = jnp.zeros((tl, h), jnp.float32).at[tok[order]].add(
        back.astype(jnp.float32) * gat[:, None])
    # tokens are shard-disjoint: the P('ep') out_spec reassembles the
    # full token axis (no psum, no gather needed)
    return out_l.astype(xt.dtype)


def moe_ffn_dropless(x: jax.Array, router_w: jax.Array,
                     wi: jax.Array, wg: jax.Array, wd: jax.Array,
                     *, top_k: int = 2,
                     mesh: Optional[jax.sharding.Mesh] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Dropless MoE SwiGLU feed-forward (same contract as moe_ffn)."""
    from jax.sharding import PartitionSpec as PSpec
    from ..parallel.mesh import AXIS_EP
    b, s, h = x.shape
    num_experts = router_w.shape[1]
    xt = x.reshape(b * s, h)
    t = xt.shape[0]
    # aux loss on the full token set (cheap: router matmul only)
    probs = router_probs(xt, router_w)
    gates, idx = top_k_routing(probs, top_k)
    aux = load_balancing_loss(probs, idx, num_experts)

    ep = 1
    if mesh is not None:
        ep = dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS_EP, 1)
    if ep <= 1:
        out = _dropless_local(xt, gates, idx, wi, wg, wd)
        return out.reshape(b, s, h), aux

    pad = (-t) % ep
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, h), xt.dtype)])
    fn = jax.shard_map(
        functools.partial(_dropless_ep_shard, top_k=top_k,
                          num_experts=num_experts, axis_name=AXIS_EP),
        mesh=mesh,
        in_specs=(PSpec(), PSpec(), PSpec(AXIS_EP), PSpec(AXIS_EP),
                  PSpec(AXIS_EP)),
        out_specs=PSpec(AXIS_EP),
        axis_names={AXIS_EP})
    # f32 across the manual-ep boundary is LOAD-BEARING: the bf16 grad
    # path through a partial-manual shard_map check-fails XLA's SPMD
    # partitioner ("Invalid binary instruction opcode copy" in
    # CloneAllReduce; re-verified on this jaxlib — forward-only bf16
    # works, jax.grad crashes). Same workaround as models/pipeline.py.
    out = fn(xt.astype(jnp.float32), router_w, wi, wg, wd)
    if pad:
        out = out[:t]
    out = out.reshape(b, s, h).astype(x.dtype)
    # pin the output back to the activation layout — without the
    # constraint the partitioner can pick a tiling that has no named
    # PartitionSpec (jit output-sharding inference then fails)
    return wlc(out, "batch", "seq", "act_embed"), aux


def moe_ffn(x: jax.Array, router_w: jax.Array,
            wi: jax.Array, wg: jax.Array, wd: jax.Array,
            *, top_k: int = 2, capacity_factor: float = 1.25,
            dropless: bool = False,
            mesh: Optional[jax.sharding.Mesh] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """MoE SwiGLU feed-forward.

    x: [B, S, H]; router_w: [H, E]; wi/wg: [E, H, F]; wd: [E, F, H].
    Returns (out [B, S, H], aux_loss scalar). Shard wi/wg/wd with logical
    axes ("experts", ...) and the dispatched activations pick up the
    all-to-all over the ep mesh axis. ``dropless=True`` switches to the
    sort + ragged_dot path (no capacity drops; see module docstring).
    """
    if dropless:
        return moe_ffn_dropless(x, router_w, wi, wg, wd,
                                top_k=top_k, mesh=mesh)
    b, s, h = x.shape
    num_experts = router_w.shape[1]
    dt = x.dtype
    xt = x.reshape(b * s, h)
    t = xt.shape[0]
    capacity = max(int(t * top_k / num_experts * capacity_factor), 1)  # jaxlint: disable=JL001 -- t is a static shape; this int() runs at trace time on Python scalars

    probs = router_probs(xt, router_w)
    dispatch, combine, aux = make_dispatch(probs, top_k, capacity)
    # dispatch/combine einsums stay f32: they are one-hot gathers (tiny
    # FLOPs next to the expert matmuls), f32 keeps the gate weighting
    # exact, and bf16 one-hot contractions inside a partial-manual
    # shard_map (PP+MoE) check-fail both XLA SPMD partitioners
    # ("Invalid binary instruction opcode copy", jax 0.9/jaxlib).

    expert_in = jnp.einsum("tec,th->ech", dispatch,
                           xt.astype(jnp.float32)).astype(dt)
    expert_in = wlc(expert_in, "experts", None, "act_embed")
    gate = jax.nn.silu(jnp.einsum("ech,ehf->ecf", expert_in,
                                  wg.astype(dt)))
    up = jnp.einsum("ech,ehf->ecf", expert_in, wi.astype(dt))
    expert_out = jnp.einsum("ecf,efh->ech", gate * up, wd.astype(dt))
    expert_out = wlc(expert_out, "experts", None, "act_embed")
    out = jnp.einsum("tec,ech->th", combine,
                     expert_out.astype(jnp.float32))
    return out.reshape(b, s, h).astype(dt), aux


# ------------------------------------------- sigmoid group-limited routing
# DeepSeek-V3's router (`topk_method` "noaux_tc", `scoring_func`
# "sigmoid") and an expert layer that is told which experts it holds:
# the serving path of models/deepseek_v3.py. No auxiliary loss, no
# capacity, no token dropped.

def sigmoid_group_routing(x: jax.Array, router_w: jax.Array,
                          bias: jax.Array, *, n_group: int,
                          topk_group: int, top_k: int,
                          scale: float, normalize: bool = True
                          ) -> Tuple[jax.Array, jax.Array]:
    """x: [T, H]; router_w: [H, E]; bias: [E] (the checkpoint's
    `e_score_correction_bias`) -> (gate weights [T, top_k] float32,
    expert indices [T, top_k] int32).

    scores = sigmoid(x W) in float32 over ALL E experts; choice =
    scores + bias picks, scores weigh: a group's score is the sum of
    its two largest choices, the `topk_group` best groups stay, the
    `top_k` largest choices inside them are the picks (ties go to the
    lower index), and the gate weights are the picked SCORES (not
    choices) over their sum, times `scale`."""
    with jax.default_matmul_precision("highest"):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    t, e = choice.shape
    per_group = choice.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(lax.top_k(per_group, 2)[0], axis=-1)
    _, kept = lax.top_k(group_score, topk_group)             # [T, kept]
    group_on = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(group_on, e // n_group, axis=1),
                       choice, -jnp.inf)
    _, idx = lax.top_k(masked, top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, idx.astype(jnp.int32)


def held_gates(idx: jax.Array, w: jax.Array, lo: int, hi: int,
               valid: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The picks that fall on the experts held here, [lo, hi): (gate
    matrix [T, hi - lo] float32, zero where a token did not pick the
    expert; that mask itself [T, hi - lo] bool, the tick's assignments;
    assignments landed on each held expert [hi - lo] int32). Rows that
    are not `valid` (a tick's padding) pick nothing."""
    hit = (idx[..., None] - lo) == jnp.arange(hi - lo)       # [T, k, E]
    if valid is not None:
        hit = hit & valid[:, None, None]
    gates = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)
    took = jnp.any(hit, axis=1)
    return gates, took, jnp.sum(took, axis=0).astype(jnp.int32)


HELD_IMPLS = ("pallas", "pallas_interpret", "gather")


def platform_impl() -> str:
    """The `impl` of a caller that has no engine to ask (a check or a
    test of one `moe_block`): what the engine's `_resolve_impl` makes
    of "auto", by the same question, so the two agree on every
    platform."""
    return "gather" if jax.devices()[0].platform == "cpu" else "pallas"


def held_plan(took: jax.Array, *, picks: int, impl: str):
    """What `held_experts_ffn` needs of a tick's assignments besides the
    gates, from `took` [T, E] alone: (the row of each assignment [T, E],
    the groups' offsets [E + 1], the kernels' tile visits or None on the
    gather path). A function of its own so that a layer whose router
    runs ahead of its attention makes it THERE, with the picks."""
    if impl not in HELD_IMPLS:
        raise ValueError(f"held_plan: impl {impl!r} is none of "
                         f"{HELD_IMPLS}")
    t, e = took.shape
    place, offsets = ge.assignment_rows(took)
    visits = (ge.row_visits(offsets, t * min(picks, e))
              if impl in ("pallas", "pallas_interpret") else None)
    return place, offsets, visits


def held_experts_ffn(x: jax.Array, gates: jax.Array, took: jax.Array,
                     ups: Tuple[jax.Array, ...], wd: jax.Array, *, act: str,
                     picks: int, impl: str, base=0, plan=None) -> jax.Array:
    """The held experts' part of an expert layer's output: for each
    token the gate-weighted sum of the feed-forward of those of its picks
    that are held here. x: [T, H]; gates and took: [T, E] from
    `held_gates`; `act`: the experts' form, a key of
    `grouped_experts.FORMS` ("swiglu": silu(x W_g) * (x W_i), "reglu":
    relu(x W_g) * (x W_i), "relu2": relu(x W_up)^2 with no gate matrix);
    `ups`: that form's up matrices, (wg, wi) [S, H, F] or (w_up,) [S, F,
    H] (the ungated form's lies out by in: `grouped_experts.Form`); wd:
    [S, F, H]; `picks`: the router's picks a token. The E held experts
    of this layer are [base, base + E) of a stack of S >= E (several
    layers' experts in one array, so that a stack that scans its layers
    hands the kernels the array whole and an index; `base` may be
    traced). `plan`: `held_plan(took, ...)` where the caller made it
    ahead (default: made here). Returns [T, H] float32. What absent
    experts would add is left out.

    One grouped matrix product a projection over the tick's assignments
    sorted by held expert (`grouped_experts.assignment_rows`): the rows
    of x that picked expert e form group e of at most T * min(picks, E)
    rows, through the up matrices, the activation cast to the operands'
    type, and down in float32 (whole: a row that ReLU zeroed is
    multiplied all the same), each row times its gate added to its
    token. Its size is what the tick's router sent here: rows past the
    last assignment are not computed and an expert that received nothing
    is not read; every assignment is computed, there is no capacity and
    nothing is dropped.

    `impl` is the caller's, resolved (the engine's `_resolve_impl`, or
    `platform_impl` for a caller with no engine), and splits chip from
    host as the attention kernels' does: "pallas" is
    `grouped_experts.grouped_ffn` (its grid visits the row tiles that
    hold assignments), "pallas_interpret" the same kernels interpreted,
    "gather" the reference over the same sorted rows: what a CPU runs
    and what the checks hold the kernels to, on the chip 2-3 x the
    kernels' time at 512 tokens and not a serving path. Any other value
    is refused."""
    if impl not in HELD_IMPLS:
        raise ValueError(f"held_experts_ffn: impl {impl!r} is none of "
                         f"{HELD_IMPLS}")
    if act not in ge.FORMS:
        raise ValueError(f"held_experts_ffn: act {act!r} is none of "
                         f"{tuple(ge.FORMS)}")
    form = ge.FORMS[act]
    t, hid = x.shape
    e = took.shape[1]
    rows = t * min(picks, e)
    place, offsets, visits = plan or held_plan(took, picks=picks, impl=impl)
    if impl in ("pallas", "pallas_interpret"):
        return ge.grouped_ffn(x, gates, place, offsets, visits, tuple(ups),
                              wd, base, act=act, rows=rows,
                              interpret=(impl == "pallas_interpret"))
    f32 = jnp.float32
    # the same rows, the other way round: each row's token and gate (a
    # row past the last assignment is token T, dropped on the way back)
    at = jnp.where(took, place, rows).reshape(-1)
    tok = jnp.full((rows,), t, jnp.int32).at[at].set(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), e), mode="drop")
    gate = jnp.zeros((rows,), f32).at[at].set(gates.reshape(-1),
                                              mode="drop")
    xs = jnp.take(x, tok, axis=0, mode="clip")
    if act == "swiglu":
        # one layer's experts (S = E): `lax.ragged_dot` over the groups
        sizes = offsets[1:] - offsets[:-1]
        mid = form.activate(*(lax.ragged_dot(
            xs, w, sizes, preferred_element_type=f32) for w in ups))
        y = lax.ragged_dot(mid.astype(x.dtype), wd, sizes,
                           preferred_element_type=f32)
    else:
        # An expert at a time, each product over EVERY row and kept where
        # the row is the expert's: 2 x E products where the kernels run
        # the tiles that hold rows. Not `lax.ragged_dot`: it would want
        # the layer's experts sliced out of the stack, a copy; turned for
        # an up matrix that lies out by in XLA turns the whole STACK (a
        # 4.5 GB copy at Nemotron-H's published sizes, whatever is sliced
        # first); and its TPU lowering refuses float32 rows against
        # weights stored in bfloat16, which a check in float32
        # activations hands it
        row = jnp.arange(rows, dtype=jnp.int32)[:, None]
        over = (((1,), (1 if form.out_by_in else 0,)), ((), ()))

        def one(j, y):
            of = lambda w: lax.dynamic_index_in_dim(
                w, base + j, 0, keepdims=False).astype(x.dtype)
            mid = form.activate(*(lax.dot_general(
                xs, of(w), over, preferred_element_type=f32) for w in ups))
            down = jnp.dot(mid.astype(x.dtype), of(wd),
                           preferred_element_type=f32)
            return jnp.where((row >= offsets[j]) & (row < offsets[j + 1]),
                             down, y)

        y = lax.fori_loop(0, e, one, jnp.zeros((rows, hid), f32))
    return jnp.zeros((t, hid), f32).at[tok].add(y * gate[:, None],
                                                mode="drop")


# ------------------------------------------- softmax-of-the-picks routing
# SmallThinker's router (`moe_primary_router_apply_softmax`,
# `norm_topk_prob`): the serving path of models/smallthinker.py.

def softmax_pick_routing(x: jax.Array, router_w: jax.Array, *, top_k: int
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [T, H]; router_w: [H, E] -> (gate weights [T, top_k] float32,
    expert indices [T, top_k] int32, the logits [T, E] float32).

    logits = x W in float32 at the highest precision; the picks are the
    `top_k` largest LOGITS (ties go to the lower index); the weights a
    softmax over the picked logits alone, which is the softmax over all
    E with the picks renormalised.

    Why this is not `router_probs` + `top_k_routing` (the training
    paths', at the top of this file), which compute the same weights:
    those form the softmax over all E first (the auxiliary loss wants
    the probabilities) at the default matmul precision, and pick on the
    probabilities, where two logits a rounding apart can tie. Serving is
    held to a float32 reference pick for pick, so it picks on the logits
    themselves, at the highest precision, hands the logits back for
    that comparison, and has no use for the E-wide softmax. Neither can
    call the other without changing what it computes."""
    with jax.default_matmul_precision("highest"):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    top, idx = lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), idx.astype(jnp.int32), logits
