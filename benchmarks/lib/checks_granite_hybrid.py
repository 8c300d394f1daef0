"""Whether the GraniteHybrid family's outputs are right: the comparisons
behind `correct` for its serving cell, made outside the timed window.
`checks_nemotron_h.py`'s shape for a model with no routing (so no pick
flips and the whole-stack limits are tight), a 3.67 GB state group (so
no program may hold a second copy of it) and a decode tick of dozens of
live rows (the timed shape)."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import numpy as np

from . import checks_nemotron_h, reference_granite_hybrid
from .checks import _gap
from .checks_deepseek_v3 import _rows_gap

# Every limit below lies between two readings on the chip at the cell's
# own sizes (40 layers, published widths, `concurrent-chat-steady`'s
# engine): the WORST the system read over its seeds (ten set-ups on ten
# seeds: my chip runs, PR 52; given beside each limit), and the SMALLEST
# reading of the reference computed wrong in one of the ways
# `precision_probe` lists that the limit is there to catch (the probe of
# seed 2147483923; PERF.md section 6 has both). Every limit stands at
# more than twice the worst reading of those seeds, so that a fresh seed
# does not fail one. Nothing here is routed: two roundings of one input
# differ by the rounding, not by an expert, so a row reads what the
# median reads, give or take a fifth.
#
# WORST_ROW: 3.2 x the largest row read on the chip (0.0467, the kernel
# path against the reference; 0.0449 kernel against gather) and under
# the worst row of the attention's multiplier dropped (0.263), of the
# state not carried over a chunk boundary (0.98) and of the conv's
# inputs not carried (1.34, the row behind the boundary; its median,
# 0.004, says nothing). Two unrelated rows of logits are 1.41 apart.
WORST_ROW = 0.15
# Kernel path against the gather path, on the SAME cache and the SAME
# stored state: the same projections, bf16 operands and f32 statistics;
# they differ in the order of the flash blocks' sums against one dense
# softmax, in the scan's cut into chunks of 128 (a decode row: the
# recurrence itself) against one piece a tick, and in where each rounds
# the residual stream to bfloat16, 80 times through the stack (two
# roundings of 2^-9 a layer: ~0.02). Read on the chip: median row 0.0227
# to 0.0247 (the limit is 2.4 x the worst). A kernel that reads another
# tile's B and C, or the wrong half of a K/V pair, moves every row by
# about 1; no probe reads this limit (it compares two paths of the
# program).
KERNEL_MEDIAN_ROW = 0.06
# Gather path and kernel path (bf16 weights as stored, bf16 activations,
# f32 state and accumulation, a cache and a state the engine's own
# program filled in 512-token chunks through the engine's own cache
# manager) against the float32 reference, which computes every token of
# the sequence itself with one sequential scan: prefill in chunks, then
# decode through pages AND state, the decode tick's 34 rows one by one.
# Read on the chip: median row 0.0385 to 0.0407 in 40 comparisons (the
# limit is 2.2 x the worst). The reference in float8 operands, the
# precision below the stated one, reads 0.419: this limit is its. (The
# attention's multiplier dropped reads 0.062 here and the state not
# carried over a chunk boundary 0.059, too near the system's own 0.04
# for a limit with room on both sides: WORST_ROW and ONE_PASS_MEDIAN_ROW
# catch both. The scan state kept in bfloat16 reads 0.0114 and Delta in
# bfloat16 0.0029, both UNDER the system's own bfloat16 rounding: the
# two limits below are theirs.)
REFERENCE_MEDIAN_ROW = 0.09
# The family's forward in ONE pass, float32 (`one_pass_float32`):
# activations float32, products at the highest precision, the weights as
# stored, a sequence from position 0 in one tick with no cache read,
# against the reference's rows. The same mathematics in another order
# (the whole-tick form of the scan against the token-by-token one): a
# fault of the model's STRUCTURE or of the scan's PRECISION fails here.
# Read on the chip: median 9.9e-5 to 1.7e-4, a row at most 2.7e-4 (the
# limit is 3.5 x the worst median). The smallest wrong readings it is to
# catch: the conv's inputs not carried 1.72e-3, Delta rounded to bfloat16
# 3.04e-3, the state kept in bfloat16 0.0129, a dropped multiplier 0.057
# (the attention's) to 7.0.
ONE_PASS_MEDIAN_ROW = 6e-4
# ONE Mamba-2 layer (`mamba2_mixer`, the kernel path at G = 1, float32
# compute on the weights as stored) through a state cache of its own, two
# slots a tick, its input cut in two ticks so that the state and the
# conv's inputs cross a chunk boundary, against the reference's mixer on
# the same input. No bfloat16 activation: the scan's own arithmetic
# shows. Read on the chip: 3.5e-6 to 2.7e-5, a factor of eight between
# seeds (the limit is 5.6 x the worst, for that spread). The smallest
# wrong reading: Delta in bfloat16 6.35e-4 (4.2 x the limit), the state
# in bfloat16 1.25e-3, the state or the conv's inputs not carried 0.050 /
# 0.050, float8 0.073.
MAMBA_LAYER_REL_RMS = 1.5e-4
# The engine's own compiled programs (`jit_run`, `jit_step`: the forward
# behind the sampler) against the kernel path's logits, on the same
# inputs with the temperature at 0. The same forward compiled into
# another program may round elsewhere, so a token may be the other
# program's second choice where two logits tie (judged as
# `checks_nemotron_h` judges ties): seven rows of ten have to give the
# largest logit or one within ENGINE_NEAR_MAX of it, and none a token
# further under it than ENGINE_FLIP_MAX, where a wrong row, table, slot
# or program gives any of 100,352 ids, ~4 RMS below. Read on the chip:
# every token of every run the kernel path's argmax (10 of 10, 34 of 34;
# the worst 0.0 under the largest logit).
ENGINE_NEAR_MAX, ENGINE_FLIP_MAX = 0.05, 0.5
# wrong in one way each: what `precision_probe` reads the reference as
VARIANTS = reference_granite_hybrid.VARIANTS
# slots the decode tick holds besides the mixed tick's ten (as many of
# them as the engine has): with them the tick has 34 live rows at 34
# contexts, the cell's timed shape
EXTRA_ROWS = 24


class _Plan(checks_nemotron_h._Plan):
    """`checks_nemotron_h._Plan` laid out for a model whose contexts end
    at 3k tokens, plus EXTRA_ROWS slots that only the DECODE tick has:
    the mixed tick's ten rows (eight decode rows: five ticks in, two
    ticks and a quarter in, two ticks in, at a tick's boundary exactly,
    one short of it, just past one of the scan's 128-token chunks, one
    page in, the second token of a sequence; a chunk that continues a
    cached state; a prompt that starts, in a slot another sequence left)
    and a decode tick of all 34, each at a context of its own."""

    def __init__(self, eng, seed: int):
        ec, cfg = eng.config, eng.model_cfg
        page, B = ec.page_size, ec.max_batch_size
        self.B, self.page = B, page
        self.budget = budget = eng._tick_token_budget()
        self.T = eng._token_bucket(budget)
        fresh = max(budget // 5, 1)
        chunk = budget - 8 - fresh
        if B < 10 or chunk < 1:
            raise ValueError("the checks want 10 slots and a tick budget "
                             "over 9 tokens")
        # slot -> (base, cached tokens before the mixed tick, tokens it
        # adds in the mixed tick)
        self.rows = {
            0: (0, 5 * budget + 3, 1),
            1: (0, 2 * budget + budget // 4, 1),
            2: (1, 2 * budget + 5, 1),
            3: (1, budget, 1),
            4: (1, budget - 1, 1),
            5: (2, min(130, budget // 2), 1),
            6: (2, page + 3, 1),
            7: (2, 1, 1),
            8: (0, 3 * budget // 2, chunk),
            9: (3, 0, fresh)}
        # the decode tick's other rows: prefixes of the two long bases,
        # no two of a length
        step = max((2 * budget - 24) // EXTRA_ROWS, 1)
        self.extra = {10 + i: (i % 2, 24 + step * i)
                      for i in range(min(EXTRA_ROWS, B - 10))}
        lens = [0, 0, 0, 0]
        for b, cached, n in self.rows.values():
            lens[b] = max(lens[b], cached + n + 1)   # + the decode tick's
        for b, cached in self.extra.values():
            lens[b] = max(lens[b], cached + 1)
        if max(lens) + 2 > eng.max_seq:
            raise ValueError(f"the checks cache {max(lens)} tokens; "
                             f"max_seq_len is {eng.max_seq}")
        rng = np.random.default_rng(seed)
        self.bases = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
                      for n in lens]
        self.ref_len = max(lens)
        self.fresh_slot = 9
        longest = max(c for _, c, _ in self.rows.values())
        self.ctx = eng._ctx_bucket(longest)
        self.gather_ctx = 1
        while self.gather_ctx < -(-(longest + 2) // page):
            self.gather_ctx *= 2
        # prefixes of base 0 that `one_pass_float32` runs from position 0
        self.one_pass = sorted({min(budget // 2, 200), budget - 1,
                                budget + budget // 3, 2 * budget - 3})

    def decode(self):
        """slot -> (base, position) of the decode tick's token."""
        at = super().decode()
        at.update(self.extra)
        return at

    def extra_fills(self):
        """`fills` for the slots that only the decode tick has."""
        for s, (b, cached) in sorted(self.extra.items()):
            for pos0 in range(0, cached, self.budget):
                yield [(s, b, pos0, min(self.budget, cached - pos0))]


def _ticks(eng, plan: "_Plan", say):
    """`checks_kimi_linear._ticks` for this family: run the plan on the
    engine's own weights, POOLS, STATE, cache manager and page table:
    each slot admitted through `CacheManager.admit` and cached by the
    engine's own ragged program in chunks of the tick budget. Before
    that the fresh prompt's slot serves and vacates another sequence.
    Then, for the mixed tick (10 rows) and, after 24 more slots are
    cached, the decode tick (34 rows) on the same pools and state: the
    gather path's logits, the kernel path's, and the engine's own
    program at temperature 0, which also writes the tick's rows and
    state for what follows.

    A program that is not handed its arrays DONATED copies them (5.3 GB
    of pools and state, which does not fit beside the engine), so the
    gather and the kernel programs take them donated, return them, and
    the state rows of the tick's slots are put back from a copy taken
    before (76 MB a slot: 0.76 GB at the mixed tick, 2.6 GB at the decode
    tick). The K and V rows they wrote are the tick's own, which the
    next program writes again and no program reads.
    Returns ({"mixed" | "decode": (gather logits, kernel logits, engine
    tokens, rows)}, what the state group did)."""
    import jax
    import jax.numpy as jnp

    cfg, fam, cache = eng.model_cfg, eng.family, eng.cache
    kernel = eng._resolve_impl()
    B, T = plan.B, plan.T
    samp = np.zeros((4, B), np.float32)        # temperature 0
    samp[1] = samp[3] = 1.0
    samp = jnp.array(samp)
    key = jax.random.PRNGKey(0)
    seen = jnp.zeros((B, cfg.vocab_size), bool)
    run = eng._ragged_fn(T, plan.ctx, False)

    def tables():
        return jnp.array(cache.tables[0])

    def engine_run(kp, vp, seen, tick):
        toks, kp, vp, seen = run(
            eng.params, kp, vp, seen, jnp.array(tick[0]),
            jnp.array(tick[1]), samp, tables(), key, eng._lora_stacks,
            False)
        return np.asarray(toks), kp, vp, seen

    def ragged(impl):
        return jax.jit(lambda params, tok, slot, kp, vp, tables: (
            fam.ragged_forward(
                cfg, params, tok[0], tok[1], tok[2], tok[3] != 0,
                slot[0], slot[1], kp, vp, tables,
                ctx_pages=(plan.gather_ctx if impl == "gather"
                           else plan.ctx), impl=impl)),
            donate_argnums=(3, 4))

    def decode(impl):
        return jax.jit(lambda params, toks, pos, kp, vp, tables, active: (
            fam.decode_step(cfg, params, toks, pos, kp, vp, tables, active,
                            impl=impl)), donate_argnums=(3, 4))

    keep = jax.jit(lambda conv, state, slots: (conv[:, slots],
                                               state[:, slots]))
    put_back = jax.jit(
        lambda conv, state, slots, kept: (
            conv.at[:, slots].set(kept[0]), state.at[:, slots].set(kept[1])),
        donate_argnums=(0, 1))

    def both(make, args, kp, vp, slots):
        """The gather path and the kernel path on the same pools and
        state -> (gather logits, kernel logits, kp, vp as they came)."""
        slots = jnp.array(sorted(slots), jnp.int32)
        out = []
        for impl in ("gather", kernel):
            kept = keep(kp[1], vp[1], slots)
            lg, kp, vp = make(impl)(*args(kp, vp))
            out.append(np.asarray(lg))
            conv, state = put_back(kp[1], vp[1], slots, kept)
            del kept
            kp, vp = (kp[0], conv), (vp[0], state)
        return out[0], out[1], kp, vp

    totals = {s: cached + n + 2 for s, (_, cached, n) in plan.rows.items()}
    totals.update({s: cached + 2 for s, (_, cached) in plan.extra.items()})
    # the engine's pools and state, lent: every program donates them, so
    # they are handed from call to call and given back zeroed
    kp, vp = eng.k_pages, eng.v_pages
    eng.k_pages = eng.v_pages = None
    first_pages, pos = {}, {}
    n_ticks = 0

    def admit(s, tokens=None):
        want = totals[s] if tokens is None else tokens
        if not cache.can_admit(want):
            raise ValueError(f"the checks' slot {s} wants {want} tokens "
                             "of cache")
        first_pages[s] = cache.admit(s, want)
        pos[s] = 0

    def fill(fills, kp, vp, seen):
        nonlocal n_ticks
        for rows in fills:
            (s, _, pos0, n), = rows
            _, kp, vp, seen = engine_run(kp, vp, seen, plan.tick(rows))
            pos[s] = pos0 + n
            cache.advance(pos.items())
            n_ticks += 1
        return kp, vp, seen

    # the fresh prompt's slot first serves another sequence, and lets go
    reused = plan.fresh_slot
    n_left = min(checks_nemotron_h.REUSED_TOKENS, plan.budget,
                 len(plan.bases[2]))
    admit(reused, n_left + 2)
    _, kp, vp, seen = engine_run(kp, vp, seen, plan.tick(
        [(reused, 2, 0, n_left)]))
    eng.allocator.free(first_pages.pop(reused))
    cache.vacate(reused)
    del pos[reused]
    state_left = float(np.abs(np.asarray(
        vp[-1][:, reused], np.float32)).max())
    for s in sorted(plan.rows):
        admit(s)
    kp, vp, seen = fill(plan.fills(), kp, vp, seen)
    out = {}
    rows = plan.mixed()
    tick = plan.tick(rows)
    tok, slot = jnp.array(tick[0]), jnp.array(tick[1])
    lg_g, lg_k, kp, vp = both(
        ragged, lambda kp, vp: (eng.params, tok, slot, kp, vp, tables()),
        kp, vp, plan.rows)
    toks, kp, vp, seen = engine_run(kp, vp, seen, tick)
    out["mixed"] = (lg_g, lg_k, toks,
                    {s: (b, pos0 + n - 1) for s, b, pos0, n in rows})
    for s in sorted(plan.extra):
        admit(s)
    kp, vp, seen = fill(plan.extra_fills(), kp, vp, seen)
    state = {"reused_slot": reused,
             "state_left_in_reused_slot": state_left,
             "state_slots_held": [st.n_held for st in cache.states]}
    say(f"  cached {[c for _, c, _ in plan.rows.values()]} and "
        f"{[c for _, c in plan.extra.values()]} tokens in {n_ticks} ticks "
        f"of the engine's ragged program (T {T}, ctx bucket {plan.ctx} "
        f"pages, {kernel}); state group: {state}")
    at = plan.decode()
    cache.advance([(s, p) for s, (_, p) in at.items()])
    toks_in, posn = np.zeros(B, np.int32), np.zeros(B, np.int32)
    live = np.zeros(B, bool)
    for s, (b, p) in at.items():
        toks_in[s], posn[s], live[s] = plan.bases[b][p], p, True
    active = jnp.array(live)
    d_tok, d_pos = jnp.array(toks_in), jnp.array(posn)
    lg_g, lg_k, kp, vp = both(
        decode, lambda kp, vp: (eng.params, d_tok, d_pos, kp, vp, tables(),
                                active), kp, vp, at)
    zeros_f, ones_f = jnp.zeros(B, jnp.float32), jnp.ones(B, jnp.float32)
    zeros_i = jnp.zeros(B, jnp.int32)
    toks, kp, vp, seen = eng._decode_fn(
        eng.params, kp, vp, seen, d_tok, d_pos, tables(), active, key,
        zeros_f, ones_f, zeros_i, ones_f, zeros_i, eng._lora_stacks,
        zeros_i, False)
    out["decode"] = (lg_g, lg_k, np.asarray(toks), at)
    del seen
    # everything goes back: the slots' pages and state, the pools and
    # the state zeroed in place
    for s in at:
        eng.allocator.free(first_pages[s])
        cache.vacate(s)
    zero = jax.jit(lambda pools: jax.tree.map(lambda a: a * 0, pools),
                   donate_argnums=0)
    eng.k_pages, eng.v_pages = zero(kp), zero(vp)
    return out, state


# the family's forward over each prefix of `plan.one_pass`, alone in one
# tick from position 0 in float32: written against `eng.family`, so the
# NemotronH checks' serves this family as it is
one_pass_float32 = checks_nemotron_h.one_pass_float32


def _first_mamba(eng):
    """The first Mamba layer's mixer as its own tree (the reference's
    form)."""
    import jax
    return jax.tree.map(lambda a: a[0], {
        k: v for k, v in eng.params["mamba"].items() if k != "mlp"})


_mamba_input = checks_nemotron_h._mamba_input


def mamba_layer(eng, plan: "_Plan", seed: int) -> np.ndarray:
    """`mamba2_mixer` by the engine's impl on two sequences of normalised
    input, float32 compute on the first Mamba layer's weights as stored,
    through a two-slot state of its own in TWO ticks (a tick budget of
    the first sequence beside a few tokens of the second, then the rest
    of both: the state and the conv's inputs cross the boundary, two runs
    share a tick and the scan's 128-token chunks). Returns the mixer's
    output [2, n, H]."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.paged_common import mamba2_mixer
    from ray_tpu.ops import selective_scan as ssm

    cfg = dataclasses.replace(eng.model_cfg, dtype=jnp.float32)
    impl = eng._resolve_impl()
    u = _mamba_input(eng, plan, seed)
    n = u.shape[1]
    layer = _first_mamba(eng)
    group = eng.family.cache_groups(cfg, impl)[-1]
    conv, scan = (jnp.zeros((1, 2) + tuple(shape), dt)
                  for _, shape, dt in group.state.parts)
    few = max(min(37, plan.budget // 4), 1)
    cuts = [((0, 0, plan.budget - few), (1, 0, few)),
            ((0, plan.budget - few, n - plan.budget + few),
             (1, few, n - few))]

    def tick(layer, x, slot_ids, positions, valid, start, last_idx, conv,
             scan):
        with jax.default_matmul_precision("highest"):
            marks = ssm.segment_marks(slot_ids, positions, valid, start,
                                      last_idx)
            return mamba2_mixer(
                cfg, layer, x, marks, (slot_ids, valid, last_idx), conv,
                scan, 0, impl)

    run = jax.jit(tick)
    out = np.zeros(u.shape, np.float32)
    for rows in cuts:
        t = 8
        while t < sum(k for _, _, k in rows):
            t *= 2
        x = np.zeros((t, cfg.hidden), np.float32)
        meta = np.zeros((3, t), np.int32)
        start, last = np.zeros(2, np.int32), np.zeros(2, np.int32)
        cur = 0
        for s, p0, k in rows:
            x[cur:cur + k] = np.asarray(u[s, p0:p0 + k])
            meta[0, cur:cur + k], meta[2, cur:cur + k] = s, 1
            meta[1, cur:cur + k] = np.arange(p0, p0 + k)
            start[s], last[s] = p0, cur + k - 1
            cur += k
        y, conv, scan = run(layer, jnp.array(x), jnp.array(meta[0]),
                            jnp.array(meta[1]), jnp.array(meta[2] != 0),
                            jnp.array(start), jnp.array(last), conv, scan)
        y, cur = np.asarray(y), 0
        for s, p0, k in rows:
            out[s, p0:p0 + k] = y[cur:cur + k]
            cur += k
    return out


def mamba_layer_reference(eng, model, plan: "_Plan", seed: int,
                          operands=None, variant=()) -> np.ndarray:
    ref = reference_granite_hybrid
    hw = ref.how(operands, variant, plan.budget)
    layer = _first_mamba(eng)
    return np.stack([np.asarray(ref.mamba(ref._sizes(model), layer, seq, hw))
                     for seq in _mamba_input(eng, plan, seed)])


def _reference_rows(eng, model: Dict[str, Any], plan: "_Plan", wanted,
                    operands=None, variant=()):
    """The reference's logits for `wanted`, a list of (base, position):
    one forward a base, padded (causal: what follows a position changes
    nothing at it) to whole tick budgets, so that its blocks compile few
    shapes."""
    import jax.numpy as jnp
    from ray_tpu.models import granite_hybrid
    trees = granite_hybrid.layer_trees(eng.model_cfg, eng.params)
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(-(-len(base) // plan.budget) * plan.budget,
                          np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_granite_hybrid.logits(
            model, trees, jnp.array(padded), operands=operands, rows=rows,
            variant=variant, chunk=plan.budget))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick of 10 rows and one decode tick of 34 live rows at
    34 contexts, at the engine's own sizes, on its own pools and state
    through its own cache manager (`_Plan`, `_ticks`): (a) kernel path
    against gather path; (b) gather path AND kernel path against the
    float32 reference's one pass on the same token histories, row by
    row: prefill in 512-token chunks and then decoding through pages and
    state, in a slot that another sequence left; (c) the engine's own
    compiled programs against the kernel path; (d) the family's forward
    in one float32 pass and one Mamba-2 layer through a state cache
    across a chunk boundary, each against the reference's on the same
    input, tighter. Logits, not tokens. Returns {"ok", ...gaps}."""
    plan = _Plan(eng, seed)
    ticks, state = _ticks(eng, plan, say)
    out: Dict[str, Any] = {"ok": True, "longest_context": plan.ref_len,
                           "T": plan.T, "ctx_bucket_pages": plan.ctx,
                           "state_group": state}
    left = state["state_left_in_reused_slot"] > 0
    say(f"  {'ok' if left else 'FAILED'}: largest state value left in "
        f"the reused slot {state['state_left_in_reused_slot']:.3g} (> 0)")
    out["ok"] = out["ok"] and left
    wanted = {name: sorted(at.items())
              for name, (_, _, _, at) in ticks.items()}
    ref = _reference_rows(
        eng, model, plan,
        [w for name in ticks for _, w in wanted[name]]
        + plan.one_pass_rows())
    ref, ref_one = ref[:-len(plan.one_pass)], ref[-len(plan.one_pass):]
    g = _rows_gap(ref_one, one_pass_float32(eng, plan))
    g["ok"] = bool(g["finite"] and g["median_row"] <= ONE_PASS_MEDIAN_ROW
                   and g["worst_row"] <= WORST_ROW)
    say(f"  {'ok' if g['ok'] else 'FAILED'}: one_pass_float32 median row "
        f"{g['median_row']:.2e} of rms (<= {ONE_PASS_MEDIAN_ROW}), worst "
        f"row {g['worst_row']:.2e}, prefixes of {plan.one_pass} tokens")
    out["one_pass_float32"] = g
    out["ok"] = out["ok"] and g["ok"]
    for name, (lg_g, lg_k, toks, _) in ticks.items():
        slots = [s for s, _ in wanted[name]]
        want, ref = ref[:len(slots)], ref[len(slots):]
        ctxs = [p for _, (_, p) in wanted[name]]
        for what, a, b, median in (
                ("kernel_vs_gather", lg_g[slots], lg_k[slots],
                 KERNEL_MEDIAN_ROW),
                ("gather_vs_reference", want, lg_g[slots],
                 REFERENCE_MEDIAN_ROW),
                ("kernel_vs_reference", want, lg_k[slots],
                 REFERENCE_MEDIAN_ROW)):
            g = _rows_gap(a, b)
            g["ok"] = bool(g["finite"] and g["median_row"] <= median
                           and g["worst_row"] <= WORST_ROW)
            say(f"  {'ok' if g['ok'] else 'FAILED'}: {what}.{name} median "
                f"row {g['median_row']:.4f} of rms (<= {median}), worst "
                f"row {g['worst_row']:.4f} (<= {WORST_ROW}), argmax agree "
                f"{g['argmax_agree']}/{len(slots)}, {len(slots)} rows at "
                f"contexts {min(ctxs)} to {max(ctxs)}")
            out[f"{what}.{name}"] = g
            out["ok"] = out["ok"] and g["ok"]
        lg = np.asarray(lg_k, np.float32)
        rms = float(np.sqrt(np.mean(lg[slots] ** 2)))
        under = [(float(lg[s].max()) - float(lg[s, int(toks[s])])) / rms
                 if 0 <= int(toks[s]) < lg.shape[1] else float("inf")
                 for s in slots]
        e = {"worst_under_max": max(under),
             "argmax_agree": int(sum(u == 0.0 for u in under)),
             "rows_near_max": int(sum(u <= ENGINE_NEAR_MAX for u in under))}
        e["ok"] = bool(e["worst_under_max"] <= ENGINE_FLIP_MAX
                       and 10 * e["rows_near_max"] >= 7 * len(slots))
        say(f"  {'ok' if e['ok'] else 'FAILED'}: engine_program.{name} "
            f"{e['rows_near_max']}/{len(slots)} tokens within "
            f"{ENGINE_NEAR_MAX} of rms of the kernel path's largest logit "
            f"(>= 7 in 10), the furthest {e['worst_under_max']:.4f} under "
            f"it (<= {ENGINE_FLIP_MAX}), {e['argmax_agree']}/{len(slots)} "
            f"its argmax")
        out[f"engine_program.{name}"] = e
        out["ok"] = out["ok"] and e["ok"]
    m = _gap(mamba_layer_reference(eng, model, plan, seed),
             mamba_layer(eng, plan, seed))
    m["ok"] = bool(m["finite"] and m["rel_rms"] <= MAMBA_LAYER_REL_RMS)
    say(f"  {'ok' if m['ok'] else 'FAILED'}: mamba layer across a chunk "
        f"boundary, two runs a tick: rms gap {m['rel_rms']:.2e} (<= "
        f"{MAMBA_LAYER_REL_RMS})")
    out["mamba_layer"] = m
    out["ok"] = out["ok"] and m["ok"]
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None], only=()) -> Dict[str, Any]:
    """The second readings a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16),
    and computed wrong in each way of VARIANTS, against the reference
    itself: on the rows of the mixed and the decode tick, on the one-pass
    rows and on the Mamba layer's input. Each has to come out over at
    least one of REFERENCE_MEDIAN_ROW, WORST_ROW, ONE_PASS_MEDIAN_ROW and
    MAMBA_LAYER_REL_RMS, and `caught_by` names which (KERNEL_MEDIAN_ROW
    compares two paths of the program, not the reference). `only`: the
    names to read ("fp8" or a variant's; all of them where empty). Not
    part of a run: `runners/serve_granite_hybrid.py --probe` prints it."""
    import jax.numpy as jnp
    plan = _Plan(eng, seed)
    wanted: List = [(b, pos0 + n - 1) for _, b, pos0, n in plan.mixed()]
    wanted += list(plan.decode().values())
    n_ticks = len(wanted)
    wanted += plan.one_pass_rows()
    want = _reference_rows(eng, model, plan, wanted)
    want_mamba = mamba_layer_reference(eng, model, plan, seed)
    out: Dict[str, Any] = {}
    for name, kw in [("fp8", {"operands": jnp.float8_e4m3fn})] + [
            (v, {"variant": (v,)}) for v in VARIANTS]:
        if only and name not in only:
            continue
        got = _reference_rows(eng, model, plan, wanted, **kw)
        g = _rows_gap(want[:n_ticks], got[:n_ticks])
        one = _rows_gap(want[n_ticks:], got[n_ticks:])
        g["one_pass"] = {k: one[k] for k in ("median_row", "worst_row")}
        g["mamba_layer"] = _gap(want_mamba, mamba_layer_reference(
            eng, model, plan, seed, **kw))["rel_rms"]
        g["caught_by"] = [limit for limit, over in (
            ("REFERENCE_MEDIAN_ROW", g["median_row"] > REFERENCE_MEDIAN_ROW),
            ("WORST_ROW", g["worst_row"] > WORST_ROW),
            ("ONE_PASS_MEDIAN_ROW",
             one["median_row"] > ONE_PASS_MEDIAN_ROW),
            ("MAMBA_LAYER_REL_RMS", g["mamba_layer"] > MAMBA_LAYER_REL_RMS))
            if over]
        g["would_pass"] = not g["caught_by"]
        say(f"  the reference with {name} against the reference: ticks' "
            f"median row {g['median_row']:.4f}, worst row "
            f"{g['worst_row']:.4f}; one-pass median "
            f"{one['median_row']:.2e}; mamba layer {g['mamba_layer']:.2e}; "
            f"caught by {g['caught_by'] or 'NOTHING'}")
        out[name] = g
    return out
