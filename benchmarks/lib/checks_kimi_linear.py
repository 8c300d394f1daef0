"""Whether the Kimi Linear family's outputs are right: the comparisons
behind `correct` for its serving cell, made outside the timed window.
The dense decoder's are in checks.py, the latent family's in
checks_deepseek_v3.py, NemotronH's in checks_nemotron_h.py (whose plan
of ticks on the engine's own pools, state and cache manager this file
runs, with a context past 12k and with every program donating the
arrays it is lent); this file is theirs for a delta-rule
state beside a one-pool latent group, a chunked ragged scan with a
triangular solve, unroped latent attention and SwiGLU held experts out
of a stack."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import numpy as np

from . import checks_nemotron_h, program_kimi_linear, reference_kimi_linear
from .checks import _gap
from .checks_deepseek_v3 import _engine_gap, _rows_gap

# Every limit below lies between two readings on the chip at the cell's
# own sizes (27 layers, published widths, `longdoc-steady`'s engine):
# what the system reads over its seeds, and the SMALLEST reading of the
# reference computed wrong in one of the ways `precision_probe` lists
# that the limit is there to catch (PERF.md section 6 has both; my chip
# runs, PR 48).
#
# Routing is not a continuous function (`checks_deepseek_v3`): a token
# whose 8th and 9th biased scores lie within the rounding noise of the
# two sides picks another expert; a SIXTEENTH of the experts is held
# here, so one flipped pick in sixteen gains or loses an expert's
# output, in 26 expert layers. Rows are judged one by one: the MEDIAN
# row carries the limit that rounding sets and every row stays under
# WORST_ROW.
#
# Read on the chip (my chip runs, PR 48: two seeds' checks, 40 rows of the
# gather path against the reference, and `--probe`'s twelve readings).
#
# WORST_ROW: between the largest row read on the chip (0.153, 0.177,
# 0.210, 0.312 in four ticks; a fresh seed reads higher, so the room is
# above) and what a row read off a wrong page, slot, state or table
# gives (two unrelated rows of logits are 1.41 apart; the state or the
# conv's inputs not carried over a tick's boundary 1.22 / 1.18 on the
# row behind it).
WORST_ROW = 0.8
# Kernel path against the gather path, on the SAME cache and the SAME
# stored state: the same projections, bf16 operands and f32 statistics;
# they differ in the order of the flash blocks' sums against one dense
# softmax, in the scan's chunked solve against the recurrence a token,
# and in the grouped kernels' order of sums. Judged on the row a QUARTER
# of the way up (the rows without a flipped pick): 0.0167 to 0.0175 in
# four ticks (the median row 0.017 to 0.032: three of ten rows had a
# flip). A kernel that rounds more than its other implementation moves
# every row.
KERNEL_QUARTILE_ROW = 0.04
# Gather path (bf16 weights as stored, bf16 activations, f32 state,
# router and accumulation, a cache and a state the engine's own program
# filled in 512-token chunks through the engine's own cache manager)
# against the float32 reference, which computes every token of the
# sequence itself with the recurrence a token and the unabsorbed
# attention. bf16 rounding of activations through ~12 matrix products a
# layer and 27 layers, and the flips. The median row read 0.1005,
# 0.1036, 0.1063, 0.1172. The smallest median of the reference computed
# wrong that this limit is to catch: float8 operands 0.599 (the state
# in bfloat16 reads 0.078 here and rotary 0.133: both are caught
# sixteenfold and more by the one-pass and the one-layer limits below).
REFERENCE_MEDIAN_ROW = 0.3
# The family's forward in ONE pass, float32 (`one_pass_float32`):
# activations float32, products at the highest precision, the weights as
# stored, a sequence from position 0 in one tick with no cache read,
# against the reference's rows. The same mathematics in another order
# (the gather path's recurrence over the ragged tick, the absorbed
# attention): a fault of the model's STRUCTURE that the bfloat16
# comparisons hide fails here. Read on the chip: 2.3e-6 (no pick
# flipped in 8 rows; one that does reads ~1e-3). The smallest wrong
# reading: the conv's inputs not carried 0.037, the state not carried
# 0.088, the state in bfloat16 0.161, rotary 0.165, float8 0.602.
ONE_PASS_MEDIAN_ROW = 0.005
# ONE KDA layer (`kda_mixer`, the engine's impl, float32 compute on the
# weights as stored) through a state cache of its own, two slots a tick,
# its input cut in two ticks so that the state and the conv's inputs
# cross a tick's boundary and the scan's 64-token chunks, against the
# reference's mixer on the same input. No routing, no bfloat16
# activation: the kernel's own arithmetic shows (the levels' products,
# the blocked solve). Read on the chip: 1.73e-5, 1.90e-5. The smallest
# wrong reading: the state kept in bfloat16 4.7e-3 (the precision below
# the stated float32 state), the conv's inputs not carried 0.058, float8
# operands 0.087, the state not carried 0.117, beta left out 0.36, one
# decay a head 0.53.
KDA_LAYER_REL_RMS = 2e-4
# ONE expert layer (`moe_block`: router, shared expert, held experts by
# the engine's impl out of the STACK by `base`) against the reference's
# on the SAME normalised input with the PROGRAM'S picks handed to the
# reference, so that no pick flips: the full output, and the routed part
# alone. bf16 products of three matrices. Read on the chip: 0.0030 to
# 0.0031 and 0.0072 to 0.0073. The smallest wrong reading: float8 0.082
# / 0.288, weights not scaled 0.136 / 0.59.
EXPERTS_REL_RMS, ROUTED_REL_RMS = 0.02, 0.03
# The engine's own compiled programs against the kernel path's logits,
# on the same inputs with the temperature at 0
# (`checks_nemotron_h`'s rule: seven rows of ten give the largest logit
# or a tie, none a token further under it than ENGINE_FLIP_MAX; the
# rider is the forward's own counts, give or take flipped picks). Read
# on the chip: 10 of 10 rows the kernel path's argmax in four ticks, the
# rider off by 0 of 7,181 / 7,157 / 152 / 150 assignments.
ENGINE_NEAR_MAX, ENGINE_FLIP_MAX, RIDER_SLACK = 0.05, 1.5, 0.05
# wrong in one way each: what `precision_probe` reads the reference as
VARIANTS = ("state_bf16", "state_reset", "conv_reset", "no_beta",
            "decay_a_head", "no_decay", "no_qk_norm", "gate_before_norm",
            "rotary", "no_route_norm", "no_route_scale")
# ticks of the budget the longest cached context takes: 24 and a quarter
# of 512 tokens, 12,416 (the traffic's prompts pass 12k one time in six)
LONG_TICKS = 24


class _Plan(checks_nemotron_h._Plan):
    """`checks_nemotron_h._Plan` with its longest row past 12k: four
    bases, ten slots that each hold a prefix of one, a MIXED tick of the
    tick budget's tokens (eight decode rows: past 12k, five ticks in,
    two ticks in, at a tick's boundary exactly, one short of it, just
    past two of the scan's 64-token chunks, one page in, the second
    token of a sequence; a chunk that continues a cached state; a prompt
    that starts, in a slot another sequence left) and a DECODE tick of
    all ten."""

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
        self.rows = {
            0: (0, LONG_TICKS * budget + budget // 4, 1),
            1: (0, 5 * budget + 3, 1),
            2: (1, 2 * budget + 5, 1),
            3: (1, budget, 1),
            4: (1, budget - 1, 1),
            5: (2, min(130, budget // 2), 1),
            6: (2, page + 3, 1),
            7: (2, 1, 1),
            8: (0, 3 * budget // 2, chunk),
            9: (3, 0, fresh)}
        lens = [0, 0, 0, 0]
        for b, cached, n in self.rows.values():
            lens[b] = max(lens[b], cached + n + 1)   # + the decode tick's
        if max(lens) + budget > eng.max_seq:
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
        while self.gather_ctx < -(-longest // page):
            self.gather_ctx *= 2
        self.one_pass = sorted({min(budget // 2, 200), budget - 1,
                                budget + budget // 3, 2 * budget - 3})


def _ticks(eng, plan: "_Plan", say):
    """`checks_nemotron_h._ticks` for an engine whose state is 2 GB:
    run the plan on the engine's own weights, POOL, STATE, cache manager
    and page table: each slot admitted through `CacheManager.admit` and
    cached by the engine's own ragged program in chunks of the tick
    budget. Before that the fresh prompt's slot serves and vacates
    another sequence. Then, for the mixed tick and the decode tick on
    the same pool and state: the gather path's logits, the kernel path's
    and its expert counts, and the engine's own program at temperature
    0, which also writes the tick's rows and state for what follows.

    What differs: a program that is not handed its arrays DONATED copies
    them (3.1 GB of pool and state here, which does not fit beside the
    engine), so the gather and the kernel programs take them donated,
    return them, and the ten slots' state rows they advanced are put
    back from a copy taken before (0.45 GB). The latent rows they wrote
    are the tick's own, which the next program writes again and no
    program reads (a tick's keys come from its own rows).
    Returns ({"mixed" | "decode": (gather logits, kernel logits, kernel
    counts, engine tokens with rider, rows)}, what the state group
    did)."""
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
    slots = jnp.array(sorted(plan.rows), jnp.int32)

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
            fam.decode_step(cfg, params, toks, pos, kp, vp, tables,
                            active, impl=impl)), donate_argnums=(3, 4))

    keep = jax.jit(lambda conv, state: (conv[:, slots], state[:, slots]))
    put_back = jax.jit(
        lambda conv, state, kept: (conv.at[:, slots].set(kept[0]),
                                   state.at[:, slots].set(kept[1])),
        donate_argnums=(0, 1))

    def both(make, args, kp, vp):
        """The gather path and the kernel path on the same pool and
        state -> (gather logits, kernel logits, kernel counts, kp, vp as
        they came)."""
        out = []
        for impl in ("gather", kernel):
            kept = keep(kp[1], vp[1])
            lg, kp, vp, counts = make(impl)(*args(kp, vp))
            out += [np.asarray(lg), np.asarray(counts)]
            conv, state = put_back(kp[1], vp[1], kept)
            kp, vp = (kp[0], conv), (None, state)
        return out[0], out[2], out[3], kp, vp

    totals = {s: cached + n + 2 for s, (_, cached, n) in plan.rows.items()}
    # the engine's pool and state, lent: every program donates them, so
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
    for rows in plan.fills():
        (s, _, pos0, n), = rows
        _, kp, vp, seen = engine_run(kp, vp, seen, plan.tick(rows))
        pos[s] = pos0 + n
        cache.advance(pos.items())
        n_ticks += 1
    state = {"reused_slot": reused,
             "state_left_in_reused_slot": state_left,
             "state_slots_held": [st.n_held for st in cache.states]}
    say(f"  cached {[c for _, c, _ in plan.rows.values()]} tokens in "
        f"{n_ticks} ticks of the engine's ragged program (T {T}, ctx "
        f"bucket {plan.ctx} pages, {kernel}); state group: {state}")
    out = {}
    rows = plan.mixed()
    tick = plan.tick(rows)
    tok, slot = jnp.array(tick[0]), jnp.array(tick[1])
    lg_g, lg_k, counts, kp, vp = both(
        ragged, lambda kp, vp: (eng.params, tok, slot, kp, vp, tables()),
        kp, vp)
    toks, kp, vp, seen = engine_run(kp, vp, seen, tick)
    out["mixed"] = (lg_g, lg_k, counts, toks,
                    {s: (b, pos0 + n - 1) for s, b, pos0, n in rows})
    at = plan.decode()
    cache.advance([(s, p) for s, (_, p) in at.items()])
    toks_in = np.zeros(B + eng._rider_len, np.int32)
    posn = np.zeros(B, np.int32)
    live = np.zeros(B, bool)
    for s, (b, p) in at.items():
        toks_in[s], posn[s], live[s] = plan.bases[b][p], p, True
    active = jnp.array(live)
    d_tok, d_pos = jnp.array(toks_in[:B]), jnp.array(posn)
    lg_g, lg_k, counts, kp, vp = both(
        decode, lambda kp, vp: (eng.params, d_tok, d_pos, kp, vp, tables(),
                                active), kp, vp)
    zeros_f, ones_f = jnp.zeros(B, jnp.float32), jnp.ones(B, jnp.float32)
    zeros_i = jnp.zeros(B, jnp.int32)
    toks, kp, vp, seen = eng._decode_fn(
        eng.params, kp, vp, seen, jnp.array(toks_in), d_pos,
        tables(), active, key, zeros_f, ones_f, zeros_i, ones_f, zeros_i,
        eng._lora_stacks, zeros_i, False)
    out["decode"] = (lg_g, lg_k, counts, np.asarray(toks), at)
    del seen
    # everything goes back: the slots' pages and state, the pool and
    # the state zeroed in place
    for s in plan.rows:
        eng.allocator.free(first_pages[s])
        cache.vacate(s)
    zero = jax.jit(lambda pools: jax.tree.map(lambda a: a * 0, pools),
                   donate_argnums=0)
    eng.k_pages, eng.v_pages = zero(kp), zero(vp)
    return out, state


def one_pass_float32(eng, plan: "_Plan") -> np.ndarray:
    """The family's forward over each prefix of `plan.one_pass`, alone in
    one tick from position 0 (gather path, a pool and a state of its
    own, just large enough, all zeros: nothing cached is read),
    activations float32, products at the highest precision, the engine's
    weights as stored. Returns the last token's logits a prefix,
    [prefixes, V]. (`checks_nemotron_h.one_pass_float32` with a one-pool
    group's None.)"""
    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(eng.model_cfg, dtype=jnp.float32)
    fam, page = eng.family, plan.page
    t = 8
    while t < max(plan.one_pass):
        t *= 2
    n_pages = -(-t // page) + 2
    made = [tuple(jnp.zeros(shape, dt) for shape, dt in g.array_shapes(
        n_pages, page, 1)) for g in fam.cache_groups(cfg, "gather")]
    kp = tuple(m[0] for m in made)
    vp = tuple(m[1] if len(m) > 1 else None for m in made)
    tables = jnp.array(np.arange(n_pages - 1, dtype=np.int32)[None])

    def logits(params, tokens, n, kp, vp):
        with jax.default_matmul_precision("highest"):
            at = jnp.arange(t, dtype=jnp.int32)
            return fam.ragged_forward(
                cfg, params, tokens, jnp.zeros((t,), jnp.int32), at,
                at < n, jnp.zeros((1,), jnp.int32), (n - 1)[None], kp, vp,
                tables, ctx_pages=0, impl="gather")[0][0]

    run = jax.jit(logits)
    out = []
    for n in plan.one_pass:
        tokens = np.zeros(t, np.int32)
        tokens[:n] = plan.bases[0][:n]
        out.append(np.asarray(run(eng.params, jnp.array(tokens),
                                  jnp.int32(n), kp, vp)))
    return np.stack(out)


def _layer_of(eng, kind: str, part: str):
    """The first layer of mixer `kind` ("K" / "M") or the first expert
    layer (`kind` None), its `part` ("mixer" / "ff") as the reference
    takes it."""
    from ray_tpu.models import kimi_linear
    cfg = eng.model_cfg
    l = (cfg.first_k_dense if kind is None else cfg.layers_of(kind)[0])
    return kimi_linear.layer_trees(cfg, eng.params)["layers"][l][part]


def _kda_input(eng, plan: "_Plan", seed: int):
    import jax
    import jax.numpy as jnp
    n = plan.budget + plan.budget // 3
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, n, eng.model_cfg.hidden), jnp.float32)


def kda_layer(eng, plan: "_Plan", seed: int) -> np.ndarray:
    """`kda_mixer` by the engine's impl on two sequences of normalised
    input, float32 compute on the first KDA layer's weights as stored,
    through a two-slot state of its own in TWO ticks (a tick budget of
    the first sequence beside a few tokens of the second, then the rest
    of both: the state and the conv's inputs cross the boundary, two
    runs share a tick and the scan's chunks). Returns the mixer's output
    [2, n, H]."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import kimi_linear
    from ray_tpu.ops import selective_scan as ssm

    cfg = dataclasses.replace(eng.model_cfg, dtype=jnp.float32)
    impl = eng._resolve_impl()
    u = _kda_input(eng, plan, seed)
    n = u.shape[1]
    layer = _layer_of(eng, "K", "mixer")
    group = eng.family.cache_groups(cfg, impl)[-1]
    conv, state = (jnp.zeros((1, 2) + tuple(shape), dt)
                   for _, shape, dt in group.state.parts)
    few = max(min(37, plan.budget // 4), 1)
    cuts = [((0, 0, plan.budget - few), (1, 0, few)),
            ((0, plan.budget - few, n - plan.budget + few),
             (1, few, n - few))]

    def tick(layer, x, slot_ids, positions, valid, start, last_idx, conv,
             state):
        with jax.default_matmul_precision("highest"):
            marks = ssm.segment_marks(slot_ids, positions, valid, start,
                                      last_idx)
            return kimi_linear.kda_mixer(
                cfg, layer, x, marks, (slot_ids, valid, last_idx), conv,
                state, 0, impl)

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
        y, conv, state = run(layer, jnp.array(x), jnp.array(meta[0]),
                             jnp.array(meta[1]), jnp.array(meta[2] != 0),
                             jnp.array(start), jnp.array(last), conv, state)
        y, cur = np.asarray(y), 0
        for s, p0, k in rows:
            out[s, p0:p0 + k] = y[cur:cur + k]
            cur += k
    return out


def kda_layer_reference(eng, model, plan: "_Plan", seed: int,
                        operands=None, variant=()) -> np.ndarray:
    ref = reference_kimi_linear
    layer = _layer_of(eng, "K", "mixer")
    with ref.computing(operands, variant, plan.budget):
        return np.stack([np.asarray(ref.kda(model, layer, seq))
                         for seq in _kda_input(eng, plan, seed)])


def expert_layer(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None], operands=None, variant=()
                 ) -> Dict[str, Any]:
    """The program's expert layer (`moe_block`, the engine's impl, the
    layer's experts taken out of the STACK by `base`) against the
    reference's on the same normalised input WITH THE PROGRAM'S PICKS,
    on the engine's weights of the first expert layer: at 48 rows (a
    decode tick) and at 512 (a chunk). With `variant` or `operands`: the
    reference so computed against the reference (the probe's
    readings)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import kimi_linear
    from ray_tpu.models.paged_common import swiglu
    from ray_tpu.ops.moe import sigmoid_group_routing

    cfg = eng.model_cfg
    impl = eng._resolve_impl()
    ref = reference_kimi_linear
    held = program_kimi_linear.experts_held(model)
    layer = _layer_of(eng, None, "ff")
    own = {k: v[0] for k, v in eng.params["moe"].items()}
    # the weights go in as arguments: closed over, a jit bakes them into
    # the program as constants
    block = jax.jit(lambda w, ex, y: kimi_linear.moe_block(
        cfg, w, y, impl=impl, experts=ex, base=jnp.int32(0))[0])
    shared = jax.jit(lambda w, y: swiglu(
        {"wg": w["shared_wg"], "wi": w["shared_wi"], "wd": w["shared_wd"]},
        y))
    picks = jax.jit(lambda w, y: sigmoid_group_routing(
        y, w["router"], w["router_bias"], n_group=1, topk_group=1,
        top_k=cfg.moe_top_k, scale=cfg.route_scale,
        normalize=cfg.route_norm)[1])
    probing = bool(variant) or operands is not None
    out: Dict[str, Any] = {"ok": True}
    for rows in (48, 512):
        y = jax.random.normal(jax.random.PRNGKey(seed + rows),
                              (rows, cfg.hidden), jnp.float32
                              ).astype(cfg.dtype)
        yf = y.astype(jnp.float32)
        with ref.computing():
            # the program's picks, the reference's own weights for
            # them; the probe's readings are of the reference alone
            want = np.asarray(ref.experts(
                model, layer, yf, held,
                picks=None if probing else picks(own, y)))
            want_routed = want - np.asarray(ref.shared_expert(layer, yf))
        if probing:
            with ref.computing(operands, variant):
                got = np.asarray(ref.experts(model, layer, yf, held))
                got_routed = got - np.asarray(ref.shared_expert(layer, yf))
        else:
            got = np.asarray(block(own, eng.params["experts"], y),
                             np.float32)
            got_routed = got - np.asarray(shared(own, y), np.float32)
        full = _gap(want, got)["rel_rms"]
        routed = _gap(want_routed, got_routed)["rel_rms"]
        ok = bool(np.isfinite(got).all() and full <= EXPERTS_REL_RMS
                  and routed <= ROUTED_REL_RMS)
        if not probing:
            say(f"  {'ok' if ok else 'FAILED'}: expert layer, {rows} rows:"
                f" rms gap {full:.4f} (<= {EXPERTS_REL_RMS}), routed part "
                f"{routed:.4f} (<= {ROUTED_REL_RMS})")
        out[f"rows{rows}"] = {"rel_rms": full, "routed_rel_rms": routed,
                              "ok": ok}
        out["ok"] = out["ok"] and ok
    return out


def _reference_rows(eng, model: Dict[str, Any], plan: "_Plan", wanted,
                    operands=None, variant=()):
    """The reference's logits for `wanted`, a list of (base, position):
    one forward a base, padded (causal: what follows a position changes
    nothing at it) to whole tick budgets, so that its blocks compile few
    shapes."""
    import jax.numpy as jnp
    from ray_tpu.models import kimi_linear
    held = program_kimi_linear.experts_held(model)
    trees = kimi_linear.layer_trees(eng.model_cfg, eng.params)
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(-(-len(base) // plan.budget) * plan.budget,
                          np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_kimi_linear.logits(
            model, trees, jnp.array(padded), held, operands=operands,
            rows=rows, variant=variant, chunk=plan.budget))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick at the engine's own sizes, on
    its own pools and state through its own cache manager (`_Plan`,
    `checks_nemotron_h._ticks`): (a) kernel path against gather path;
    (b) gather path against the float32 reference on the same token
    histories (one past 12k), prefill in 512-token chunks and then
    decoding through the latent pages AND the state, in a slot that
    another sequence left; (c) the engine's own compiled programs
    against the kernel path; (d) the family's forward in one float32
    pass, one KDA layer through a state cache across a tick's boundary,
    and one expert layer with the program's picks, each against the
    reference's on the same input, tighter. Logits, not tokens. Returns
    {"ok", ...gaps}."""
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
              for name, (_, _, _, _, at) in ticks.items()}
    ref = _reference_rows(
        eng, model, plan,
        [w for name in ticks for _, w in wanted[name]]
        + plan.one_pass_rows())
    ref, ref_one = ref[:-len(plan.one_pass)], ref[-len(plan.one_pass):]
    g = _rows_gap(ref_one, one_pass_float32(eng, plan))
    g["ok"] = bool(g["finite"] and g["median_row"] <= ONE_PASS_MEDIAN_ROW
                   and g["worst_row"] <= WORST_ROW)
    say(f"  {'ok' if g['ok'] else 'FAILED'}: one_pass_float32 median row "
        f"{g['median_row']:.5f} of rms (<= {ONE_PASS_MEDIAN_ROW}), worst "
        f"row {g['worst_row']:.5f}, prefixes of {plan.one_pass} tokens")
    out["one_pass_float32"] = g
    out["ok"] = out["ok"] and g["ok"]
    for name, (lg_g, lg_k, counts, toks, _) in ticks.items():
        slots = [s for s, _ in wanted[name]]
        want, ref = ref[:len(slots)], ref[len(slots):]
        for what, a, b, quartile in (
                ("kernel_vs_gather", lg_g[slots], lg_k[slots],
                 KERNEL_QUARTILE_ROW),
                ("gather_vs_reference", want, lg_g[slots], None)):
            g = _rows_gap(a, b)
            g["quartile_row"] = float(np.percentile(g["rows"], 25))
            g["ok"] = bool(
                g["finite"] and g["median_row"] <= REFERENCE_MEDIAN_ROW
                and g["worst_row"] <= WORST_ROW
                and (quartile is None or g["quartile_row"] <= quartile))
            say(f"  {'ok' if g['ok'] else 'FAILED'}: {what}.{name} quartile "
                f"row {g['quartile_row']:.4f} of rms"
                + (f" (<= {quartile})" if quartile else "")
                + f", median row {g['median_row']:.4f} (<= "
                f"{REFERENCE_MEDIAN_ROW}), worst row {g['worst_row']:.4f} "
                f"(<= {WORST_ROW}), argmax agree {g['argmax_agree']}/"
                f"{len(slots)}, contexts "
                f"{min(p for _, (_, p) in wanted[name])} to "
                f"{max(p for _, (_, p) in wanted[name])}")
            out[f"{what}.{name}"] = g
            out["ok"] = out["ok"] and g["ok"]
        e = _engine_gap(lg_k, counts, toks, slots)
        lg = np.asarray(lg_k, np.float32)
        rms = float(np.sqrt(np.mean(lg[slots] ** 2)))
        under = [(float(lg[s].max()) - float(lg[s, int(toks[s])])) / rms
                 for s in slots]
        e["rows_near_max"] = int(sum(u <= ENGINE_NEAR_MAX for u in under))
        e["ok"] = bool(
            e["rider_len_ok"] and e["worst_under_max"] <= ENGINE_FLIP_MAX
            and 10 * e["rows_near_max"] >= 7 * len(slots)
            and e["rider_diff"] <= RIDER_SLACK * e["rider_total"] + 2)
        say(f"  {'ok' if e['ok'] else 'FAILED'}: engine_program.{name} "
            f"{e['rows_near_max']}/{len(slots)} tokens within "
            f"{ENGINE_NEAR_MAX} of rms of the kernel path's largest logit "
            f"(>= 7 in 10), the furthest {e['worst_under_max']:.4f} under "
            f"it (<= {ENGINE_FLIP_MAX}), {e['argmax_agree']}/{len(slots)} "
            f"its argmax; rider off by {e['rider_diff']} of "
            f"{e['rider_total']} assignments")
        out[f"engine_program.{name}"] = e
        out["ok"] = out["ok"] and e["ok"]
    m = _gap(kda_layer_reference(eng, model, plan, seed),
             kda_layer(eng, plan, seed))
    m["ok"] = bool(m["finite"] and m["rel_rms"] <= KDA_LAYER_REL_RMS)
    say(f"  {'ok' if m['ok'] else 'FAILED'}: kda layer across a tick's "
        f"boundary, two runs a tick: rms gap {m['rel_rms']:.2e} (<= "
        f"{KDA_LAYER_REL_RMS})")
    out["kda_layer"] = m
    out["expert_layer"] = expert_layer(eng, model, seed, say)
    out["ok"] = out["ok"] and m["ok"] and out["expert_layer"]["ok"]
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None], only=()) -> Dict[str, Any]:
    """The second readings a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16),
    and computed wrong in each way of VARIANTS, against the reference
    itself: on the rows of the mixed and the decode tick, on the
    one-pass rows, on the KDA layer's input and on the expert layer's.
    Each has to come out over at least one of REFERENCE_MEDIAN_ROW,
    WORST_ROW, ONE_PASS_MEDIAN_ROW, KDA_LAYER_REL_RMS, EXPERTS_REL_RMS
    and ROUTED_REL_RMS. `only`: the names to read ("fp8" or a variant's;
    all of them where empty). Not part of a run:
    `runners/serve_kimi_linear.py --probe` prints it."""
    import jax.numpy as jnp
    plan = _Plan(eng, seed)
    wanted: List = [(b, pos0 + n - 1) for _, b, pos0, n in plan.mixed()]
    wanted += list(plan.decode().values())
    n_ticks = len(wanted)
    wanted += plan.one_pass_rows()
    want = _reference_rows(eng, model, plan, wanted)
    want_kda = kda_layer_reference(eng, model, plan, seed)
    out: Dict[str, Any] = {}
    for name, kw in [("fp8", {"operands": jnp.float8_e4m3fn})] + [
            (v, {"variant": (v,)}) for v in VARIANTS]:
        if only and name not in only:
            continue
        got = _reference_rows(eng, model, plan, wanted, **kw)
        g = _rows_gap(want[:n_ticks], got[:n_ticks])
        one = _rows_gap(want[n_ticks:], got[n_ticks:])
        g["one_pass"] = {k: one[k] for k in ("median_row", "worst_row")}
        g["kda_layer"] = _gap(want_kda, kda_layer_reference(
            eng, model, plan, seed, **kw))["rel_rms"]
        ex = expert_layer(eng, model, seed, say, **kw)
        g["expert_layer"] = max(ex[r]["rel_rms"] for r in ("rows48",
                                                           "rows512"))
        g["expert_layer_routed"] = max(
            ex[r]["routed_rel_rms"] for r in ("rows48", "rows512"))
        g["would_pass"] = bool(
            g["median_row"] <= REFERENCE_MEDIAN_ROW
            and g["worst_row"] <= WORST_ROW
            and one["median_row"] <= ONE_PASS_MEDIAN_ROW
            and g["kda_layer"] <= KDA_LAYER_REL_RMS and ex["ok"])
        say(f"  the reference with {name} against the reference: ticks' "
            f"median row {g['median_row']:.4f}, worst row "
            f"{g['worst_row']:.4f}; one-pass median "
            f"{one['median_row']:.5f}; kda layer {g['kda_layer']:.2e}; "
            f"expert layer {g['expert_layer']:.4f}, routed part "
            f"{g['expert_layer_routed']:.4f}; would pass "
            f"{g['would_pass']}")
        out[name] = g
    return out
