"""Whether the NemotronH family's outputs are right: the comparisons
behind `correct` for its serving cell, made outside the timed window.
The dense decoder's are in checks.py, the latent family's in
checks_deepseek_v3.py, Trinity's in checks_trinity.py, Phi4Flash's in
checks_phi4flash.py; this file is theirs for a Mamba-2 state beside one
page group, a chunked ragged scan, ungated held experts and attention
with no positions."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import numpy as np

from . import program_nemotron_h, reference_nemotron_h
from .checks import _gap
from .checks_deepseek_v3 import _engine_gap, _rows_gap

# Every limit below lies between two readings on the chip at the cell's
# own sizes (16 layers, published widths, `agent-turns-steady`'s engine):
# what the system reads over its seeds, and the SMALLEST reading of the
# reference computed wrong in one of the ways `precision_probe` lists
# that the limit is there to catch (PERF.md section 6 has both; my chip
# runs, PR 41).
#
# Routing is not a continuous function: a token whose 6th and 7th biased
# scores lie within the rounding noise of the two sides picks another
# expert, and HALF the experts are held here (64 of 128; a sixteenth in
# the two other expert cells), so every other flipped pick gains or loses
# an expert's output, a tenth of the residual stream with these seeded
# weights, in a layer, for that token and, through the scan's state and
# the attention, for every token behind it. Where the two sides pick
# alike they agree to 0.009 (kernel against gather); where they round
# differently (bfloat16 against float32) the flips ARE the gap: rows read
# 0.01 to 0.55, the median 0.06 to 0.16. So the comparisons through the
# whole stack in bfloat16 carry limits that a fault of the CACHE can
# reach (a state or a page not carried, a row read off another slot) and
# the tight limits sit where no pick can flip: one float32 pass, one
# Mamba-2 layer, one expert layer with the program's picks.
#
# Read over eleven runs of the cell and its sweeps (220 rows of the
# gather path against the reference): a quarter of the rows under 0.035,
# the median row 0.11, one in nine over 0.35, the largest 0.806; kernel
# against gather 151 rows of 180 at 0.008 to 0.012 and 29 with a flipped
# pick at 0.04 to 0.41, up to five of a tick's ten.
#
# WORST_ROW: between the largest row read on the chip (0.806) and what a
# row read off a wrong page, slot, state or table gives (two unrelated
# rows of logits are 1.41 apart; the conv's inputs not carried over a
# chunk boundary 1.25 on the row behind the boundary).
WORST_ROW = 1.1
# Kernel path against the gather path, on the SAME cache and the SAME
# stored state: the same projections, bf16 operands and f32 statistics;
# they differ in the order of the flash blocks' sums against one dense
# softmax, in the scan's cut into chunks of 128 against one piece a
# tick, and in the grouped kernels' order of sums against a loop over the
# experts. Judged on the row a QUARTER of the way up: the rows without a
# flipped pick, which are most, read 0.008 to 0.012, and a kernel that
# rounds more than its other implementation moves every row. (The median
# row read 0.0084 to 0.067: 0.067 where five of ten rows had a flip.)
KERNEL_QUARTILE_ROW = 0.03
# Gather path (bf16 weights as stored, bf16 activations, f32 state,
# router and accumulation, a cache and a state the engine's own program
# filled in 512-token chunks through the engine's own cache manager)
# against the float32 reference, which computes every token of the
# sequence itself with one sequential scan and every held expert on
# every token. The median row read 0.032 to 0.237 in 22 ticks (the
# flips, above; drawing ten rows from those read, one tick in ten
# thousand passes 0.4). The SMALLEST median of the reference computed
# wrong that this limit is to catch: float8 operands 0.60 (the state not
# carried over a chunk boundary reads 0.48 here and is caught thirtyfold
# below; every other variant is caught tighter, below).
REFERENCE_MEDIAN_ROW = 0.4
# The family's forward in ONE pass, float32 (`one_pass_float32`):
# activations float32, products at the highest precision, the weights as
# stored, a sequence from position 0 in one tick with no cache read,
# against the reference's rows. The same mathematics in another order
# (the whole-tick form of the scan against the token-by-token one): a
# fault of the model's STRUCTURE that the bfloat16 comparisons' flips
# hide fails here. Read on the chip: rows 0.00000 to 0.00123 (a pick
# flips here too, rarely). The smallest wrong reading it is to catch:
# the state not carried 0.199, rotary applied 0.283, float8 0.557.
ONE_PASS_MEDIAN_ROW = 0.01
# ONE Mamba-2 layer (`mamba_mixer`, the kernel path, float32 compute on
# the weights as stored) through a state cache of its own, two slots a
# tick, its input cut in two ticks so that the state and the conv's
# inputs cross a chunk boundary, against the reference's mixer on the
# same input. No routing, no bfloat16 activation: the scan's own
# arithmetic shows. Read on the chip: 1.2e-5 to 1.5e-5. The smallest
# wrong reading: a run that starts from another's state 0.046, the
# state or the conv's inputs not carried 0.046 / 0.052, float8 0.074;
# the state kept in bfloat16: PERF.md section 6.
MAMBA_LAYER_REL_RMS = 2e-4
# ONE expert layer (`moe_block`: router, shared expert, held experts by
# the engine's impl) against the reference's on the SAME normalised
# input with the PROGRAM'S picks handed to the reference (their weights
# are the reference's own), so that no pick flips: the full output, and
# the routed part alone. bf16 products of two matrices. Read on the
# chip: 0.0023 to 0.0036 and 0.0039 to 0.0076. The smallest wrong
# reading: float8 0.163 / 0.261, a gate matrix 0.200.
EXPERTS_REL_RMS, ROUTED_REL_RMS = 0.02, 0.03
# The engine's own compiled programs (`jit_run`, `jit_step`: the forward
# behind the sampler, the rider behind the tokens) against the kernel
# path's logits, on the same inputs with the temperature at 0. The same
# forward compiled into another program rounds elsewhere, so here too a
# pick can flip (two of 22 ticks: one row's token 0.45 of the logits'
# RMS under the other program's largest). Seven rows of ten have to give
# the largest logit or one within ENGINE_NEAR_MAX of it (a tie), and none
# a token further under it than ENGINE_FLIP_MAX, where a wrong row,
# table, slot or program gives any of 65,536 ids, ~4 RMS below. The
# rider has to be the forward's own counts, give or take picks that flip
# (118 of 11,138 and 6 of 192 on the chip).
ENGINE_NEAR_MAX, ENGINE_FLIP_MAX, RIDER_SLACK = 0.05, 1.5, 0.05
# wrong in one way each: what `precision_probe` reads the reference as
VARIANTS = ("state_bf16", "state_reset", "conv_reset", "neighbour_leak",
            "group_by_mod", "norm_all", "norm_before_gate",
            "relu_not_squared", "gated_experts", "no_route_norm",
            "no_route_scale", "no_d", "no_dt_bias", "rotary")
# tokens a sequence leaves in the slot that the fresh prompt then takes
REUSED_TOKENS = 40


class _Plan:
    """What the checks run, laid out from the engine's own sizes (its
    slots, page size, tick budget): four token sequences ("bases"): A
    past 8k, B of two ticks and a bit, C short, D a fresh prompt; ten
    slots that each hold a prefix of a base, cached by the engine's own
    program through the engine's own cache manager; a MIXED tick of the
    tick budget's tokens (eight decode rows: past 8k (16 ticks and a
    quarter), five ticks in, two ticks in, at a tick's boundary exactly,
    one short of it, just past one of the scan's 128-token chunks, one
    page in, the second token of a sequence; a chunk that continues a
    cached state; a prompt that starts, in a slot another sequence left)
    and a DECODE tick of all ten. Every row's tokens are a base's, so
    the reference's logits for it are one row of that base's forward."""

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
            0: (0, 16 * budget + budget // 4, 1),
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
        # the gather path cuts the page table to the tick's context
        # bucket; `self.ctx`, the engine's own, is the whole table where
        # the kernels run (`ModelFamily.whole_table_kernels`)
        self.gather_ctx = 1
        while self.gather_ctx < -(-longest // page):
            self.gather_ctx *= 2
        # prefixes of base 0 that `one_pass_float32` runs from position 0
        self.one_pass = sorted({min(budget // 2, 200), budget - 1,
                                budget + budget // 3, 2 * budget - 3})

    def tick(self, rows):
        """rows: [(slot, base, first position, tokens)] -> the packed
        host arrays of one ragged tick: tok_meta (5, T), slot_meta
        (4, B), as `InferenceEngine._ragged_step` packs them."""
        tok = np.zeros((5, self.T), np.int32)
        slot = np.zeros((4, self.B), np.int32)
        cur = 0
        for s, b, pos0, n in rows:
            tok[0, cur:cur + n] = self.bases[b][pos0:pos0 + n]
            tok[1, cur:cur + n] = s
            tok[2, cur:cur + n] = np.arange(pos0, pos0 + n)
            tok[3, cur:cur + n] = 1
            slot[0, s], slot[1, s], slot[2, s] = pos0, cur + n - 1, 1
            cur += n
        return tok, slot

    def mixed(self):
        return [(s, b, cached, n)
                for s, (b, cached, n) in sorted(self.rows.items())]

    def decode(self):
        """slot -> (base, position) of the decode tick's token."""
        return {s: (b, cached + n)
                for s, (b, cached, n) in self.rows.items()}

    def fills(self):
        """The ticks that cache the slots' prefixes, a slot at a time, a
        tick budget at a time."""
        for s, (b, cached, _) in sorted(self.rows.items()):
            for pos0 in range(0, cached, self.budget):
                yield [(s, b, pos0, min(self.budget, cached - pos0))]

    def one_pass_rows(self):
        return [(0, n - 1) for n in self.one_pass]


def _ticks(eng, plan: "_Plan", say):
    """Run the plan on the engine's own weights, POOLS, STATE, cache
    manager and page table: each slot admitted through
    `CacheManager.admit` and cached by the engine's own ragged program
    in chunks of the tick budget (the state and the conv's inputs carried
    from tick to tick through the donated arrays). Before that the fresh
    prompt's slot serves and vacates another sequence. Then, for the
    mixed tick and the decode tick on the same pools and state: the
    gather path's logits, the kernel path's and its expert counts, and
    the engine's own program at temperature 0, which also writes the
    tick's rows and state for what follows. Returns ({"mixed" | "decode":
    (gather logits, kernel logits, kernel counts, engine tokens with
    rider, rows)}, what the state group did)."""
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
        # logits and counts alone: the scatter into the pool and the
        # state's update are dead code here, and nothing is copied
        return jax.jit(lambda params, tok, slot, kp, vp, tables: (
            fam.ragged_forward(
                cfg, params, tok[0], tok[1], tok[2], tok[3] != 0,
                slot[0], slot[1], kp, vp, tables,
                ctx_pages=(plan.gather_ctx if impl == "gather"
                           else plan.ctx), impl=impl)[::3]))

    def decode(impl):
        return jax.jit(lambda params, toks, pos, kp, vp, tables, active: (
            fam.decode_step(cfg, params, toks, pos, kp, vp, tables,
                            active, impl=impl)[::3]))

    totals = {s: cached + n + 2 for s, (_, cached, n) in plan.rows.items()}
    # the engine's pools and state, lent: its programs donate them, so
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
    n_left = min(REUSED_TOKENS, plan.budget, len(plan.bases[2]))
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
    args = (eng.params, jnp.array(tick[0]), jnp.array(tick[1]), kp, vp,
            tables())
    lg_g = np.asarray(ragged("gather")(*args)[0])
    lg_k, counts = (np.asarray(a) for a in ragged(kernel)(*args))
    del args
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
    args = (eng.params, jnp.array(toks_in[:B]), jnp.array(posn), kp, vp,
            tables(), active)
    lg_g = np.asarray(decode("gather")(*args)[0])
    lg_k, counts = (np.asarray(a) for a in decode(kernel)(*args))
    del args
    zeros_f, ones_f = jnp.zeros(B, jnp.float32), jnp.ones(B, jnp.float32)
    zeros_i = jnp.zeros(B, jnp.int32)
    toks, kp, vp, seen = eng._decode_fn(
        eng.params, kp, vp, seen, jnp.array(toks_in), jnp.array(posn),
        tables(), active, key, zeros_f, ones_f, zeros_i, ones_f, zeros_i,
        eng._lora_stacks, zeros_i, False)
    out["decode"] = (lg_g, lg_k, counts, np.asarray(toks), at)
    del seen
    # everything goes back: the slots' pages and state, the pools and
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
    one tick from position 0 (gather path, pools and state of its own,
    just large enough, all zeros: nothing cached is read), activations
    float32, products at the highest precision, the engine's weights as
    stored. Returns the last token's logits a prefix, [prefixes, V]."""
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
    kp, vp = tuple(m[0] for m in made), tuple(m[1] for m in made)
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


def _layer_of(eng, kind: str):
    """The first layer of `kind` as its own tree (the reference's form)."""
    from ray_tpu.models import nemotron_h
    cfg = eng.model_cfg
    return nemotron_h.layer_trees(cfg, eng.params)["layers"][
        cfg.layers_of(kind)[0]]


def _mamba_input(eng, plan: "_Plan", seed: int):
    import jax
    import jax.numpy as jnp
    n = plan.budget + plan.budget // 3
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, n, eng.model_cfg.hidden), jnp.float32)


def mamba_layer(eng, plan: "_Plan", seed: int) -> np.ndarray:
    """`mamba_mixer` by the engine's impl on two sequences of normalised
    input, float32 compute on the first Mamba layer's weights as stored,
    through a two-slot state of its own in TWO ticks (a tick budget of
    the first sequence beside a few tokens of the second, then the rest
    of both: the state and the conv's inputs cross the boundary, two runs
    share a tick and the scan's 128-token chunks). Returns the mixer's
    output [2, n, H]."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import nemotron_h
    from ray_tpu.ops import selective_scan as ssm

    cfg = dataclasses.replace(eng.model_cfg, dtype=jnp.float32)
    impl = eng._resolve_impl()
    u = _mamba_input(eng, plan, seed)
    n = u.shape[1]
    layer = _layer_of(eng, "M")
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
            return nemotron_h.mamba_mixer(
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
    import jax
    ref = reference_nemotron_h
    ref._OPERANDS, ref._VARIANT = operands, frozenset(variant)
    ref.CHUNK = plan.budget
    try:
        layer = _layer_of(eng, "M")
        with jax.default_matmul_precision("highest"):
            return np.stack([np.asarray(ref.mamba(model, layer, seq))
                             for seq in _mamba_input(eng, plan, seed)])
    finally:
        ref._OPERANDS, ref._VARIANT = None, frozenset()


def expert_layer(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None], operands=None, variant=()
                 ) -> Dict[str, Any]:
    """The program's expert layer (`moe_block`, the engine's impl)
    against the reference's on the same normalised input WITH THE
    PROGRAM'S PICKS, on the engine's weights of the first expert layer:
    at 64 rows (a decode tick) and at 512 (a chunk). With `variant` or
    `operands`: the reference so computed against the reference (the
    probe's readings)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import nemotron_h
    from ray_tpu.ops.moe import sigmoid_group_routing

    cfg = eng.model_cfg
    impl = eng._resolve_impl()
    ref = reference_nemotron_h
    held = program_nemotron_h.experts_held(model)
    layer = _layer_of(eng, "E")
    # the weights go in as arguments: closed over, a jit bakes them into
    # the program as constants
    block = jax.jit(lambda w, y: nemotron_h.moe_block(cfg, w, y,
                                                      impl=impl)[0])
    picks = jax.jit(lambda w, y: sigmoid_group_routing(
        y, w["router"], w["router_bias"], n_group=1, topk_group=1,
        top_k=cfg.moe_top_k, scale=cfg.route_scale,
        normalize=cfg.route_norm)[1])
    probing = bool(variant) or operands is not None
    out: Dict[str, Any] = {"ok": True}
    for rows in (64, 512):
        y = jax.random.normal(jax.random.PRNGKey(seed + rows),
                              (rows, cfg.hidden), jnp.float32
                              ).astype(cfg.dtype)
        yf = y.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            # the program's picks, the reference's own weights for
            # them; the probe's readings are of the reference alone
            want = np.asarray(ref.experts(
                model, layer, yf, held,
                picks=None if probing else picks(layer, y)))
            want_routed = want - np.asarray(ref.shared_expert(layer, yf))
        if probing:
            ref._OPERANDS, ref._VARIANT = operands, frozenset(variant)
            try:
                with jax.default_matmul_precision("highest"):
                    got = np.asarray(ref.experts(model, layer, yf, held))
                    got_routed = got - np.asarray(
                        ref.shared_expert(layer, yf))
            finally:
                ref._OPERANDS, ref._VARIANT = None, frozenset()
        else:
            got = np.asarray(block(layer, y), np.float32)
            mid = nemotron_h.relu2(
                (y @ layer["shared_up"]).astype(jnp.float32))
            got_routed = got - np.asarray(
                mid.astype(cfg.dtype) @ layer["shared_down"], np.float32)
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
    one forward a base, padded (causal: what follows a
    position changes nothing at it) to whole tick budgets, so its
    op-by-op run compiles few shapes."""
    import jax.numpy as jnp
    from ray_tpu.models import nemotron_h
    held = program_nemotron_h.experts_held(model)
    trees = nemotron_h.layer_trees(eng.model_cfg, eng.params)
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(-(-len(base) // plan.budget) * plan.budget,
                          np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_nemotron_h.logits(
            model, trees, jnp.array(padded), held, operands=operands,
            rows=rows, variant=variant, chunk=plan.budget))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick at the engine's own sizes, on
    its own pools and state through its own cache manager (`_Plan`,
    `_ticks`): (a) kernel path against gather path; (b) gather path
    against the float32 reference on the same token histories (one past
    8k), prefill in 512-token chunks and then decoding through pages AND
    state, in a slot that another sequence left; (c) the engine's own
    compiled programs against the kernel path; (d) the family's forward
    in one float32 pass, one Mamba-2 layer through a state cache across
    a chunk boundary, and one expert layer with the program's picks, each
    against the reference's on the same input, tighter. Logits, not
    tokens. Returns {"ok", ...gaps}."""
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
    m = _gap(mamba_layer_reference(eng, model, plan, seed),
             mamba_layer(eng, plan, seed))
    m["ok"] = bool(m["finite"] and m["rel_rms"] <= MAMBA_LAYER_REL_RMS)
    say(f"  {'ok' if m['ok'] else 'FAILED'}: mamba layer across a chunk "
        f"boundary, two runs a tick: rms gap {m['rel_rms']:.2e} (<= "
        f"{MAMBA_LAYER_REL_RMS})")
    out["mamba_layer"] = m
    out["expert_layer"] = expert_layer(eng, model, seed, say)
    out["ok"] = out["ok"] and m["ok"] and out["expert_layer"]["ok"]
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None], only=()) -> Dict[str, Any]:
    """The second readings a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16),
    and computed wrong in each way of VARIANTS, against the reference
    itself: on the rows of the mixed and the decode tick, on the
    one-pass rows, on the Mamba layer's input and on the expert layer's.
    Each has to come out over at least one of REFERENCE_MEDIAN_ROW,
    WORST_ROW, ONE_PASS_MEDIAN_ROW, MAMBA_LAYER_REL_RMS, EXPERTS_REL_RMS
    and ROUTED_REL_RMS (KERNEL_QUARTILE_ROW compares two paths of the
    program, not the reference). `only`: the names to read ("fp8" or a variant's;
    all of them where empty). Not part of a run:
    `runners/serve_nemotron_h.py --probe` prints it."""
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
        ex = expert_layer(eng, model, seed, say, **kw)
        g["expert_layer"] = max(ex[r]["rel_rms"] for r in ("rows64",
                                                           "rows512"))
        g["expert_layer_routed"] = max(
            ex[r]["routed_rel_rms"] for r in ("rows64", "rows512"))
        g["would_pass"] = bool(
            g["median_row"] <= REFERENCE_MEDIAN_ROW
            and g["worst_row"] <= WORST_ROW
            and one["median_row"] <= ONE_PASS_MEDIAN_ROW
            and g["mamba_layer"] <= MAMBA_LAYER_REL_RMS and ex["ok"])
        say(f"  the reference with {name} against the reference: ticks' "
            f"median row {g['median_row']:.4f}, worst row "
            f"{g['worst_row']:.4f}; one-pass median "
            f"{one['median_row']:.5f}; mamba layer {g['mamba_layer']:.2e}; "
            f"expert layer {g['expert_layer']:.4f}, routed part "
            f"{g['expert_layer_routed']:.4f}; would pass "
            f"{g['would_pass']}")
        out[name] = g
    return out
