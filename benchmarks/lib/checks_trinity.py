"""Whether the Trinity family's outputs are right: the comparisons behind
`correct` for its serving cells, made outside the timed window. The
dense decoder's are in checks.py, the latent family's in
checks_deepseek_v3.py; this file is theirs for two page groups, a
window-aware kernel and gated QK-normed attention."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from . import program_trinity, reference_trinity
from .checks_deepseek_v3 import _engine_gap, _rows_gap

# What the logits comparisons are up against. Routing is not a continuous
# function: a token whose 4th and 5th biased scores lie within the
# rounding noise of the two sides picks another expert, and when exactly
# one of the two is held here (16 of 256 are) the token gains or loses
# one expert's output in that layer. So rows are judged one by one, as
# the latent family's are: the MEDIAN row's gap carries the limit that
# rounding sets, and every row stays under WORST_ROW, which lies between
# the largest row read on the chip at the cell's sizes (0.129, a row
# of the gather path with a flipped pick, where every other row of 40
# read 0.010 to 0.013; PERF.md section 6) and what a row read off a
# wrong page, table, group or head order gives (two unrelated rows of
# logits are 1.41 apart; the reference without the embedding's
# sqrt(hidden) reads 0.77 and more on every row).
WORST_ROW = 0.7
# Kernel path against the gather path, on the SAME cache (both page
# groups, the window group's pages behind the windows handed back and
# most of them written again by another sequence): the same
# projections, bf16 operands and f32 softmax statistics; they differ in
# the order of the flash blocks' sums against one dense softmax, in
# where the probabilities are rounded to bf16 before the value product,
# and in that the kernel never reads a block behind the window where
# the gather reads and masks it: bf16 rounding of the attention output
# through 9 layers, 2^-8 * sqrt(9) = 0.012 of the logits' RMS. Read on
# the chip at contexts of 19 to 8,833: 0.0117 to 0.0118.
KERNEL_MEDIAN_ROW = 0.03
# Gather path (bf16 weights as stored, bf16 activations, f32
# accumulation, a cache the engine's own program filled in 512-token
# chunks through the engine's own cache manager) against the float32
# reference, which computes every token of the sequence itself with a
# mask a layer kind: activations rounded to 8 mantissa bits through ~9
# matrix products a layer and 9 layers, the four norms a layer holding
# the residual's scale. Read on the chip: 0.0105 both ticks. The limit
# lies between that and the SMALLEST median of the reference computed
# wrong in one of the ways `precision_probe` lists, each of which has
# to fail a limit: rope on a full layer 0.087, a window layer that
# attends to everything 0.080, no route_scale 0.130, no QK norm 0.159,
# float8 operands 0.182, no gate 0.528, no sqrt(hidden) on the
# embedding 0.796 (PERF.md section 6).
REFERENCE_MEDIAN_ROW = 0.03
# ... and over the rows whose context lies a page and more PAST THE
# WINDOW alone, which is where a window's edge shows: a full layer that
# is windowed changes no row under the window and reads 0.009 over all
# twenty rows, under any median limit, but 0.054 (the decode tick) and
# 0.169 (the mixed tick) over the five rows past the window; a window
# layer that attends to everything 0.28 and 0.33 there. The system
# reads those rows as it reads the others, 0.0105.
PAST_WINDOW_MEDIAN_ROW = 0.03
# The engine's own compiled programs (`jit_run`, `jit_step`: the forward
# behind the sampler, the rider behind the tokens) against the kernel
# path's logits above, on the same inputs with the temperature at 0:
# each token they give has to be the largest logit or within this much
# of it (the same forward compiled into another program: a tie at most),
# where a wrong row, table, group or program gives any of 25,024 ids,
# ~4 RMS below it. The rider (assignments landed on each held expert)
# has to be the forward's own counts, give or take picks that flip on a
# tie.
ENGINE_NEAR_MAX, RIDER_SLACK = 0.05, 0.05
# One expert layer (`moe_block`: router, shared expert, held experts)
# against the reference's on the SAME normalised input, so that
# upstream rounding flips no pick: the full output, and the routed part
# alone (a quarter of a pick a token on average: 4 picks x 16 / 256).
# bf16 products of three matrices: 2^-9 * sqrt(3) * a few = 0.01. A gate
# without `route_scale` is off by 0.59 of the routed part.
EXPERTS_REL_RMS, ROUTED_REL_RMS = 0.02, 0.03
# wrong in one way each: what `precision_probe` reads the reference as
VARIANTS = ("all_full", "all_window", "rope_on_full", "no_gate",
            "no_qk_norm", "no_route_scale", "no_embed_scale")


class _Plan:
    """What the checks run, laid out from the engine's own sizes (its
    slots, page size, tick budget, `max_seq_len`, the model's window W):
    four token sequences ("bases"): A over 2 W + two ticks, B of W + a
    tick, C under W, D a fresh prompt; ten slots that each hold a prefix
    of a base, cached by the engine's own program through the engine's
    own cache manager; a MIXED tick of the tick budget's tokens (eight
    decode rows: past twice the window, just past twice, past it,
    between W and W + a tick, at W exactly, one short of W, half of W,
    one page in; a chunk whose context crosses the window; a prompt
    that starts) and a DECODE tick of all ten. Every row's tokens are a
    base's, so the reference's logits for it are one row of that base's
    forward."""

    def __init__(self, eng, seed: int):
        ec, cfg = eng.config, eng.model_cfg
        page, B = ec.page_size, ec.max_batch_size
        w = cfg.sliding_window
        self.B, self.page, self.window = B, page, w
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
            0: (0, 2 * w + budget + budget // 4, 1),
            1: (0, 2 * w + 3, 1),
            2: (0, w + budget + 5, 1),
            3: (1, w + budget // 2, 1),
            4: (1, w, 1),
            5: (1, w - 1, 1),
            6: (2, w // 2, 1),
            7: (2, page + 3, 1),
            8: (0, max(w - budget // 2, 0), chunk),
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
        self.ctx = eng._ctx_bucket(max(c for _, c, _ in
                                       self.rows.values()))

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
        """The ticks that cache the slots' prefixes, a slot at a time
        (the longest first, so that the pages it hands back are there
        for the others to take), a tick budget at a time."""
        for s, (b, cached, _) in sorted(self.rows.items()):
            for pos0 in range(0, cached, self.budget):
                yield [(s, b, pos0, min(self.budget, cached - pos0))]


def _ticks(eng, plan: "_Plan", say):
    """Run the plan on the engine's own weights, POOLS, cache manager
    and page tables, at its tick's token bucket and context bucket.
    Each slot is admitted through `CacheManager.admit` and cached by
    the engine's own ragged program (`jit_run`, the kernel path: chunks
    of the tick budget against a growing context), with
    `CacheManager.advance` after every tick as the engine calls it: the
    window group hands pages back behind the windows. The longest slot
    is cached first; then the group's free list is cut to what the other
    slots reserve, the pages handed back at its head, as a nearly full
    pool's is, so that those are the pages the other slots are given.
    Then, for the mixed tick and for the decode tick on
    the same pools: the gather path's logits, the kernel path's (both
    through the family's forwards, which the engine's programs call),
    and the engine's own program with the temperature at 0, which also
    writes the tick's rows for what follows. Returns ({"mixed" |
    "decode": (gather logits, kernel logits, kernel counts, engine
    tokens with rider, rows)}, what the window group did)."""
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
    win = next(g for g in cache.groups if g.spec.window is not None)

    def tables():
        # stacked, as the engine uploads them: the same program
        return jnp.array(np.stack(cache.tables))

    def engine_run(kp, vp, seen, tick):
        toks, kp, vp, seen = run(
            eng.params, kp, vp, seen, jnp.array(tick[0]),
            jnp.array(tick[1]), samp, tables(), key, eng._lora_stacks,
            False)
        return np.asarray(toks), kp, vp, seen

    def ragged(impl):
        # logits and counts alone: the scatter into the pools is dead
        # code here, and no pool is copied
        return jax.jit(lambda params, tok, slot, kp, vp, tables: (
            fam.ragged_forward(
                cfg, params, tok[0], tok[1], tok[2], tok[3] != 0,
                slot[0], slot[1], kp, vp, tables,
                ctx_pages=plan.ctx, impl=impl)[::3]))

    def decode(impl):
        return jax.jit(lambda params, toks, pos, kp, vp, tables, active: (
            fam.decode_step(cfg, params, toks, pos, kp, vp, tables,
                            active, impl=impl)[::3]))

    totals = {s: cached + n + 2 for s, (_, cached, n) in plan.rows.items()}
    # the engine's pools, lent: its programs donate them, so they are
    # handed from call to call and given back zeroed
    kp, vp = eng.k_pages, eng.v_pages
    eng.k_pages = eng.v_pages = None
    first_pages, pos = {}, {}
    handed_back: set = set()
    held_back: List[int] = []
    n_ticks = 0

    def admit(s):
        if not cache.can_admit(totals[s]):
            raise ValueError(f"the checks' slot {s} wants "
                             f"{totals[s]} tokens of cache")
        first_pages[s] = cache.admit(s, totals[s])
        pos[s] = 0

    longest = min(plan.rows)
    admit(longest)
    for rows in plan.fills():
        (s, _, pos0, n), = rows
        if s not in pos:
            if not held_back:
                # the free list cut and turned, once the longest slot
                # is cached: what the others reserve, the pages handed
                # back first
                free = win.allocator.allocate_pages(
                    win.allocator.free_pages)
                need = win.outstanding + 2 + sum(
                    cache.reserve_pages(win, totals[o])
                    for o in plan.rows if o != longest)
                turned = ([p for p in free if p in handed_back]
                          + [p for p in free if p not in handed_back])
                win.allocator.free(turned[:need])
                held_back = turned[need:]
                for o in sorted(plan.rows):
                    if o != longest:
                        admit(o)
        _, kp, vp, seen = engine_run(kp, vp, seen, plan.tick(rows))
        pos[s] = pos0 + n
        before = set(win.tables[s, win.lo[s]:win.hi[s]].tolist())
        cache.advance(pos.items())
        handed_back |= before - set(
            win.tables[s, win.lo[s]:win.hi[s]].tolist())
        n_ticks += 1
    for s in plan.rows:                 # slots with nothing to cache
        if s not in pos:
            admit(s)
    in_use = set()
    for s in plan.rows:
        in_use |= set(win.tables[s, win.lo[s]:win.hi[s]].tolist())
    window = {"pages_handed_back": len(handed_back),
              "handed_back_and_held_by_another": len(handed_back & in_use),
              "pages_held": [win.hi[s] - win.lo[s]
                             for s in sorted(plan.rows)]}
    say(f"  cached {[c for _, c, _ in plan.rows.values()]} tokens in "
        f"{n_ticks} ticks of the engine's ragged program (T {T}, ctx "
        f"bucket {plan.ctx} pages, {kernel}); window group: {window}")
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
    # everything goes back: the slots' pages, the pages held back, the
    # pools zeroed
    for s in plan.rows:
        eng.allocator.free(first_pages[s])
        cache.vacate(s)
    win.allocator.free(held_back)
    # in place (the result reads its donated input, so the two alias):
    # beside the weights there is no room for a second set of pools
    zero = jax.jit(lambda pools: jax.tree.map(lambda a: a * 0, pools),
                   donate_argnums=0)
    eng.k_pages, eng.v_pages = zero(kp), zero(vp)
    return out, window


def _reference_rows(eng, model: Dict[str, Any], plan: "_Plan", wanted,
                    operands=None, variant=()):
    """The reference's logits for `wanted`, a list of (base, position):
    one forward a base, padded to the longest (causal: what follows a
    position changes nothing at it), so its op-by-op run compiles one
    shape."""
    import jax.numpy as jnp
    held = program_trinity.experts_held(model)
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(plan.ref_len, np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_trinity.logits(
            model, eng.params, jnp.array(padded), held,
            operands=operands, rows=rows, variant=variant))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


def _past_window(plan: "_Plan", wanted) -> List[int]:
    """Which of `wanted` = [(base, position)] sit a page and more past
    the window: the rows a window's edge moves."""
    return [i for i, (_, p) in enumerate(wanted)
            if p >= plan.window + plan.page]


def expert_layer(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """The program's expert layer (`moe_block`) against the reference's
    on the same normalised input, on the engine's weights of the first
    expert layer: at 32 rows (a decode tick: every expert takes every
    row) and at 512 (a chunk: each expert's rows gathered)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import trinity
    from .checks import _gap

    cfg = eng.model_cfg
    held = program_trinity.experts_held(model)
    layer = next(w for w in eng.params["layers"] if "router" in w)
    # the weights go in as arguments: closed over, a jit bakes them into
    # the program as constants
    block = jax.jit(lambda w, y: trinity.moe_block(cfg, w, y)[0])
    shared = jax.jit(lambda w, y: trinity.swiglu(w, y))
    out: Dict[str, Any] = {"ok": True}
    for rows in (32, 512):
        y = jax.random.normal(jax.random.PRNGKey(seed + rows),
                              (rows, cfg.hidden), jnp.float32
                              ).astype(cfg.dtype)
        got = np.asarray(block(layer, y), np.float32)
        got_routed = got - np.asarray(shared(layer["shared"], y),
                                      np.float32)
        with jax.default_matmul_precision("highest"):
            yf = y.astype(jnp.float32)
            want = np.asarray(reference_trinity.experts(
                model, layer, yf, held))
            sh = layer["shared"]
            want_routed = want - np.asarray(reference_trinity._swiglu(
                yf, sh["wg"], sh["wi"], sh["wd"]))
        full = _gap(want, got)["rel_rms"]
        routed = _gap(want_routed, got_routed)["rel_rms"]
        ok = bool(np.isfinite(got).all() and full <= EXPERTS_REL_RMS
                  and routed <= ROUTED_REL_RMS)
        say(f"  {'ok' if ok else 'FAILED'}: expert layer, {rows} rows: "
            f"rms gap {full:.4f} (<= {EXPERTS_REL_RMS}), routed part "
            f"{routed:.4f} (<= {ROUTED_REL_RMS})")
        out[f"rows{rows}"] = {"rel_rms": full, "routed_rel_rms": routed,
                              "ok": ok}
        out["ok"] = out["ok"] and ok
    return out


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick at the engine's own sizes, on
    its own pools through its own cache manager (`_Plan`, `_ticks`):
    (a) kernel path against gather path; (b) gather path against the
    float32 reference on the same token histories: prefill, then
    decoding through both page groups, against the reference's full
    forward, on sequences under, across and past twice the window,
    after window pages were handed back and taken by other sequences;
    (c) the engine's own compiled programs against the kernel path;
    (d) one expert layer against the reference's on the same input.
    Logits, not tokens. Returns {"ok", ...gaps}."""
    plan = _Plan(eng, seed)
    ticks, window = _ticks(eng, plan, say)
    out: Dict[str, Any] = {"ok": True, "longest_context": plan.ref_len,
                           "T": plan.T, "ctx_bucket_pages": plan.ctx,
                           "window_group": window}
    # the comparison means what it says only if pages went back and
    # came round again
    moved = (window["pages_handed_back"] > 0
             and window["handed_back_and_held_by_another"] > 0)
    say(f"  {'ok' if moved else 'FAILED'}: window pages handed back "
        f"{window['pages_handed_back']}, of them held by another "
        f"sequence at the compared ticks "
        f"{window['handed_back_and_held_by_another']} (both > 0)")
    out["ok"] = out["ok"] and moved
    wanted = {name: sorted(at.items())
              for name, (_, _, _, _, at) in ticks.items()}
    ref = _reference_rows(
        eng, model, plan,
        [w for name in ticks for _, w in wanted[name]])
    for name, (lg_g, lg_k, counts, toks, _) in ticks.items():
        slots = [s for s, _ in wanted[name]]
        want, ref = ref[:len(slots)], ref[len(slots):]
        past = _past_window(plan, [w for _, w in wanted[name]])
        for what, a, b, mid, worst in (
                ("kernel_vs_gather", lg_g[slots], lg_k[slots],
                 KERNEL_MEDIAN_ROW, WORST_ROW),
                ("gather_vs_reference", want, lg_g[slots],
                 REFERENCE_MEDIAN_ROW, WORST_ROW)):
            g = _rows_gap(a, b)
            g["past_window_median_row"] = float(np.median(
                [g["rows"][i] for i in past]))
            g["ok"] = bool(
                g["finite"] and g["median_row"] <= mid
                and g["worst_row"] <= worst
                and g["past_window_median_row"] <= PAST_WINDOW_MEDIAN_ROW)
            say(f"  {'ok' if g['ok'] else 'FAILED'}: {what}.{name} "
                f"median row {g['median_row']:.4f} of rms (<= {mid}), "
                f"of the {len(past)} rows past the window "
                f"{g['past_window_median_row']:.4f} (<= "
                f"{PAST_WINDOW_MEDIAN_ROW}), "
                f"worst row {g['worst_row']:.4f} (<= {worst}), argmax "
                f"agree {g['argmax_agree']}/{len(slots)}, contexts "
                f"{min(p for _, (_, p) in wanted[name])} to "
                f"{max(p for _, (_, p) in wanted[name])}")
            out[f"{what}.{name}"] = g
            out["ok"] = out["ok"] and g["ok"]
        e = _engine_gap(lg_k, counts, toks, slots)
        e["ok"] = bool(
            e["rider_len_ok"] and e["worst_under_max"] <= ENGINE_NEAR_MAX
            and e["rider_diff"] <= RIDER_SLACK * e["rider_total"] + 2)
        say(f"  {'ok' if e['ok'] else 'FAILED'}: engine_program.{name} "
            f"tokens at most {e['worst_under_max']:.4f} of rms under "
            f"the kernel path's largest logit (<= {ENGINE_NEAR_MAX}), "
            f"{e['argmax_agree']}/{len(slots)} its argmax; rider off by "
            f"{e['rider_diff']} of {e['rider_total']} assignments")
        out[f"engine_program.{name}"] = e
        out["ok"] = out["ok"] and e["ok"]
    out["expert_layer"] = expert_layer(eng, model, seed, say)
    out["ok"] = out["ok"] and out["expert_layer"]["ok"]
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None]) -> Dict[str, Any]:
    """The second readings a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16),
    and computed wrong in each way of VARIANTS, against the reference
    itself, on the rows of the mixed and the decode tick. Each has to
    come out over REFERENCE_MEDIAN_ROW, PAST_WINDOW_MEDIAN_ROW (over
    the rows past the window, a tick at a time) or WORST_ROW. Not part
    of a run: `runners/serve_trinity.py --probe` prints it."""
    import jax.numpy as jnp
    plan = _Plan(eng, seed)
    wanted: List = [(b, pos0 + n - 1) for _, b, pos0, n in plan.mixed()]
    n_mixed = len(wanted)
    wanted += list(plan.decode().values())
    past = _past_window(plan, wanted)
    ticks = ([i for i in past if i < n_mixed],
             [i for i in past if i >= n_mixed])
    want = _reference_rows(eng, model, plan, wanted)
    out: Dict[str, Any] = {}
    for name, kw in [("fp8", {"operands": jnp.float8_e4m3fn})] + [
            (v, {"variant": (v,)}) for v in VARIANTS]:
        g = _rows_gap(want, _reference_rows(eng, model, plan, wanted, **kw))
        g["past_window_median_row"] = [
            float(np.median([g["rows"][i] for i in tick]))
            for tick in ticks]
        g["would_pass"] = bool(
            g["median_row"] <= REFERENCE_MEDIAN_ROW
            and g["worst_row"] <= WORST_ROW
            and max(g["past_window_median_row"]) <= PAST_WINDOW_MEDIAN_ROW)
        say(f"  the reference with {name} against the reference: median "
            f"row {g['median_row']:.4f}, of the rows past the window "
            f"{g['past_window_median_row']}, worst row "
            f"{g['worst_row']:.4f}, would pass {g['would_pass']}")
        out[name] = g
    return out
