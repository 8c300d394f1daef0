"""Whether the Phi4Flash family's outputs are right: the comparisons
behind `correct` for its serving cell, made outside the timed window.
The dense decoder's are in checks.py, the latent family's in
checks_deepseek_v3.py, Trinity's in checks_trinity.py; this file is
theirs for a recurrent state beside two page groups, a ragged scan,
differential attention and a cross-decoder on the sampling rows."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from . import reference_phi4flash
from .checks_deepseek_v3 import _rows_gap
from .checks_trinity import _Plan as _TrinityPlan
from .checks_trinity import _past_window

# Every limit below lies between two readings on the chip at the cell's
# own sizes (all 32 layers, published widths, `reason-steady`'s engine):
# what the system reads over its seeds, and the SMALLEST reading of the
# reference computed wrong in one of the ways `precision_probe` lists
# (PERF.md section 6 has both). No routing here: no row may flip, so the
# worst row carries a limit of its own, tight enough for a fault that
# touches ONE compared row (the conv's inputs not carried over a chunk
# boundary move the three tokens behind the boundary and little else).
#
# Kernel path against the gather path, on the SAME cache (both page
# groups, window pages handed back and written again by another
# sequence) and the SAME stored state: the same projections, bf16
# operands and f32 statistics; they differ in the order of the flash
# blocks' sums against one dense softmax, in where probabilities are
# rounded to bf16, in the scan's order (token by token against an
# associative scan's tree) and in that the kernel never reads a block
# behind the window.
KERNEL_MEDIAN_ROW, KERNEL_WORST_ROW = 0.05, 0.08
# Gather path (bf16 weights as stored, bf16 activations, f32 state and
# accumulation, a cache and a state the engine's own program filled in
# 512-token chunks through the engine's own cache manager) against the
# float32 reference, which computes every token of the sequence itself,
# every layer on every token, with one sequential scan.
REFERENCE_MEDIAN_ROW, REFERENCE_WORST_ROW = 0.075, 0.12
# ... and over the rows a page and more PAST THE WINDOW alone, where a
# window's edge shows (a full layer that is windowed changes no row
# under the window).
PAST_WINDOW_MEDIAN_ROW = 0.075
# The family's forward in ONE pass, float32 (`one_pass_float32`):
# activations float32, products at the highest precision, the weights as
# stored, a sequence from position 0 in one tick with no cache read,
# against the reference's rows. The same mathematics in another order:
# it reads 4e-6 on the chip (median and worst row, two seeds), so a
# fault of the model's STRUCTURE that the bfloat16 comparisons' own
# rounding (0.04 through 32 layers) would hide fails here: the shared
# layer windowed reads 0.038 to 0.051 over the rows past the window
# where the system's bfloat16 path reads 0.039 to 0.046 (PERF.md section
# 6), and here 0.018 (median row) and 0.044 (worst row); every other
# variant reads 0.09 or more on these rows.
ONE_PASS_MEDIAN_ROW = 0.005
# The engine's own compiled programs (`jit_run`, `jit_step`: the forward
# behind the sampler) against the kernel path's logits, on the same
# inputs with the temperature at 0: each token they give has to be the
# largest logit or within this much of it (a tie at most), where a wrong
# row, table, group or state gives any of 200,064 ids.
ENGINE_NEAR_MAX = 0.05
# wrong in one way each: what `precision_probe` reads the reference as
VARIANTS = ("state_reset", "conv_reset", "all_full", "full_windowed",
            "cross_reads_window", "no_subtraction", "m_after_gate",
            "untied_head")
# tokens a sequence leaves in the slot that the fresh prompt then takes
# (fewer where a tick or the base holds fewer)
REUSED_TOKENS = 40


class _Plan(_TrinityPlan):
    """`checks_trinity._Plan` at this engine's sizes (window 512, a tick
    of 512: slots that hold 1,664, 1,027, 1,029, 768, 512, 511, 256 and
    19 tokens, a 402-token chunk from 256 whose context crosses the
    window and one 512-token chunk boundary, a prompt that starts), and
    one thing more: the slot of the prompt that starts held ANOTHER
    sequence before (`REUSED_TOKENS` of base 2, then vacated), so its
    stored state and conv inputs are not zeros when the prompt begins."""

    def __init__(self, eng, seed: int):
        super().__init__(eng, seed)
        self.fresh_slot = max(self.rows)
        if self.rows[self.fresh_slot][1] != 0:
            raise ValueError("the last row is the prompt that starts")
        # prefixes of base 0 that `one_pass_float32` runs from position
        # 0: under the window, a page past it, and on to twice the window
        w, page = self.window, self.page
        top = min(len(self.bases[0]), max(2 * w, w + 2 * page))
        past = w + page + 1
        self.one_pass = sorted({w // 2, past, (past + top) // 2, top})
        # the gather path cuts the page tables to the tick's context
        # bucket; `self.ctx`, the engine's own, is the whole table where
        # the kernels run (`ModelFamily.whole_table_kernels`)
        need = -(-max(c for _, c, _ in self.rows.values()) // page)
        self.gather_ctx = 1
        while self.gather_ctx < need:
            self.gather_ctx *= 2

    def one_pass_rows(self):
        """(base, position) of the last token of each prefix."""
        return [(0, n - 1) for n in self.one_pass]


def _ticks(eng, plan: "_Plan", say):
    """Run the plan on the engine's own weights, POOLS, STATE, cache
    manager and page tables (`checks_trinity._ticks`' procedure: each
    slot admitted through `CacheManager.admit`, cached by the engine's
    own ragged program in chunks of the tick budget with
    `CacheManager.advance` after every tick, the window group's free
    list turned so that pages handed back are the pages the other slots
    get). Before that the fresh prompt's slot serves and vacates another
    sequence. Then, for the mixed tick and the decode tick on the same
    pools and state: the gather path's logits, the kernel path's, and
    the engine's own program at temperature 0, which also writes the
    tick's rows and state for what follows. Returns ({"mixed" |
    "decode": (gather logits, kernel logits, engine tokens, rows)}, what
    the window group did)."""
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
        return jnp.array(np.stack(cache.tables))

    def engine_run(kp, vp, seen, tick):
        toks, kp, vp, seen = run(
            eng.params, kp, vp, seen, jnp.array(tick[0]),
            jnp.array(tick[1]), samp, tables(), key, eng._lora_stacks,
            False)
        return np.asarray(toks), kp, vp, seen

    # The cross layers read the tick's own rows from the full group's
    # pool, so the forward writes them before it reads: that pool is
    # handed over (donated) and taken back, or XLA would copy its 2 GB
    # beside the engine. The rows a path writes are the rows every path
    # writes (the same projections), and a layer's context ends before
    # them; the window pools' and the state's new values are dropped,
    # so the three runs of a tick start from the same state.
    def ragged(impl):
        def logits(params, tok, slot, full_k, full_v, kp, vp, tables):
            lg, kp, vp = fam.ragged_forward(
                cfg, params, tok[0], tok[1], tok[2], tok[3] != 0,
                slot[0], slot[1], (full_k,) + kp, (full_v,) + vp, tables,
                ctx_pages=(plan.gather_ctx if impl == "gather"
                           else plan.ctx), impl=impl)
            return lg, kp[0], vp[0]
        return jax.jit(logits, donate_argnums=(3, 4))

    def decode(impl):
        def logits(params, toks, pos, full_k, full_v, kp, vp, tables,
                   active):
            lg, kp, vp = fam.decode_step(
                cfg, params, toks, pos, (full_k,) + kp, (full_v,) + vp,
                tables, active, impl=impl)
            return lg, kp[0], vp[0]
        return jax.jit(logits, donate_argnums=(3, 4))

    def both_paths(program, head, tail):
        """The gather path's logits, then the kernel path's, the full
        group's pools lent to each in turn."""
        nonlocal kp, vp
        out = []
        for impl in ("gather", kernel):
            lg, full_k, full_v = program(impl)(
                *head, kp[0], vp[0], kp[1:], vp[1:], *tail)
            kp, vp = (full_k,) + kp[1:], (full_v,) + vp[1:]
            out.append(np.asarray(lg))
        return out

    totals = {s: cached + n + 2 for s, (_, cached, n) in plan.rows.items()}
    # the engine's pools and state, lent: its programs donate them, so
    # they are handed from call to call and given back zeroed
    kp, vp = eng.k_pages, eng.v_pages
    eng.k_pages = eng.v_pages = None
    first_pages, pos = {}, {}
    handed_back: set = set()
    held_back: List[int] = []
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

    longest = min(plan.rows)
    admit(longest)
    for rows in plan.fills():
        (s, _, pos0, n), = rows
        if s not in pos:
            if not held_back:
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
                             for s in sorted(plan.rows)],
              "reused_slot": reused,
              "state_left_in_reused_slot": state_left,
              "state_slots_held": [st.n_held for st in cache.states]}
    say(f"  cached {[c for _, c, _ in plan.rows.values()]} tokens in "
        f"{n_ticks} ticks of the engine's ragged program (T {T}, ctx "
        f"bucket {plan.ctx} pages, {kernel}); window group and state: "
        f"{window}")
    out = {}
    rows = plan.mixed()
    tick = plan.tick(rows)
    lg_g, lg_k = both_paths(
        ragged, (eng.params, jnp.array(tick[0]), jnp.array(tick[1])),
        (tables(),))
    toks, kp, vp, seen = engine_run(kp, vp, seen, tick)
    out["mixed"] = (lg_g, lg_k, toks,
                    {s: (b, pos0 + n - 1) for s, b, pos0, n in rows})
    at = plan.decode()
    cache.advance([(s, p) for s, (_, p) in at.items()])
    toks_in = np.zeros(B, np.int32)
    posn = np.zeros(B, np.int32)
    live = np.zeros(B, bool)
    for s, (b, p) in at.items():
        toks_in[s], posn[s], live[s] = plan.bases[b][p], p, True
    active = jnp.array(live)
    lg_g, lg_k = both_paths(
        decode, (eng.params, jnp.array(toks_in), jnp.array(posn)),
        (tables(), active))
    zeros_f, ones_f = jnp.zeros(B, jnp.float32), jnp.ones(B, jnp.float32)
    zeros_i = jnp.zeros(B, jnp.int32)
    toks, kp, vp, seen = eng._decode_fn(
        eng.params, kp, vp, seen, jnp.array(toks_in), jnp.array(posn),
        tables(), active, key, zeros_f, ones_f, zeros_i, ones_f, zeros_i,
        eng._lora_stacks, zeros_i, False)
    out["decode"] = (lg_g, lg_k, np.asarray(toks), at)
    del seen
    # everything goes back: the slots' pages and state, the pages held
    # back, the pools and the state zeroed
    for s in plan.rows:
        eng.allocator.free(first_pages[s])
        cache.vacate(s)
    win.allocator.free(held_back)
    zero = jax.jit(lambda pools: jax.tree.map(lambda a: a * 0, pools),
                   donate_argnums=0)
    eng.k_pages, eng.v_pages = zero(kp), zero(vp)
    return out, window


def one_pass_float32(eng, plan: "_Plan") -> np.ndarray:
    """The family's forward over each prefix of `plan.one_pass`, alone in
    one tick from position 0 (gather path, pools and state of its own,
    just large enough, all zeros: nothing cached is read), activations
    float32, products at the highest precision, the engine's weights as
    stored. Returns the last token's logits a prefix, [prefixes, V]."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(eng.model_cfg, dtype=jnp.float32)
    fam, page = eng.family, plan.page
    top = max(plan.one_pass)
    t = 8
    while t < top:
        t *= 2
    n_pages = -(-t // page) + 2
    made = [tuple(jnp.zeros(shape, dt) for shape, dt in g.array_shapes(
        n_pages, page, 1)) for g in fam.cache_groups(cfg, "gather")]
    kp, vp = tuple(m[0] for m in made), tuple(m[1] for m in made)
    tables = jnp.array(np.broadcast_to(
        np.arange(n_pages - 1, dtype=np.int32), (2, 1, n_pages - 1)))

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


def _one_pass_gap(plan: "_Plan", want, got) -> Dict[str, Any]:
    """`_rows_gap` of the one-pass rows, and the median over those a page
    and more past the window."""
    g = _rows_gap(want, got)
    past = _past_window(plan, plan.one_pass_rows())
    g["past_window_median_row"] = float(np.median(
        [g["rows"][i] for i in past]))
    return g


def _reference_rows(eng, model: Dict[str, Any], plan: "_Plan", wanted,
                    operands=None, variant=()):
    """The reference's logits for `wanted`, a list of (base, position):
    one forward a base, padded to the longest (causal: what follows a
    position changes nothing at it)."""
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(plan.ref_len, np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_phi4flash.logits(
            model, phi4flash.layer_trees(eng.model_cfg, eng.params),
            jnp.array(padded), operands=operands,
            rows=rows, variant=variant, chunk=plan.budget))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick at the engine's own sizes, on
    its own pools and state through its own cache manager (`_Plan`,
    `_ticks`): (a) kernel path against gather path; (b) gather path
    against the float32 reference on the same token histories, on
    sequences under, across and past twice the window and past one and
    three chunk boundaries, after window pages were handed back and
    taken by other sequences and in a slot that another sequence left;
    (c) the engine's own compiled programs against the kernel path.
    Logits, not tokens. Returns {"ok", ...gaps}."""
    plan = _Plan(eng, seed)
    ticks, window = _ticks(eng, plan, say)
    out: Dict[str, Any] = {"ok": True, "longest_context": plan.ref_len,
                           "T": plan.T, "ctx_bucket_pages": plan.ctx,
                           "window_group": window}
    # the comparison means what it says only if pages went back and came
    # round again, and the reused slot was left with a state
    moved = (window["pages_handed_back"] > 0
             and window["handed_back_and_held_by_another"] > 0
             and window["state_left_in_reused_slot"] > 0)
    say(f"  {'ok' if moved else 'FAILED'}: window pages handed back "
        f"{window['pages_handed_back']}, of them held by another "
        f"sequence at the compared ticks "
        f"{window['handed_back_and_held_by_another']}, largest state "
        f"value left in the reused slot "
        f"{window['state_left_in_reused_slot']:.3g} (all > 0)")
    out["ok"] = out["ok"] and moved
    wanted = {name: sorted(at.items())
              for name, (_, _, _, at) in ticks.items()}
    ref = _reference_rows(
        eng, model, plan,
        [w for name in ticks for _, w in wanted[name]]
        + plan.one_pass_rows())
    ref, ref_one_pass = (ref[:-len(plan.one_pass)],
                         ref[-len(plan.one_pass):])
    g = _one_pass_gap(plan, ref_one_pass, one_pass_float32(eng, plan))
    g["ok"] = bool(g["finite"]
                   and g["median_row"] <= ONE_PASS_MEDIAN_ROW
                   and g["worst_row"] <= ONE_PASS_MEDIAN_ROW)
    say(f"  {'ok' if g['ok'] else 'FAILED'}: one_pass_float32 median row "
        f"{g['median_row']:.5f} of rms, worst row {g['worst_row']:.5f} "
        f"(<= {ONE_PASS_MEDIAN_ROW}), of the rows past the window "
        f"{g['past_window_median_row']:.5f}, prefixes of "
        f"{plan.one_pass} tokens, argmax agree {g['argmax_agree']}/"
        f"{len(plan.one_pass)}")
    out["one_pass_float32"] = g
    out["ok"] = out["ok"] and g["ok"]
    for name, (lg_g, lg_k, toks, _) in ticks.items():
        slots = [s for s, _ in wanted[name]]
        want, ref = ref[:len(slots)], ref[len(slots):]
        past = _past_window(plan, [w for _, w in wanted[name]])
        for what, a, b, mid, worst in (
                ("kernel_vs_gather", lg_g[slots], lg_k[slots],
                 KERNEL_MEDIAN_ROW, KERNEL_WORST_ROW),
                ("gather_vs_reference", want, lg_g[slots],
                 REFERENCE_MEDIAN_ROW, REFERENCE_WORST_ROW)):
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
        lg = np.asarray(lg_k, np.float32)
        rms = float(np.sqrt(np.mean(lg[slots] ** 2)))
        under = [(float(lg[s].max()) - float(lg[s, int(toks[s])])) / rms
                 for s in slots]
        e = {"worst_under_max": max(under),
             "argmax_agree": int(sum(u == 0.0 for u in under)),
             "ok": bool(max(under) <= ENGINE_NEAR_MAX)}
        say(f"  {'ok' if e['ok'] else 'FAILED'}: engine_program.{name} "
            f"tokens at most {e['worst_under_max']:.4f} of rms under "
            f"the kernel path's largest logit (<= {ENGINE_NEAR_MAX}), "
            f"{e['argmax_agree']}/{len(slots)} its argmax")
        out[f"engine_program.{name}"] = e
        out["ok"] = out["ok"] and e["ok"]
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None]) -> Dict[str, Any]:
    """The second readings a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16),
    and computed wrong in each way of VARIANTS, against the reference
    itself, on the rows of the mixed and the decode tick. Each has to
    come out over REFERENCE_MEDIAN_ROW, PAST_WINDOW_MEDIAN_ROW (over
    the rows past the window, a tick at a time), REFERENCE_WORST_ROW or,
    on the one-pass rows, ONE_PASS_MEDIAN_ROW.
    Not part of a run: `runners/serve_phi4flash.py --probe` prints it."""
    import jax.numpy as jnp
    plan = _Plan(eng, seed)
    wanted: List = [(b, pos0 + n - 1) for _, b, pos0, n in plan.mixed()]
    n_mixed = len(wanted)
    wanted += list(plan.decode().values())
    past = _past_window(plan, wanted)
    ticks = ([i for i in past if i < n_mixed],
             [i for i in past if i >= n_mixed])
    n_ticks = len(wanted)
    wanted += plan.one_pass_rows()
    want = _reference_rows(eng, model, plan, wanted)
    out: Dict[str, Any] = {}
    for name, kw in [("fp8", {"operands": jnp.float8_e4m3fn})] + [
            (v, {"variant": (v,)}) for v in VARIANTS]:
        got = _reference_rows(eng, model, plan, wanted, **kw)
        g = _rows_gap(want[:n_ticks], got[:n_ticks])
        g["past_window_median_row"] = [
            float(np.median([g["rows"][i] for i in tick]))
            for tick in ticks]
        one = _one_pass_gap(plan, want[n_ticks:], got[n_ticks:])
        g["one_pass"] = {k: one[k] for k in (
            "median_row", "worst_row", "past_window_median_row")}
        g["would_pass"] = bool(
            g["median_row"] <= REFERENCE_MEDIAN_ROW
            and g["worst_row"] <= REFERENCE_WORST_ROW
            and max(g["past_window_median_row"]) <= PAST_WINDOW_MEDIAN_ROW
            and one["worst_row"] <= ONE_PASS_MEDIAN_ROW)
        say(f"  the reference with {name} against the reference: median "
            f"row {g['median_row']:.4f}, of the rows past the window "
            f"{g['past_window_median_row']}, worst row "
            f"{g['worst_row']:.4f}; on the one-pass rows median "
            f"{one['median_row']:.4f}, worst {one['worst_row']:.4f}; "
            f"would pass {g['would_pass']}")
        out[name] = g
    return out
