"""Whether the SmallThinker family's outputs are right: the comparisons
behind `correct` for its serving cell, made outside the timed window.
The dense decoder's are in checks.py, the latent family's in
checks_deepseek_v3.py, Trinity's in checks_trinity.py, Phi4Flash's in
checks_phi4flash.py, NemotronH's in checks_nemotron_h.py; this file is
theirs for a router that reads the layer's input, gated-ReLU experts ALL
held here, and 28 query heads over 4 on two merged-rows page groups.

The plan and the ticks are Trinity's (`checks_trinity._Plan`, `_ticks`:
ten slots holding prefixes to past twice the window, cached by the
engine's own program through the engine's own cache manager, window
pages handed back and taken by other sequences): they read the engine's
sizes and the model's window, nothing of Trinity's layer."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from . import program_smallthinker, reference_smallthinker
from .checks import _gap
from .checks_deepseek_v3 import _engine_gap, _rows_gap
from .checks_trinity import _Plan, _past_window, _ticks

# Every limit below lies between two readings on the chip at the cell's
# own sizes (12 layers, published widths, `assist-longanswer-steady`'s
# engine): what the system reads over its seeds, and the SMALLEST reading
# of the reference computed wrong in one of the ways `precision_probe`
# lists that the limit is there to catch (PERF.md section 6 has both; my
# chip runs, PR 43).
#
# Routing is not a continuous function: a token whose 6th and 7th logits
# lie within the rounding noise of the two sides picks another expert,
# and EVERY expert is held here (a sixteenth in the two older expert
# cells, half in `nemotron-agent`), so every flipped pick swaps one
# expert's output (a tenth of the layer's routed sum) for another's, in
# that layer, for that token and, through attention, for every token
# behind it. So the comparisons through the whole stack from the
# engine's cache carry limits that a fault of the CACHE can reach (a page
# or a table of another slot or group, a window's edge) and are judged as
# `checks_nemotron_h` judges its own (quartile, median, worst row); the
# tight limits sit where no pick can flip: the whole stack in one pass
# with the PROGRAM'S picks handed to the reference, one expert layer
# with the program's picks, the router's logits float32 against float32,
# one full and one window attention block through a cache.
#
# WORST_ROW: between the largest row read on the chip and what a row read
# off a wrong page, slot, group or table gives (two unrelated rows of
# logits are 1.41 apart).
WORST_ROW = 1.1
# Kernel path against the gather path, on the SAME cache: the same
# projections, bf16 operands and f32 statistics; they differ in the order
# of the flash blocks' sums against one dense softmax, in the zero query
# head the kernels carry beside each seven, and in the grouped kernels'
# order of sums against a loop over the experts. Judged on the row a
# QUARTER of the way up and on the median against the limit below. Rows
# without a flipped pick read 0.008 to 0.009; on most seeds they are
# eight of ten and the quarter row is one of them, but a mixed tick can
# carry a flip in eight rows of ten (seed 2147512001: 0.0212, the largest
# of eight seeds), so the limit stands three times over that and under
# the 0.127 that the SMALLEST row of the reference with float8 operands
# reads against the reference (quarter row 0.155).
KERNEL_QUARTILE_ROW = 0.06
# Gather path (bf16 weights as stored, bf16 activations, f32 router and
# accumulation, a cache the engine's own program filled in 512-token
# chunks through the engine's own cache manager) against the float32
# reference, which computes every token of the sequence itself with a
# mask a layer and routes on its own: the flips, above, are the gap.
REFERENCE_MEDIAN_ROW = 0.4
# ... and over the rows whose context lies a page and more PAST THE
# WINDOW alone, where a window's edge shows, against the same limit (a
# window layer that attends to everything, or a full layer windowed,
# moves those rows and no other).
PAST_WINDOW_MEDIAN_ROW = 0.4
# The whole stack in ONE pass (`one_pass`): the family's own layer
# function on a tick budget of tokens from position 0, no cache read, the
# engine's impl, bfloat16 as the configuration states, with the picks
# (weights and indices, every layer) it made HANDED TO THE REFERENCE: no
# pick flips, so what is left is bf16 rounding through 12 layers of ~6
# matrix products: 2^-9 * sqrt(72) = 0.017 of the logits' RMS. The
# precision below (float8 operands) and every variant of the layer's
# STRUCTURE has to fail here or at a tighter limit below.
ONE_PASS_MEDIAN_ROW = 0.04
# ... and the routers' logits of that pass, every layer, against the
# reference's own at the same layer (float32 both, the streams apart by
# the bf16 rounding above): 0.0075 to 0.0087 over eight seeds; a router
# that reads another input (the normed one, the experts') is off by the
# logits' own size, and float8 operands read 0.147.
ONE_PASS_ROUTER_REL_RMS = 0.05
# The router alone, float32 against float32 on the SAME input
# (`ops/moe.softmax_pick_routing` against the reference's `_route`):
# logits, and the picks' weights where the picks agree (they all do
# bar ties).
ROUTER_REL_RMS = 1e-4
# ONE expert layer (`smallthinker.experts` by the engine's impl, the
# grouped ReGLU kernels on the chip) against the reference's on the SAME
# normalised input with the PROGRAM'S picks: bf16 products of three
# matrices with f32 accumulation, the mid value rounded to bf16: 2^-9 *
# sqrt(3) * a few = 0.01. The same with float8 operands or an int8-like
# rounding of the experts has to fail it.
EXPERTS_REL_RMS = 0.02
# ONE attention block (RMSNorm, q/k/v, rope where the layer has it,
# attention over a cache of its own filled a tick at a time, W_o) of a
# FULL layer and of a WINDOW layer, on a random input of the plan's
# longest context, the last tick by the kernel path and by the gather
# path, against the reference's attention of the whole sequence: bf16
# q, k, v and probabilities, f32 statistics.
ATTENTION_REL_RMS = 0.03
# The engine's own compiled programs (`jit_run`, `jit_step`: the forward
# behind the sampler, the rider behind the tokens) against the kernel
# path's logits, on the same inputs with the temperature at 0; as
# `checks_nemotron_h`: seven rows of ten give the largest logit or one
# within ENGINE_NEAR_MAX of it, none further under it than
# ENGINE_FLIP_MAX. The rider's TOTAL is exact whatever flips (every
# expert is held: valid rows x 6 x 12); its spread over the experts may
# differ by the flips.
ENGINE_NEAR_MAX, ENGINE_FLIP_MAX, RIDER_SLACK = 0.05, 1.5, 0.05
# wrong in one way each: what `precision_probe` reads the reference as
VARIANTS = ("router_after_norm", "router_after_attn", "no_pick_norm",
            "silu_gate", "no_gate", "all_full", "all_window",
            "rope_everywhere", "no_rope", "rope_interleaved")
# rows of the one-pass comparison (positions of the tick budget's tokens)
ONE_PASS_ROWS = 16


def _reference_rows(eng, model: Dict[str, Any], plan: "_Plan", wanted,
                    operands=None, variant=()):
    """The reference's logits for `wanted`, a list of (base, position):
    one forward a base, padded to the longest (causal: what follows a
    position changes nothing at it), so its blocks compile one shape."""
    import jax.numpy as jnp
    held = program_smallthinker.experts_held(model)
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(plan.ref_len, np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_smallthinker.logits(
            model, eng.params, jnp.array(padded), held,
            operands=operands, rows=rows, variant=variant))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


# ---- the whole stack in one pass, the program's picks ------------------

def _one_pass_rows(plan: "_Plan") -> List[int]:
    n = plan.budget
    return sorted({max(n * (i + 1) // ONE_PASS_ROWS - 1, 0)
                   for i in range(ONE_PASS_ROWS)})


def one_pass(eng, plan: "_Plan"):
    """The family's layers on the first tick budget of base 0 from
    position 0 in ONE tick with no cache read (the engine's impl, the
    configuration's types, `smallthinker._layer` as the forwards call
    it). Returns (logits of `_one_pass_rows` [rows, V], the picks [(w,
    idx) a layer], the routers' logits [a layer])."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import smallthinker as st
    from ray_tpu.models.llama import rms_norm
    from ray_tpu.models.phi4flash import _attend_fn

    cfg, impl = eng.model_cfg, eng._resolve_impl()
    n, t = plan.budget, plan.T
    rows = jnp.array(_one_pass_rows(plan), jnp.int32)
    tok = np.zeros(t, np.int32)
    tok[:n] = plan.bases[0][:n]
    groups = eng.family.cache_groups(cfg, impl)

    def run(params, tok):
        positions = jnp.arange(t, dtype=jnp.int32)
        valid = positions < n
        slot_ids = jnp.zeros(t, jnp.int32)
        start = jnp.zeros(1, jnp.int32)
        pools = tuple(tuple(jnp.zeros(s, d) for s, d in g.array_shapes(
            2, plan.page, 1)) for g in groups)
        tables = jnp.zeros((len(groups), 1, 1), jnp.int32)
        attend = _attend_fn(cfg, impl, pools, tables, slot_ids, positions,
                            valid, start, 0)
        cos, sin = st.rope_cos_sin(cfg, positions)
        x = params["embed"][tok].astype(cfg.dtype)
        picks, routers = [], []
        for li in range(cfg.n_layers):
            x, _, _, routing = st._layer(cfg, params, li, x, cos, sin,
                                         valid, attend, impl)
            picks.append((routing.w, routing.idx))
            routers.append(routing.logits)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (jnp.dot(x[rows], params["lm_head"],
                        preferred_element_type=jnp.float32), picks, routers)

    lg, picks, routers = jax.jit(run)(eng.params, jnp.array(tok))
    return (np.asarray(lg), [(w[:n], i[:n]) for w, i in picks],
            [np.asarray(r[:n]) for r in routers])


def one_pass_reference(eng, model, plan: "_Plan", picks, operands=None,
                       variant=()):
    """The reference on the same tokens with `picks` for its own:
    (logits of `_one_pass_rows`, the routers' logits a layer)."""
    import jax.numpy as jnp
    held = program_smallthinker.experts_held(model)
    lg, routers = reference_smallthinker.logits(
        model, eng.params, jnp.array(plan.bases[0][:plan.budget]), held,
        operands=operands, rows=_one_pass_rows(plan), variant=variant,
        picks=picks, with_router=True)
    return np.asarray(lg), [np.asarray(r) for r in routers]


# ---- the router, float32 against float32 --------------------------------

def router(eng, model: Dict[str, Any], seed: int, say) -> Dict[str, Any]:
    """`softmax_pick_routing` against the reference's `_route` on the
    same input of the stream's type, the first layer's router."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.moe import softmax_pick_routing

    cfg = eng.model_cfg
    k = cfg.moe_top_k
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (512, cfg.hidden),
                          jnp.float32).astype(cfg.dtype)
    w_r = eng.params["layers"][0]["router"]
    w, idx, lg = jax.jit(lambda w_r, x: softmax_pick_routing(
        x, w_r, top_k=k))(w_r, x)
    with reference_smallthinker.computing():
        rw, ridx, rlg = reference_smallthinker._route(
            w_r, x.astype(jnp.float32), top_k=k)
    same = np.asarray(idx) == np.asarray(ridx)
    gap = _gap(rlg, lg)["rel_rms"]
    wgap = float(np.abs(np.where(same, np.asarray(w) - np.asarray(rw),
                                 0.0)).max())
    ok = bool(gap <= ROUTER_REL_RMS and wgap <= ROUTER_REL_RMS
              and same.mean() >= 0.99)
    say(f"  {'ok' if ok else 'FAILED'}: router float32 against float32: "
        f"logits rms gap {gap:.2e}, weights off by {wgap:.2e} at most "
        f"(<= {ROUTER_REL_RMS}), picks alike {same.mean():.4f} (>= 0.99)")
    return {"rel_rms": gap, "weights_max": wgap,
            "picks_alike": float(same.mean()), "ok": ok}


def _router_weights(eng, model, seed: int, operands=None, variant=()):
    """The reference's weights for its picks on `router`'s input, given
    the unvaried reference's PICKS (so that only the weights differ):
    [512, k]."""
    import jax
    import jax.numpy as jnp
    ref = reference_smallthinker
    cfg = eng.model_cfg
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (512, cfg.hidden),
                          jnp.float32).astype(cfg.dtype).astype(jnp.float32)
    w_r = eng.params["layers"][0]["router"]
    with ref.computing():
        _, idx, _ = ref._route(w_r, x, top_k=cfg.moe_top_k)
    with ref.computing(operands, variant):
        w, got, _ = ref._route(w_r, x, top_k=cfg.moe_top_k)
    return np.where(np.asarray(got) == np.asarray(idx), np.asarray(w), 0.0)


# ---- one expert layer, the program's picks ------------------------------

def expert_layer(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None], operands=None, variant=()
                 ) -> Dict[str, Any]:
    """The program's expert product (`smallthinker.experts`, the
    engine's impl, out of the engine's stack of all layers' experts)
    against the reference's on the same normalised input WITH THE
    PROGRAM'S PICKS, the second layer's experts (`base` not 0): at 48
    rows (a decode tick) and at 512 (a chunk). With `variant` or
    `operands`: the reference so computed against the reference (the
    probe's readings)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import smallthinker as st

    cfg = eng.model_cfg
    impl = eng._resolve_impl()
    ref = reference_smallthinker
    held = program_smallthinker.experts_held(model)
    li = min(1, cfg.n_layers - 1)
    base = li * cfg.n_held
    w_r = eng.params["layers"][li]["router"]

    # the weights go in as arguments: closed over, a jit bakes them into
    # the program as constants
    def block(stacks, w_r, y):
        routing = st.route(cfg, {"router": w_r}, y, impl=impl)
        return (st.experts(cfg, stacks, y, routing, base=base, impl=impl),
                routing.w, routing.idx)

    block = jax.jit(block)
    probing = bool(variant) or operands is not None
    out: Dict[str, Any] = {"ok": True}
    for rows in (48, 512):
        y = jax.random.normal(jax.random.PRNGKey(seed + rows),
                              (rows, cfg.hidden), jnp.float32
                              ).astype(cfg.dtype)
        got, w, idx = block(eng.params["experts"], w_r, y)
        yf = y.astype(jnp.float32)
        with ref.computing():
            want = np.asarray(ref.experts(model, eng.params["experts"], yf,
                                          w, idx, held, base=base))
        if probing:
            with ref.computing(operands, variant):
                got = ref.experts(model, eng.params["experts"], yf, w, idx,
                                  held, base=base)
        got = np.asarray(got, np.float32)
        gap = _gap(want, got)["rel_rms"]
        ok = bool(np.isfinite(got).all() and gap <= EXPERTS_REL_RMS)
        if not probing:
            say(f"  {'ok' if ok else 'FAILED'}: expert layer {li}, {rows} "
                f"rows, the program's picks: rms gap {gap:.4f} (<= "
                f"{EXPERTS_REL_RMS})")
        out[f"rows{rows}"] = {"rel_rms": gap, "ok": ok}
        out["ok"] = out["ok"] and ok
    return out


# ---- one attention block a kind, through a cache ------------------------

def _attention_input(eng, plan: "_Plan", seed: int):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(
        jax.random.PRNGKey(seed + 2), (plan.ref_len, eng.model_cfg.hidden),
        jnp.float32).astype(eng.model_cfg.dtype)


def attention_block(eng, plan: "_Plan", seed: int, li: int
                    ) -> Dict[str, np.ndarray]:
    """Layer `li`'s attention block (norm, q/k/v, rope, attention, W_o)
    on a random input of the plan's longest context, cached a tick at a
    time in a pool of its OWN (one layer, the context's pages: 18 MB)
    through the pieces the forwards call, the last tick by the kernel
    path and by the gather path. Returns {impl: the last tick's output
    [tokens, H]} and "context"."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import smallthinker as st
    from ray_tpu.models.phi4flash import _attend_fn, scatter_rows

    cfg, kernel = eng.model_cfg, eng._resolve_impl()
    S, T, page = plan.ref_len, plan.T, plan.page
    windowed = cfg.windowed[li]
    window = cfg.sliding_window if windowed else None
    n_pages = -(-(S + T) // page)
    layer = eng.params["layers"][li]
    x = jnp.pad(_attention_input(eng, plan, seed), ((0, -S % T), (0, 0)))
    slot_ids = jnp.zeros(T, jnp.int32)
    tables = jnp.arange(n_pages, dtype=jnp.int32)[None, :]

    def tick(impl, keep):
        def run(layer, x, pos0, kp, vp):
            positions = pos0 + jnp.arange(T, dtype=jnp.int32)
            valid = positions < S
            start = jnp.zeros(1, jnp.int32).at[0].set(pos0)
            q, k, v = st.attn_project(cfg, layer, x, cfg.roped[li],
                                      *st.rope_cos_sin(cfg, positions))
            attend = _attend_fn(cfg, impl, ((kp, vp),), (tables,),
                                slot_ids, positions, valid, start, n_pages)
            where = (0, 0, window)
            if impl != "gather":
                o = st._attend_padded(cfg, attend, q, k, v, *where)
            else:
                o = attend(q, k, v, *where)
            out = o.reshape(T, -1).astype(cfg.dtype) @ layer["wo"]
            if not keep:
                return out
            own = tables[slot_ids]
            return (out, scatter_rows(kp, k[None], own, positions, valid),
                    scatter_rows(vp, v[None], own, positions, valid))
        return jax.jit(run, donate_argnums=(3, 4) if keep else ())

    row = eng.family.cache_groups(cfg, kernel)[0].row
    kp, vp = (jnp.zeros(row.pool_shape(1, n_pages + 1, page), row.dtype)
              for _ in range(2))
    fill = tick(kernel, True)
    last = (S - 1) // T * T
    for pos0 in range(0, last, T):
        _, kp, vp = fill(layer, x[pos0:pos0 + T], jnp.int32(pos0), kp, vp)
    out: Dict[str, Any] = {impl: np.asarray(tick(impl, False)(
        layer, x[last:last + T], jnp.int32(last), kp, vp),
        np.float32)[:S - last]
        for impl in dict.fromkeys(("gather", kernel))}
    out["context"] = last
    return out


def attention_block_reference(eng, model, plan: "_Plan", seed: int,
                              li: int, operands=None, variant=()):
    import jax.numpy as jnp
    S, T = plan.ref_len, plan.T
    with reference_smallthinker.computing(operands, variant):
        return np.asarray(reference_smallthinker.attention(
            model, eng.params["layers"][li],
            _attention_input(eng, plan, seed).astype(jnp.float32),
            model["sliding_window_layout"][li],
            model["rope_layout"][li])[(S - 1) // T * T:])


def _kinds(cfg) -> Dict[str, int]:
    """The first full and the first window layer."""
    out = {"full": cfg.windowed.index(0)}
    if 1 in cfg.windowed:
        out["swa"] = cfg.windowed.index(1)
    return out


def attention_blocks(eng, model, plan: "_Plan", seed: int, say
                     ) -> Dict[str, Any]:
    out: Dict[str, Any] = {"ok": True}
    for kind, li in _kinds(eng.model_cfg).items():
        got = attention_block(eng, plan, seed, li)
        want = attention_block_reference(eng, model, plan, seed, li)
        ctx = got.pop("context")
        for impl, g in got.items():
            gap = _gap(want, g)
            ok = bool(gap["finite"]
                      and gap["rel_rms"] <= ATTENTION_REL_RMS)
            say(f"  {'ok' if ok else 'FAILED'}: attention block, layer "
                f"{li} ({kind}), {impl}, {len(g)} tokens at context {ctx}:"
                f" rms gap {gap['rel_rms']:.4f} (<= {ATTENTION_REL_RMS})")
            out[f"{kind}.{impl}"] = {"rel_rms": gap["rel_rms"], "ok": ok}
            out["ok"] = out["ok"] and ok
    return out


# ---- the cell's comparison ----------------------------------------------

def _router_gap(want, got) -> float:
    """The worst layer's rms gap of the routers' logits."""
    return max(_gap(a, b)["rel_rms"] for a, b in zip(want, got))


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick at the engine's own sizes, on
    its own pools through its own cache manager (`_Plan`, `_ticks`):
    (a) kernel path against gather path; (b) gather path against the
    float32 reference on the same token histories: prefill, then
    decoding through both page groups, on sequences under, across and
    past twice the window, after window pages were handed back and taken
    by other sequences; (c) the engine's own compiled programs against
    the kernel path; (d) where no pick flips, tighter: the whole stack in
    one pass with the program's picks, the router float32 against
    float32, one expert layer with the program's picks, one full and one
    window attention block through a cache. Logits, not tokens. Returns
    {"ok", ...gaps}."""
    cfg = eng.model_cfg
    plan = _Plan(eng, seed)
    ticks, window = _ticks(eng, plan, say)
    out: Dict[str, Any] = {"ok": True, "longest_context": plan.ref_len,
                           "T": plan.T, "ctx_bucket_pages": plan.ctx,
                           "window_group": window}
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
        for what, a, b, quartile in (
                ("kernel_vs_gather", lg_g[slots], lg_k[slots],
                 KERNEL_QUARTILE_ROW),
                ("gather_vs_reference", want, lg_g[slots], None)):
            g = _rows_gap(a, b)
            g["quartile_row"] = float(np.percentile(g["rows"], 25))
            g["past_window_median_row"] = float(np.median(
                [g["rows"][i] for i in past]))
            g["ok"] = bool(
                g["finite"] and g["median_row"] <= REFERENCE_MEDIAN_ROW
                and g["worst_row"] <= WORST_ROW
                and g["past_window_median_row"] <= PAST_WINDOW_MEDIAN_ROW
                and (quartile is None or g["quartile_row"] <= quartile))
            say(f"  {'ok' if g['ok'] else 'FAILED'}: {what}.{name} quartile "
                f"row {g['quartile_row']:.4f} of rms"
                + (f" (<= {quartile})" if quartile else "")
                + f", median row {g['median_row']:.4f} (<= "
                f"{REFERENCE_MEDIAN_ROW}), of the {len(past)} rows past "
                f"the window {g['past_window_median_row']:.4f} (<= "
                f"{PAST_WINDOW_MEDIAN_ROW}), worst row "
                f"{g['worst_row']:.4f} (<= {WORST_ROW}), argmax agree "
                f"{g['argmax_agree']}/{len(slots)}, contexts "
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
        # every expert is held: each valid row lands its picks in every
        # layer, whatever flips
        tokens = (sum(n for _, _, _, n in plan.mixed()) if name == "mixed"
                  else len(slots))
        e["rider_total_wanted"] = tokens * cfg.moe_top_k * cfg.n_layers * (
            cfg.n_held == cfg.n_routed_experts)
        rider = np.asarray(toks[lg.shape[0]:], np.int64)
        e["rider_total_got"] = int(rider.sum())
        e["ok"] = bool(
            e["rider_len_ok"] and e["worst_under_max"] <= ENGINE_FLIP_MAX
            and 10 * e["rows_near_max"] >= 7 * len(slots)
            and e["rider_diff"] <= RIDER_SLACK * e["rider_total"] + 2
            and (not e["rider_total_wanted"] or e["rider_total_wanted"]
                 == e["rider_total_got"] == e["rider_total"]))
        say(f"  {'ok' if e['ok'] else 'FAILED'}: engine_program.{name} "
            f"{e['rows_near_max']}/{len(slots)} tokens within "
            f"{ENGINE_NEAR_MAX} of rms of the kernel path's largest logit "
            f"(>= 7 in 10), the furthest {e['worst_under_max']:.4f} under "
            f"it (<= {ENGINE_FLIP_MAX}), {e['argmax_agree']}/{len(slots)} "
            f"its argmax; rider off by {e['rider_diff']} of "
            f"{e['rider_total']} assignments, its total "
            f"{e['rider_total_got']} (= {tokens} rows x {cfg.moe_top_k} x "
            f"{cfg.n_layers} = {e['rider_total_wanted']})")
        out[f"engine_program.{name}"] = e
        out["ok"] = out["ok"] and e["ok"]
    # (d) where no pick flips
    lg, picks, routers = one_pass(eng, plan)
    want, want_routers = one_pass_reference(eng, model, plan, picks)
    g = _rows_gap(want, lg)
    g["router_rel_rms"] = _router_gap(want_routers, routers)
    g["ok"] = bool(g["finite"] and g["median_row"] <= ONE_PASS_MEDIAN_ROW
                   and g["worst_row"] <= WORST_ROW
                   and g["router_rel_rms"] <= ONE_PASS_ROUTER_REL_RMS)
    say(f"  {'ok' if g['ok'] else 'FAILED'}: one pass of {plan.budget} "
        f"tokens, the program's picks: median row {g['median_row']:.4f} of"
        f" rms (<= {ONE_PASS_MEDIAN_ROW}), worst row {g['worst_row']:.4f}"
        f"; routers' logits, the worst layer {g['router_rel_rms']:.4f} "
        f"(<= {ONE_PASS_ROUTER_REL_RMS})")
    out["one_pass"] = g
    out["router"] = router(eng, model, seed, say)
    out["expert_layer"] = expert_layer(eng, model, seed, say)
    out["attention_blocks"] = attention_blocks(eng, model, plan, seed, say)
    out["ok"] = bool(out["ok"] and g["ok"] and out["router"]["ok"]
                     and out["expert_layer"]["ok"]
                     and out["attention_blocks"]["ok"])
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None], only=()) -> Dict[str, Any]:
    """The second readings a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16),
    and computed wrong in each way of VARIANTS, against the reference
    itself: on the one-pass rows with the SAME picks handed to both, on
    the routers' logits of that pass, on the expert layer's input with
    the same picks, on the attention blocks' input, and (the slow one,
    "ticks" in `only` or `only` empty) on the rows of the mixed and the
    decode tick with each side routing on its own. Each has to come out
    over at least one limit. `only`: the names to read ("fp8" or a
    variant's, and "ticks"). Not part of a run:
    `runners/serve_smallthinker.py --probe` prints it."""
    import jax.numpy as jnp
    plan = _Plan(eng, seed)
    with_ticks = not only or "ticks" in only
    names = [n for n in only if n != "ticks"]
    wanted: List = [(b, pos0 + n - 1) for _, b, pos0, n in plan.mixed()]
    wanted += list(plan.decode().values())
    past = _past_window(plan, wanted)
    want_ticks = (_reference_rows(eng, model, plan, wanted)
                  if with_ticks else None)
    # the reference's own picks, handed to both sides
    held = program_smallthinker.experts_held(model)
    _, own_routers = reference_smallthinker.logits(
        model, eng.params, jnp.array(plan.bases[0][:plan.budget]), held,
        rows=[0], with_router=True)
    own_picks = [reference_smallthinker.picks_of(model, r)
                 for r in own_routers]
    want_one, want_routers = one_pass_reference(eng, model, plan, own_picks)
    want_weights = _router_weights(eng, model, seed)
    want_attn = {kind: attention_block_reference(eng, model, plan, seed, li)
                 for kind, li in _kinds(eng.model_cfg).items()}
    out: Dict[str, Any] = {}
    for name, kw in [("fp8", {"operands": jnp.float8_e4m3fn})] + [
            (v, {"variant": (v,)}) for v in VARIANTS]:
        if names and name not in names:
            continue
        one, routers = one_pass_reference(eng, model, plan, own_picks, **kw)
        g = {"one_pass": {k: v for k, v in _rows_gap(want_one, one).items()
                          if k in ("median_row", "worst_row")},
             "one_pass_router": _router_gap(want_routers, routers)}
        g["router_weights"] = float(np.abs(
            want_weights - _router_weights(eng, model, seed, **kw)).max())
        ex = expert_layer(eng, model, seed, say, **kw)
        g["expert_layer"] = max(ex[r]["rel_rms"] for r in ("rows48",
                                                           "rows512"))
        g["attention"] = {
            kind: _gap(want_attn[kind], attention_block_reference(
                eng, model, plan, seed, li, **kw))["rel_rms"]
            for kind, li in _kinds(eng.model_cfg).items()}
        passes = (g["one_pass"]["median_row"] <= ONE_PASS_MEDIAN_ROW
                  and g["one_pass"]["worst_row"] <= WORST_ROW
                  and g["one_pass_router"] <= ONE_PASS_ROUTER_REL_RMS
                  and g["router_weights"] <= ROUTER_REL_RMS
                  and ex["ok"]
                  and max(g["attention"].values()) <= ATTENTION_REL_RMS)
        line = (f"  the reference with {name} against the reference: one "
                f"pass median row {g['one_pass']['median_row']:.4f}, "
                f"worst {g['one_pass']['worst_row']:.4f}, routers "
                f"{g['one_pass_router']:.4f}; the router's weights off by "
                f"{g['router_weights']:.2e}; expert layer "
                f"{g['expert_layer']:.4f}; attention blocks "
                f"{ {k: round(v, 4) for k, v in g['attention'].items()} }")
        if with_ticks:
            t = _rows_gap(want_ticks, _reference_rows(eng, model, plan,
                                                      wanted, **kw))
            t["past_window_median_row"] = float(np.median(
                [t["rows"][i] for i in past]))
            g["ticks"] = t
            passes = (passes and t["median_row"] <= REFERENCE_MEDIAN_ROW
                      and t["worst_row"] <= WORST_ROW
                      and t["past_window_median_row"]
                      <= PAST_WINDOW_MEDIAN_ROW)
            line += (f"; ticks' median row {t['median_row']:.4f}, past the"
                     f" window {t['past_window_median_row']:.4f}, worst "
                     f"{t['worst_row']:.4f}")
        g["would_pass"] = bool(passes)
        say(line + f"; would pass {g['would_pass']}")
        out[name] = g
    return out
