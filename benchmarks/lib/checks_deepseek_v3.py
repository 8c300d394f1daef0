"""Whether the DeepSeek-V3 family's outputs are right: the comparisons
behind `correct` for its serving cells, made outside the timed window.
The dense decoder's are in checks.py; this file is theirs for a latent
cache and an expert layer."""

from __future__ import annotations

from typing import Any, Callable, Dict

import numpy as np

from . import program_deepseek_v3, reference_deepseek_v3
from .checks import _gap

# What the logits comparisons are up against. Routing is not a continuous
# function: a token whose 8th and 9th biased scores lie within the
# rounding noise of the two sides picks another expert, and when exactly
# one of the two is held here the token gains or loses one expert's
# output, about a tenth of its residual; a pick that flips in an early
# layer can move the later layers' picks with it. With this seed's
# router that happens to about one row in ten whatever the precision,
# and moves that ROW by 0.1 to 0.35 of the logits' RMS. So rows are
# judged one by one: the MEDIAN row's gap carries the limit that
# rounding sets, and every row stays under WORST_ROW, which lies between
# the largest row read on the chip at the cell's sizes (0.334 of 492
# rows over three seeds; 0.27 of 192 at contexts under 128) and what a
# row read off a wrong page, mask, head order or row width gives (two
# unrelated rows of logits are 1.41 apart).
WORST_ROW = 0.7
# Kernel path against the gather path, on the SAME cache: the same
# projections, bf16 operands and f32 softmax statistics; they differ in
# the order of the flash blocks' sums against one dense softmax and in
# where the probabilities are rounded to bf16 before the value product:
# bf16 rounding of the attention output through 5 layers, 2^-8 * sqrt(5)
# = 0.009 of the logits' RMS. On the chip at contexts of 1.5k to 6.1k:
# 0.0165 to 0.0174 (PERF.md section 6).
KERNEL_MEDIAN_ROW = 0.04
# Gather path (bf16 weights as stored, bf16 activations, f32
# accumulation, absorbed attention, a cache the engine's own program
# filled in 512-token chunks) against the float32 non-absorbed
# reference, which computes every token of the context itself:
# activations rounded to 8 mantissa bits through ~12 matrix products a
# layer and 5 layers, 2^-9 * sqrt(60) = 0.015. The limit lies between
# the largest median read on the chip at the cell's sizes (0.0294 over
# three seeds and two ticks; 0.027 to 0.037 at contexts under 128) and
# the reading of the reference computed with float8_e4m3 operands, the
# precision below the stated one, which has to fail (0.513 to 0.516);
# the reference without m^2 reads 0.98 (all in PERF.md section 6). It is
# also what found the kernel counting 16 in-batch keys twice a block
# (0.10 to 0.14 then, every cached token past its chunk's first 128
# being a little off).
REFERENCE_MEDIAN_ROW = 0.06
# One attention block (`mla_project`, attention over the cache through
# `cache_attention`, `mla_output`: what the forwards call) against the
# reference's on the SAME input, where no pick can differ: the longest
# context cached a tick budget at a time by the kernel, then the last
# chunk by the kernel and by the gather, against the reference's
# non-absorbed float32 attention of the whole sequence. bf16 rounding of
# the absorbed queries, the cached rows, the probabilities and the
# outputs: 2^-9 * a few. Read on the chip, 512 tokens at context 5,632:
# 0.0077 to 0.0078 either path, seven runs. The limit lies between that
# and the smallest fault read: 16 keys of 6,000 counted twice 0.040, the
# reference with float8 operands 0.148, without m^2 0.58.
ATTENTION_REL_RMS = 0.02
# One expert layer against the reference's, both given the SAME
# normalised input, so that upstream rounding flips no pick: the full
# output (shared expert + held routed experts), and the routed part
# alone, which is what the chip's share leaves of 8 picks a token (half
# a pick on average) and what the logits barely see. bf16 products of
# three matrices: 2^-9 * sqrt(3) * a few = 0.01. An unscaled gate is off
# by 0.60 of the routed part, a dropped pick (7 of 8, renormalised) by
# 0.14 of every gate, softmax for sigmoid or a missing bias by a
# different set of picks: all far over the limit.
EXPERTS_REL_RMS, ROUTED_REL_RMS = 0.02, 0.03
# The engine's own compiled programs (`jit_run`, `jit_step`: the forward
# behind the sampler, the rider behind the tokens) against the kernel
# path's logits above, on the same inputs with the temperature at 0:
# each token they give has to be the largest logit or within this much
# of it (the same forward compiled into another program: a tie at most),
# where a wrong row, table or program gives any of 16,160 ids, 4 RMS
# below it. The rider (assignments landed on each held expert) has to be
# the forward's own counts, give or take picks that flip on a tie (7 of
# 1,106 on the chip; a rider off its place is off by all of them).
ENGINE_NEAR_MAX, RIDER_SLACK = 0.05, 0.05
# The longest cached context of the checks: the traffic's longest prompt
# (6,144), or three quarters of a smaller engine's `max_seq_len`
LONGEST = 6144


class _Plan:
    """What the checks run, laid out from the engine's own sizes (its
    slots, page size, table width, tick budget, `max_seq_len`): four
    token sequences ("bases") of LONGEST down to LONGEST / 2 tokens,
    each cached once in pages of its own; a MIXED tick of the tick
    budget's tokens (16 decode rows whose tables share a base's pages
    up to a page boundary and continue in a page of their own, as a
    prefix-cache hit does; a chunk that continues a base from three
    quarters in; a prompt that starts), and a DECODE tick of every
    slot. Every row's tokens are a base's, so the reference's logits
    for it are one row of that base's forward."""

    def __init__(self, eng, seed: int):
        ec, cfg = eng.config, eng.model_cfg
        page, B = ec.page_size, ec.max_batch_size
        self.B, self.page = B, page
        self.budget = eng._tick_token_budget()
        self.T = eng._token_bucket(self.budget)
        longest = min(LONGEST, eng.max_seq * 3 // 4) // page * page
        lens = [longest * k // 6 // page * page for k in (6, 5, 4, 3)]
        rng = np.random.default_rng(seed)
        self.bases = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
                      for n in lens]
        self.ref_len = longest
        self.ctx = eng._ctx_bucket(longest)
        width = eng.max_pages_per_seq
        nxt = 1                               # page 0 stays unused
        self.base_pages = []
        for n in lens:
            self.base_pages.append(np.arange(nxt, nxt + n // page))
            nxt += n // page
        self.fill_tables = np.zeros((B, width), np.int32)
        for b, pages in enumerate(self.base_pages):
            self.fill_tables[b, :len(pages)] = pages
        # slot -> (base, cached tokens before the mixed tick, tokens it
        # adds in the mixed tick); decode rows of the mixed tick first
        self.dec = min(16, B - 2)
        left = self.budget - self.dec
        chunk = left * 3 // 5
        self.rows = {}
        steps = -(-B // len(lens))
        for s in range(B):
            b = s % len(lens)
            n_b = lens[b] // page
            cached = (n_b - 1 - (s // len(lens))
                      * max(n_b // 2 // steps, 1)) * page
            self.rows[s] = (b, cached, 1 if s < self.dec else 0)
        self.rows[self.dec] = (1, lens[1] * 3 // 4 // page * page, chunk)
        self.rows[self.dec + 1] = (2, 0, left - chunk)
        self.tables = np.zeros((B, width), np.int32)
        for s, (b, cached, n) in self.rows.items():
            shared = cached // page
            own = -(-(max(n, 1) + 1) // page) + 1
            self.tables[s, :shared] = self.base_pages[b][:shared]
            self.tables[s, shared:shared + own] = np.arange(nxt, nxt + own)
            nxt += own
        if nxt > ec.num_pages - 1:            # the last page is scratch
            raise ValueError(f"the checks want {nxt} pages of the "
                             f"pool's {ec.num_pages}")

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
        return [(s, b, cached, n) for s, (b, cached, n)
                in sorted(self.rows.items()) if n]

    def decode(self):
        """slot -> (base, position) of the decode tick's token."""
        return {s: (b, cached + n) for s, (b, cached, n)
                in self.rows.items()}

    def fills(self):
        """The ticks that cache the bases, a tick budget at a time."""
        for b, base in enumerate(self.bases):
            for pos0 in range(0, len(base), self.budget):
                yield [(b, b, pos0, min(self.budget, len(base) - pos0))]


def _ticks(eng, plan: "_Plan", say):
    """Run the plan on the engine's own weights, POOL and page-table
    width, at its tick's token bucket and context bucket. The bases are
    cached by the engine's own ragged program (`jit_run`, the kernel
    path: chunks of the tick budget against a growing context, as it
    prefills a prompt). Then, for the mixed tick and for the decode
    tick on the same pool: the gather path's logits, the kernel path's
    (both through the family's forwards, which the engine's programs
    call), and the engine's own program with the temperature at 0,
    which also writes the tick's rows for what follows. Returns
    {"mixed" | "decode": (gather logits, kernel logits, kernel counts,
    engine tokens with rider, rows)}."""
    import jax
    import jax.numpy as jnp

    cfg, fam = eng.model_cfg, eng.family
    kernel = eng._resolve_impl()
    B, T = plan.B, plan.T
    samp = np.zeros((4, B), np.float32)        # temperature 0
    samp[1] = samp[3] = 1.0
    samp = jnp.array(samp)
    key = jax.random.PRNGKey(0)
    seen = jnp.zeros((B, cfg.vocab_size), bool)
    run = eng._ragged_fn(T, plan.ctx, False)

    def engine_run(pool, seen, tick, tables):
        toks, pool, _, seen = run(
            eng.params, pool, None, seen, jnp.array(tick[0]),
            jnp.array(tick[1]), samp, tables, key, eng._lora_stacks,
            False)
        return np.asarray(toks), pool, seen

    def ragged(impl):
        # logits and counts alone: the scatter into the pool is dead
        # code here, and the one pool is never copied
        return jax.jit(lambda params, tok, slot, pool, tables: (
            fam.ragged_forward(
                cfg, params, tok[0], tok[1], tok[2], tok[3] != 0,
                slot[0], slot[1], pool, None, tables,
                ctx_pages=plan.ctx, impl=impl)[::3]))

    def decode(impl):
        return jax.jit(lambda params, toks, pos, pool, tables, active: (
            fam.decode_step(cfg, params, toks, pos, pool, None, tables,
                            active, impl=impl)[::3]))

    # the engine's pool, lent: its programs donate it, so it is handed
    # from call to call and given back zeroed
    pool, eng.k_pages = eng.k_pages, None
    fill_tables = jnp.array(plan.fill_tables)
    n = 0
    for rows in plan.fills():
        _, pool, seen = engine_run(pool, seen, plan.tick(rows),
                                   fill_tables)
        n += 1
    say(f"  cached {[len(b) for b in plan.bases]} tokens in {n} ticks "
        f"of the engine's ragged program (T {T}, ctx bucket {plan.ctx} "
        f"pages, {kernel})")
    tables = jnp.array(plan.tables)
    out = {}
    rows = plan.mixed()
    tick = plan.tick(rows)
    args = (eng.params, jnp.array(tick[0]), jnp.array(tick[1]), pool,
            tables)
    lg_g = np.asarray(ragged("gather")(*args)[0])
    lg_k, counts = (np.asarray(a) for a in ragged(kernel)(*args))
    del args
    toks, pool, seen = engine_run(pool, seen, tick, tables)
    out["mixed"] = (lg_g, lg_k, counts, toks,
                    {s: (b, pos0 + n - 1) for s, b, pos0, n in rows})
    at = plan.decode()
    toks_in = np.zeros(B + eng._rider_len, np.int32)
    posn = np.zeros(B, np.int32)
    for s, (b, pos) in at.items():
        toks_in[s], posn[s] = plan.bases[b][pos], pos
    active = jnp.ones(B, bool)
    args = (eng.params, jnp.array(toks_in[:B]), jnp.array(posn), pool,
            tables, active)
    lg_g = np.asarray(decode("gather")(*args)[0])
    lg_k, counts = (np.asarray(a) for a in decode(kernel)(*args))
    del args
    zeros_f, ones_f = jnp.zeros(B, jnp.float32), jnp.ones(B, jnp.float32)
    zeros_i = jnp.zeros(B, jnp.int32)
    toks, pool, _, seen = eng._decode_fn(
        eng.params, pool, None, seen, jnp.array(toks_in),
        jnp.array(posn), tables, active, key, zeros_f, ones_f, zeros_i,
        ones_f, zeros_i, eng._lora_stacks, zeros_i, False)
    out["decode"] = (lg_g, lg_k, counts, np.asarray(toks), at)
    del seen
    eng.k_pages = jax.jit(jnp.zeros_like, donate_argnums=0)(pool)
    return out


def _reference_rows(eng, model: Dict[str, Any], plan: "_Plan", wanted,
                    operands=None):
    """The reference's logits for `wanted`, a list of (base, position):
    one forward a base, padded to the longest (causal: what follows a
    position changes nothing at it), so its op-by-op run compiles one
    shape."""
    import jax.numpy as jnp
    held = program_deepseek_v3.experts_held(model)
    got = {}
    for b, base in enumerate(plan.bases):
        rows = sorted({pos for bb, pos in wanted if bb == b})
        if not rows:
            continue
        padded = np.zeros(plan.ref_len, np.int32)
        padded[:len(base)] = base
        lg = np.asarray(reference_deepseek_v3.logits(
            model, eng.params, jnp.array(padded), held,
            operands=operands, rows=rows))
        got.update({(b, pos): lg[i] for i, pos in enumerate(rows)})
    return np.stack([got[w] for w in wanted])


def _rows_gap(want, got) -> Dict[str, Any]:
    """Per-row gaps of two [rows, vocab] logits, each row's RMS
    difference over the RMS of all of `want`."""
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    rms = float(np.sqrt(np.mean(want * want)))
    rows = np.sqrt(np.mean((want - got) ** 2, axis=-1)) / rms
    return {"rows": [round(float(r), 5) for r in rows],
            "median_row": float(np.median(rows)),
            "worst_row": float(rows.max()),
            "argmax_agree": int((want.argmax(-1) == got.argmax(-1)).sum()),
            "finite": bool(np.isfinite(want).all()
                           and np.isfinite(got).all())}


def attention_block(eng, model: Dict[str, Any], plan: "_Plan", seed: int,
                    say: Callable[[str], None]) -> Dict[str, Any]:
    """One layer's attention block on a random input of the plan's
    longest context: cached a tick budget at a time in the engine's pool
    through the pieces the forwards call, the last chunk by the kernel
    path and by the gather path, against the reference's attention of
    the whole sequence on the same input."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import deepseek_v3 as ds
    from ray_tpu.ops import mla_attention as mla_ops

    cfg = eng.model_cfg
    kernel = eng._resolve_impl()
    S, T = plan.ref_len, plan.T
    layer = eng.params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(seed), (S, cfg.hidden),
                          jnp.float32).astype(cfg.dtype)
    x = jnp.pad(x, ((0, -S % T), (0, 0)))
    slot_ids = jnp.zeros(T, jnp.int32)          # slot 0, the first base's pages
    tables = jnp.array(plan.fill_tables)

    def tick(impl, keep_pool):
        # the weights go in as arguments: closed over, a jit bakes them
        # into the program as constants
        def run(layer, x, pos0, pool):
            positions = pos0 + jnp.arange(T, dtype=jnp.int32)
            valid = positions < S
            start = jnp.zeros(plan.B, jnp.int32).at[0].set(pos0)
            q, new = ds.mla_project(cfg, layer, x,
                                    *ds.rope_cos_sin(cfg, positions))
            attend = ds.cache_attention(cfg, impl, pool, tables, slot_ids,
                                        positions, valid, start, plan.ctx)
            out = ds.mla_output(cfg, layer, attend(q, new, 0))
            if not keep_pool:
                return out
            rows = jnp.zeros((pool.shape[0],) + new.shape,
                             new.dtype).at[0].set(new)
            return out, mla_ops.scatter_latent(
                pool, rows, tables[slot_ids], positions, valid)
        return jax.jit(run, donate_argnums=(3,) if keep_pool else ())

    pool, eng.k_pages = eng.k_pages, None
    fill = tick(kernel, True)
    last = (S - 1) // T * T
    for pos0 in range(0, last, T):
        _, pool = fill(layer, x[pos0:pos0 + T], jnp.int32(pos0), pool)
    got = {impl: np.asarray(tick(impl, False)(
        layer, x[last:last + T], jnp.int32(last), pool))[:S - last]
        for impl in dict.fromkeys(("gather", kernel))}
    eng.k_pages = jax.jit(jnp.zeros_like, donate_argnums=0)(pool)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_deepseek_v3.attention(
            model, layer, x[:S].astype(jnp.float32))[last:])
    out: Dict[str, Any] = {"ok": True, "context": last}
    for impl, g in got.items():
        gap = _gap(want, g)
        ok = bool(gap["finite"] and gap["rel_rms"] <= ATTENTION_REL_RMS)
        say(f"  {'ok' if ok else 'FAILED'}: attention block, {impl}, "
            f"{S - last} tokens at context {last}: rms gap "
            f"{gap['rel_rms']:.4f} (<= {ATTENTION_REL_RMS})")
        out[impl] = {"rel_rms": gap["rel_rms"], "ok": ok}
        out["ok"] = out["ok"] and ok
    return out


def expert_layer(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """The program's expert layer (`moe_block`: router, shared expert,
    held experts) against the reference's on the same normalised input,
    on the engine's weights of the first expert layer: at 64 rows (every
    expert takes every row) and at 256 (each expert's rows gathered)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import deepseek_v3 as ds

    cfg = eng.model_cfg
    held = program_deepseek_v3.experts_held(model)
    layer = next(w for w in eng.params["layers"] if "router" in w)
    # the weights go in as arguments: closed over, a jit bakes them into
    # the program as constants (3.5 GB of them, on the host)
    block = jax.jit(lambda w, y: ds.moe_block(cfg, w, y)[0])
    shared = jax.jit(lambda w, y: ds.swiglu(w, y))
    out: Dict[str, Any] = {"ok": True}
    for rows in (64, 256):
        y = jax.random.normal(jax.random.PRNGKey(seed + rows),
                              (rows, cfg.hidden), jnp.float32
                              ).astype(cfg.dtype)
        got = np.asarray(block(layer, y), np.float32)
        got_routed = got - np.asarray(shared(layer["shared"], y),
                                      np.float32)
        with jax.default_matmul_precision("highest"):
            yf = y.astype(jnp.float32)
            want = np.asarray(reference_deepseek_v3.experts(
                model, layer, yf, held))
            sh = layer["shared"]
            want_routed = want - np.asarray(reference_deepseek_v3._swiglu(
                yf, sh["wg"], sh["wi"], sh["wd"]))
        full = _gap(want, got)["rel_rms"]
        # the routed part is zero for rows with no pick held here: its
        # gap is over its own RMS
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


def _engine_gap(lg_k, counts, toks, slots) -> Dict[str, Any]:
    """The engine's own program against the kernel path's logits and
    expert counts on the same tick: how far under the row's largest
    logit each token it gave lies (over the logits' RMS), and how much
    of the rider differs from the forward's counts."""
    lg = np.asarray(lg_k, np.float32)
    rms = float(np.sqrt(np.mean(lg[slots] ** 2)))
    under = [(float(lg[s].max()) - float(lg[s, int(toks[s])])) / rms
             if 0 <= int(toks[s]) < lg.shape[1] else float("inf")
             for s in slots]
    rider = np.asarray(toks[lg.shape[0]:], np.int64)
    counts = np.asarray(counts, np.int64).reshape(-1)
    return {"worst_under_max": max(under),
            "argmax_agree": int(sum(u == 0.0 for u in under)),
            "rider_diff": int(np.abs(rider - counts).sum()),
            "rider_total": int(counts.sum()),
            "rider_len_ok": bool(rider.shape == counts.shape)}


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick at the engine's own sizes, on
    its own pool (`_Plan`, `_ticks`): (a) kernel path against gather
    path; (b) gather path against the float32 reference on the same
    token histories: prefill, then decoding through the latent cache,
    against the reference's full forward; (c) the engine's own compiled
    programs against the kernel path; (d) one attention block through
    the cache and one expert layer, each against the reference's on the
    same input. Logits, not tokens. Returns {"ok", ...gaps}."""
    plan = _Plan(eng, seed)
    ticks = _ticks(eng, plan, say)
    out: Dict[str, Any] = {"ok": True, "longest_context": plan.ref_len,
                           "T": plan.T, "ctx_bucket_pages": plan.ctx}
    wanted = {name: sorted(at.items())
              for name, (_, _, _, _, at) in ticks.items()}
    ref = _reference_rows(
        eng, model, plan,
        [w for name in ticks for _, w in wanted[name]])
    for name, (lg_g, lg_k, counts, toks, _) in ticks.items():
        slots = [s for s, _ in wanted[name]]
        want, ref = ref[:len(slots)], ref[len(slots):]
        for what, a, b, mid, worst in (
                ("kernel_vs_gather", lg_g[slots], lg_k[slots],
                 KERNEL_MEDIAN_ROW, WORST_ROW),
                ("gather_vs_reference", want, lg_g[slots],
                 REFERENCE_MEDIAN_ROW, WORST_ROW)):
            g = _rows_gap(a, b)
            g["ok"] = bool(g["finite"] and g["median_row"] <= mid
                           and g["worst_row"] <= worst)
            say(f"  {'ok' if g['ok'] else 'FAILED'}: {what}.{name} "
                f"median row {g['median_row']:.4f} of rms (<= {mid}), "
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
    out["attention_block"] = attention_block(eng, model, plan, seed, say)
    out["expert_layer"] = expert_layer(eng, model, seed, say)
    out["ok"] = (out["ok"] and out["attention_block"]["ok"]
                 and out["expert_layer"]["ok"])
    return out


def precision_probe(eng, model: Dict[str, Any], seed: int,
                    say: Callable[[str], None]) -> Dict[str, Any]:
    """The second reading a limit is set from: the reference computed
    with float8_e4m3 operands (the precision below the stated bfloat16)
    against the reference itself, on the mixed tick's own rows. It has
    to come out over REFERENCE_MEDIAN_ROW or WORST_ROW; and the same for
    the reference with m^2 left out of its softmax scale. Not part of a
    run: `runners/serve_deepseek_v3.py --probe` prints it."""
    import jax.numpy as jnp
    plan = _Plan(eng, seed)
    wanted = [(b, pos0 + n - 1) for _, b, pos0, n in plan.mixed()]
    want = _reference_rows(eng, model, plan, wanted)
    got = _reference_rows(eng, model, plan, wanted,
                          operands=jnp.float8_e4m3fn)
    g = _rows_gap(want, got)
    g["would_pass"] = bool(g["median_row"] <= REFERENCE_MEDIAN_ROW
                           and g["worst_row"] <= WORST_ROW)
    say(f"  fp8-operand reference against the reference: median row "
        f"{g['median_row']:.4f}, worst row {g['worst_row']:.4f}, "
        f"would pass {g['would_pass']}")
    # the reference with YaRN's m^2 left out of the softmax scale
    flat = {**model, "rope_scaling": {**model["rope_scaling"],
                                      "mscale_all_dim": 0}}
    m = _rows_gap(want, _reference_rows(eng, flat, plan, wanted))
    m["would_pass"] = bool(m["median_row"] <= REFERENCE_MEDIAN_ROW
                           and m["worst_row"] <= WORST_ROW)
    say(f"  reference without m^2 against the reference: median row "
        f"{m['median_row']:.4f}, worst row {m['worst_row']:.4f}, "
        f"would pass {m['would_pass']}")
    g["without_m2"] = m
    g["attention_block"] = _attention_probe(eng, model, plan, seed, flat,
                                            say)
    return g


def _attention_probe(eng, model, plan, seed, flat, say) -> Dict[str, Any]:
    """`attention_block`'s second readings: the reference's attention of
    its input with float8_e4m3 operands, and without m^2, each against
    the reference's own, on the rows that check compares."""
    import jax
    import jax.numpy as jnp
    ref = reference_deepseek_v3
    cfg, S = eng.model_cfg, plan.ref_len
    last = (S - 1) // plan.T * plan.T
    layer = eng.params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(seed), (S, cfg.hidden),
                          jnp.float32).astype(cfg.dtype).astype(jnp.float32)
    out = {}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention(model, layer, x)[last:])
        out["without_m2"] = _gap(want, np.asarray(
            ref.attention(flat, layer, x)[last:]))["rel_rms"]
        ref._OPERANDS = jnp.float8_e4m3fn
        try:
            out["fp8"] = _gap(want, np.asarray(ref.attention(
                model, layer, ref._f32(x))[last:]))["rel_rms"]
        finally:
            ref._OPERANDS = None
    say(f"  attention block, the reference against itself: float8 "
        f"operands {out['fp8']:.4f}, without m^2 "
        f"{out['without_m2']:.4f} (limit {ATTENTION_REL_RMS})")
    return out
