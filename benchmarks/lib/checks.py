"""Whether the program's outputs are right: the comparisons behind
`correct`, made outside the timed window."""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List

import numpy as np

from . import reference

# Kernel path against the dense gather path (copied from chip_smoke.py's
# _logits_check). Both compute in bf16 with an f32 softmax but sum in
# different orders (flash blocks against one dense softmax) and round the
# attention output to bf16 at different points, so they agree to bf16
# rounding carried through the layer stack: an RMS gap near
# 2^-8 * sqrt(layers) ~ 0.02 on logits of unit scale. A wrong page, mask
# or head mapping moves logits by about one RMS.
KERNEL_REL_RMS, KERNEL_MAX_OVER_RMS = 0.03, 0.25
# Gather path (bf16 operands, f32 accumulation) against the float32
# reference: every matmul input is rounded to 8 mantissa bits, about
# 2^-9 relative each, through ~7 matmuls a layer; over 24 layers that is
# an RMS gap of 2^-9 * sqrt(7 * 24) ~ 0.025 of the logits' RMS. Measured
# on the chip at InternLM2.5-1.8B's widths: 0.017 to 0.018, worst single
# logit 0.09 (PERF.md). The bounds are a little over twice that. One
# dropped layer of 24 changes the residual stream by ~1/sqrt(24) = 0.2
# of its RMS and a wrong kv-head mapping by ~1, so both fail; computing
# in fp8 where bf16 is stated (2^-4 a rounding) fails too.
REFERENCE_REL_RMS, REFERENCE_MAX_OVER_RMS = 0.04, 0.25


def _gap(a, b) -> Dict[str, float]:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    rms = float(np.sqrt(np.mean(a * a)))
    return {"rel_rms": float(np.sqrt(np.mean((a - b) ** 2))) / rms,
            "max_over_rms": float(np.abs(a - b).max()) / rms,
            "argmax_agree": int((a.argmax(-1) == b.argmax(-1)).sum()),
            "finite": bool(np.isfinite(a).all() and np.isfinite(b).all())}


def serve_logits(eng, model: Dict[str, Any], seed: int,
                 say: Callable[[str], None]) -> Dict[str, Any]:
    """One mixed tick and one decode tick through the forwards the
    engine's programs call (`ragged_forward`, `decode_step`), on the
    engine's own weights and pool layout but a small pool of its own:
    (a) kernel path against gather path, (b) gather path against the
    float32 reference on the same three slots' token histories. Returns
    {"ok", ...gaps}. With random weights tokens flip on rounding, so
    logits are compared."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama_infer import decode_step, ragged_forward

    cfg, ec = eng.model_cfg, eng.config
    kernel = eng._resolve_impl()
    B = ec.max_batch_size
    rng = np.random.default_rng(seed)
    per_slot = 8                                  # pages per test slot
    # tables as wide as the test needs, not max_seq: the gather decode
    # path gathers every slot's whole table width
    tables = np.zeros((B, per_slot), np.int32)
    tables[:3] = 1 + np.arange(3 * per_slot).reshape(3, per_slot)
    tables = jnp.array(tables)
    pool_shape = list(eng.k_pages.shape)
    pool_shape[1] = 1 + 3 * per_slot
    chunk = min(40, ec.max_prefill_tokens)
    history: List[List[int]] = [[], [], []]

    def pack(plan):
        total = sum(n for _, _, n in plan)
        T = eng._token_bucket(total)
        toks, slots, pos = (np.zeros(T, np.int32) for _ in range(3))
        valid = np.zeros(T, bool)
        start, last = np.zeros(B, np.int32), np.zeros(B, np.int32)
        cur = 0
        for s, st, n in plan:
            new = rng.integers(3, cfg.vocab_size, n)
            history[s].extend(int(t) for t in new)
            toks[cur:cur + n] = new
            slots[cur:cur + n] = s
            pos[cur:cur + n] = np.arange(st, st + n)
            valid[cur:cur + n] = True
            start[s], last[s] = st, cur + n - 1
            cur += n
        arrs = tuple(jnp.array(a) for a in
                     (toks, slots, pos, valid, start, last))
        return (arrs, eng._ctx_bucket(max(st for _, st, _ in plan)),
                min(T, max(ec.max_prefill_tokens, 1)))

    def ragged(impl, batch, k, v):
        (toks, slots, pos, valid, start, last), ctx, seg = batch
        fn = jax.jit(functools.partial(
            ragged_forward, cfg, ctx_pages=ctx, impl=impl, max_seg_len=seg))
        return fn(eng.params, toks, slots, pos, valid, start, last, k, v,
                  tables)

    k0 = jnp.zeros(pool_shape, eng.k_pages.dtype)
    v0 = jnp.zeros(pool_shape, eng.v_pages.dtype)
    # context: slot 0 holds 70 cached tokens, slot 1 holds 37
    _, k1, v1 = ragged("gather", pack([(0, 0, 70), (1, 0, 37)]), k0, v0)
    # mixed tick: slot 0 decodes, slot 1 continues a chunk against its
    # cached context, slot 2 starts a prompt
    mixed = pack([(0, 70, 1), (1, 37, chunk), (2, 0, min(24, chunk))])
    lg_g, k2, v2 = ragged("gather", mixed, k1, v1)
    lg_k = ragged(kernel, mixed, k1, v1)[0]
    ref = jax.jit(functools.partial(reference.logits, model))
    ref_mixed = np.stack([np.asarray(
        ref(eng.params, jnp.array(h, jnp.int32))[-1]) for h in history])
    # decode tick over the three live slots
    dec_toks = rng.integers(3, cfg.vocab_size, B)
    posn = np.zeros(B, np.int32)
    posn[:3] = [len(h) for h in history]
    for s in range(3):
        history[s].append(int(dec_toks[s]))
    active = jnp.array(np.arange(B) < 3)
    dec = lambda impl: jax.jit(functools.partial(
        decode_step, cfg, impl=impl))(
            eng.params, jnp.array(dec_toks, jnp.int32), jnp.array(posn),
            k2, v2, tables, active)[0]
    ld_g, ld_k = dec("gather"), dec(kernel)
    ref_dec = np.stack([np.asarray(
        ref(eng.params, jnp.array(h, jnp.int32))[-1]) for h in history])

    out: Dict[str, Any] = {"ok": True}
    for name, a, b, rel, worst in (
            ("kernel_vs_gather.mixed", lg_g, lg_k,
             KERNEL_REL_RMS, KERNEL_MAX_OVER_RMS),
            ("kernel_vs_gather.decode", ld_g, ld_k,
             KERNEL_REL_RMS, KERNEL_MAX_OVER_RMS),
            ("gather_vs_reference.mixed", ref_mixed, lg_g,
             REFERENCE_REL_RMS, REFERENCE_MAX_OVER_RMS),
            ("gather_vs_reference.decode", ref_dec, ld_g,
             REFERENCE_REL_RMS, REFERENCE_MAX_OVER_RMS)):
        g = _gap(np.asarray(a)[:3], np.asarray(b)[:3])
        g["ok"] = bool(g["finite"] and g["rel_rms"] <= rel
                       and g["max_over_rms"] <= worst)
        say(f"  {'ok' if g['ok'] else 'FAILED'}: {name} rms gap "
            f"{g['rel_rms']:.4f} of rms (<= {rel}), worst "
            f"{g['max_over_rms']:.4f} (<= {worst}), argmax agree "
            f"{g['argmax_agree']}/3")
        out[name] = g
        out["ok"] = out["ok"] and g["ok"]
    return out


def train_logits(cfg, mesh, model: Dict[str, Any], params, tokens,
                 say: Callable[[str], None], rows: int = 64
                 ) -> Dict[str, Any]:
    """The forward the train step differentiates (`llama.forward`: the
    program's own layer stack with the configuration's attention kernel,
    bf16 operands) against the float32 reference on one sequence
    `tokens` (S,), compared on the logits of its last `rows` positions,
    which attend to all of it. Same bounds as the serving cells' gather
    path against the reference, and for the same reason: bf16 rounding
    through the stack is ~0.01 to 0.02 of the logits' RMS, a dropped
    layer of 8 moves the residual stream by 1/sqrt(8) = 0.35 of its RMS.
    Unlike a mean loss on untrained weights, which every model of the
    right vocabulary scores alike, this binds the forward math."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        got = jax.jit(lambda p, t: llama.forward(cfg, p, t[None], mesh)
                      [0, -rows:])(params, tokens)
    want = jax.jit(lambda p, t: reference.logits(model, p, t)[-rows:])(
        params, tokens)
    g = _gap(want, got)
    g["argmax_agree"] = f"{g['argmax_agree']}/{rows}"
    g["ok"] = bool(g["finite"] and g["rel_rms"] <= REFERENCE_REL_RMS
                   and g["max_over_rms"] <= REFERENCE_MAX_OVER_RMS)
    say(f"  {'ok' if g['ok'] else 'FAILED'}: train forward against the "
        f"float32 reference, last {rows} of {tokens.shape[0]} positions: "
        f"rms gap {g['rel_rms']:.4f} of rms (<= {REFERENCE_REL_RMS}), "
        f"worst {g['max_over_rms']:.4f} (<= {REFERENCE_MAX_OVER_RMS}), "
        f"argmax agree {g['argmax_agree']}")
    return g
