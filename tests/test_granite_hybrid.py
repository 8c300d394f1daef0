"""The GraniteHybrid family (`models/granite_hybrid.py`): Mamba-2 layers
of ONE group whose state is kept a slot beside one page group of PAIRED
K/V heads, unroped attention scaled by a multiplier, a SwiGLU block in
every layer and the four muP multipliers, against the plain float32
reference the benchmark keeps (`benchmarks/lib/
reference_granite_hybrid.py`: a sequential scan over the whole history),
at a toy size on the CPU in float32."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program_granite_hybrid
from benchmarks.lib import reference_granite_hybrid as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models.family import family_of, resolve_config

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, B, T, PAGES = 4, 3, 16, 48
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# ---- the configuration --------------------------------------------------

def test_published_sizes_hold_the_issues_parameter_counts():
    whole = gh.GraniteHybridConfig()
    assert whole.n_layers == 40
    assert whole.layers_of(gh.ATTN) == (5, 15, 25, 35)
    assert whole.mixer_params(gh.MAMBA) == 25_847_232
    assert whole.mixer_params(gh.ATTN) == 10_485_760
    assert whole.num_params() == 3_191_396_096
    assert (whole.d_inner, whole.conv_dim, whole.in_width, whole.head_dim) \
        == (4096, 4352, 8512, 64)
    # 36 units, an attention layer ahead of the Mamba layers 6, 16, 26, 36
    assert len(whole.units) == 36
    assert [u for u in whole.units if u[0] is not None] == [
        (5, 6), (15, 16), (25, 26), (35, 36)]
    with pytest.raises(ValueError, match="followed by a Mamba layer"):
        gh.GraniteHybridConfig(layer_types=("mamba", "attention"))
    with pytest.raises(ValueError, match="needs an attention layer"):
        gh.GraniteHybridConfig(layer_types=("mamba", "mamba"))
    with pytest.raises(ValueError, match="a layer is"):
        gh.GraniteHybridConfig(layer_types=("mamba", "moe"))
    assert isinstance(resolve_config("granite_hybrid:tiny"),
                      gh.GraniteHybridConfig)
    # the tree is what num_params says it is, leaf for leaf
    toy = gh.config("tiny")
    assert toy.n_groups == 1 and toy.mamba_heads == 8
    shapes = jax.eval_shape(
        lambda: gh.init_params(toy, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == toy.num_params()
    assert "lm_head" not in shapes                     # tied
    dtypes = gh.storage_dtypes(toy)
    assert dtypes["embed"] == dtypes["mamba"]["mlp"]["wg"] == jnp.bfloat16
    assert dtypes["mamba"]["a_log"] == dtypes["attn"]["ln"] == jnp.float32


def test_family_describes_a_page_group_of_pairs_and_a_state_group():
    cfg = gh.GraniteHybridConfig()
    fam = family_of(cfg)
    assert fam.name == "granite_hybrid" and fam.rider_len(cfg) == 0
    # ONE Mamba-2 mixer: NemotronH's is this family's, under its old name
    from ray_tpu.models import nemotron_h, paged_common
    assert nemotron_h.mamba_mixer is paged_common.mamba2_mixer
    full, state = fam.cache_groups(cfg, "pallas")
    # 8 K/V heads of 64 as 4 rows of 128: no lane padding
    assert (full.row.heads, full.row.width, full.row.padded_width,
            full.row.layout) == (4, 128, 128, "rows")
    assert full.layers == (5, 15, 25, 35) and full.bytes_per_token == 8192
    assert state.state is not None and len(state.layers) == 36
    assert state.bytes_per_slot == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    shapes = [s for g in (full, state)
              for s, _ in g.array_shapes(12288, 16, 48)]
    assert shapes == [(4, 12288, 64, 128), (4, 12288, 64, 128),
                      (36, 48, 13056), (36, 48, 64, 64, 128)]
    cm = CostModel(cfg, 16)
    assert cm.kv_bytes_per_token == 8192
    assert cm.state_bytes_per_row == 36 * 2_123_264
    d = cm.decode_cost(1600)
    assert d["bytes_kv_read"] == 8192 * 1600 + 36 * 2_123_264
    own = cfg.serving_costs()
    assert own["weight_bytes"] == 2 * 3_191_396_096
    assert own["gemm_flops_per_token"] == (
        36 * 2 * (2048 * 8512 + 4096 * 2048)
        + 4 * 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
        + 40 * 6 * 2048 * 8192)


# ---- ticks against the reference ---------------------------------------

@pytest.fixture(scope="module")
def world():
    cfg = gh.config("tiny", **F32)
    params = gh.init_params(cfg, jax.random.PRNGKey(3))
    # norm weights and D off 1, so that each one matters
    key = jax.random.PRNGKey(7)
    for n, (kind, name) in enumerate((("mamba", "ln"), ("mamba", "norm"),
                                      ("mamba", "d_skip"), ("attn", "ln"))):
        leaf = params[kind][name]
        params[kind][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, n), leaf.shape)
    for kind in ("mamba", "attn"):
        params[kind]["mlp"]["ln"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, 9), params[kind]["mlp"]["ln"].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    model = program_granite_hybrid.published_keys(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, cfg.vocab_size, 40).astype(np.int32)
            for _ in range(3)]
    trees = gh.layer_trees(cfg, params)
    want = [np.asarray(ref.logits(model, trees, jnp.array(s)))
            for s in seqs]
    return cfg, params, model, seqs, want


def _arrays(cfg, impl):
    made = [tuple(jnp.zeros(s, d) for s, d in g.array_shapes(PAGES, PAGE, B))
            for g in gh.cache_groups(cfg, impl)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def _tables():
    """Slot s holds pages s * 12 .. s * 12 + 11."""
    return jnp.array(np.arange(B * 12, dtype=np.int32).reshape(B, 12))


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, decode):
    if decode:
        return jax.jit(functools.partial(gh.decode_step, cfg, impl=impl))
    return jax.jit(functools.partial(gh.ragged_forward, cfg, ctx_pages=-1,
                                     impl=impl))


def _run(world, ticks, impl="gather", cfg=None):
    """ticks: [[(slot, sequence, first position, tokens)]] or, a decode
    tick, {"decode": [(slot, sequence, position)]}. One set of pools,
    state and tables for the whole packing; returns the worst gap of a
    tick's rows to the reference's rows. `cfg`: the forward's, where it
    is not the world's."""
    own, params, _, seqs, want = world
    cfg = cfg or own
    kp, vp = _arrays(cfg, impl)
    tables = _tables()
    worst, rows_seen = 0.0, 0
    for rows in ticks:
        if isinstance(rows, dict):
            tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
            live = np.zeros(B, bool)
            for s, q, p in rows["decode"]:
                tok[s], pos[s], live[s] = seqs[q][p], p, True
            lg, kp, vp = _tick_fn(cfg, impl, True)(
                params, jnp.array(tok), jnp.array(pos), kp, vp, tables,
                jnp.array(live))
            at = [(s, q, p) for s, q, p in rows["decode"]]
        else:
            tok = np.zeros((4, T), np.int32)
            start, last = np.zeros(B, np.int32), np.zeros(B, np.int32)
            cur, at = 0, []
            for s, q, p0, n in rows:
                tok[0, cur:cur + n] = seqs[q][p0:p0 + n]
                tok[1, cur:cur + n], tok[3, cur:cur + n] = s, 1
                tok[2, cur:cur + n] = np.arange(p0, p0 + n)
                start[s], last[s] = p0, cur + n - 1
                at.append((s, q, p0 + n - 1))
                cur += n
            lg, kp, vp = _tick_fn(cfg, impl, False)(
                params, jnp.array(tok[0]), jnp.array(tok[1]),
                jnp.array(tok[2]), jnp.array(tok[3] != 0),
                jnp.array(start), jnp.array(last), kp, vp, tables)
        lg = np.asarray(lg)
        for s, q, p in at:
            worst = max(worst, _rel(lg[s], want[q][p]))
            rows_seen += 1
    assert rows_seen
    return worst


def _decodes(slot, q, lo, hi):
    return [{"decode": [(slot, q, p)]} for p in range(lo, hi)]


PACKINGS = {
    # a prefill in uneven chunks, then decode through the cache
    "uneven chunks then decode ticks": (
        [[(1, 0, 0, 5)], [(1, 0, 5, 1)], [(1, 0, 6, 2)], [(1, 0, 8, 3)],
         [(1, 0, 11, 16)]] + _decodes(1, 0, 27, 34)),
    "several sequences a tick": [
        [(0, 0, 0, 5), (2, 1, 0, 6), (1, 2, 0, 5)],
        [(1, 2, 5, 9), (0, 0, 5, 1), (2, 1, 6, 6)],
        [(2, 1, 12, 1), (0, 0, 6, 14)], [(1, 2, 14, 16)]],
    "decode rows beside a chunk": [
        [(0, 0, 0, 9)], [(1, 1, 0, 13)],
        [(0, 0, 9, 1), (1, 1, 13, 1), (2, 2, 0, 14)],
        [(0, 0, 10, 1), (2, 2, 14, 13), (1, 1, 14, 1)],
        {"decode": [(0, 0, 11), (1, 1, 15), (2, 2, 27)]}],
    "a slot reused after another sequence": [
        [(1, 0, 0, 16)], [(1, 0, 16, 6)], [(1, 1, 0, 7), (0, 2, 0, 9)],
        [(1, 1, 7, 9)]] + _decodes(1, 1, 16, 20),
}


@pytest.mark.parametrize("name,impl", [
    (name, "gather") for name in PACKINGS] + [
    (name, "pallas_interpret") for name in list(PACKINGS)[:1]])
def test_every_packing_gives_the_references_logits(world, name, impl):
    assert _run(world, PACKINGS[name], impl) < 2e-5


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_matters(world, name):
    """Take one multiplier away (1 in its place; the attention's: the
    plain 1/sqrt(d)) in the FORWARD and the reference's logits are no
    longer met; and the reference's own variant without it is not the
    reference."""
    cfg, params, model, seqs, want = world
    gone = dataclasses.replace(cfg, **{name: (
        cfg.head_dim ** -0.5 if name == "attention_multiplier" else 1.0)})
    packing = PACKINGS["several sequences a tick"]
    assert _run(world, packing, cfg=gone) > 1e-2
    variant = "no_" + name
    got = np.asarray(ref.logits(model, gh.layer_trees(cfg, params),
                                jnp.array(seqs[0]), variant=(variant,)))
    assert _rel(got, want[0]) > 1e-2, variant


def test_wrong_in_one_way_is_not_the_reference(world):
    """Each variant the chip's probe reads moves the logits: the
    comparison can see it."""
    cfg, params, model, seqs, want = world
    trees = gh.layer_trees(cfg, params)
    # the multipliers: `test_each_multiplier_matters`
    for v in ("state_bf16", "delta_bf16", "state_reset", "conv_reset",
              "norm_before_gate", "rotary"):
        got = np.asarray(ref.logits(model, trees, jnp.array(seqs[0]),
                                    variant=(v,), chunk=16))
        assert _rel(got, want[0]) > (1e-4 if v.endswith("bf16") else 1e-3), v
    with pytest.raises(ValueError, match="no variant"):
        ref.how(variant=("state_fp4",))


def test_paired_heads_attend_as_the_plain_heads_do():
    """`wide_queries` / `own_half`: queries against PAIRS of K/V heads,
    each with zeros in the other head's half, give plain GQA scaled by
    the multiplier."""
    cfg = gh.config("tiny", **F32)
    rng = np.random.default_rng(2)
    t, d = 6, cfg.head_dim
    q = jnp.array(rng.standard_normal((t, cfg.n_heads, d)), jnp.float32)
    k = jnp.array(rng.standard_normal((t, cfg.n_kv_heads, d)), jnp.float32)
    v = jnp.array(rng.standard_normal((t, cfg.n_kv_heads, d)), jnp.float32)
    per = cfg.n_heads // cfg.n_kv_heads
    causal = np.tril(np.ones((t, t), bool))
    want = np.zeros((t, cfg.n_heads, d), np.float32)
    for h in range(cfg.n_heads):
        sc = np.asarray(q[:, h] @ k[:, h // per].T) * cfg.attention_multiplier
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        want[:, h] = np.asarray(pr @ v[:, h // per])
    wide = gh.wide_queries(cfg, q)
    rows = (t, cfg.n_kv_heads // gh.PAIR, gh.PAIR * d)
    kk, vv = k.reshape(rows), v.reshape(rows)
    got = np.zeros((t, cfg.n_heads, gh.PAIR * d), np.float32)
    for h in range(cfg.n_heads):
        row = h // (per * gh.PAIR)
        sc = np.asarray(wide[:, h] @ kk[:, row].T) / np.sqrt(gh.PAIR * d)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        got[:, h] = np.asarray(pr @ vv[:, row])
    np.testing.assert_allclose(np.asarray(gh.own_half(cfg, jnp.array(got))),
                               want, rtol=1e-5, atol=1e-5)


def test_a_ticks_program_holds_each_mixers_body_once(world):
    cfg, params, *_ = world
    kp, vp = _arrays(cfg, "pallas_interpret")
    i32 = lambda n: jnp.zeros((n,), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        gh.ragged_forward, cfg, ctx_pages=-1, impl="pallas_interpret"))(
        params, i32(T), i32(T), i32(T), jnp.ones((T,), bool), i32(B),
        i32(B), kp, vp, _tables())
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [len(cfg.units)] == [6]

    def calls(jp, under_cond=False):
        n = []
        for e in jp.eqns:
            if e.primitive.name == "pallas_call":
                n.append(under_cond)
            for sub in jax.core.jaxprs_in_params(e.params):
                n += calls(sub, under_cond or e.primitive.name == "cond")
        return n
    # the scan kernel in the units' body, the attention kernel under the
    # cond: the state never passes through a branch
    assert sorted(calls(jaxpr.jaxpr)) == [False, True]
    cond = [e for e in scans[0].params["jaxpr"].jaxpr.eqns
            if e.primitive.name == "cond"]
    assert len(cond) == 1
    assert all(v.aval.shape != vp[1].shape for v in cond[0].outvars)


# ---- the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = gh.config("tiny", **F32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, num_pages=64, max_batch_size=2, page_size=PAGE,
        max_seq_len=64, max_prefill_tokens=8, max_num_batched_tokens=8,
        seed=5))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 255, n).tolist() for n in (5, 19, 13)]
    # what each tick's dispatch span carried
    eng.dispatched = []
    phase = eng._phase

    def recording(name, **args):
        if name == "dispatch":
            eng.dispatched.append(args)
        return phase(name, **args)
    eng._phase = recording
    return cfg, eng, eng.generate(prompts, SamplingParams(max_tokens=8))


def test_engine_greedy_tokens_are_the_references(served):
    """Prefill then decode through the ENGINE in float32: admission by
    pages and by slots, chunked prefill over several ticks, decode
    ticks, the state donated from tick to tick, two sequences
    interleaved, three requests through two slots (a slot reused, from
    zero state, with no zeroing program): every token is the
    reference's largest logit given the tokens before it."""
    cfg, eng, outs = served
    model = program_granite_hybrid.published_keys(cfg)
    trees = gh.layer_trees(cfg, eng.params)
    for req in outs:
        seq = np.asarray(req.prompt_tokens + req.output_tokens, np.int32)
        # the reference is causal: padded to one length for all requests
        # (eagerly, each primitive compiles again at every new length)
        seq = np.pad(seq, (0, -len(seq) % 32))
        lg = np.asarray(ref.logits(model, trees, jnp.array(seq)))
        n = len(req.prompt_tokens)
        assert len(req.output_tokens) == 8
        for i, tok in enumerate(req.output_tokens):
            row = lg[n + i - 1]
            assert row[tok] >= np.sort(row)[-1] - 1e-4, (n, i)


def test_stats_and_spans_show_the_state_group(served):
    cfg, eng, _ = served
    st = eng.stats()
    full, state = st["cache_groups"]
    assert (full["name"], full["layers"]) == ("full", [2, 6])
    assert (state["kind"], state["layers"]) == ("state", [0, 1, 3, 4, 5, 7])
    assert (state["slots_total"], state["slots_held"]) == (2, 0)
    assert state["slots_peak"] == 2
    assert st["prefix_cache"].startswith("off: a resume")
    assert st["weights"]["bytes"] == 4 * cfg.num_params()
    assert eng.dispatched
    for args in eng.dispatched:
        assert args["ssm_rows"] >= 1
        assert args["ssm_tokens"] >= args["ssm_rows"]
    assert gh.span_counts(None, [(0, 7), (12, 1), (3, 1)], None) == {
        "ssm_tokens": 9, "ssm_rows": 3}


@pytest.mark.parametrize("kw,what", [
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"enable_kv_offload": True}, "enable_kv_offload"),
    ({"mesh_shape": (1, 2)}, "mesh_shape"),
    ({"mesh": {"tp": 2}}, "mesh"),
    ({"checkpoint": "/nowhere"}, "checkpoint"),
])
def test_pairings_nobody_built_are_refused_with_the_reason(kw, what):
    with pytest.raises(ValueError) as e:
        InferenceEngine(EngineConfig(model="granite_hybrid:tiny", **kw))
    assert gh.GRANITE_HYBRID_REFUSES[what] in str(e.value)


def test_entry_points_nobody_built_are_refused(served):
    _, eng, _ = served
    with pytest.raises(ValueError, match="does not compose with lora"):
        eng.register_loras({"a": {}})
    with pytest.raises(ValueError, match="session_shipping"):
        eng.export_prefix([1, 2, 3])
    assert set(gh.GRANITE_HYBRID_REFUSES) == {
        "prefix_cache", "lora", "kv_dtype", "enable_kv_offload", "mesh",
        "mesh_shape", "checkpoint", "session_shipping"}
    with pytest.raises(ValueError, match="take no lora"):
        gh.ragged_forward(eng.model_cfg, eng.params, *[None] * 9, lora={})
