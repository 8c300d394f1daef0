"""The Kimi Linear family (`models/kimi_linear.py`): KDA layers (a delta
rule with a decay a channel) whose state is kept a slot beside ONE
one-pool latent page group in one cache manager
(`kv_cache.CacheManager`), unroped latent attention over the layers of
that group alone, a dense first layer and SwiGLU held experts through
the grouped layout with a `base`, against the plain float32 reference
the benchmark keeps (`benchmarks/lib/reference_kimi_linear.py`: the
recurrence a token at a time over the whole history, the unabsorbed
latent attention, every held expert on every token), at a toy size on
the CPU in float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program_kimi_linear, reference_kimi_linear as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.llm._internal.kv_cache import CacheManager
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import kimi_linear as kl
from ray_tpu.models.cache_row import CacheGroup, CacheRow, StateRow
from ray_tpu.models.family import DEEPSEEK_REFUSES, family_of, resolve_config

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, B, T, PAGES = 4, 3, 16, 48
CUT = dict(experts_held=(0, 16), vocab_size=20480)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# ---- the configuration --------------------------------------------------

def test_published_sizes_hold_the_issues_parameter_counts():
    whole = kl.KimiLinearConfig()
    assert whole.n_layers == 27
    assert [len(whole.layers_of(k)) for k in "KM"] == [20, 7]
    assert "".join(whole.kinds) == "KKKM" * 6 + "KKM"
    assert whole.mixer_params("K") == 39_514_272
    assert whole.mixer_params("M") == 29_114_880
    assert whole.num_params() == 49_122_681_728
    cut = kl.KimiLinearConfig(**CUT)
    assert cut.num_params() == 4_296_057_728
    assert cut.units == ((0, 3), (4, 3), (8, 3), (12, 3), (16, 3), (20, 3),
                         (24, 2))
    assert (cut.kda_width, cut.latent_width, cut.qk_head_dim) == (
        4096, 576, 192)
    with pytest.raises(ValueError, match="ends on an MLA layer"):
        kl.KimiLinearConfig(kda_layers=(2, 3), full_attn_layers=(1,))
    with pytest.raises(ValueError, match="each once"):
        kl.KimiLinearConfig(kda_layers=(1, 2), full_attn_layers=(2, 3))
    with pytest.raises(ValueError, match="not a range"):
        kl.KimiLinearConfig(experts_held=(250, 300))
    assert isinstance(resolve_config("kimi_linear:tiny"),
                      kl.KimiLinearConfig)
    # the tree is what num_params says it is, leaf for leaf
    toy = kl.config("tiny")
    shapes = jax.eval_shape(
        lambda: kl.init_params(toy, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == toy.num_params()
    assert shapes["experts"]["wg"].shape == (4 * 4, 64, 32)
    assert shapes["experts"]["wd"].shape == (4 * 4, 32, 64)
    dtypes = kl.storage_dtypes(toy)
    assert dtypes["embed"] == dtypes["experts"]["wg"] == jnp.bfloat16
    assert dtypes["kda"]["a_log"] == dtypes["moe"]["router_bias"] \
        == dtypes["kda"]["dt_bias"] == jnp.float32


def test_family_describes_a_latent_group_and_a_state_group():
    cfg = kl.KimiLinearConfig(**CUT)
    fam = family_of(cfg)
    assert fam.name == "kimi_linear"
    assert fam.rider_len(cfg) == 26 * 16
    latent, state = fam.cache_groups(cfg, "pallas")
    assert (latent.name, latent.window) == ("latent", None)
    assert latent.layers == (3, 7, 11, 15, 19, 23, 26)
    # ONE pool of one head: [c | k_pe], 576 values in 640 lanes
    assert (latent.row.kind, latent.row.pools, latent.row.heads) == (
        "latent", 1, 1)
    assert (latent.row.width, latent.row.padded_width) == (576, 640)
    assert latent.bytes_per_token == 7 * 640 * 2 == 8960
    assert fam.cache_groups(cfg, "gather")[0].row.padded_width == 576
    assert (state.kind, len(state.layers)) == ("state", 20)
    assert state.state.kind == "kda"
    assert state.state.bytes_per_slot_layer == (
        32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2) == 2_170_880
    assert state.bytes_per_slot == 43_417_600
    assert state.array_shapes(0, 16, 48) == (
        ((20, 48, 3 * 3 * 4096), jnp.bfloat16),
        ((20, 48, 32, 128, 128), jnp.float32))
    assert len(latent.array_shapes(100, 16, 48)) == 1
    with pytest.raises(ValueError, match="quantized"):
        fam.cache_groups(cfg, "pallas", "int8")
    # the latent family's reasons and the state group's, reworded
    assert set(kl.KIMI_LINEAR_REFUSES) == set(DEEPSEEK_REFUSES) | {
        "prefix_cache"}


# ---- ticks against the reference ---------------------------------------

# float32 on both sides: the same sums in another order (the absorbed
# form against the plain one, the chunked solve against the recurrence,
# the grouped product against a loop over the experts). Rows read 2e-7
# to 2e-6; a dropped decay or beta reads 1e-2 and more
# (`test_wrong_in_one_way_is_not_the_reference`)
LOGITS_REL_RMS = 2e-5


@pytest.fixture(scope="module")
def world():
    cfg = kl.config("tiny", **F32)
    params = kl.init_params(cfg, jax.random.PRNGKey(3))
    # norm weights off 1, so that each one matters
    key = jax.random.PRNGKey(7)
    for n, (kind, name) in enumerate((("kda", "ln"), ("kda", "norm"),
                                      ("mla", "ln"), ("mla", "kv_norm"),
                                      ("dense", "ln"), ("moe", "ln"))):
        leaf = params[kind][name]
        params[kind][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, n), leaf.shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    model = program_kimi_linear.published_keys(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, cfg.vocab_size, 40).astype(np.int32)
            for _ in range(3)]
    trees = kl.layer_trees(cfg, params)
    want = [np.asarray(ref.logits(model, trees, jnp.array(s), cfg.held))
            for s in seqs]
    return cfg, params, model, seqs, want


def _arrays(cfg, impl):
    made = [tuple(jnp.zeros(s, d) for s, d in g.array_shapes(PAGES, PAGE, B))
            for g in kl.cache_groups(cfg, impl)]
    return (tuple(m[0] for m in made),
            tuple(m[1] if len(m) > 1 else None for m in made))


def _tables():
    """Slot s holds pages s * 12 .. s * 12 + 11."""
    return jnp.array(np.arange(B * 12, dtype=np.int32).reshape(B, 12))


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, decode):
    if decode:
        return jax.jit(functools.partial(kl.decode_step, cfg, impl=impl))
    return jax.jit(functools.partial(kl.ragged_forward, cfg, ctx_pages=-1,
                                     impl=impl))


def _run(world, ticks, impl="gather"):
    """ticks: [[(slot, sequence, first position, tokens)]] or, a decode
    tick, {"decode": [(slot, sequence, position)]}. One set of pools,
    state and tables for the whole packing; returns the worst gap of a
    tick's rows to the reference's rows."""
    cfg, params, _, seqs, want = world
    kp, vp = _arrays(cfg, impl)
    tables = _tables()
    worst, rows_seen = 0.0, 0
    for rows in ticks:
        if isinstance(rows, dict):
            tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
            live = np.zeros(B, bool)
            for s, q, p in rows["decode"]:
                tok[s], pos[s], live[s] = seqs[q][p], p, True
            lg, kp, vp, _ = _tick_fn(cfg, impl, True)(
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
            lg, kp, vp, _ = _tick_fn(cfg, impl, False)(
                params, jnp.array(tok[0]), jnp.array(tok[1]),
                jnp.array(tok[2]), jnp.array(tok[3] != 0),
                jnp.array(start), jnp.array(last), kp, vp, tables)
        assert vp[0] is None                   # a latent group: one pool
        lg = np.asarray(lg)
        for s, q, p in at:
            worst = max(worst, _rel(lg[s], want[q][p]))
            rows_seen += 1
    assert rows_seen
    return worst


def _decodes(slot, q, lo, hi):
    return [{"decode": [(slot, q, p)]} for p in range(lo, hi)]


PACKINGS = {
    "whole chunks": [[(1, 0, 0, 16)], [(1, 0, 16, 16)], [(1, 0, 32, 8)]],
    "a boundary inside the conv's taps": [
        [(1, 0, 0, 5)], [(1, 0, 5, 1)], [(1, 0, 6, 2)], [(1, 0, 8, 3)],
        [(1, 0, 11, 16)], [(1, 0, 27, 13)]],
    "several sequences a tick": [
        [(0, 0, 0, 5), (2, 1, 0, 6), (1, 2, 0, 5)],
        [(1, 2, 5, 9), (0, 0, 5, 1), (2, 1, 6, 6)],
        [(2, 1, 12, 1), (0, 0, 6, 14)], [(1, 2, 14, 16)]],
    "prefill then decode ticks": [[(1, 0, 0, 11)]] + _decodes(1, 0, 11, 24),
    "three rows decode, interleaved": (
        [[(0, 0, 0, 12)], [(1, 1, 0, 9)], [(2, 2, 0, 3)]]
        + [{"decode": [(0, 0, 12 + i), (1, 1, 9 + i), (2, 2, 3 + i)]}
           for i in range(8)]),
    "decode rows beside a chunk": [
        [(0, 0, 0, 9)], [(1, 1, 0, 13)],
        [(0, 0, 9, 1), (1, 1, 13, 1), (2, 2, 0, 14)],
        [(0, 0, 10, 1), (2, 2, 14, 13), (1, 1, 14, 1)]],
    "a slot reused after another sequence": [
        [(1, 0, 0, 16)], [(1, 0, 16, 6)], [(1, 1, 0, 7), (0, 2, 0, 9)],
        [(1, 1, 7, 9)]] + _decodes(1, 1, 16, 20),
    "a row that sits a tick out": [
        [(0, 0, 0, 8), (1, 1, 0, 8)], [(1, 1, 8, 16)], [(0, 0, 8, 8)],
        {"decode": [(0, 0, 16), (1, 1, 24)]}],
    "one-token chunks": [[(2, 0, p, 1)] for p in range(10)],
}


@pytest.mark.parametrize("name", list(PACKINGS))
def test_every_packing_gives_the_references_logits(world, name):
    assert _run(world, PACKINGS[name]) < LOGITS_REL_RMS


@pytest.mark.parametrize("name", ["decode rows beside a chunk",
                                  "a slot reused after another sequence",
                                  "a boundary inside the conv's taps"])
def test_kernel_path_gives_the_references_logits(world, name):
    assert _run(world, PACKINGS[name], "pallas_interpret") < LOGITS_REL_RMS


def test_wrong_in_one_way_is_not_the_reference(world):
    """Each variant the chip's probe reads moves the logits: the
    comparison can see it."""
    from benchmarks.lib.checks_kimi_linear import VARIANTS
    cfg, params, model, seqs, want = world
    trees = kl.layer_trees(cfg, params)
    for v in VARIANTS:
        got = np.asarray(ref.logits(model, trees, jnp.array(seqs[0]),
                                    cfg.held, variant=(v,), chunk=16))
        assert _rel(got, want[0]) > 1e-3, v


def test_a_ticks_program_holds_each_mixers_body_once(world):
    cfg, params, *_ = world
    kp, vp = _arrays(cfg, "pallas_interpret")
    i32 = lambda n: jnp.zeros((n,), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        kl.ragged_forward, cfg, ctx_pages=-1, impl="pallas_interpret"))(
        params, i32(T), i32(T), i32(T), jnp.ones((T,), bool), i32(B),
        i32(B), kp, vp, _tables())
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [len(cfg.units)] == [2]

    def calls(jp, name):
        n = 0
        for e in jp.eqns:
            n += (e.primitive.name == "pallas_call"
                  and name in str(e.params.get("name", e.params.get(
                      "name_and_src_info", ""))))
            for sub in jax.core.jaxprs_in_params(e.params):
                n += calls(sub, name)
        return n
    # each mixer's kernel once; the experts' pair twice (behind a KDA
    # layer and behind an MLA layer)
    assert calls(jaxpr.jaxpr, "kda_ragged_scan") == 1
    assert calls(jaxpr.jaxpr, "mla_ragged_attention") == 1
    assert calls(jaxpr.jaxpr, "moe_grouped_up") == 2


# ---- the share ----------------------------------------------------------

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model (the guide's section
    4): the parts that all 16 shares of an expert layer give, each the
    held experts' part and the shared expert, with the shared expert
    counted once, add up to the uncut reference's layer."""
    cfg = kl.config("tiny", n_routed_experts=32, moe_top_k=6, **F32)
    whole = kl.config(cfg, experts_held=(0, 32))
    layer = kl.init_params(whole, jax.random.PRNGKey(11))
    tree = {k: v[0] for k, v in layer["moe"].items()}
    ex = {k: v[:32] for k, v in layer["experts"].items()}
    model = program_kimi_linear.published_keys(whole)
    u = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.hidden),
                          jnp.float32)
    with ref.computing():
        want = np.asarray(ref.experts(model, {**tree, **ex}, u, (0, 32)))
        shared = np.asarray(ref.shared_expert(tree, u))
    total = -15.0 * shared                # counted once, not 16 times
    landed = 0
    for lo in range(0, 32, 2):
        share = kl.config(cfg, experts_held=(lo, lo + 2))
        out, counts = kl.moe_block(
            share, {**tree, **{k: v[lo:lo + 2] for k, v in ex.items()}}, u,
            impl="gather")
        total = total + np.asarray(out)
        landed += int(counts.sum())
    assert landed == 40 * 6               # every pick landed on one share
    assert _rel(total, want) < 2e-6


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_a_layers_experts_are_taken_out_of_the_stack_by_base(impl):
    """`moe_block` with the stack and a (traced) base is the block on
    that layer's own experts."""
    cfg = kl.config("tiny", **F32)
    params = kl.init_params(cfg, jax.random.PRNGKey(2))
    u = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.hidden),
                          jnp.float32)
    tree = {k: v[2] for k, v in params["moe"].items()}
    held = cfg.n_held
    own = {k: v[2 * held:3 * held] for k, v in params["experts"].items()}
    want, _ = kl.moe_block(cfg, {**tree, **own}, u, impl="gather")
    got, _ = jax.jit(lambda b: kl.moe_block(
        cfg, tree, u, impl=impl, experts=params["experts"], base=b))(
        jnp.int32(2 * held))
    assert _rel(got, want) < 2e-6


# ---- the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = kl.config("tiny", **F32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, num_pages=64, max_batch_size=2, page_size=PAGE,
        max_seq_len=64, max_prefill_tokens=8, max_num_batched_tokens=8,
        seed=5))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 255, n).tolist() for n in (5, 19, 13, 7)]
    # what each tick's dispatch span carried
    eng.dispatched = []
    phase = eng._phase

    def recording(name, **args):
        if name == "dispatch":
            eng.dispatched.append(args)
        return phase(name, **args)
    eng._phase = recording
    return cfg, eng, eng.generate(prompts, SamplingParams(max_tokens=10))


def test_engine_greedy_tokens_are_the_references(served):
    """Prefill then decode through the ENGINE in float32: admission by
    pages and by slots, chunked prefill over several ticks, decode
    ticks, the latent pool and the state donated from tick to tick, two
    sequences interleaved, four requests through two slots (each slot
    reused, from zero state, with no zeroing program): every token is
    the reference's largest logit given the tokens before it."""
    cfg, eng, outs = served
    assert eng.v_pages[0] is None and eng.k_pages[0].ndim == 5
    model = program_kimi_linear.published_keys(cfg)
    trees = kl.layer_trees(cfg, eng.params)
    for req in outs:
        seq = np.asarray(req.prompt_tokens + req.output_tokens, np.int32)
        # the reference is causal: padded to one length for all requests
        # (eagerly, each primitive compiles again at every new length)
        seq = np.pad(seq, (0, -len(seq) % 32))
        lg = np.asarray(ref.logits(model, trees, jnp.array(seq), cfg.held))
        n = len(req.prompt_tokens)
        assert len(req.output_tokens) == 10
        for i, tok in enumerate(req.output_tokens):
            row = lg[n + i - 1]
            assert row[tok] >= np.sort(row)[-1] - 1e-4, (n, i)


def test_stats_list_the_groups_and_the_routing(served):
    cfg, eng, _ = served
    st = eng.stats()
    latent, state = st["cache_groups"]
    assert (latent["name"], latent["layers"]) == ("latent", [2, 4])
    assert latent["pages_total"] == 63 and latent["pages_used"] == 0
    assert latent["pages_peak"] > 0
    assert (state["kind"], state["layers"]) == ("state", [0, 1, 3])
    assert (state["slots_total"], state["slots_held"]) == (2, 0)
    assert state["slots_peak"] == 2
    assert state["bytes_per_slot"] == 3 * (3 * 3 * 32 * 4 + 2 * 16 * 16 * 4)
    assert st["prefix_cache"].startswith("off: a resume")
    routed = st["moe"]
    assert routed["experts_held"] == [0, 4] and routed["expert_layers"] == 4
    assert routed["assignments_landed"] > 0
    assert np.asarray(routed["landed"]).shape == (4, 4)
    # the weights are as the forwards use them
    assert st["weights"]["bytes"] == 4 * cfg.num_params()


def test_engines_dispatch_spans_carry_the_counts(served):
    _, eng, _ = served
    assert eng.dispatched
    for args in eng.dispatched:
        assert args["ssm_rows"] >= 1
        assert args["ssm_tokens"] >= args["ssm_rows"]
    ragged = [a for a in eng.dispatched if a.get("kind") != "decode"]
    assert any(a["ssm_tokens"] > a["ssm_rows"] for a in ragged)
    # the latent family's counts ride beside them
    assert all("kv_tokens" in a for a in eng.dispatched)
    assert kl.span_counts(None, [(0, 7), (12, 1), (3, 1)], None) == {
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
        InferenceEngine(EngineConfig(model="kimi_linear:tiny", **kw))
    assert kl.KIMI_LINEAR_REFUSES[what] in str(e.value)


def test_entry_points_nobody_built_are_refused(served):
    _, eng, _ = served
    with pytest.raises(ValueError, match="does not compose with lora"):
        eng.register_loras({"a": {}})
    with pytest.raises(ValueError, match="session_shipping"):
        eng.export_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="take no lora"):
        kl.ragged_forward(eng.model_cfg, eng.params, *[None] * 9, lora={})


# ---- the cache manager --------------------------------------------------

def test_a_one_pool_latent_group_and_a_state_group_admit_by_both():
    """The layout no family had asked of `CacheManager`: ONE page group
    of ONE pool and a state group. Admission by slots and by pages,
    vacate frees both, the prefix cache matches nothing and says why."""
    row = CacheRow("latent", 1, 1, 24, 24, jnp.float32, value_width=16)
    state = StateRow("kda", (("conv", (18,), jnp.float32),
                             ("kda", (2, 4, 4), jnp.float32)))
    latent = CacheGroup("latent", row, (2, 4))
    held = CacheGroup("state", None, (0, 1, 3), state=state)
    assert len(latent.array_shapes(64, 4, 2)) == 1
    m = CacheManager([latent, held], [64, 0], 4, 2, 16, tick_tokens=4)
    assert len(m.groups) == 1 and len(m.tables) == 1 and len(m.states) == 1
    assert not m.windowed and m.prefix_cache.startswith("off: a resume")
    pages = m.admit(0, 40)
    per_slot = 3 * (18 + 32) * 4
    assert held.bytes_per_slot == per_slot
    assert m.bytes_used() == 10 * 4 * latent.bytes_per_token + per_slot
    # by pages: 63 usable, 10 held; 54 more are too many, a slot is free
    assert not m.can_admit(54 * 4) and m.can_admit(53 * 4)
    m.admit(1, 8)
    assert m.groups[0].allocator.free_pages > 40   # pages are there...
    assert not m.can_admit(8)                      # ...the slots are not
    latent_st, state_st = m.stats()["cache_groups"]
    assert latent_st["pages_used"] == 12
    assert (state_st["slots_held"], state_st["slots_peak"],
            state_st["slots_total"]) == (2, 2, 2)
    m.first.free(pages)
    m.vacate(0)
    assert m.can_admit(8)
    assert m.stats()["cache_groups"][1]["slots_held"] == 1
    assert m.stats()["cache_groups"][0]["pages_used"] == 2


def test_cost_model_prices_the_latent_rows_and_the_state():
    cfg = kl.KimiLinearConfig(**CUT)
    # as written (576 lanes a row), and as a kernel pool pads it (640)
    assert CostModel(cfg, 16).kv_bytes_per_token == 7 * 576 * 2
    cm = CostModel(cfg, 16,
                   cache_groups=family_of(cfg).cache_groups(cfg, "pallas"))
    assert cm.kv_bytes_per_token == 8960
    assert cm.state_bytes_per_row == 43_417_600
    d = cm.decode_cost(2000)
    assert d["bytes_kv_read"] == 8960 * 2000 + 43_417_600
    assert d["bytes_kv_write"] == 8960 + 43_417_600
    own = cfg.serving_costs()
    h = 2304
    # an expert layer: the router, the shared expert, and 8 x 16 / 256 =
    # half a routed expert of three matrices a token
    expert_layer = 2 * h * 256 + 1.5 * 3 * 2 * h * 1024
    kda = 2 * (4 * h * 4096 + 2 * (h * 128 + 128 * 4096) + h * 32) \
        + 6 * 32 * 128 * 128
    mla = 2 * (h * 32 * 192 + h * 576 + 32 * 128 * 512 * 2 + 4096 * h)
    assert own["gemm_flops_per_token"] == (
        26 * expert_layer + 20 * kda + 7 * mla + 3 * 2 * h * 9216)
    assert own["weight_bytes"] == 2 * 4_296_057_728
    assert d["flops_gemm"] == (own["gemm_flops_per_token"]
                               + own["head_flops"])


def test_latent_items_take_more_tokens_with_fewer_heads():
    """The latent kernel's item is 1,024 query rows: 8 tokens at the 128
    heads it was sized for (the latent family's programs are what they
    were), 32 at this family's 32, and never more than 32 tokens; the
    family's host-side count is the kernel's."""
    from ray_tpu.ops import mla_attention as mla
    assert [mla.mla_q_block(512, h) for h in (128, 64, 32, 4)] == [
        8, 16, 32, 32]
    assert mla.mla_q_block(512) == 8 and mla.mla_q_block(4, 32) == 4
    segs = [(1000, 100), (5000, 1)]
    items, blocks = kl.work_counts(segs, 128, 16, 1600, None)
    assert (items, blocks) == mla.mla_work_counts(segs, 128, 16, 1600,
                                                  heads=32)
    assert items == 4 + 1            # 100 tokens in 32s, and a decode row
    assert mla.mla_work_counts(segs, 128, 16, 1600)[0] == 13 + 1
    with pytest.raises(ValueError, match="MLA heads"):
        kl.KimiLinearConfig(n_heads=64)
