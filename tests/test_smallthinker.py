"""The SmallThinker family (`models/smallthinker.py`): a router that
reads the layer's input ahead of attention, gated-ReLU held experts
through the grouped layout out of one stack of every layer's experts, 7
query heads a K/V head on window and full layers over two merged-rows
page groups in one cache manager (`kv_cache.CacheManager`), against the
plain float32 reference the benchmark keeps
(`benchmarks/lib/reference_smallthinker.py`), at a toy size on the CPU in
float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program_smallthinker
from benchmarks.lib import reference_smallthinker as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.llm._internal.kv_cache import CacheManager
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import smallthinker as st
from ray_tpu.models.family import family_of, resolve_config
from ray_tpu.ops import moe

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, B, T, PAGES = 4, 3, 16, 48


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# ---- the configuration --------------------------------------------------

def test_published_sizes_hold_the_issues_parameter_counts():
    whole = st.SmallThinkerConfig()
    assert whole.n_layers == 52 and whole.n_moe_layers == 52
    assert len(whole.layers_of(st.FULL)) == 13
    assert len(whole.layers_of(st.SLIDING)) == 39
    assert whole.windowed == whole.roped == (0, 1, 1, 1) * 13
    per = whole.layer_params()
    # by hand: attention 20,971,520; router 163,840; two norms 5,120;
    # one expert 3 x 2560 x 768 = 5,898,240, 64 of them 377,487,360
    assert per["wq"] + per["wk"] + per["wv"] + per["wo"] == (
        2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560) == 20_971_520
    assert per["router"] == 2560 * 64 == 163_840
    assert per["ln_in"] + per["ln_post"] == 5_120
    assert per["experts.wg"] == per["experts.wi"] == per["experts.wd"] \
        == 64 * 2560 * 768
    assert 3 * per["experts.wg"] == 377_487_360
    assert sum(per.values()) == 398_627_840
    assert 2 * 151_936 * 2560 == 777_912_320
    assert whole.num_params() == 21_506_562_560
    cut = st.SmallThinkerConfig(n_layers=12)
    assert cut.num_params() == 5_561_448_960
    assert cut.kinds == (st.FULL, st.SLIDING, st.SLIDING, st.SLIDING) * 3
    assert [cut.group_index(l) for l in range(5)] == [0, 0, 1, 2, 1]
    assert cut.held == (0, 64) and cut.n_held == 64
    assert st.kernel_group(cut) == 8         # 7 query heads go in as 8
    with pytest.raises(ValueError, match="not a range"):
        st.SmallThinkerConfig(experts_held=(60, 70))
    with pytest.raises(ValueError, match="sliding_window_layout"):
        st.SmallThinkerConfig(n_layers=4, sliding_window_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="rope_layout"):
        st.SmallThinkerConfig(n_layers=4, rope_layout=(0, 1, 2, 1))
    with pytest.raises(ValueError, match="needs a full-attention"):
        st.SmallThinkerConfig(n_layers=2, sliding_window_layout=(1, 1))
    with pytest.raises(ValueError, match="moe_top_k"):
        st.SmallThinkerConfig(moe_top_k=65)
    assert isinstance(resolve_config("smallthinker:tiny"),
                      st.SmallThinkerConfig)
    # the tree is what num_params says it is, leaf for leaf
    toy = st.config("tiny")
    shapes = jax.eval_shape(
        lambda: st.init_params(toy, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == toy.num_params()
    assert shapes["experts"]["wg"].shape == (8 * 8, 64, 32)
    assert shapes["experts"]["wd"].shape == (8 * 8, 32, 64)
    assert shapes["experts"]["wg"].dtype == shapes["embed"].dtype \
        == jnp.bfloat16
    assert shapes["layers"][0]["ln_in"].dtype == jnp.float32
    got = {k: int(np.prod(shapes["layers"][0][k].shape))
           for k in ("wq", "wk", "wv", "wo", "router", "ln_in", "ln_post")}
    assert got == {k: v for k, v in toy.layer_params().items() if k in got}


def test_family_describes_two_merged_rows_groups():
    cfg = st.SmallThinkerConfig(n_layers=12)
    fam = family_of(cfg)
    assert fam.name == "smallthinker"
    full, win = fam.cache_groups(cfg, "pallas")
    assert (full.name, full.layers, full.window) == ("full", (0, 4, 8), None)
    assert (win.name, win.window) == ("window", 4096)
    assert win.layers == (1, 2, 3, 5, 6, 7, 9, 10, 11)
    assert full.row == win.row and full.row.layout == "rows"
    # 2 pools x 4 heads x 128 x 2 B
    assert full.row.bytes_per_token_layer == 2048
    assert full.row.pool_shape(3, 10240, 16) == (3, 10240, 64, 128)
    assert fam.cache_row(cfg, "pallas") == full.row
    assert fam.rider_len(cfg) == 12 * 64
    assert fam.refuses is st.SMALLTHINKER_REFUSES
    assert fam.span_counts(cfg, [(5000, 1), (100, 12)], [True, False]) == {
        "win_kv_tokens": 4096 + 112, "win_attn_pairs": 4096 + (
            12 * 100 + 12 * 13 // 2), "win_decode_pairs": 4096}
    with pytest.raises(ValueError, match="int8/fp8"):
        fam.cache_groups(cfg, "pallas", "int8")
    # the engine's pools are the groups' own
    groups = fam.cache_groups(cfg, "gather")
    assert [len(g.layers) for g in groups] == [3, 9]


# ---- ticks against the reference ---------------------------------------

@pytest.fixture(scope="module")
def world():
    cfg = st.config("tiny", **F32)
    params = st.init_params(cfg, jax.random.PRNGKey(3))
    # norm weights off 1, so that each one matters
    key = jax.random.PRNGKey(7)
    for li, layer in enumerate(params["layers"]):
        for n, name in enumerate(("ln_in", "ln_post")):
            layer[name] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 2 * li + n), layer[name].shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    model = program_smallthinker.published_keys(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, cfg.vocab_size, 40).astype(np.int32)
            for _ in range(3)]
    want = [np.asarray(ref.logits(model, params, jnp.array(s), cfg.held))
            for s in seqs]
    return cfg, params, model, seqs, want


def _pools(cfg, impl):
    made = [tuple(jnp.zeros(s, d) for s, d in g.array_shapes(PAGES, PAGE, B))
            for g in st.cache_groups(cfg, impl)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def _tables():
    """Slot s holds pages s * 12 .. s * 12 + 11, in both groups."""
    one = np.arange(B * 12, dtype=np.int32).reshape(B, 12)
    return jnp.array(np.stack([one, one]))


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, decode):
    if decode:
        return jax.jit(functools.partial(st.decode_step, cfg, impl=impl))
    return jax.jit(functools.partial(st.ragged_forward, cfg, ctx_pages=-1,
                                     impl=impl))


def _run(world, ticks, impl="gather"):
    """ticks: [[(slot, sequence, first position, tokens)]] or, a decode
    tick, {"decode": [(slot, sequence, position)]}. One set of pools and
    tables for the whole packing (nothing is handed back: the window is
    the kernels' and the mask's); returns the worst gap of a tick's rows
    to the reference's rows and the assignments landed a valid row."""
    cfg, params, _, seqs, want = world
    kp, vp = _pools(cfg, impl)
    tables = _tables()
    worst, rows_seen = 0.0, 0
    for rows in ticks:
        if isinstance(rows, dict):
            tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
            live = np.zeros(B, bool)
            for s, q, p in rows["decode"]:
                tok[s], pos[s], live[s] = seqs[q][p], p, True
            lg, kp, vp, counts = _tick_fn(cfg, impl, True)(
                params, jnp.array(tok), jnp.array(pos), kp, vp, tables,
                jnp.array(live))
            at = [(s, q, p) for s, q, p in rows["decode"]]
            valid = len(at)
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
            lg, kp, vp, counts = _tick_fn(cfg, impl, False)(
                params, jnp.array(tok[0]), jnp.array(tok[1]),
                jnp.array(tok[2]), jnp.array(tok[3] != 0),
                jnp.array(start), jnp.array(last), kp, vp, tables)
            valid = cur
        # every expert is held: valid rows x picks x layers, exactly
        assert counts.shape == (cfg.n_layers, cfg.n_held)
        assert int(counts.sum()) == valid * cfg.moe_top_k * cfg.n_layers
        lg = np.asarray(lg)
        for s, q, p in at:
            worst = max(worst, _rel(lg[s], want[q][p]))
            rows_seen += 1
    assert rows_seen
    return worst


def _decodes(slot, q, lo, hi):
    return [{"decode": [(slot, q, p)]} for p in range(lo, hi)]


# the window is 8 tokens: every packing runs past it
PACKINGS = {
    "whole chunks": [[(1, 0, 0, 16)], [(1, 0, 16, 16)], [(1, 0, 32, 8)]],
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
    "one-token chunks": [[(2, 0, p, 1)] for p in range(12)],
}


@pytest.mark.parametrize("name", list(PACKINGS))
def test_every_packing_gives_the_references_logits(world, name):
    assert _run(world, PACKINGS[name]) < 2e-5


@pytest.mark.parametrize("name", ["decode rows beside a chunk",
                                  "a slot reused after another sequence",
                                  "prefill then decode ticks"])
def test_kernel_path_gives_the_references_logits(world, name):
    """The interpreted kernels: both attention kernels with each K/V
    head's seven query heads padded to eight, the grouped ReGLU kernels
    with the router's plan made ahead of attention."""
    assert _run(world, PACKINGS[name], "pallas_interpret") < 2e-5


def test_wrong_in_one_way_is_not_the_reference(world):
    """Each variant the chip's probe reads moves the logits: the
    comparison can see it."""
    from benchmarks.lib.checks_smallthinker import VARIANTS
    cfg, params, model, seqs, want = world
    for v in VARIANTS:
        got = np.asarray(ref.logits(model, params, jnp.array(seqs[0]),
                                    cfg.held, variant=(v,)))
        assert _rel(got, want[0]) > 1e-3, v


def test_the_router_runs_ahead_of_attention(world):
    """Structural: in a tick's program the router's products, the picks
    and the expert product's plan come BEFORE the layer's attention
    kernel, and depend on the layer's input alone."""
    cfg, params, *_ = world
    kp, vp = _pools(cfg, "pallas_interpret")
    i32 = lambda n: jnp.zeros((n,), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        st.ragged_forward, cfg, ctx_pages=-1, impl="pallas_interpret"))(
        params, i32(T), i32(T), i32(T), jnp.ones((T,), bool), i32(B),
        i32(B), kp, vp, _tables())

    def walk(jp, out):
        for e in jp.eqns:
            if e.primitive.name == "top_k":
                out.append("top_k")
            if e.primitive.name == "pallas_call":
                out.append(str(e.params.get("name")
                               or e.params["name_and_src_info"]))
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, out)
        return out

    seen = walk(jaxpr.jaxpr, [])
    kind = lambda s: ("route" if s == "top_k" else "attn" if "attention"
                      in s else "up" if "up_reglu" in s else "down"
                      if "down_reglu" in s else s)
    order = [kind(s) for s in seen]
    assert order == ["route", "attn", "up", "down"] * cfg.n_layers, order
    # route() of a layer is a function of that layer's input alone
    x = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.hidden))
    r = st.route(cfg, params["layers"][2], x, impl="gather")
    w, idx, logits = moe.softmax_pick_routing(
        x, params["layers"][2]["router"], top_k=cfg.moe_top_k)
    np.testing.assert_array_equal(r.idx, idx)
    np.testing.assert_array_equal(r.logits, logits)
    assert int(r.counts.sum()) == T * cfg.moe_top_k
    assert r.plan[2] is None                       # no visits off the kernels
    assert st.route(cfg, params["layers"][2], x,
                    impl="pallas_interpret").plan[2] is not None


# ---- the expert layer ---------------------------------------------------

def _dense_reglu(x, gates, wg, wi, wd):
    """Every expert on every row, weighted by the gate matrix."""
    out = np.zeros(x.shape, np.float64)
    x = np.asarray(x, np.float64)
    for e in range(gates.shape[1]):
        mid = np.maximum(x @ np.asarray(wg[e], np.float64), 0.0) * (
            x @ np.asarray(wi[e], np.float64))
        out += np.asarray(gates[:, e:e + 1], np.float64) * (
            mid @ np.asarray(wd[e], np.float64))
    return out


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
@pytest.mark.parametrize("base", [0, 8])
def test_reglu_grouped_path_is_the_dense_sum(impl, base):
    t, h, f, e, k = 24, 64, 32, 8, 3
    key = jax.random.PRNGKey(base + 1)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (t, h))
    stack = 3 * e
    wg = jax.random.normal(ks[1], (stack, h, f)) / 8
    wi = jax.random.normal(ks[2], (stack, h, f)) / 8
    wd = jax.random.normal(ks[3], (stack, f, h)) / 6
    w, idx, _ = moe.softmax_pick_routing(
        x, jax.random.normal(ks[4], (h, e)), top_k=k)
    valid = jnp.arange(t) < 20                  # four rows of padding
    gates, took, counts = moe.held_gates(idx, w, 0, e, valid)
    assert int(counts.sum()) == 20 * k
    for plan in (None, moe.held_plan(took, picks=k, impl=impl)):
        got = moe.held_experts_ffn(x, gates, took, (wg, wi), wd,
                                   act="reglu", picks=k, impl=impl,
                                   base=base, plan=plan)
        want = _dense_reglu(x, gates, wg[base:base + e],
                            wi[base:base + e], wd[base:base + e])
        assert got.dtype == jnp.float32
        assert _rel(got, want) < 2e-6
        assert not np.asarray(got)[20:].any()   # padding rows: nothing
    with pytest.raises(ValueError, match="none of"):
        moe.held_experts_ffn(x, gates, took, (wg, wi), wd, act="reglu",
                             picks=k, impl="x")
    with pytest.raises(ValueError, match="none of"):
        moe.held_experts_ffn(x, gates, took, (wg, wi), wd, act="gelu",
                             picks=k, impl=impl)
    with pytest.raises(ValueError, match="none of"):
        moe.held_plan(took, picks=k, impl="x")


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_swiglu_and_relu2_paths_are_what_they_were(impl):
    """The two older expert forms through the same entry."""
    t, h, f, e, k = 16, 64, 32, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (t, h))
    wg = jax.random.normal(ks[1], (e, h, f)) / 8
    wi = jax.random.normal(ks[2], (e, h, f)) / 8
    wd = jax.random.normal(ks[3], (e, f, h)) / 6
    w, idx, _ = moe.softmax_pick_routing(
        x, jax.random.normal(ks[4], (h, e)), top_k=k)
    gates, took, _ = moe.held_gates(idx, w, 0, e)
    got = moe.held_experts_ffn(x, gates, took, (wg, wi), wd, act="swiglu",
                               picks=k, impl=impl)
    want = sum(np.asarray(gates[:, j:j + 1]) * np.asarray(
        (jax.nn.silu(x @ wg[j]) * (x @ wi[j])) @ wd[j]) for j in range(e))
    assert _rel(got, want) < 2e-5
    wu = jnp.swapaxes(wi, 1, 2)                 # relu^2: W_up out by in
    got = moe.held_experts_ffn(x, gates, took, (wu,), wd, act="relu2",
                               picks=k, impl=impl)
    want = sum(np.asarray(gates[:, j:j + 1]) * np.asarray(
        jnp.square(jax.nn.relu(x @ wi[j])) @ wd[j]) for j in range(e))
    assert _rel(got, want) < 2e-5


def test_routing_on_a_hand_worked_case_with_a_tie():
    """Four experts, two picks. Row 0: logits 2, 1, 1, 0: the tie for
    the second pick goes to the LOWER index (1), weights softmax(2, 1).
    Row 1: all equal: picks 0 and 1, a half each. Row 2: the picks' own
    softmax, whatever the others are."""
    x = jnp.eye(3, dtype=jnp.float32)
    w_r = jnp.array([[2.0, 1.0, 1.0, 0.0],
                     [0.5, 0.5, 0.5, 0.5],
                     [-1.0, 3.0, -9.0, 1.0]], jnp.float32)
    w, idx, logits = moe.softmax_pick_routing(x, w_r, top_k=2)
    np.testing.assert_array_equal(idx, [[0, 1], [0, 1], [1, 3]])
    e = np.exp(1.0)
    np.testing.assert_allclose(
        w, [[e / (1 + e), 1 / (1 + e)], [0.5, 0.5],
            [np.exp(2) / (1 + np.exp(2)), 1 / (1 + np.exp(2))]], rtol=1e-6)
    np.testing.assert_array_equal(logits, w_r)
    assert idx.dtype == jnp.int32 and w.dtype == logits.dtype == jnp.float32
    # the softmax over ALL experts with the picks renormalised, which is
    # what the training paths' two functions give
    probs = moe.router_probs(x, w_r)
    tw, tidx = moe.top_k_routing(probs, 2)
    np.testing.assert_array_equal(np.sort(tidx, -1), np.sort(idx, -1))
    np.testing.assert_allclose(np.sort(tw, -1), np.sort(w, -1), rtol=1e-5)
    # the reference routes alike
    rw, ridx, rlg = ref._route(w_r, x, top_k=2)
    np.testing.assert_array_equal(ridx, idx)
    np.testing.assert_allclose(rw, w, rtol=1e-6)
    # a bfloat16 stream goes in as it is; the product is float32
    w16, idx16, lg16 = moe.softmax_pick_routing(
        x.astype(jnp.bfloat16), w_r.astype(jnp.bfloat16), top_k=2)
    assert lg16.dtype == jnp.float32
    np.testing.assert_array_equal(idx16, idx)


def test_two_shares_add_up_to_the_uncut_layer(world):
    """The guide's share test: the experts' part of a layer computed by
    the chip that holds experts (0, 4) plus that of the chip that holds
    (4, 8) is what the uncut reference gives for the whole layer."""
    cfg, params, model, *_ = world
    li = 2
    y = jax.random.normal(jax.random.PRNGKey(9), (T, cfg.hidden))
    layer = params["layers"][li]
    stacks = st.layer_experts(cfg, params, li)
    gate, idx, _ = ref.route(model, layer, y)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(model, stacks, y, gate, idx, (0, 8)))
    total = 0.0
    for lo, hi in ((0, 4), (4, 8)):
        share = st.config(cfg, experts_held=(lo, hi))
        routing = st.route(share, layer, y, impl="gather")
        assert int(routing.counts.sum()) < T * cfg.moe_top_k
        part = {k: a[lo:hi] for k, a in stacks.items()}
        total = total + np.asarray(st.experts(share, part, y, routing,
                                              impl="gather"))
        # the reference is given the same share
        with jax.default_matmul_precision("highest"):
            alone = np.asarray(ref.experts(model, part, y, gate, idx,
                                           (lo, hi)))
        assert _rel(st.experts(share, part, y, routing, impl="gather"),
                    alone) < 2e-6
    assert _rel(total, want) < 2e-6


# ---- the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = st.config("tiny", **F32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, num_pages=64, max_batch_size=2, page_size=PAGE,
        max_seq_len=64, max_prefill_tokens=8, max_num_batched_tokens=8,
        seed=5))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 255, n).tolist() for n in (5, 19, 13, 7)]
    # what each tick's dispatch and fold spans carried
    eng.dispatched, eng.folded = [], []
    phase = eng._phase

    def recording(name, **args):
        if name == "dispatch":
            eng.dispatched.append(args)
        if name == "fold":
            eng.folded.append(args)
        return phase(name, **args)
    eng._phase = recording
    return cfg, eng, eng.generate(prompts, SamplingParams(max_tokens=10))


def test_engine_greedy_tokens_are_the_references(served):
    """Prefill then decode through the ENGINE in float32: admission by
    both page groups, chunked prefill over several ticks, decode ticks
    past the 8-token window, window pages handed back, two sequences
    interleaved, four requests through two slots (each slot reused):
    every token is the reference's largest logit given the tokens before
    it."""
    cfg, eng, outs = served
    model = program_smallthinker.published_keys(cfg)
    for req in outs:
        seq = np.asarray(req.prompt_tokens + req.output_tokens, np.int32)
        # the reference is causal: padded to one length for all requests
        # (eagerly, each primitive compiles again at every new length)
        seq = np.pad(seq, (0, -len(seq) % 32))
        lg = np.asarray(ref.logits(model, eng.params, jnp.array(seq),
                                   cfg.held))
        n = len(req.prompt_tokens)
        assert len(req.output_tokens) == 10 and n + 10 > cfg.sliding_window
        for i, tok in enumerate(req.output_tokens):
            row = lg[n + i - 1]
            assert row[tok] >= np.sort(row)[-1] - 1e-4, (n, i)


def test_stats_list_the_groups_and_the_routing(served):
    cfg, eng, _ = served
    stats = eng.stats()
    full, win = stats["cache_groups"]
    assert (full["name"], full["layers"], full["window"]) == (
        "full", [0, 4], None)
    assert (win["name"], win["layers"], win["window"]) == (
        "window", [1, 2, 3, 5, 6, 7], 8)
    assert full["row"]["layout"] == "rows"
    assert win["pages_returned"] > 0
    routed = stats["moe"]
    assert routed["experts_held"] == [0, 8] and routed["expert_layers"] == 8
    # every expert is held: each routed token lands its 3 picks in each
    # of the 8 layers
    assert routed["assignments_landed"] == routed["tokens_routed"] * 3 * 8
    # the prompts and nine fed-back tokens a request, and the token a
    # retiring slot's last tick over-generates
    assert 5 + 19 + 13 + 7 + 4 * 9 <= routed["tokens_routed"] <= 44 + 40
    assert np.asarray(routed["landed"]).shape == (8, 8)
    assert routed["experts_with_tokens"] == 64
    # the weights are as the forwards use them
    assert stats["weights"]["bytes"] == 4 * cfg.num_params()


def test_engines_spans_carry_the_counts(served):
    cfg, eng, _ = served
    assert eng.dispatched and eng.folded
    for args in eng.dispatched:
        assert {"win_kv_tokens", "win_attn_pairs",
                "win_decode_pairs"} <= set(args)
        assert args["win_kv_tokens"] <= args["kv_tokens"]
    # a tick's fold says what its expert layers read and computed
    by_tick = {a["tick"]: a for a in eng.dispatched}
    for fold in eng.folded:
        if "moe_assignments" not in fold:
            continue
        span = by_tick[fold["of"]]
        rows = span["decode_rows"] + span["prefill_tokens"]
        assert fold["moe_assignments"] == rows * 3 * 8
        assert 0 < fold["moe_experts_hit"] <= 64


@pytest.mark.parametrize("kw,what", [
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"enable_kv_offload": True}, "enable_kv_offload"),
    ({"mesh_shape": (1, 2)}, "mesh_shape"),
    ({"mesh": {"tp": 2}}, "mesh"),
    ({"checkpoint": "/nowhere"}, "checkpoint"),
])
def test_pairings_nobody_built_are_refused_with_the_reason(kw, what):
    with pytest.raises(ValueError) as e:
        InferenceEngine(EngineConfig(model="smallthinker:tiny", **kw))
    assert st.SMALLTHINKER_REFUSES[what] in str(e.value)


def test_entry_points_nobody_built_are_refused(served):
    from ray_tpu.models.trinity import TRINITY_REFUSES
    _, eng, _ = served
    with pytest.raises(ValueError, match="does not compose with lora"):
        eng.register_loras({"a": {}})
    with pytest.raises(ValueError, match="session_shipping"):
        eng.export_prefix([1, 2, 3])
    # Trinity's seven reasons, word for word but the one that named its
    # gated attention
    assert set(st.SMALLTHINKER_REFUSES) == set(TRINITY_REFUSES)
    for key, why in TRINITY_REFUSES.items():
        assert (st.SMALLTHINKER_REFUSES[key] == why) == (key != "lora"), key
    assert "gated" not in st.SMALLTHINKER_REFUSES["lora"]
    with pytest.raises(ValueError, match="take no lora"):
        st.ragged_forward(eng.model_cfg, eng.params, *[None] * 9, lora={})
    assert eng.stats()["prefix_cache"].startswith("off")


# ---- the cache manager --------------------------------------------------

def test_this_row_in_both_groups_of_one_manager():
    """4 heads of 16 in merged rows, a full group of 2 layers and a
    window group of 6: pages go back behind the window, admission wants
    both groups."""
    cfg = st.config("tiny")
    groups = st.cache_groups(cfg, "gather")
    full, win = groups
    assert full.row.pool_shape(2, 8, PAGE) == (2, 8, PAGE * 2, 16)
    m = CacheManager(groups, (32, 12), PAGE, 2, 16, tick_tokens=4)
    assert m.windowed and m.prefix_cache.startswith("off")
    pages = m.admit(0, 40)
    assert len(pages) == 10
    w = m.groups[1]
    # a window of 8 + a 4-token tick + 2 pages: 5 pages reserved
    assert w.reserve[0] == 5
    back = 0
    for pos in range(0, 36, 4):
        back += m.advance([(0, pos)])[0]
    assert back == w.returned == (32 - 8 + 1) // PAGE
    assert m.bytes_used() > 0
    assert m.can_admit(12) and not m.can_admit(400)
    st_full, st_win = m.stats()["cache_groups"]
    assert st_full["row"]["bytes_per_token_layer"] == 2 * 2 * 16 * 2
    assert st_win["pages_returned"] == back
    m.first.free(pages)
    m.vacate(0)
    assert w.allocator.used_pages == 0 and m.first.used_pages == 0


def test_cost_model_prices_the_groups_and_the_held_experts():
    cfg = st.SmallThinkerConfig(n_layers=12)
    cm = CostModel(cfg, 16)
    # 2,048 B a token a layer: 3 full layers and 9 window layers
    assert cm.kv_bytes_per_token == 12 * 2048
    d = cm.decode_cost(6000)
    pages = lambda n: -(-n // 16) * 16
    assert d["bytes_kv_read"] == (3 * 2048 * pages(6000)
                                  + 9 * 2048 * pages(4096))
    assert d["bytes_kv_write"] == 12 * 2048
    own = cfg.serving_costs()
    h = 2560
    layer = 2 * (2 * h * 3584 + 2 * h * 512 + h * 64 + 6 * 3 * h * 768)
    assert own["gemm_flops_per_token"] == 12 * layer
    assert own["head_flops"] == 2 * h * 151_936
    assert own["attn_flops_per_pair"] == 4 * 12 * 28 * 128
    assert own["weight_bytes"] == 2 * 5_561_448_960
    assert d["flops_gemm"] == own["gemm_flops_per_token"] + own["head_flops"]
    # half the experts held: half the routed products
    half = st.SmallThinkerConfig(n_layers=12, experts_held=(0, 32))
    assert half.serving_costs()["gemm_flops_per_token"] == 12 * (
        layer - 2 * 3 * 3 * h * 768)
