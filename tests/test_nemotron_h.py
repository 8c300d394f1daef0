"""The NemotronH family (`models/nemotron_h.py`): Mamba-2 layers whose
state is kept a slot beside ONE page group in one cache manager
(`kv_cache.CacheManager`), ungated relu^2 held experts through the
grouped layout, attention with no positional encoding, layers that are a
mixer or a feed-forward part alone, against the plain float32 reference
the benchmark keeps (`benchmarks/lib/reference_nemotron_h.py`: a
sequential scan over the whole history, every expert held on every
token), at a toy size on the CPU in float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program_nemotron_h, reference_nemotron_h as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.llm._internal.kv_cache import CacheManager
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import nemotron_h as nh
from ray_tpu.models.cache_row import CacheGroup, CacheRow, StateRow
from ray_tpu.models.family import family_of, resolve_config
from ray_tpu.ops import moe

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, B, T, PAGES = 4, 3, 16, 48
CUT = dict(pattern="MEMEM*EMEMEM*EME", experts_held=(0, 64),
           vocab_size=65536)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# ---- the configuration --------------------------------------------------

def test_published_sizes_hold_the_issues_parameter_counts():
    whole = nh.NemotronHConfig()
    assert whole.n_layers == 52
    assert [len(whole.layers_of(k)) for k in "ME*"] == [23, 23, 6]
    assert whole.layer_params("M") == 38_744_896
    assert whole.layer_params("*") == 23_399_040
    assert whole.layer_params("E") == 1_297_468_160
    assert whole.num_params() == 31_577_940_288
    cut = nh.NemotronHConfig(**CUT)
    assert [len(cut.layers_of(k)) for k in "ME*"] == [7, 7, 2]
    assert cut.layer_params("E") == 658_885_376
    assert cut.num_params() == 5_282_534_208
    assert (cut.d_inner, cut.conv_dim, cut.in_width) == (4096, 6144, 10304)
    assert cut.units == ((0, None, 1), (2, None, 3), (4, 5, 6),
                         (7, None, 8), (9, None, 10), (11, 12, 13),
                         (14, None, 15))
    with pytest.raises(ValueError, match="units of M"):
        nh.NemotronHConfig(pattern="MM*E")
    with pytest.raises(ValueError, match="needs an attention layer"):
        nh.NemotronHConfig(pattern="MEME")
    with pytest.raises(ValueError, match="not a range"):
        nh.NemotronHConfig(experts_held=(100, 200))
    assert isinstance(resolve_config("nemotron_h:tiny"), nh.NemotronHConfig)
    # the tree is what num_params says it is, leaf for leaf
    toy = nh.config("tiny")
    shapes = jax.eval_shape(
        lambda: nh.init_params(toy, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == toy.num_params()
    assert shapes["experts"]["up"].shape == (4 * 4, 32, 64)
    dtypes = nh.storage_dtypes(toy)
    assert dtypes["embed"] == dtypes["experts"]["up"] == jnp.bfloat16
    assert dtypes["mamba"]["a_log"] == dtypes["moe"]["router_bias"] \
        == jnp.float32


def test_family_describes_a_page_group_and_a_state_group():
    cfg = nh.NemotronHConfig(**CUT)
    fam = family_of(cfg)
    assert fam.name == "nemotron_h"
    assert fam.rider_len(cfg) == 7 * 64
    full, state = fam.cache_groups(cfg, "pallas")
    assert (full.name, full.layers, full.window) == ("full", (5, 12), None)
    # 2 heads x 128 x K and V x 2 B a layer: pages of [16 x 2 rows, 128]
    assert (full.row.heads, full.row.width, full.row.layout) == (
        2, 128, "rows")
    assert full.bytes_per_token == 2048
    assert full.row.pool_shape(2, 100, 16) == (2, 100, 32, 128)
    assert (state.kind, state.layers) == ("state",
                                          (0, 2, 4, 7, 9, 11, 14))
    assert state.state.bytes_per_slot_layer == 2_134_016
    assert state.bytes_per_slot == 7 * 2_134_016
    assert state.array_shapes(0, 16, 64) == (
        ((7, 64, 3 * 6144), jnp.bfloat16),
        ((7, 64, 64, 64, 128), jnp.float32))
    with pytest.raises(ValueError, match="quantized"):
        fam.cache_groups(cfg, "pallas", "int8")


# ---- ticks against the reference ---------------------------------------

@pytest.fixture(scope="module")
def world():
    cfg = nh.config("tiny", **F32)
    params = nh.init_params(cfg, jax.random.PRNGKey(3))
    # norm weights and D off 1, so that each one matters
    key = jax.random.PRNGKey(7)
    for n, (kind, name) in enumerate((("mamba", "ln"), ("mamba", "norm"),
                                      ("mamba", "d_skip"), ("attn", "ln"),
                                      ("moe", "ln"))):
        leaf = params[kind][name]
        params[kind][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, n), leaf.shape)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)
    model = program_nemotron_h.published_keys(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, cfg.vocab_size, 40).astype(np.int32)
            for _ in range(3)]
    trees = nh.layer_trees(cfg, params)
    want = [np.asarray(ref.logits(model, trees, jnp.array(s), cfg.held))
            for s in seqs]
    return cfg, params, model, seqs, want


def _arrays(cfg, impl):
    made = [tuple(jnp.zeros(s, d) for s, d in g.array_shapes(PAGES, PAGE, B))
            for g in nh.cache_groups(cfg, impl)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def _tables():
    """Slot s holds pages s * 12 .. s * 12 + 11."""
    return jnp.array(np.arange(B * 12, dtype=np.int32).reshape(B, 12))


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, decode):
    if decode:
        return jax.jit(functools.partial(nh.decode_step, cfg, impl=impl))
    return jax.jit(functools.partial(nh.ragged_forward, cfg, ctx_pages=-1,
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
    assert _run(world, PACKINGS[name]) < 2e-5


@pytest.mark.parametrize("name", ["decode rows beside a chunk",
                                  "a slot reused after another sequence",
                                  "a boundary inside the conv's taps"])
def test_kernel_path_gives_the_references_logits(world, name):
    assert _run(world, PACKINGS[name], "pallas_interpret") < 2e-5


def test_wrong_in_one_way_is_not_the_reference(world):
    """Each variant the chip's probe reads moves the logits: the
    comparison can see it."""
    from benchmarks.lib.checks_nemotron_h import VARIANTS
    cfg, params, model, seqs, want = world
    trees = nh.layer_trees(cfg, params)
    for v in VARIANTS:
        got = np.asarray(ref.logits(model, trees, jnp.array(seqs[0]),
                                    cfg.held, variant=(v,), chunk=16))
        assert _rel(got, want[0]) > 1e-3, v


def test_a_ticks_program_holds_each_kinds_body_once(world):
    cfg, params, *_ = world
    kp, vp = _arrays(cfg, "pallas_interpret")
    i32 = lambda n: jnp.zeros((n,), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        nh.ragged_forward, cfg, ctx_pages=-1, impl="pallas_interpret"))(
        params, i32(T), i32(T), i32(T), jnp.ones((T,), bool), i32(B),
        i32(B), kp, vp, _tables())
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [len(cfg.units)] == [4]

    def calls(jp):
        n = 0
        for e in jp.eqns:
            n += e.primitive.name == "pallas_call"
            for sub in jax.core.jaxprs_in_params(e.params):
                n += calls(sub)
        return n
    # the scan, the attention kernel, the experts' up and down
    assert calls(jaxpr.jaxpr) == 4


# ---- the expert layer ---------------------------------------------------

def _dense_relu2(x, gates, wu, wd):
    """Every held expert on every token, in float64."""
    x, gates = np.asarray(x, np.float64), np.asarray(gates, np.float64)
    out = np.zeros_like(x)
    for e in range(wu.shape[0]):
        u = np.maximum(x @ np.asarray(wu[e], np.float64).T, 0.0) ** 2
        out += gates[:, e:e + 1] * (u @ np.asarray(wd[e], np.float64))
    return out


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
@pytest.mark.parametrize("base", [0, 8])
def test_relu2_grouped_path_is_the_dense_sum(impl, base):
    rng = np.random.default_rng(2)
    t, h, f, e, stack = 24, 32, 48, 4, 12
    x = jnp.array(rng.standard_normal((t, h)), jnp.float32)
    wu = jnp.array(rng.standard_normal((stack, f, h)) / 6, jnp.float32)
    wd = jnp.array(rng.standard_normal((stack, f, h)) / 7, jnp.float32)
    idx = jnp.array(np.stack([rng.permutation(8)[:3] for _ in range(t)]),
                    jnp.int32)
    w = jnp.array(rng.uniform(0.1, 1.0, (t, 3)), jnp.float32)
    valid = jnp.arange(t) < 20
    gates, took, counts = moe.held_gates(idx, w, 2, 2 + e, valid)
    got = moe.held_experts_ffn(x, gates, took, (wu,), wd, act="relu2",
                               picks=3, impl=impl, base=base)
    want = _dense_relu2(x, gates, wu[base:base + e], wd[base:base + e])
    assert _rel(got, want) < 2e-6
    assert int(counts.sum()) == int(took.sum()) > 0


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_swiglu_path_is_what_it_was(impl):
    rng = np.random.default_rng(4)
    t, h, f, e = 16, 32, 48, 4
    x = jnp.array(rng.standard_normal((t, h)), jnp.float32)
    wg, wi = (jnp.array(rng.standard_normal((e, h, f)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.array(rng.standard_normal((e, f, h)) / 7, jnp.float32)
    idx = jnp.array(np.stack([rng.permutation(8)[:3] for _ in range(t)]),
                    jnp.int32)
    w = jnp.array(rng.uniform(0.1, 1.0, (t, 3)), jnp.float32)
    gates, took, _ = moe.held_gates(idx, w, 0, e)
    got = moe.held_experts_ffn(x, gates, took, (wg, wi), wd, act="swiglu",
                               picks=3, impl=impl)
    xs = np.asarray(x, np.float64)
    want = np.zeros_like(xs)
    for j in range(e):
        g = xs @ np.asarray(wg[j], np.float64)
        u = xs @ np.asarray(wi[j], np.float64)
        want += np.asarray(gates, np.float64)[:, j:j + 1] * (
            (g / (1 + np.exp(-g)) * u) @ np.asarray(wd[j], np.float64))
    assert _rel(got, want) < 2e-6


def test_two_shares_add_up_to_the_uncut_layer(world):
    """The test that ties the share to the model: the held parts of an
    expert layer's output from share (0, 4) and share (4, 8), with the
    shared expert counted once, are the uncut reference's layer."""
    cfg, params, model, *_ = world
    whole = nh.config(cfg, experts_held=(0, 8))
    layer = nh.init_params(whole, jax.random.PRNGKey(11))
    tree = {k: v[0] for k, v in layer["moe"].items()}
    up, down = (layer["experts"][k][:8] for k in ("up", "down"))
    u = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.hidden),
                          jnp.float32)
    want = np.asarray(ref.experts(model, {**tree, "up": up, "down": down},
                                  u, (0, 8)))
    shared = np.asarray(ref.shared_expert(tree, u))
    total = -shared                       # counted once, not twice
    for lo, hi in ((0, 4), (4, 8)):
        share = nh.config(cfg, experts_held=(lo, hi))
        out, counts = nh.moe_block(
            share, {**tree, "up": up[lo:hi], "down": down[lo:hi]}, u,
            impl="gather")
        total = total + np.asarray(out)
        assert int(counts.sum()) > 0
    assert _rel(total, want) < 2e-6


# ---- the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = nh.config("tiny", **F32)
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
    ticks, the state donated from tick to tick, two sequences
    interleaved, four requests through two slots (each slot reused,
    from zero state, with no zeroing program): every token is the
    reference's largest logit given the tokens before it."""
    cfg, eng, outs = served
    model = program_nemotron_h.published_keys(cfg)
    trees = nh.layer_trees(cfg, eng.params)
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
    full, state = st["cache_groups"]
    assert (full["name"], full["layers"]) == ("full", [3, 8])
    assert (state["kind"], state["layers"]) == ("state", [0, 2, 5, 7])
    assert (state["slots_total"], state["slots_held"]) == (2, 0)
    assert state["slots_peak"] == 2
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
    assert nh.span_counts(None, [(0, 7), (12, 1), (3, 1)], None) == {
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
        InferenceEngine(EngineConfig(model="nemotron_h:tiny", **kw))
    assert nh.NEMOTRON_H_REFUSES[what] in str(e.value)


def test_entry_points_nobody_built_are_refused(served):
    _, eng, _ = served
    with pytest.raises(ValueError, match="does not compose with lora"):
        eng.register_loras({"a": {}})
    with pytest.raises(ValueError, match="session_shipping"):
        eng.export_prefix([1, 2, 3])
    assert set(nh.NEMOTRON_H_REFUSES) == {
        "prefix_cache", "lora", "kv_dtype", "enable_kv_offload", "mesh",
        "mesh_shape", "checkpoint", "session_shipping"}
    with pytest.raises(ValueError, match="take no lora"):
        nh.ragged_forward(eng.model_cfg, eng.params, *[None] * 9, lora={})


# ---- the cache manager --------------------------------------------------

def test_one_page_group_and_a_state_group_admit_by_slots():
    """The layout no family had asked of `CacheManager`: ONE page group
    and a state group. Pages are plenty; the slots decide."""
    row = CacheRow("kv", 2, 2, 16, 16, jnp.float32, layout="rows")
    state = StateRow("ssd", (("conv", (6,), jnp.float32),
                             ("ssm", (2, 4, 2), jnp.float32)))
    full = CacheGroup("full", row, (2, 5))
    held = CacheGroup("state", None, (0, 1, 3), state=state)
    m = CacheManager([full, held], [64, 0], 4, 2, 16, tick_tokens=4)
    assert len(m.groups) == 1 and len(m.tables) == 1 and len(m.states) == 1
    assert not m.windowed and m.prefix_cache.startswith("off: a resume")
    pages = m.admit(0, 40)
    per_slot = 3 * (6 + 16) * 4
    assert held.bytes_per_slot == per_slot
    assert m.bytes_used() == 10 * 4 * full.bytes_per_token + per_slot
    m.admit(1, 8)
    assert m.groups[0].allocator.free_pages > 40   # pages are there...
    assert not m.can_admit(8)                      # ...the slots are not
    full_st, state_st = m.stats()["cache_groups"]
    assert full_st["pages_used"] == 12
    assert (state_st["slots_held"], state_st["slots_peak"],
            state_st["slots_total"]) == (2, 2, 2)
    m.first.free(pages)
    m.vacate(0)
    assert m.can_admit(8)
    assert m.stats()["cache_groups"][1]["slots_held"] == 1


def test_cost_model_prices_the_state_and_the_ungated_experts():
    cfg = nh.NemotronHConfig(**CUT)
    cm = CostModel(cfg, 16)
    assert cm.kv_bytes_per_token == 2048
    state = 7 * 2_134_016
    assert cm.state_bytes_per_row == state
    d = cm.decode_cost(2000)
    assert d["bytes_kv_read"] == 2048 * 2000 + state
    assert d["bytes_kv_write"] == 2048 + state
    own = cfg.serving_costs()
    # an expert layer: the router, the shared expert's two matrices, and
    # 6 x 64 / 128 = 3 routed experts of two matrices each a token
    h = 2688
    expert_layer = 2 * (h * 128 + 2 * h * 3712 + 3 * 2 * h * 1856)
    mamba = 2 * (h * 10304 + 4096 * h)
    attn = 2 * (2 * h * 4096 + 2 * h * 256)
    assert own["gemm_flops_per_token"] == (7 * expert_layer + 7 * mamba
                                           + 2 * attn)
    assert own["weight_bytes"] == 2 * 5_282_534_208
    assert d["flops_gemm"] == (own["gemm_flops_per_token"]
                               + own["head_flops"])
