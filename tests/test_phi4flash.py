"""The Phi4Flash family (`models/phi4flash.py`): Mamba layers whose state
is kept a slot beside two page groups in one cache manager
(`kv_cache.CacheManager`), differential attention as GQA over paired
rows, ONE layer's pages read by the cross layers, and a cross-decoder run
on the sampling rows alone, against the plain float32 reference the
benchmark keeps (`benchmarks/lib/reference_phi4flash.py`: a sequential
scan over the whole history, every layer on every token), at a toy size
on the CPU in float32: a window of 8, 4 conv taps, sequences past two
windows, so that an edge, a tap or a state off by one token fails."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program_phi4flash, reference_phi4flash as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.llm._internal.kv_cache import CacheManager
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import phi4flash
from ray_tpu.models.cache_row import CacheGroup, CacheRow, StateRow
from ray_tpu.models.family import family_of, resolve_config

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, B, T, PAGES = 4, 3, 16, 48


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# ---- the configuration --------------------------------------------------

def test_published_sizes_hold_the_issues_parameter_count():
    cfg = phi4flash.Phi4FlashConfig()
    # ISSUE 36: 9 x 119.9 + 9 x 98.3 + 7 x 104.9 + 7 x 91.8 + 512.2
    # million; the norms, biases and lam vectors (0.4M) are counted
    assert abs(cfg.num_params() - 3_852e6) < 1e6
    assert cfg.num_params() == 3_852_562_944
    assert cfg.layers_of("mamba") == tuple(range(0, 17, 2))
    assert cfg.layers_of("swa") == tuple(range(1, 16, 2))
    assert cfg.layers_of("full") == (17,)
    assert cfg.layers_of("gmu") == tuple(range(18, 32, 2))
    assert cfg.layers_of("cross") == tuple(range(19, 32, 2))
    assert (cfg.head_dim, cfg.d_inner, cfg.n_self) == (64, 5120, 18)
    assert cfg.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    with pytest.raises(ValueError, match="multiple of 4"):
        phi4flash.Phi4FlashConfig(n_layers=6)
    assert isinstance(resolve_config("phi4flash:debug"),
                      phi4flash.Phi4FlashConfig)
    # the tree is what num_params says it is, leaf for leaf
    toy = phi4flash.config("debug")
    shapes = jax.eval_shape(
        lambda: phi4flash.init_params(toy, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == toy.num_params()
    assert "lm_head" not in shapes           # tied: the embedding
    dtypes = phi4flash.storage_dtypes(toy)
    assert dtypes["embed"] == jnp.bfloat16
    assert dtypes["self_pairs"]["mamba"]["a_log"] == jnp.float32
    assert dtypes["memory"]["in_proj"] == jnp.bfloat16


def test_family_describes_three_groups():
    cfg = phi4flash.Phi4FlashConfig()
    fam = family_of(cfg)
    assert fam.name == "phi4flash"
    full, window, state = fam.cache_groups(cfg, "pallas")
    assert (full.name, full.layers, full.window) == ("full", (17,), None)
    assert full.readers == tuple(range(19, 32, 2)) and len(full.readers) == 7
    assert (window.layers, window.window) == (tuple(range(1, 16, 2)), 512)
    # 20 heads x 64 x K and V x 2 B, as 10 rows of 128: no padding
    assert (full.row.heads, full.row.width, full.row.padded_width,
            full.row.layout) == (10, 128, 128, "rows")
    assert full.bytes_per_token == 5120
    assert window.bytes_per_token == 40960
    assert full.read_bytes_per_token == 8 * 5120
    assert full.row.pool_shape(1, 100, 16) == (1, 100, 160, 128)
    # 9 Mamba layers x (5120 x 16 float32 + 5120 x 3 bfloat16)
    assert (state.kind, state.layers) == ("state", tuple(range(0, 17, 2)))
    assert state.bytes_per_slot == 9 * (5120 * 16 * 4 + 5120 * 3 * 2)
    assert state.bytes_per_token == 0 and full.bytes_per_slot == 0
    assert state.array_shapes(0, 16, 64) == (
        ((9, 64, 15360), jnp.bfloat16), ((9, 64, 16, 5120), jnp.float32))
    assert state.describe()["kind"] == "state"
    assert full.describe()["readers"] == list(full.readers)
    assert "readers" not in window.describe()
    with pytest.raises(ValueError, match="one of the two"):
        CacheGroup("both", full.row, (0,), state=state.state)
    assert fam.cache_row(cfg, "pallas") == full.row


# ---- ticks against the reference ---------------------------------------

@pytest.fixture(scope="module")
def world():
    cfg = phi4flash.config("debug", **F32)
    params = phi4flash.init_params(cfg, jax.random.PRNGKey(3))
    # norm weights and biases off 1 and 0, so that each one matters
    key = jax.random.PRNGKey(7)
    for i, layer in enumerate(params["layers"]):
        for j, name in enumerate(("ln1", "ln2")):
            k = jax.random.fold_in(key, 2 * i + j)
            layer[name] = {
                "w": 1.0 + 0.3 * jax.random.normal(k, (cfg.hidden,)),
                "b": 0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                             (cfg.hidden,))}
        if "subln" in layer:
            layer["subln"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), layer["subln"].shape)
    model = program_phi4flash.published_keys(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, cfg.vocab_size, 40).astype(np.int32)
            for _ in range(3)]
    want = [np.asarray(ref.logits(model, params, jnp.array(s)))
            for s in seqs]
    return cfg, params, model, seqs, want


def _arrays(cfg, impl):
    made = [tuple(jnp.zeros(s, d) for s, d in g.array_shapes(PAGES, PAGE, B))
            for g in phi4flash.cache_groups(cfg, impl)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def _tables():
    """Slot s holds pages s * 12 .. s * 12 + 11 of either group."""
    one = np.arange(B * 12, dtype=np.int32).reshape(B, 12)
    return jnp.array(np.stack([one, one]))


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, decode):
    if decode:
        return jax.jit(functools.partial(phi4flash.decode_step, cfg,
                                         impl=impl))
    return jax.jit(functools.partial(phi4flash.ragged_forward, cfg,
                                     ctx_pages=-1, impl=impl))


def _run(world, ticks, impl="gather"):
    """ticks: [[(slot, sequence, first position, tokens)]] or, a decode
    tick, {"decode": [(slot, sequence, position)]}. One set of pools,
    state and tables for the whole packing; returns the worst gap of a
    tick's rows to the reference's rows."""
    cfg, params, _, seqs, want = world
    params = phi4flash.stack_layers(cfg, params)
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
    "whole chunks": [[(1, 0, 0, 16)], [(1, 0, 16, 16)], [(1, 0, 32, 8)]],
    "a boundary inside the conv's taps": [
        [(1, 0, 0, 5)], [(1, 0, 5, 1)], [(1, 0, 6, 2)], [(1, 0, 8, 3)],
        [(1, 0, 11, 16)], [(1, 0, 27, 13)]],
    "a boundary inside the window": [
        [(0, 1, 0, 7)], [(0, 1, 7, 2)], [(0, 1, 9, 6)], [(0, 1, 15, 10)],
        [(0, 1, 25, 15)]],
    "chunks past two windows": [[(2, 2, 0, 16)], [(2, 2, 16, 3)],
                                [(2, 2, 19, 16)], [(2, 2, 35, 5)]],
    "several sequences a tick": [
        [(0, 0, 0, 5), (2, 1, 0, 6), (1, 2, 0, 5)],
        [(1, 2, 5, 9), (0, 0, 5, 1), (2, 1, 6, 6)],
        [(2, 1, 12, 1), (0, 0, 6, 14)], [(1, 2, 14, 16)]],
    "prefill then decode ticks": [[(1, 0, 0, 11)]] + _decodes(1, 0, 11, 24),
    "decode past the window, three rows": (
        [[(0, 0, 0, 12)], [(1, 1, 0, 9)], [(2, 2, 0, 3)]]
        + [{"decode": [(0, 0, 12 + i), (1, 1, 9 + i), (2, 2, 3 + i)]}
           for i in range(12)]),
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
    "a full tick of three rows": [
        [(0, 0, 0, 6), (1, 1, 0, 5), (2, 2, 0, 5)],
        [(0, 0, 6, 6), (1, 1, 5, 5), (2, 2, 5, 5)]],
}


@pytest.mark.parametrize("name", list(PACKINGS))
def test_every_packing_gives_the_references_logits(world, name):
    assert len(PACKINGS) >= 12
    assert _run(world, PACKINGS[name]) < 2e-5


@pytest.mark.parametrize("name", ["decode rows beside a chunk",
                                  "a slot reused after another sequence"])
def test_kernel_path_gives_the_references_logits(world, name):
    """The scan kernel and both attention kernels, interpreted, over
    merged-rows pools lane-padded to 128."""
    assert _run(world, PACKINGS[name], "pallas_interpret") < 2e-5


def test_wrong_in_one_way_is_not_the_reference(world):
    """The comparison has teeth at this size: the reference with each
    variant of `checks_phi4flash.VARIANTS` against itself."""
    _, params, model, seqs, want = world
    from benchmarks.lib import checks_phi4flash
    for v in checks_phi4flash.VARIANTS:
        got = ref.logits(model, params, jnp.array(seqs[0]), variant=(v,),
                         chunk=16)
        assert _rel(np.asarray(got)[20:], want[0][20:]) > 1e-2, v


def test_cross_decoder_on_the_sampling_rows_is_every_layer_everywhere(
        world):
    """A 14-token chunk beside two decode rows: 16 tokens run layers
    0-5, three rows run layers 6-7, and the three rows' logits are those
    of the reference, which runs all 8 layers on all 40 tokens."""
    cfg = world[0]
    assert cfg.n_self == 6 and cfg.n_layers == 8
    assert _run(world, [[(0, 0, 0, 9)], [(1, 1, 0, 13)],
                        [(0, 0, 9, 1), (1, 1, 13, 1), (2, 2, 0, 14)]]) < 2e-5
    counts = phi4flash.span_counts(cfg, [(9, 1), (13, 1), (0, 14)],
                                   [True, True, False])
    assert (counts["ssm_tokens"], counts["ssm_rows"],
            counts["cross_tokens"]) == (16, 3, 3)


def test_paired_gqa_is_the_four_softmax_form(world):
    """40 query heads [q1 | 0] / [0 | q2] over 10 rows [k1 | k2] with
    values [v1 | v2], plain softmax attention at 1/sqrt(128) on q x
    sqrt(2), then `diff_output`: the reference's `differential` (two
    softmaxes a pair on 64-wide heads, the difference on the paired
    values, the sub-norm)."""
    cfg, params, model, _, _ = world
    layer, li = params["layers"][5], 5
    s, h, kvh, d = 12, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k0 = jax.random.PRNGKey(1)
    q = jax.random.normal(k0, (s, h * d))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (s, kvh, d))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (s, kvh, d))
    wide = phi4flash.wide_queries(cfg, q)                # [s, h, 2d]
    kr = k.reshape(s, kvh // 2, 2 * d)
    vr = v.reshape(s, kvh // 2, 2 * d)
    g = h // (kvh // 2)
    sc = jnp.einsum("tjgd,sjd->jgts", wide.reshape(s, kvh // 2, g, 2 * d),
                    kr) / np.sqrt(2 * d)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("jgts,sjd->tjgd", p, vr).reshape(s, h, 2 * d)
    lam0 = phi4flash.lam_consts(cfg, (li,))[0]
    got = phi4flash.diff_output(cfg, layer, o, lam0)
    want = (ref.differential(model, layer, q.reshape(s, h, d), k, v, li,
                             None) @ layer["wo"] + layer["bo"])
    assert _rel(got, want) < 1e-5
    # lam is the layer's, not 0: without the subtraction it is another
    assert _rel(phi4flash.diff_output(
        cfg, {**layer, "lam_q1": layer["lam_q1"] * 0 - 9.0,
              "lam_q2": layer["lam_q2"] * 0 - 9.0}, o, lam0), want) > 0.05



# ---- the stack as the engine keeps it -----------------------------------

def test_stacked_tree_is_the_layer_list_and_back():
    """`stack_layers`: the eight (Mamba, window) and the seven (memory
    unit, cross) pairs stacked, layers 16 and 17 trees of their own;
    `layer_trees` gives every layer back, leaf for leaf."""
    cfg = phi4flash.config("debug")
    drawn = phi4flash.init_params(cfg, jax.random.PRNGKey(2))
    stacked = phi4flash.stack_layers(cfg, drawn)
    assert set(stacked) == {"embed", "final_norm", "self_pairs", "memory",
                            "shared", "cross_pairs"}
    assert set(stacked["self_pairs"]) == {"mamba", "swa"}
    assert set(stacked["cross_pairs"]) == {"gmu", "cross"}
    # 8 layers: mamba 0 2 | 4, window 1 3, full 5, gmu 6, cross 7
    assert stacked["self_pairs"]["mamba"]["in_proj"].shape[0] == 2
    assert stacked["cross_pairs"]["cross"]["wq"].shape[0] == 1
    assert stacked["memory"] is drawn["layers"][4]
    assert stacked["shared"] is drawn["layers"][5]
    assert sum(a.size for a in jax.tree.leaves(stacked)) == cfg.num_params()
    back = phi4flash.layer_trees(cfg, stacked)
    assert len(back["layers"]) == cfg.n_layers
    for li, (want, got) in enumerate(zip(drawn["layers"], back["layers"])):
        assert set(want) == set(got), li
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert a.dtype == b.dtype and np.array_equal(a, b), li
    assert np.array_equal(np.asarray(back["layers"][-1]["wq"]),
                          np.asarray(drawn["layers"][7]["wq"]))
    # the engine's own draw hands its list over: nothing is held twice
    spent = phi4flash.init_params(cfg, jax.random.PRNGKey(2))
    again = phi4flash.stack_layers(cfg, spent, spend=True)
    assert spent["layers"][0] == {} and spent["layers"][7] == {}
    assert spent["layers"][4] is again["memory"]
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(again)):
        assert np.array_equal(a, b)
    # the published sizes: eight and seven pairs
    full = jax.eval_shape(lambda: phi4flash.init_stacked(
        phi4flash.Phi4FlashConfig(), jax.random.PRNGKey(0)))
    assert full["self_pairs"]["swa"]["wqkv"].shape == (8, 2560, 5120)
    assert full["cross_pairs"]["gmu"]["gmu_in"].shape == (7, 2560, 5120)
    assert full["memory"]["in_proj"].shape == (2560, 10240)


def test_a_ticks_program_holds_a_pairs_body_once(world):
    """The forward is two scans (the self-decoder's pairs, the
    cross-decoder's pairs) with layers n/2 and n/2 + 1 between them:
    five kernel calls in the program's text whatever the depth (a Mamba
    scan and a window kernel a self pair, the memory layer's scan, the
    full layer's kernel, a cross pair's kernel), not one a layer."""
    cfg, params, _, _, _ = world
    kp, vp = _arrays(cfg, "pallas_interpret")
    t = 8
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, kp, vp: phi4flash.ragged_forward(
            cfg, p, i32(t), i32(t), jnp.arange(t, dtype=jnp.int32),
            jnp.ones((t,), bool), i32(B), i32(B) + t - 1, kp, vp,
            tuple(_tables()), ctx_pages=-1, impl="pallas_interpret"))(
        phi4flash.stack_layers(cfg, params), kp, vp)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [2, 1]

    def calls(jp):
        n = 0
        for e in jp.eqns:
            n += e.primitive.name == "pallas_call"
            for sub in jax.core.jaxprs_in_params(e.params):
                n += calls(sub)
        return n
    assert calls(jaxpr.jaxpr) == 5


# ---- the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = phi4flash.config("debug", **F32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, num_pages=64, num_pages_by_group={"window": 20},
        max_batch_size=2, page_size=PAGE, max_seq_len=64,
        max_prefill_tokens=8, max_num_batched_tokens=8, seed=5))
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
    """Prefill then decode through the ENGINE in float32: admission in
    every group, chunked prefill, decode ticks, the state donated from
    tick to tick, window pages handed back at tick boundaries and taken
    by the next sequence, four requests through two slots (each slot
    reused, from zero state, with no zeroing program): every token is
    the reference's largest logit given the tokens before it."""
    cfg, eng, outs = served
    model = program_phi4flash.published_keys(cfg)
    for req in outs:
        seq = np.asarray(req.prompt_tokens + req.output_tokens, np.int32)
        # the reference is causal: padded to one length for all requests
        # (eagerly, each primitive compiles again at every new length)
        seq = np.pad(seq, (0, -len(seq) % 32))
        lg = np.asarray(ref.logits(
            model, phi4flash.layer_trees(cfg, eng.params), jnp.array(seq)))
        n = len(req.prompt_tokens)
        assert len(req.output_tokens) == 10
        for i, tok in enumerate(req.output_tokens):
            row = lg[n + i - 1]
            assert row[tok] >= np.sort(row)[-1] - 1e-4, (n, i)


def test_stats_list_three_groups(served):
    _, eng, _ = served
    st = eng.stats()
    full, window, state = st["cache_groups"]
    assert [g["name"] for g in st["cache_groups"]] == [
        "full", "window", "state"]
    assert full["readers"] == [7] and full["layers"] == [5]
    assert window["pages_returned"] > 0 and window["pages_used"] == 0
    # a page handed back went to another sequence: more pages were
    # claimed over the run than the group has
    assert full["pages_used"] == 0 and full["pages_peak"] > 0
    assert state["kind"] == "state" and state["slots_total"] == 2
    assert (state["slots_held"], state["slots_peak"]) == (0, 2)
    assert state["bytes_per_slot"] == 3 * (16 * 128 * 4 + 3 * 128 * 4)
    assert st["prefix_cache"].startswith("off: a resume at token m")
    assert st["cache_row"] == full["row"]
    # the top-level counts are the fullest PAGE group's
    assert st["total_pages"] in (63, 19) and st["occupancy"] == 0.0
    assert st["kv_device_bytes_used"] == 0 and st["moe"] is None
    # the arrays ride the pools' tuples: conv inputs, then the state
    assert [a.shape for a in eng.k_pages] == [
        (1, 64, 8, 16), (2, 20, 8, 16), (3, 2, 384)]
    assert eng.v_pages[2].shape == (3, 2, 16, 128)
    assert eng.v_pages[2].dtype == jnp.float32


def test_engines_dispatch_spans_carry_the_counts(served):
    _, eng, outs = served
    ragged = [a for a in eng.dispatched if a["kind"] == "ragged"]
    decode = [a for a in eng.dispatched if a["kind"] == "decode"]
    assert ragged and decode
    for a in ragged:
        assert a["ssm_tokens"] == a["decode_rows"] + a["prefill_tokens"]
        assert a["ssm_rows"] == a["cross_tokens"] == a["rows"]
        assert 0 < a["win_kv_tokens"] <= a["kv_tokens"]
    for a in decode:
        assert a["ssm_tokens"] == a["ssm_rows"] == a["cross_tokens"] \
            == a["rows"]
        assert a["win_attn_pairs"] == a["win_decode_pairs"] <= 8 * a["rows"]
    # every prompt token went through the scan exactly once
    assert sum(a["prefill_tokens"] for a in ragged) == sum(
        len(r.prompt_tokens) for r in outs)


def test_dispatch_span_counts_by_hand():
    cfg = phi4flash.config("debug")                  # window 8
    # a decode row at 20, a chunk of 6 from 5, a prompt of 3
    got = phi4flash.span_counts(cfg, [(20, 1), (5, 6), (0, 3)],
                                [True, False, False])
    assert got["ssm_tokens"] == 10 and got["ssm_rows"] == 3
    assert got["cross_tokens"] == 3
    # keys inside the windows: min(c + n, n + 7) a row
    assert got["win_kv_tokens"] == 8 + 11 + 3
    # pairs: the decode row 8; the chunk's queries keep 6, 7, 8, 8, 8,
    # 8; the prompt's 1, 2, 3
    assert got["win_attn_pairs"] == 8 + 45 + 6
    assert got["win_decode_pairs"] == 8
    fam = family_of(cfg)
    assert fam.span_counts is phi4flash.span_counts
    assert fam.rider_len(cfg) == 0


@pytest.mark.parametrize("kw,what", [
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"enable_kv_offload": True}, "enable_kv_offload"),
    ({"mesh_shape": (1, 2)}, "mesh_shape"),
    ({"mesh": {"tp": 2}}, "mesh"),
    ({"checkpoint": "/nowhere"}, "checkpoint"),
])
def test_pairings_nobody_built_are_refused_with_the_reason(kw, what):
    with pytest.raises(ValueError) as e:
        InferenceEngine(EngineConfig(model="phi4flash:debug", **kw))
    assert phi4flash.PHI4FLASH_REFUSES[what] in str(e.value)


def test_entry_points_nobody_built_are_refused(served):
    _, eng, _ = served
    with pytest.raises(ValueError, match="does not compose with lora"):
        eng.register_loras({"a": {}})
    assert set(phi4flash.PHI4FLASH_REFUSES) == {
        "prefix_cache", "lora", "kv_dtype", "enable_kv_offload", "mesh",
        "mesh_shape", "checkpoint", "session_shipping"}
    with pytest.raises(ValueError, match="take no lora"):
        phi4flash.ragged_forward(eng.model_cfg, eng.params, *[None] * 9,
                                 lora={})


# ---- the cache manager --------------------------------------------------

def _groups():
    row = CacheRow("kv", 2, 2, 16, 16, jnp.float32)
    state = StateRow("ssm", (("conv", (6,), jnp.float32),
                             ("scan", (4, 2), jnp.float32)))
    return (CacheGroup("full", row, (5,), readers=(7,)),
            CacheGroup("window", row, (1, 3), 8),
            CacheGroup("state", None, (0, 2, 4), state=state))


def test_a_state_group_is_held_a_slot_from_admission_to_vacate():
    full, window, state = _groups()
    m = CacheManager([full, window, state], [32, 16, 0], 4, 2, 16,
                     tick_tokens=4)
    assert len(m.groups) == 2 and len(m.tables) == 2 and len(m.states) == 1
    assert m.windowed and m.prefix_cache.startswith("off: a resume")
    assert m.fits(40) is None and m.can_admit(40)
    pages = m.admit(0, 40)
    per_slot = 3 * (6 + 8) * 4
    assert state.bytes_per_slot == per_slot
    assert m.bytes_used() == (
        10 * 4 * full.bytes_per_token
        + m.groups[1].allocator.used_pages * 4 * window.bytes_per_token
        + per_slot)
    m.admit(1, 8)
    assert not m.can_admit(8)                # both slots hold their state
    st = m.stats()["cache_groups"][2]
    assert (st["slots_held"], st["slots_peak"], st["slots_total"]) == (
        2, 2, 2)
    assert st["slots_at_peak"] == 2          # the bytes' peak saw both
    m.first.free(pages)
    m.vacate(0)
    assert m.can_admit(8)
    assert m.stats()["cache_groups"][2]["slots_held"] == 1
    m.reset_peaks()
    assert m.stats()["cache_groups"][2]["slots_peak"] == 1
    # the top-level counts stay the fullest page group's
    assert m.stats()["total_pages"] in (31, 15)


def test_old_layouts_are_as_they_were():
    full, window, state = _groups()
    row = full.row
    one = CacheManager([CacheGroup("all", row, (0, 1))], [32], 4, 2, 16,
                       tick_tokens=4)
    assert (one.windowed, one.prefix_cache, one.states) == (False, "on", [])
    assert "slots_total" not in str(one.stats())
    assert one.stats()["cache_groups"][0] == {
        "name": "all", "row": row.describe(), "layers": [0, 1],
        "window": None, "pages_total": 31, "pages_used": 0,
        "pages_peak": 0, "pages_reserved": 0, "pages_at_peak": 0}
    two = CacheManager([CacheGroup("full", row, (1,)), window], [32, 16],
                       4, 2, 16, tick_tokens=4)
    assert two.windowed and two.prefix_cache.startswith(
        "off: a window group")
    with pytest.raises(ValueError, match="no family has asked"):
        CacheManager([window, full], [16, 32], 4, 2, 16, tick_tokens=4)
    with pytest.raises(ValueError, match="after the page groups"):
        CacheManager([state, full], [0, 32], 4, 2, 16, tick_tokens=4)
    with pytest.raises(ValueError, match="after the page groups"):
        CacheManager([state], [0], 4, 2, 16, tick_tokens=4)


def test_cost_model_prices_the_state_and_the_shared_group():
    cfg = phi4flash.Phi4FlashConfig()
    cm = CostModel(cfg, 16)
    assert cm.kv_bytes_per_token == 5120 + 40960
    state = 9 * (5120 * 16 * 4 + 5120 * 3 * 2)
    assert cm.state_bytes_per_row == state
    # a decode token at context 2000: the full group's 1,999 keys (125
    # pages) read by 8 layers, the window group's 512 by its 8, and the
    # row's state in; the token's rows and the state out
    d = cm.decode_cost(2000)
    assert d["bytes_kv_read"] == 8 * 5120 * 2000 + 40960 * 512 + state
    assert d["bytes_kv_write"] == 5120 + 40960 + state
    per_layer = cm.attn_flops_per_pair / 16
    assert d["flops_attn"] == pytest.approx(
        per_layer * (8 * 2000 + 8 * 512))
    own = cfg.serving_costs()
    assert d["flops_gemm"] == (own["gemm_flops_per_token"]
                               + own["gemm_flops_per_sampled_row"]
                               + own["head_flops"])
    # a chunk pays the cross-decoder once, not a token
    c = cm.chunk_cost(0, 512)
    assert c["flops_gemm"] == (512 * own["gemm_flops_per_token"]
                               + own["gemm_flops_per_sampled_row"]
                               + own["head_flops"])
    # ISSUE 36: 18 layers' matrix products, 2 x 1,964M a token
    assert own["gemm_flops_per_token"] == pytest.approx(2 * 1964e6,
                                                        rel=0.01)
