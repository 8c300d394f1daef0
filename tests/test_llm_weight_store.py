"""The dense family's serving weights are stored as the tick uses them
(PR 30): matrices and embedding in cfg.dtype, head and norms in
float32, cast ONCE where the tree enters the engine
(models/family.store_params, llama_infer.storage_dtypes).

The gates: the logits on the stored tree are BITWISE those of the
float32 tree cast at every use; every way a tree enters the engine ends
in the same types; the engine's programs convert no weight; the
explicit-tp engine keeps the Megatron shardings; stats() and the cost
model price the stored bytes; sampled tokens are the parent's."""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm._internal import engine as engine_mod
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.models import checkpoint_io, llama
from ray_tpu.models.family import family_of, store_params
from ray_tpu.models.llama_infer import (decode_step, ragged_forward,
                                        storage_dtypes, tp_param_specs)
from ray_tpu.ops.paged_attention import pool_head_dim

COMPUTE = ("embed", "wq", "wk", "wv", "wo", "wg", "wi", "wd")
_ENGINE = dict(max_batch_size=4, page_size=8, num_pages=64,
               max_prefill_tokens=16, seed=5)


def _cfg(dtype=jnp.bfloat16):
    return llama.config("debug", dtype=dtype)


def _leaf_types(params):
    return {path[-1].key: str(leaf.dtype) for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def _expected_types(cfg):
    dt = str(jnp.dtype(cfg.dtype))
    names = COMPUTE + ("ln1", "ln2", "final_norm", "lm_head")
    return {n: dt if n in COMPUTE else "float32" for n in names}


# -- (a) bitwise logits ----------------------------------------------------

def _ticks(cfg, impl):
    """A context fill, then a mixed tick (a decode row, a chunk against
    its cached context, a fresh prompt) and a decode tick, as functions
    of the parameter tree alone."""
    rng = np.random.default_rng(3)
    B, page, per = 4, 8, 4
    shape = (cfg.n_layers, 1 + 3 * per, page, cfg.n_kv_heads,
             pool_head_dim(cfg.head_dim, impl))
    tables = np.zeros((B, per), np.int32)
    tables[:3] = 1 + np.arange(3 * per).reshape(3, per)
    tables = jnp.asarray(tables)

    def pack(plan, T):
        toks, slots, pos = (np.zeros(T, np.int32) for _ in range(3))
        valid = np.zeros(T, bool)
        start, last = np.zeros(B, np.int32), np.zeros(B, np.int32)
        cur = 0
        for s, st, n in plan:
            toks[cur:cur + n] = rng.integers(3, cfg.vocab_size, n)
            slots[cur:cur + n] = s
            pos[cur:cur + n] = np.arange(st, st + n)
            valid[cur:cur + n] = True
            start[s], last[s] = st, cur + n - 1
            cur += n
        return tuple(jnp.asarray(a) for a in
                     (toks, slots, pos, valid, start, last))

    fill = pack([(0, 0, 11), (1, 0, 9)], 32)
    mixed = pack([(0, 11, 1), (1, 9, 12), (2, 0, 7)], 32)
    dec_toks = jnp.asarray(rng.integers(3, cfg.vocab_size, B), jnp.int32)
    posn = jnp.asarray([12, 21, 7, 0], jnp.int32)
    active = jnp.asarray(np.arange(B) < 3)
    ragged = jax.jit(functools.partial(
        ragged_forward, cfg, ctx_pages=per, impl=impl))
    decode = jax.jit(functools.partial(decode_step, cfg, impl=impl))

    def run(params):
        zero = jnp.zeros(shape, cfg.dtype)
        _, k, v = ragged(params, *fill, zero, zero, tables)
        lg_mixed, k, v = ragged(params, *mixed, k, v, tables)
        lg_dec = decode(params, dec_toks, posn, k, v, tables, active)[0]
        return np.asarray(lg_mixed)[:3], np.asarray(lg_dec)[:3]

    return run


@pytest.fixture(scope="module")
def logits_of_both_trees():
    """impl -> ((mixed, decode) on the stored tree, the same on the
    float32 tree it was made from), computed once an impl."""
    cfg = _cfg()
    wide = llama.init_params(cfg, jax.random.PRNGKey(1))
    stored = store_params(family_of(cfg), cfg, wide)
    assert _leaf_types(stored) == _expected_types(cfg)
    assert wide["embed"].dtype == jnp.float32      # caller's tree intact
    done = {}

    def get(impl):
        if impl not in done:
            run = _ticks(cfg, impl)
            done[impl] = (run(stored), run(wide))
        return done[impl]
    return get


@pytest.mark.parametrize("tick", ["mixed", "decode"])
@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_logits_on_stored_tree_are_bitwise_the_float32_trees(
        logits_of_both_trees, impl, tick):
    got, want = (pair[tick == "decode"]
                 for pair in logits_of_both_trees(impl))
    assert np.isfinite(want).all() and np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)


# -- (b) every way in ends in the same types -------------------------------

def _engine(cfg, params=None, **kw):
    return InferenceEngine(EngineConfig(model=cfg, **_ENGINE, **kw),
                           params=params)


def _ckpt(tmp_path, cfg, dtype=None):
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if dtype is not None:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    ckpt = str(tmp_path / "ckpt")
    checkpoint_io.save_llama_checkpoint(cfg, params, ckpt)
    checkpoint_io.save_config(cfg, ckpt)
    return params, ckpt


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("source", ["params", "checkpoint", "seed"])
def test_engine_holds_each_leaf_in_the_type_the_tick_uses(
        tmp_path, source, dtype):
    cfg = _cfg(dtype)
    if source == "params":
        wide = llama.init_params(cfg, jax.random.PRNGKey(0))
        eng = _engine(cfg, params=wide)
        assert not wide["embed"].is_deleted()
        assert wide["embed"].dtype == jnp.float32
    elif source == "checkpoint":
        eng = _engine(cfg, checkpoint=_ckpt(tmp_path, cfg)[1])
    else:
        eng = _engine(cfg)
    assert _leaf_types(eng.params) == _expected_types(cfg)
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.params))
    if source == "seed":
        # the seed's draw, rounded once: the parent's weights
        wide = llama.init_params(cfg, jax.random.PRNGKey(_ENGINE["seed"]))
        for name in ("embed", "lm_head"):
            np.testing.assert_array_equal(
                np.asarray(eng.params[name], np.float32),
                np.asarray(wide[name].astype(eng.params[name].dtype),
                           np.float32))


def test_store_is_the_identity_for_a_family_without_storage_types():
    from ray_tpu.models import deepseek_v3
    cfg = deepseek_v3.config("debug")
    fam = family_of(cfg)
    assert fam.storage_dtypes is None
    tree = {"w": jnp.ones((2, 2), jnp.float32)}
    assert store_params(fam, cfg, tree)["w"] is tree["w"]


def test_store_keeps_a_leaf_already_in_its_type():
    cfg = _cfg()
    stored = store_params(family_of(cfg), cfg,
                          llama.init_params(cfg, jax.random.PRNGKey(0)))
    again = store_params(family_of(cfg), cfg, stored)
    assert all(a is b for a, b in zip(jax.tree.leaves(stored),
                                       jax.tree.leaves(again)))


# -- (c) the programs convert no weight ------------------------------------

_CONVERT = re.compile(
    r"stablehlo\.convert\s+%\S+\s*:\s*\(tensor<([0-9x]+)x(\w+)>\)"
    r"\s*->\s*tensor<[0-9x]+x(\w+)>")


def _weight_shapes(params):
    """Shapes a weight can enter a convert in: a whole stack, one
    layer's slice of it, the embedding (the test's T and B are chosen
    so that no activation has one of them)."""
    shapes = set()
    for name in COMPUTE:
        leaf = (params["embed"] if name == "embed"
                else params["layers"][name])
        shapes.add(tuple(leaf.shape))
        if name != "embed":
            shapes.add(tuple(leaf.shape[1:]))
    return shapes


def _lowered_programs(eng):
    """Run a prompt and a few decode ticks; the lowered text of every
    jit_run and of jit_step the engine called, read before each call."""
    texts = {"run": [], "step": []}

    def spy(kind, fn):
        def call(*args):
            texts[kind].append(fn.lower(*args).as_text())
            return fn(*args)
        return call

    real_ragged = eng._ragged_fn
    eng._ragged_fn = lambda *a: spy("run", real_ragged(*a))
    eng._decode_fn = spy("step", eng._decode_fn)
    req = Request("r", list(range(3, 14)),
                  SamplingParams(max_tokens=4, temperature=0.7, seed=1))
    eng.add_request(req)
    while eng.has_work():
        eng.step()
    assert req.finished and texts["run"] and texts["step"]
    return texts


def _store_as_the_parent_did(monkeypatch):
    """The engine's store step with no storage types: the tree placed
    and kept as given, float32 from the seed, cast at every use."""
    monkeypatch.setattr(
        engine_mod, "store_params",
        lambda family, *a, **kw: store_params(
            dataclasses.replace(family, storage_dtypes=None), *a, **kw))


def _weight_converts(text, shapes):
    return [m.group(0) for m in _CONVERT.finditer(text)
            if tuple(int(d) for d in m.group(1).split("x")) in shapes]


def test_engine_programs_convert_no_weight(monkeypatch):
    cfg = _cfg()
    eng = _engine(cfg)
    shapes = _weight_shapes(eng.params)
    for kind, texts in _lowered_programs(eng).items():
        for text in texts:
            assert "stablehlo.convert" in text      # the reader reads
            assert _weight_converts(text, shapes) == [], kind
    # and the reader finds them where they are: the parent's engine
    # (the tree kept as given) converts every stack in both programs
    _store_as_the_parent_did(monkeypatch)
    old = _engine(cfg)
    assert _leaf_types(old.params)["wq"] == "float32"
    for kind, texts in _lowered_programs(old).items():
        assert len(_weight_converts(texts[0], shapes)) >= len(COMPUTE), kind


# -- (d) explicit tp: same rule, Megatron shardings ------------------------

@pytest.mark.parametrize("source", ["seed", "params"])
def test_explicit_tp_engine_stores_narrow_under_its_specs(source):
    cfg = _cfg()
    wide = (llama.init_params(cfg, jax.random.PRNGKey(0))
            if source == "params" else None)
    eng = _engine(cfg, params=wide, mesh_shape=(1, 2))
    assert _leaf_types(eng.params) == _expected_types(cfg)
    specs = tp_param_specs(cfg, "tp")
    ok = jax.tree.map(
        lambda leaf, spec: leaf.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(eng.mesh, spec), leaf.ndim),
        eng.params, specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert all(jax.tree.leaves(ok)), ok
    shapes = _weight_shapes(eng.params)
    # one shard's slices too: column- and row-parallel halves
    for shape in list(shapes):
        if len(shape) == 2:
            shapes.add((shape[0], shape[1] // 2))
            shapes.add((shape[0] // 2, shape[1]))
    for kind, texts in _lowered_programs(eng).items():
        for text in texts:
            assert _weight_converts(text, shapes) == [], kind


def test_gspmd_engine_stores_narrow_under_its_shardings():
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.parallel.sharding import tree_shardings
    cfg = _cfg()
    eng = _engine(cfg, mesh=MeshSpec(tp=2))
    assert _leaf_types(eng.params) == _expected_types(cfg)
    expect = tree_shardings(llama.param_logical_axes(cfg), eng.mesh)
    ok = jax.tree.map(
        lambda leaf, sh: leaf.sharding.is_equivalent_to(sh, leaf.ndim),
        eng.params, expect)
    assert all(jax.tree.leaves(ok)), ok


# -- (e) the counter and the cost model ------------------------------------

@pytest.mark.parametrize("kw", [{}, {"mesh_shape": (1, 2)}],
                         ids=["one_chip", "tp2"])
def test_stats_and_cost_model_price_the_stored_bytes(kw):
    cfg = _cfg()
    eng = _engine(cfg, **kw)
    held = eng.stats()["weights"]
    leaves = jax.tree.leaves(eng.params)
    assert held["bytes"] == sum(int(x.nbytes) for x in leaves)
    assert held["bytes"] == sum(held["by_dtype"].values())
    narrow = sum(int(x.nbytes) for x in leaves if x.dtype == jnp.bfloat16)
    assert held["by_dtype"] == {"bfloat16": narrow,
                                "float32": held["bytes"] - narrow}
    assert eng.perf.model.weight_bytes == held["bytes"]
    # under the configuration's own count (float32 masters)
    assert held["bytes"] < cfg.num_params() * 4
    by_hand = sum(
        int(np.prod(a.shape)) * (2 if name in COMPUTE else 4)
        for name, a in (
            (path[-1].key, leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(eng.params)[0]))
    assert held["bytes"] == by_hand


def test_cost_model_without_an_engine_keeps_the_configurations_count():
    from ray_tpu.llm._internal.perfmodel import CostModel
    cfg = _cfg()
    assert CostModel(cfg, 8).weight_bytes == cfg.num_params() * 4
    assert CostModel(cfg, 8, weight_bytes=123).weight_bytes == 123.0


# -- (f) sampled tokens are the parent's -----------------------------------

def _sampled_run(cfg, **kw):
    eng = _engine(cfg, **kw)
    rng = np.random.default_rng(7)
    reqs = [Request(f"r{i}", list(rng.integers(3, 200, n)),
                    SamplingParams(max_tokens=12, temperature=0.7,
                                   top_p=0.9, seed=100 + i))
            for i, n in enumerate((5, 19, 33))]
    for r in reqs:
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    return [r.output_tokens for r in reqs], eng


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_sampled_tokens_equal_the_parents(monkeypatch, impl):
    cfg = _cfg()
    new, eng = _sampled_run(cfg, decode_impl=impl)
    assert _leaf_types(eng.params)["wq"] == "bfloat16"
    _store_as_the_parent_did(monkeypatch)
    old, parent = _sampled_run(cfg, decode_impl=impl)
    assert _leaf_types(parent.params)["wq"] == "float32"
    assert all(len(t) == 12 for t in new)
    assert new == old


# -- checkpoint_io: straight into the storage types ------------------------

def test_bf16_checkpoint_is_read_for_serving_into_the_storage_types(
        tmp_path):
    """The tiny safetensors fixture, saved in bfloat16: read with the
    serving rule's tree of types each compute leaf arrives as the file
    holds it (one host cast a window, to the leaf's own type: nothing
    is widened to float32 on the way and narrowed again), head and
    norms arrive float32; one type for all still gives the trainer its
    masters; the engine takes a checkpoint through the same rule."""
    cfg = _cfg()
    saved, ckpt = _ckpt(tmp_path, cfg, dtype=jnp.bfloat16)
    loaded = checkpoint_io.load_llama_params(
        cfg, ckpt, dtype=storage_dtypes(cfg))
    assert _leaf_types(loaded) == _expected_types(cfg)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(saved)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    masters = checkpoint_io.load_llama_params(cfg, ckpt)
    assert set(_leaf_types(masters).values()) == {"float32"}
    eng = _engine(cfg, checkpoint=ckpt)
    assert _leaf_types(eng.params) == _expected_types(cfg)
    for got, want in zip(jax.tree.leaves(eng.params),
                         jax.tree.leaves(saved)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
