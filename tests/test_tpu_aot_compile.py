"""Compile today's TPU programs with the REAL XLA:TPU and Mosaic
compilers — no chip needed.

libtpu is part of the installation, and
`jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")` describes four `TPU v5 lite` devices with no
hardware behind them. `jax.jit(f).lower(<ShapeDtypeStructs sharded on
those devices>).compile()` then runs the same compilers the chip does.

Interpret mode (every other kernel test here) ignores tiling, DMA
alignment and partitioning, which is how kernels the compiler refuses
lived in this tree for twenty PRs. This file is the CPU-side guard that
no kernel lives in interpret mode only: every engine geometry that
constructs must compile for v5e, and every geometry the engine rejects
at construction must really be refused by the compiler (when a later
compiler accepts one, the second half fails and the rejection goes).
What the compiled kernels COMPUTE is checked on the chip, by
chip_smoke.py.

One file a family, so that `--dist loadfile` spreads the gate over the
workers (each case is a real compile that nothing else repeats): this
one holds the dense family with training and the pipeline stage, and what
the engine rejects; `test_tpu_aot_compile_phi4flash.py`,
`..._deepseek_kimi.py` (the latent-attention kernel),
`..._nemotron_granite.py` (the SSD scan), `..._smallthinker.py` and
`..._trinity.py` hold the other families'. A new family's cases go in a
file of its own; `aot_v5e.py` has what they share.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aot_v5e import (BATCH, PAGE, PAGES, TABLE, _on, _param_structs, _pools,
                     v5e)
from benchmarks.lib.program import llama_config
from ray_tpu.llm._internal.engine import EngineConfig, InferenceEngine
from ray_tpu.models import llama
from ray_tpu.models.llama_infer import decode_step, ragged_forward
from ray_tpu.models.training import TrainStepBundle, default_optimizer
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.ragged_paged_attention import ragged_paged_attention_pallas
from ray_tpu.parallel import MeshSpec

pytestmark = pytest.mark.usefixtures("no_compile_cache")  # aot_v5e.py

# 8b at tp=4: what ONE shard of the explicit-tp engine computes
# (llama_infer.tp_local_config divides the heads; Megatron layout
# divides ffn)
_8B_TP4_LOCAL = dataclasses.replace(
    llama.config("8b"), n_heads=8, n_kv_heads=2, ffn=14336 // 4)

SERVE_CONFIGS = {
    "1b": llama.config("1b"),               # head_dim 64: lane-padded
    "debug": llama.config("debug"),         # head_dim 32
    "8b_tp4_local": _8B_TP4_LOCAL,          # head_dim 128, 2 kv heads
}


@pytest.mark.parametrize("name", list(SERVE_CONFIGS))
def test_serving_forwards_compile_for_v5e(v5e, name):
    """ragged_forward (mixed ticks) and decode_step (pure-decode ticks:
    the same forward at one token a slot) at published widths, two
    layers deep (the layer scan makes depth irrelevant to what
    compiles)."""
    cfg = dataclasses.replace(SERVE_CONFIGS[name], n_layers=2)
    S = _on(v5e[0])
    params = _param_structs(cfg, S)
    k, v = _pools(cfg, S)
    tables = S((BATCH, TABLE), jnp.int32)
    i32 = lambda *shape: S(shape, jnp.int32)
    T = 64
    jax.jit(functools.partial(
        ragged_forward, cfg, ctx_pages=16, impl="pallas")
    ).lower(params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
            i32(BATCH), i32(BATCH), k, v, tables).compile()
    jax.jit(functools.partial(decode_step, cfg, impl="pallas")).lower(
        params, i32(BATCH), i32(BATCH), k, v, tables,
        S((BATCH,), jnp.bool_)).compile()


@pytest.mark.parametrize("T", [0, 512])
def test_dense_forwards_copy_no_pool_at_chat_opens_sizes(v5e, T):
    """The decode tick (T 0: the ragged tick of one token a slot) and
    the 512-token ragged program at `chat-open`'s sizes, read from the
    cell's own file: the kernel gets the pools whole, so no layer's
    pages ([2048, 16, 8, 128] bf16, 67 MB) are copied out before it. A
    pool that is an xs of the layer scan costs 64.4 and 113.0 MB of
    temporaries; as compiled they are 0.61 and 0.81 MB."""
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmarks", "configs",
                           "internlm2_5-1_8b.json")) as f:
        config = json.load(f)
    cfg, engine = llama_config(config), config["engine"]
    b, page, pages = (engine[k] for k in (
        "max_batch_size", "page_size", "num_pages"))
    width = engine["max_seq_len"] // page
    S = _on(v5e[0])
    params = _param_structs(cfg, S)
    pool = S((cfg.n_layers, pages, page, cfg.n_kv_heads, cfg.head_dim),
             cfg.dtype)
    tables = S((b, width), jnp.int32)
    i32 = lambda n: S((n,), jnp.int32)
    if T:
        run = functools.partial(ragged_forward, cfg, ctx_pages=128,
                                impl="pallas")
        args = (params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
                i32(b), i32(b), pool, pool, tables)
        donate = (7, 8)
    else:
        def run(params, tok, pos, active, k, v, tables):
            return decode_step(cfg, params, tok, pos, k, v, tables,
                               active, impl="pallas")
        args = (params, i32(b), i32(b), S((b,), jnp.bool_), pool, pool,
                tables)
        donate = (4, 5)
    compiled = jax.jit(run, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    # the decode tick is the ragged tick of one token a slot (PR 50)
    assert "ragged_paged_attention" in text
    assert "paged_decode" not in text
    layer_pages = f"bf16[{pages},{page},{cfg.n_kv_heads},{cfg.head_dim}]"
    copies = [line.strip()[:120] for line in text.splitlines()
              if f" = {layer_pages}" in line]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20
    # both pools are still updated in place
    assert mem.alias_size_in_bytes >= 3.2e9


@pytest.mark.parametrize("b", [1, 4])
def test_dense_decode_tick_compiles_under_the_smallest_token_bucket(v5e, b):
    """A small engine's decode tick is a ragged tick of fewer tokens
    than the smallest bucket a ragged program has (8): Mosaic takes the
    work-list kernel at 1 and 4 query rows as it stands."""
    cfg = dataclasses.replace(SERVE_CONFIGS["1b"], n_layers=2)
    S = _on(v5e[0])
    k, v = _pools(cfg, S)
    i32 = lambda *shape: S(shape, jnp.int32)
    text = jax.jit(functools.partial(decode_step, cfg, impl="pallas")).lower(
        _param_structs(cfg, S), i32(b), i32(b), k, v, i32(b, TABLE),
        S((b,), jnp.bool_)).compile().as_text()
    assert "ragged_paged_attention" in text
    assert "paged_decode" not in text


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, head_dim):
    S = _on(v5e[0])
    q = S((1, 2048, 8, head_dim), jnp.bfloat16)
    kv = S((1, 2048, 4, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()


def _train_step_compiles(cfg, mesh, batch, seq):
    bundle = TrainStepBundle(cfg, mesh, optimizer=default_optimizer(
        total_steps=1000, mu_dtype=jnp.bfloat16))
    state = jax.eval_shape(bundle._init_impl, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    with bundle._mesh_ctx():
        return bundle._step.lower(state, tokens).compile()


def test_889m_train_step_compiles_on_2x2_with_pallas(v5e):
    """bench.py's 889M configuration at fsdp=2 x tp=2: the flash kernel
    must sit under shard_map ("Mosaic kernels cannot be automatically
    partitioned") — the branch the virtual CPU mesh never takes."""
    cfg = llama.config(
        "tiny", vocab_size=32768, hidden=2048, n_layers=12, n_heads=16,
        n_kv_heads=8, head_dim=128, ffn=8192, max_seq=2048,
        attention_impl="pallas", remat_policy="nothing")
    compiled = _train_step_compiles(
        cfg, MeshSpec(fsdp=2, tp=2).build(v5e), batch=4, seq=2048)
    per_chip = compiled.memory_analysis()
    assert (per_chip.argument_size_in_bytes
            + per_chip.temp_size_in_bytes) < 16 * 2**30


def test_pipeline_stage_flash_compiles_nested_in_pp(v5e):
    """The flash kernel inside the GPipe stages: a shard_map over the
    batch/head axes NESTED in the pipeline's pp shard_map. Lowering it
    under jax.set_mesh is refused on this jax (the concrete mesh drops
    the outer Manual axis); TrainStepBundle's abstract-mesh context is
    what lets it through."""
    cfg = llama.config(
        "tiny", vocab_size=2048, hidden=512, n_layers=4, n_heads=4,
        n_kv_heads=2, head_dim=128, ffn=1024, max_seq=512,
        attention_impl="pallas", pp_microbatches=2)
    _train_step_compiles(cfg, MeshSpec(pp=2, fsdp=2).build(v5e),
                         batch=4, seq=512)


# ------------------------------------------------ what the engine rejects

def _ragged_kernel_lowering(S, dt, kvh, quantized, group=2, T=64,
                            slots=BATCH, ctx_pages=16, table=TABLE,
                            pages=PAGES):
    h, d = kvh * group, 128
    pool = S((pages, PAGE, kvh, d), dt)
    # an unquantized pool has the model's dtype (engine: pool_dt is
    # cfg.dtype), so the tick's fresh rows come in it too; the kernel
    # DMAs them as [rows, kvh, d] blocks under the pages' own rule
    act = jnp.bfloat16 if quantized else dt
    new = S((T, kvh, d), act)
    scales = S((pages, PAGE, kvh), jnp.float32)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, kp, vp, tables, slots_, pos, valid, start, kn, vn,
            ks=None, vs=None):
        return ragged_paged_attention_pallas(
            q, kp, vp, tables, slots_, pos, valid, start, kn, vn,
            ctx_pages=ctx_pages, k_scales=ks, v_scales=vs)

    args = [S((T, h, d), act), pool, pool,
            i32(slots, table), i32(T), i32(T), S((T,), jnp.bool_),
            i32(slots), new, new]
    if quantized:
        args += [scales, scales]
    return jax.jit(run).lower(*args)


# the ragged kernel at the shapes chat-open runs it (InternLM2.5-1.8B:
# 8 kv heads, group 2, head_dim 128, bf16 pool of 2048 pages, 32
# slots, table 512 wide, read whole whatever the context bucket) and at what one
# shard of Mistral-7B-v0.3 at tp=4 sees (2 kv heads, group 4)
CELL_SHAPES = [
    # T, context pages, kv heads, group
    (8, 16, 8, 2), (64, 16, 8, 2), (64, 64, 8, 2), (256, 64, 8, 2),
    (256, 128, 8, 2), (512, 16, 8, 2), (512, 128, 8, 2),
    (64, 16, 2, 4), (512, 128, 2, 4),
]


@pytest.mark.parametrize("T,ctx,kvh,group", CELL_SHAPES)
def test_ragged_kernel_compiles_at_the_cells_shapes(v5e, T, ctx, kvh,
                                                    group):
    """The guard against a kernel that lives in interpret mode only:
    Mosaic takes the item-grid kernel (in-kernel loops with trip
    counts from prefetched scalars, double-buffered page DMA, flat
    q/new-KV/output rows DMA'd at a dynamic row) at every token bucket
    and context bucket of the cell, within its VMEM."""
    _ragged_kernel_lowering(
        _on(v5e[0]), jnp.bfloat16, kvh, quantized=False, group=group,
        T=T, slots=32, ctx_pages=ctx, table=512, pages=2048).compile()


def test_a_group_of_two_keeps_its_compiler_parameters():
    """chat-open's geometry asks for no VMEM limit: its kernel's
    compiler parameters, and so its programs, are what they were."""
    from ray_tpu.ops.ragged_paged_attention import _vmem_limit
    assert _vmem_limit(128, 16, 8, 2, 128, 128, 2) == {}
    assert _vmem_limit(128, 8, 2, 4, 128, 128, 2) == {}
    big = _vmem_limit(128, 48, 8, 6, 128, 128, 2)
    assert 32 << 20 < big["vmem_limit_bytes"] <= 96 << 20


REJECTED = {
    # kv_dtype, pool dtype, kv heads per shard
    "int8_kv": ("int8", jnp.int8, 8),
    "fp8_kv": ("fp8", jnp.float8_e4m3fn, 8),
    "one_kv_head_per_shard_bf16": ("f32", jnp.bfloat16, 1),
}


@pytest.mark.parametrize("name", list(REJECTED))
def test_rejected_geometry_is_refused_by_the_compiler(v5e, name):
    kind, dt, kvh = REJECTED[name]
    assert pa.kernel_layout_error(kind, kvh, dt) is not None
    with pytest.raises(Exception, match="aligned to tiling"):
        _ragged_kernel_lowering(_on(v5e[0]), dt, kvh,
                                quantized=kind != "f32").compile()


def test_accepted_geometries_pass_the_layout_rule(v5e):
    for kvh, dt in [(8, jnp.bfloat16), (2, jnp.bfloat16),
                    (1, jnp.float32)]:
        assert pa.kernel_layout_error("f32", kvh, dt) is None
        _ragged_kernel_lowering(_on(v5e[0]), dt, kvh,
                                quantized=False).compile()


@pytest.mark.parametrize("kw,match", [
    (dict(kv_dtype="int8"), "scale DMA"),
    (dict(kv_dtype="fp8"), "scale DMA"),
    # debug has 2 kv heads: tp=2 leaves one per shard in a bf16 pool
    (dict(mesh_shape=(1, 2)), "1 kv head"),
    (dict(mesh={"tp": 2, "fsdp": 1}), "1 kv head"),
])
def test_engine_rejects_at_construction_never_gathers(kw, match):
    """decode_impl="pallas" is what "auto" resolves to on a TPU: a
    geometry the compiler refuses raises HERE with its reason, instead
    of dying in Mosaic on the first tick or quietly running gather."""
    with pytest.raises(ValueError, match=match):
        InferenceEngine(EngineConfig(model="debug", decode_impl="pallas",
                                     **kw))
    # the same geometry on the explicit gather path still constructs
    eng = InferenceEngine(EngineConfig(model="debug",
                                       decode_impl="gather", **kw))
    assert eng._resolve_impl() == "gather"
    assert eng.k_pages.shape[-1] == eng.model_cfg.head_dim


def test_kernel_engine_pool_is_lane_padded():
    eng = InferenceEngine(EngineConfig(model="debug",
                                       decode_impl="pallas_interpret"))
    assert eng.model_cfg.head_dim == 32      # the model keeps its width
    assert eng.k_pages.shape[-1] == pa.LANES
    np.testing.assert_array_equal(np.asarray(eng.k_pages), 0)
