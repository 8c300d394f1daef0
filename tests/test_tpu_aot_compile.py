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
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmarks.lib.program import llama_config
from ray_tpu.llm._internal.engine import EngineConfig, InferenceEngine
from ray_tpu.models import llama
from ray_tpu.models.llama_infer import (decode_step, ragged_forward,
                                        storage_dtypes)
from ray_tpu.models.training import TrainStepBundle, default_optimizer
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.ragged_paged_attention import ragged_paged_attention_pallas
from ray_tpu.parallel import MeshSpec

PAGE, PAGES, BATCH, TABLE = 16, 512, 8, 64


@pytest.fixture(scope="module")
def v5e():
    devs = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    assert [d.device_kind for d in devs] == ["TPU v5 lite"] * 4
    return devs


def _on(dev):
    s = SingleDeviceSharding(dev)
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s)


def _param_structs(cfg, S):
    """The tree as a serving engine stores it (PR 30): matrices and
    embedding in cfg.dtype, head and norms in float32."""
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a, dt: S(a.shape, dt), shapes,
                        storage_dtypes(cfg))


def _pools(cfg, S):
    shape = (cfg.n_layers, PAGES, PAGE, cfg.n_kv_heads,
             pa.pool_head_dim(cfg.head_dim, "pallas"))
    return S(shape, cfg.dtype), S(shape, cfg.dtype)


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


def _row_write_is_one_scatter(text, calls, most=None):
    """A tick program's text: each call of `scatter_rows` is one
    `scatter` under scope `kv_write`, none of them a `while`; at most
    `most` instructions in all, read off PR 46's tree (+1.5%), so that
    a later form of the write does not unroll it by layer, slab or head
    (refused PR 45's multiplied a program's instructions, and warm
    set-up rose 25 s in `phi4flash-reason`)."""
    lines = text.splitlines()
    writes = [x for x in lines if " scatter(" in x and "kv_write" in x]
    assert len(writes) == calls, len(writes)
    loops = [x.strip()[:160] for x in lines if " while(" in x
             and ("kv_write" in x or "/scatter" in x)]
    assert not loops, loops
    if most is not None:
        assert sum(" = " in x for x in lines) <= most


# rows a token, layers a call, pages, table width: a group of each of the
# three merged-rows families at its cell's sizes (benchmarks/configs)
ROW_WRITES = {
    "phi4flash-window": (10, 8, 4608, 512),
    "phi4flash-full": (10, 1, 24576, 512),
    "smallthinker-window": (4, 9, 8192, 1024),
    "nemotron": (2, 2, 32768, 1088),
}


@pytest.mark.parametrize("T", [64, 512], ids=["decode", "chunk"])
@pytest.mark.parametrize("group", list(ROW_WRITES))
def test_row_write_is_one_scatter_at_the_cells_geometries(v5e, group, T):
    """`phi4flash.scatter_rows` alone (PR 46): ONE native `scatter` of
    single 128-lane rows on the donated pool, no `while` (a scatter of
    [rows, 128] windows compiles to a serial loop of one
    `dynamic-update-slice` a (layer, token): 17 ms of a 512-token tick
    in `smallthinker-assist`), and no copy of the pool."""
    from ray_tpu.models.phi4flash import scatter_rows
    kvh, layers, pages, width = ROW_WRITES[group]
    S = _on(v5e[0])
    pool = S((layers, pages, PAGE * kvh, 128), jnp.bfloat16)
    compiled = jax.jit(scatter_rows, donate_argnums=0).lower(
        pool, S((layers, T, kvh, 128), jnp.bfloat16),
        S((T, width), jnp.int32), S((T,), jnp.int32),
        S((T,), jnp.bool_)).compile()
    text = compiled.as_text()
    _row_write_is_one_scatter(text, 1)
    assert " while(" not in text
    flat = f"bf16[{layers * pages * PAGE * kvh},128]"
    copies = [line.strip()[:120] for line in text.splitlines()
              if f" = {flat}" in line and " copy(" in line]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == layers * pages * PAGE * kvh * 256


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


def _trinity_kernel_lowering(S, T, window, has_ctx):
    """The work-list kernel as `trinity-mixed` runs it: 48 query heads
    over 8 kv heads (a group of 6, where chat-open's is 2), head_dim
    128, a cache group's bf16 pools WHOLE and flattened over its layers
    (the layer's index rides in the page table), 32 slots, a table
    1,024 pages wide; window 4,096 names it `ragged_window_attention`."""
    kvh, group, d = 8, 6, 128
    pages = 7 * 6144 if window else 2 * 12288
    pool = S((pages, PAGE, kvh, d), jnp.bfloat16)
    new = S((T, kvh, d), jnp.bfloat16)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, kp, vp, tables, slots, pos, valid, start, kn, vn):
        return ragged_paged_attention_pallas(
            q, kp, vp, tables, slots, pos, valid, start, kn, vn,
            ctx_pages=-1 if has_ctx else 0, window=window)

    return jax.jit(run).lower(
        S((T, kvh * group, d), jnp.bfloat16), pool, pool, i32(32, 1024),
        i32(T), i32(T), S((T,), jnp.bool_), i32(32), new, new)


@pytest.mark.parametrize("T,window,has_ctx", [
    (8, 4096, True), (32, 4096, True), (32, None, True),
    (128, 4096, True), (512, 4096, True), (512, 4096, False),
    (512, None, True), (512, None, False)])
def test_window_kernel_compiles_at_the_cells_shapes(v5e, T, window,
                                                    has_ctx):
    """A group of 6 makes a 128-row query block 768 score rows a kv
    head: its scratch outgrows Mosaic's default scoped VMEM (refused:
    'Ran out of memory in memory space vmem') and the kernel asks for
    what it needs (`_vmem_limit`); T = 32 is the decode tick. The
    pools are read where they lie."""
    compiled = _trinity_kernel_lowering(_on(v5e[0]), T, window,
                                        has_ctx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    name = ("ragged_window_attention" if window
            else "ragged_paged_attention")
    assert f"{name}" in compiled.as_text()


def _phi4flash_kernel_lowering(S, T, window, has_ctx, merged=True):
    """The work-list kernel as `phi4flash-reason` runs it: 40 query
    heads of 128 ([q1 | 0] and [0 | q2]) over 10 K/V rows of 128 (a
    group of 4), 64 slots, a table 512 pages wide, a group's bf16 pools
    whole and flattened over its layers; the pools are MERGED-ROWS
    ([pages, 16 x 10 rows, 128]) because 10 heads are no multiple of
    the 8-row tile."""
    kvh, group, d = 10, 4, 128
    pages = 8 * 4608 if window else 24576
    pool = S((pages, PAGE * kvh, d) if merged else (pages, PAGE, kvh, d),
             jnp.bfloat16)
    new = S((T, kvh, d), jnp.bfloat16)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, kp, vp, tables, slots, pos, valid, start, kn, vn):
        return ragged_paged_attention_pallas(
            q, kp, vp, tables, slots, pos, valid, start, kn, vn,
            ctx_pages=-1 if has_ctx else 0, window=window,
            merged_rows=merged)

    return jax.jit(run).lower(
        S((T, kvh * group, d), jnp.bfloat16), pool, pool, i32(64, 512),
        i32(T), i32(T), S((T,), jnp.bool_), i32(64), new, new)


@pytest.mark.parametrize("T,window,has_ctx", [
    (8, 512, True), (64, 512, True), (64, None, True), (512, 512, True),
    (512, 512, False), (512, None, True), (512, None, False)])
def test_paired_head_kernels_compile_at_phi4flashs_shapes(v5e, T, window,
                                                          has_ctx):
    """Both attention kernels at the cell's widths: a 512 window inside
    one 512-token chunk, T = 64 the decode tick and the cross-decoder's
    rows. A head's keys are every tenth row of a context block, read by
    strided loads."""
    compiled = _phi4flash_kernel_lowering(_on(v5e[0]), T, window,
                                          has_ctx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    name = ("ragged_window_attention" if window
            else "ragged_paged_attention")
    assert name in compiled.as_text()


def test_ten_heads_a_page_are_refused_by_the_compiler(v5e):
    """Why the pools are merged-rows: a [page, 10, 128] page is padded
    to 16 heads in HBM and Mosaic will not slice it for a page's DMA."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _phi4flash_kernel_lowering(_on(v5e[0]), 64, None, True,
                                   merged=False).compile()


@pytest.mark.parametrize("T", [8, 64, 512])
def test_scan_kernel_compiles_at_phi4flashs_shapes(v5e, T):
    """`ssm_ragged_scan` at E 5120, N 16, 64 slots, the nine layers'
    state whole and aliased in place (layer 5's rows visited)."""
    from ray_tpu.ops import selective_scan as ssm
    S = _on(v5e[0])
    e, n, b = 5120, 16, 64
    f32 = lambda *shape: S(shape, jnp.float32)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(x, delta, a_t, bm, cm, d, slots, valid, first, last,
            last_idx, state):
        marks = ssm.Marks(first, first, last, last_idx >= 0)
        return ssm.selective_scan_ragged(
            x, delta, a_t, bm, cm, d, slots, valid, last_idx, marks,
            state, 5, impl="pallas")

    compiled = jax.jit(run, donate_argnums=11).lower(
        S((T, e), jnp.bfloat16), f32(T, e), f32(n, e), f32(T, n),
        f32(T, n), f32(e), i32(T), S((T,), jnp.bool_), i32(T), i32(T),
        i32(b), f32(9, b, n, e)).compile()
    assert "ssm_ragged_scan" in compiled.as_text()
    # in place: the state is not copied beside itself
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("T,temp_mb", [(0, 64), (512, 150)])
def test_phi4flashs_scanned_forwards_compile_at_the_cells_sizes(v5e, T,
                                                                temp_mb):
    """The whole forward at the published sizes and `phi4flash-reason`'s
    pools (T 0: the decode tick of 64 slots): all 32 layers as two scans
    over stacked pairs. A layer's matrices are read through a slice
    fused into the product: a copy of one (105 MB the widest) beside the
    stack would show in the temporaries (17 and 88 MB as compiled)."""
    from ray_tpu.models import phi4flash
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = phi4flash.Phi4FlashConfig()
    fam = family_of(cfg)
    b, page, pages = 64, 16, {"full": 24576, "window": 4608}
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: fam.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    made = [tuple(S(shape, dt) for shape, dt in g.array_shapes(
        pages.get(g.name, 0), page, b))
        for g in fam.cache_groups(cfg, "pallas")]
    kp, vp = tuple(m[0] for m in made), tuple(m[1] for m in made)
    tables = (S((b, 512), jnp.int32),) * 2
    i32 = lambda n: S((n,), jnp.int32)
    if T:
        def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
            return fam.ragged_forward(
                cfg, params, tok, slot, pos, valid, start, last, kp, vp,
                tables, ctx_pages=512, impl="pallas")
        args = (params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
                i32(b), i32(b), kp, vp, tables)
    else:
        def run(params, tok, pos, active, kp, vp, tables):
            return fam.decode_step(cfg, params, tok, pos, kp, vp, tables,
                                   active, impl="pallas")
        args = (params, i32(b), i32(b), S((b,), jnp.bool_), kp, vp, tables)
    n = len(args)
    compiled = jax.jit(run, donate_argnums=(n - 3, n - 2)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "ssm_ragged_scan" in text and text.count(" while(") >= 2
    # K and V of the two page groups (PR 46)
    _row_write_is_one_scatter(text, 4, 5610 if T else 4890)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_mb << 20
    # the pools and the state are updated in place
    assert mem.alias_size_in_bytes > 5.2e9


def test_a_group_of_two_keeps_its_compiler_parameters():
    """chat-open's geometry asks for no VMEM limit: its kernel's
    compiler parameters, and so its programs, are what they were."""
    from ray_tpu.ops.ragged_paged_attention import _vmem_limit
    assert _vmem_limit(128, 16, 8, 2, 128, 128, 2) == {}
    assert _vmem_limit(128, 8, 2, 4, 128, 128, 2) == {}
    big = _vmem_limit(128, 48, 8, 6, 128, 128, 2)
    assert 32 << 20 < big["vmem_limit_bytes"] <= 96 << 20


# (query heads, layers of the pool, slots, table width in pages): the
# kernel's item is 1,024 query rows at both (8 tokens x 128, 32 x 32)
_DSV3_LATENT, _KIMI_LATENT = (128, 5, 64, 512), (32, 7, 48, 1600)


@pytest.mark.parametrize("T,has_ctx,cell", [
    (8, True, _DSV3_LATENT), (64, True, _DSV3_LATENT),
    (512, True, _DSV3_LATENT), (512, False, _DSV3_LATENT),
    (512, True, _KIMI_LATENT), (48, True, _KIMI_LATENT)])
def test_mla_kernel_compiles_at_the_cells_shapes(v5e, T, has_ctx, cell):
    """`mla_ragged_attention` at DeepSeek-V3's published widths as
    dsv3-longchat runs it: 128 heads on one latent row of 640 lanes
    (576 + padding), values its first 512, the WHOLE 5-layer pool of
    16,384 pages handed over with a traced layer index (no layer's
    slice is copied out), 64 slots, a table 512 pages wide; a decode
    tick is T = 64. And as kimi-longdoc runs it: 32 heads on the same
    row, a 7-layer pool, 48 slots, a table 1,600 pages wide, a decode
    tick T = 48 (an in-batch block of 48 rows: no whole lane tile). The
    2-D new-row array is read at an aligned row: Mosaic refuses an
    unaligned dynamic slice of a tiled dim."""
    from ray_tpu.ops.mla_attention import mla_ragged_attention_pallas
    heads, layers, n_slots, table = cell
    S = _on(v5e[0])
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, pool, layer, tables, slots, pos, valid, start, new):
        return mla_ragged_attention_pallas(
            q, pool, layer, tables, slots, pos, valid, start, new,
            dv=512, scale=0.1147, ctx_pages=-1 if has_ctx else 0)

    compiled = jax.jit(run).lower(
        S((T, heads, 576), jnp.bfloat16),
        S((layers, 16384, PAGE, 1, 640), jnp.bfloat16), i32(),
        i32(n_slots, table), i32(T), i32(T), S((T,), jnp.bool_),
        i32(n_slots),
        S((T, 576), jnp.bfloat16)).compile()
    # the pool is read where it lies: no 1.68 GB copy of it, no 0.34 GB
    # copy of a layer of it
    assert compiled.memory_analysis().temp_size_in_bytes < 200 << 20


@pytest.mark.parametrize("T,picks,hidden,ffn", [
    (64, 8, 7168, 2048), (512, 8, 7168, 2048),     # dsv3-longchat
    (32, 4, 3072, 3072), (512, 4, 3072, 3072)])    # trinity-mixed
def test_grouped_experts_compile_at_the_cells_shapes(v5e, T, picks,
                                                     hidden, ffn):
    """`held_experts_ffn` by the kernels (`moe_grouped_up`,
    `moe_grouped_down`) over 16 held experts at both expert-layer cells'
    widths: a decode tick's row bound (64 x 8 = 512, 32 x 4 = 128) and a
    512-token tick's (4,096 and 2,048). The grid's middle bound is read
    on the device; the experts are read where they lie."""
    from ray_tpu.ops.moe import held_experts_ffn
    S = _on(v5e[0])
    bf16 = jnp.bfloat16

    def run(x, gates, took, wg, wi, wd):
        return held_experts_ffn(x, gates, took, (wg, wi), wd, act="swiglu",
                                picks=picks, impl="pallas")

    compiled = jax.jit(run).lower(
        S((T, hidden), bf16), S((T, 16), jnp.float32),
        S((T, 16), jnp.bool_), S((16, hidden, ffn), bf16),
        S((16, hidden, ffn), bf16), S((16, ffn, hidden), bf16)).compile()
    text = compiled.as_text()
    assert "moe_grouped_up" in text and "moe_grouped_down" in text
    # the sorted rows and their SwiGLU (T * picks rows of hidden and of
    # ffn), never a copy of an expert's 88 or 57 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20


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


# ---- the NemotronH family (`nemotron-agent`) ----------------------------

_NEMOTRON_CUT = dict(pattern="MEMEM*EMEMEM*EME", experts_held=(0, 64),
                     vocab_size=65536)


@pytest.mark.parametrize("T", [8, 64, 512])
def test_ssd_scan_kernel_compiles_at_nemotrons_shapes(v5e, T):
    """`ssd_ragged_scan` at 64 heads of 64 in 8 groups, N 128, 64 slots,
    the seven layers' state whole and aliased in place (layer 5's rows
    of the slots with a run visited): both bodies, the decode tick's T
    and a chunk's."""
    from ray_tpu.ops import selective_scan as ssm
    from ray_tpu.ops import ssd_scan
    S = _on(v5e[0])
    h, p, g, n, b = 64, 64, 8, 128, 64
    f32 = lambda *shape: S(shape, jnp.float32)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(x, dt, a, bm, cm, d, slots, valid, first, last, last_idx,
            state):
        marks = ssm.Marks(first, first, last, last_idx >= 0)
        return ssd_scan.ssd_ragged_scan(
            x, dt, a, bm, cm, d, marks, slots, valid, last_idx, state, 5,
            impl="pallas")

    compiled = jax.jit(run, donate_argnums=11).lower(
        S((T, h, p), jnp.bfloat16), f32(T, h), f32(h), f32(T, g, n),
        f32(T, g, n), f32(h), i32(T), S((T,), jnp.bool_), i32(T), i32(T),
        i32(b), f32(7, b, h, p, n)).compile()
    assert "ssd_ragged_scan" in compiled.as_text()
    # in place: the 0.96 GB of state is not copied beside itself
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.alias_size_in_bytes > 0.9e9


@pytest.mark.parametrize("T", [64, 512])
def test_relu2_grouped_experts_compile_at_nemotrons_shapes(v5e, T):
    """`held_experts_ffn(act="relu2")` by the kernels over 64 held
    experts of width 1856 (no whole number of 128-lane vectors) out of a
    stack of seven layers' 448, the layer's first expert a traced index: the experts
    are read where they lie (W_up out by in: stored in by out, XLA pads
    1856 to 1920 in a 4.4 GB copy of the stack before the kernel)."""
    from ray_tpu.ops.moe import held_experts_ffn
    S = _on(v5e[0])
    bf16 = jnp.bfloat16
    hidden, ffn, held, stack = 2688, 1856, 64, 7 * 64

    def run(x, gates, took, wu, wd, base):
        return held_experts_ffn(x, gates, took, (wu,), wd, act="relu2",
                                picks=6, impl="pallas", base=base)

    compiled = jax.jit(run).lower(
        S((T, hidden), bf16), S((T, held), jnp.float32),
        S((T, held), jnp.bool_), S((stack, ffn, hidden), bf16),
        S((stack, ffn, hidden), bf16), S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "moe_grouped_up_relu2" in text
    assert "moe_grouped_down_relu2" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20


@pytest.mark.parametrize("T,temp_mb", [(0, 96), (512, 160)])
def test_nemotrons_scanned_forwards_compile_at_the_cells_sizes(v5e, T,
                                                               temp_mb):
    """The whole forward at the published widths and `nemotron-agent`'s
    pools (T 0: the decode tick of 64 slots): 16 layers as ONE scan over
    seven units with a cond on the attention layer; 32 query heads over
    2 K/V heads through `ragged_paged_attention` on merged-rows pages of
    [16 x 2, 128]. Neither the pool (1.1 GB), the state (0.96 GB) nor a
    layer's held experts (0.64 GB) is copied: it would show in the
    temporaries (47 and 79 MB as compiled)."""
    from ray_tpu.models import nemotron_h
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = nemotron_h.NemotronHConfig(**_NEMOTRON_CUT)
    fam = family_of(cfg)
    args = _nemotron_args(S, cfg, fam, T)
    impl = "pallas"
    if T:
        def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
            return fam.ragged_forward(
                cfg, params, tok, slot, pos, valid, start, last, kp, vp,
                tables, ctx_pages=tables.shape[1], impl=impl)
    else:
        def run(params, tok, pos, active, kp, vp, tables):
            return fam.decode_step(cfg, params, tok, pos, kp, vp, tables,
                                   active, impl=impl)
    n = len(args)
    compiled = jax.jit(run, donate_argnums=(n - 3, n - 2)).lower(
        *args).compile()
    text = compiled.as_text()
    for kernel in ("ssd_ragged_scan", "moe_grouped_up_relu2",
                   "ragged_paged_attention"):
        assert kernel in text, kernel
    # K and V of the one page group (PR 46)
    _row_write_is_one_scatter(text, 2, 3550 if T else 2985)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_mb << 20
    # the pool and the state are updated in place
    assert mem.alias_size_in_bytes > 2.0e9
    # weights, pool and state: what the configuration's file reckons
    assert mem.argument_size_in_bytes == pytest.approx(12.60e9, rel=0.003)


def _nemotron_args(S, cfg, fam, T):
    """The forwards' arguments at `nemotron-agent`'s engine: 64 slots,
    32,768 pages of 16, a table 1,088 pages wide."""
    b, page, pages, width = 64, 16, 32768, 1088
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: fam.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    made = [tuple(S(shape, dt) for shape, dt in g.array_shapes(
        pages, page, b)) for g in fam.cache_groups(cfg, "pallas")]
    kp, vp = tuple(m[0] for m in made), tuple(m[1] for m in made)
    tables = S((b, width), jnp.int32)
    i32 = lambda n: S((n,), jnp.int32)
    if T:
        return (params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
                i32(b), i32(b), kp, vp, tables)
    return (params, i32(b), i32(b), S((b,), jnp.bool_), kp, vp, tables)


def test_nemotrons_gather_path_fits_beside_the_engine(v5e):
    """The checks' OTHER implementation at the cell's sizes, as
    `checks_nemotron_h._ticks` calls it: logits and counts alone, the
    pools and the state NOT donated (the kernel path runs on the same
    ones next). It has to fit in what 12.6 GB of weights, pool and state
    leave of 15.75: the state's copy (0.9 GB) and little else. W_up
    turned for `ragged_dot` was a 4.3 GB copy of the experts' stack
    (the first chip run of PR 41 died of it); W_up cut out a layer at a
    time and turned 1.2 GB."""
    from ray_tpu.models import nemotron_h
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = nemotron_h.NemotronHConfig(**_NEMOTRON_CUT)
    fam = family_of(cfg)

    def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
        return fam.ragged_forward(
            cfg, params, tok, slot, pos, valid, start, last, kp, vp,
            tables, ctx_pages=1024, impl="gather")[::3]

    compiled = jax.jit(run).lower(
        *_nemotron_args(S, cfg, fam, 512)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1700 << 20


# ---- SmallThinker: 28 query heads over 4, ReGLU experts in one stack ---

def _smallthinker_kernel_lowering(S, T, window, has_ctx, heads=32):
    """The work-list kernels as `smallthinker-assist` runs them: 4 K/V
    rows of 128 on MERGED-ROWS pages ([pages, 16 x 4 rows, 128]: 4 heads
    are no multiple of the 8-row tile), each K/V head's 7 query heads
    handed over as 8 (`smallthinker.kernel_group`: 32 heads), 48 slots, a
    table 1,024 pages wide, a group's bf16 pools whole and flattened
    over its layers (3 x 10,240 full, 9 x 8,192 window)."""
    kvh, d = 4, 128
    pages = 9 * 8192 if window else 3 * 10240
    pool = S((pages, PAGE * kvh, d), jnp.bfloat16)
    new = S((T, kvh, d), jnp.bfloat16)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, kp, vp, tables, slots, pos, valid, start, kn, vn):
        return ragged_paged_attention_pallas(
            q, kp, vp, tables, slots, pos, valid, start, kn, vn,
            ctx_pages=-1 if has_ctx else 0, window=window,
            merged_rows=True)

    return jax.jit(run).lower(
        S((T, heads, d), jnp.bfloat16), pool, pool, i32(48, 1024),
        i32(T), i32(T), S((T,), jnp.bool_), i32(48), new, new)


@pytest.mark.parametrize("T,window,has_ctx", [
    (16, 4096, True), (64, None, True), (512, 4096, True),
    (512, 4096, False), (512, None, True), (512, None, False)])
def test_both_attention_kernels_compile_at_smallthinkers_shapes(
        v5e, T, window, has_ctx):
    """Both attention kernels at 28-over-4 as the program hands it over
    (32 over 4 on the rows layout): T = 16 and 64 the decode ticks'
    buckets, 512 a chunk with and without context."""
    compiled = _smallthinker_kernel_lowering(_on(v5e[0]), T, window,
                                             has_ctx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    name = ("ragged_window_attention" if window
            else "ragged_paged_attention")
    assert name in compiled.as_text()


# the five head geometries the cells hand the work-list kernel (query
# heads over K/V rows of 128): every one reads its bf16 pages through
# `split_heads`, 32-bit words of two adjacent heads by a strided load
# off a bitcast view of the page buffer
PAIRED_GEOMETRIES = [
    # cell, kv heads, query heads a kv head
    ("trinity-mixed", 8, 6), ("chat-open", 8, 2),
    ("smallthinker-assist", 4, 8), ("nemotron-agent", 2, 16),
    ("phi4flash-reason", 10, 4),
]


@pytest.mark.parametrize("merged", [False, True], ids=["tile", "rows"])
@pytest.mark.parametrize("cell,kvh,group", PAIRED_GEOMETRIES,
                         ids=[g[0] for g in PAIRED_GEOMETRIES])
def test_paired_head_loads_compile_in_both_pool_forms(v5e, cell, kvh,
                                                      group, merged):
    """The reshaped, bitcast view of a page block and its strided load
    of words (stride kvh / 2: 4, 4, 2, 1 and 5) pass Mosaic for either
    pool form at a chunk's 128 query rows, over a table 1,024 pages
    wide. Ten heads in the tile form are the one refusal, and it is the
    page DMA's (`test_ten_heads_a_page_are_refused_by_the_compiler`)."""
    S = _on(v5e[0])
    T, d, pages = 512, 128, 20000
    pool = S((pages, PAGE * kvh, d) if merged else (pages, PAGE, kvh, d),
             jnp.bfloat16)
    new = S((T, kvh, d), jnp.bfloat16)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, kp, vp, tables, slots, pos, valid, start, kn, vn):
        return ragged_paged_attention_pallas(
            q, kp, vp, tables, slots, pos, valid, start, kn, vn,
            merged_rows=merged)

    lowered = jax.jit(run).lower(
        S((T, kvh * group, d), jnp.bfloat16), pool, pool, i32(32, 1024),
        i32(T), i32(T), S((T,), jnp.bool_), i32(32), new, new)
    if kvh == 10 and not merged:
        with pytest.raises(Exception, match="aligned to tiling"):
            lowered.compile()
        return
    assert "ragged_paged_attention" in lowered.compile().as_text()


def test_28_query_heads_are_refused_by_the_compiler(v5e):
    """Why `smallthinker._attend_padded` exists: the kernels move a
    tick's queries in tiles of 8 heads, and 28 are three and a half."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _smallthinker_kernel_lowering(_on(v5e[0]), 64, None, True,
                                      heads=28).compile()


@pytest.mark.parametrize("T", [16, 64, 512])
def test_reglu_grouped_experts_compile_at_smallthinkers_shapes(v5e, T):
    """`held_experts_ffn(act="reglu")` by the kernels over 64 held
    experts of width 768 out of the stack of twelve layers' 768 (3.0 GB a
    projection), the layer's first expert a traced index, the plan made
    ahead by `held_plan`: the experts are read where they lie, and no
    padded or sliced copy of a stack shows in the temporaries (PR 41's
    4.4 GB lesson)."""
    from ray_tpu.ops.moe import held_experts_ffn, held_plan
    S = _on(v5e[0])
    bf16 = jnp.bfloat16
    hidden, ffn, held, stack = 2560, 768, 64, 12 * 64

    def run(x, gates, took, wg, wi, wd, base):
        plan = held_plan(took, picks=6, impl="pallas")
        return held_experts_ffn(x, gates, took, (wg, wi), wd, act="reglu",
                                picks=6, impl="pallas", base=base,
                                plan=plan)

    compiled = jax.jit(run).lower(
        S((T, hidden), bf16), S((T, held), jnp.float32),
        S((T, held), jnp.bool_), S((stack, hidden, ffn), bf16),
        S((stack, hidden, ffn), bf16), S((stack, ffn, hidden), bf16),
        S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "moe_grouped_up_reglu" in text
    assert "moe_grouped_down_reglu" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20


def _smallthinker_args(S, cfg, fam, T, impl="pallas"):
    """The forwards' arguments at `smallthinker-assist`'s engine: 48
    slots, 10,240 + 8,192 pages of 16, tables 1,024 pages wide."""
    b, page, width = 48, 16, 1024
    pages = {"full": 10240, "window": 8192}
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: fam.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    made = [tuple(S(shape, dt) for shape, dt in g.array_shapes(
        pages[g.name], page, b)) for g in fam.cache_groups(cfg, impl)]
    kp, vp = tuple(m[0] for m in made), tuple(m[1] for m in made)
    tables = S((2, b, width), jnp.int32)
    i32 = lambda n: S((n,), jnp.int32)
    if T:
        return (params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
                i32(b), i32(b), kp, vp, tables)
    return (params, i32(b), i32(b), S((b,), jnp.bool_), kp, vp, tables)


@pytest.mark.parametrize("T,temp_mb", [(0, 48), (512, 64)])
def test_smallthinkers_forwards_compile_at_the_cells_sizes(v5e, T,
                                                           temp_mb):
    """The whole forward at the published widths and
    `smallthinker-assist`'s pools (T 0: the decode tick of 48 slots): 12
    layers unrolled, each a router ahead of attention, one of the two
    attention kernels and the two grouped ReGLU kernels out of the
    768-expert stacks. Neither a pool (1.0 + 2.4 GB) nor a stack (3.0 GB
    each) is copied: it would show in the temporaries (16 and 27 MB as
    compiled, in 3 to 5 s)."""
    from ray_tpu.models import smallthinker
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = smallthinker.SmallThinkerConfig(n_layers=12)
    fam = family_of(cfg)
    args = _smallthinker_args(S, cfg, fam, T)
    impl = "pallas"
    if T:
        def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
            return fam.ragged_forward(
                cfg, params, tok, slot, pos, valid, start, last, kp, vp,
                tables, ctx_pages=tables.shape[-1], impl=impl)
    else:
        def run(params, tok, pos, active, kp, vp, tables):
            return fam.decode_step(cfg, params, tok, pos, kp, vp, tables,
                                   active, impl=impl)
    n = len(args)
    compiled = jax.jit(run, donate_argnums=(n - 3, n - 2)).lower(
        *args).compile()
    text = compiled.as_text()
    for kernel in ("moe_grouped_up_reglu", "moe_grouped_down_reglu",
                   "ragged_paged_attention", "ragged_window_attention"):
        assert kernel in text, kernel
    # K and V of the two page groups (PR 46)
    _row_write_is_one_scatter(text, 4, 8280 if T else 7750)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_mb << 20
    # both groups' pools are updated in place
    assert mem.alias_size_in_bytes > 3.4e9
    # weights and pools: what the configuration's file reckons
    assert mem.argument_size_in_bytes == pytest.approx(14.55e9, rel=0.003)


def test_smallthinkers_gather_path_fits_beside_the_engine(v5e):
    """The checks' OTHER implementation at the cell's sizes, as
    `checks_trinity._ticks` calls it for this family: logits and counts
    alone, the pools NOT donated. It has to fit in what 14.55 GB of
    weights and pools leave of 15.75 GiB: the loop over the held experts
    takes each out of the stacks by index (no copy of a stack)."""
    from ray_tpu.models import smallthinker
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = smallthinker.SmallThinkerConfig(n_layers=12)
    fam = family_of(cfg)

    def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
        return fam.ragged_forward(
            cfg, params, tok, slot, pos, valid, start, last, kp, vp,
            tables, ctx_pages=1024, impl="gather")[::3]

    compiled = jax.jit(run).lower(
        *_smallthinker_args(S, cfg, fam, 512, "gather")).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 400 << 20


# ---- Kimi Linear: kimi-longdoc's shapes ---------------------------------

_KIMI_CUT = dict(experts_held=(0, 16), vocab_size=20480)


@pytest.mark.parametrize("T", [8, 48, 512])
def test_kda_scan_kernel_compiles_at_kimis_shapes(v5e, T):
    """`kda_ragged_scan` at 32 heads of 128 x 128, 48 slots, the twenty
    layers' state whole and aliased in place (layer 5's rows of the
    slots with a run visited): both bodies, the decode tick's T (48,
    padded to 64: the levels halve a power of two) and a chunk's (the
    levels' masked products, the blocked solve and the state's
    transposed product through Mosaic)."""
    from ray_tpu.ops import kda_scan
    from ray_tpu.ops import selective_scan as ssm
    S = _on(v5e[0])
    h, d, b = 32, 128, 48
    f32 = lambda *shape: S(shape, jnp.float32)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, k, v, g, beta, slots, valid, first, last, last_idx, state):
        marks = ssm.Marks(first, first, last, last_idx >= 0)
        return kda_scan.kda_ragged_scan(
            q, k, v, g, beta, marks, slots, valid, last_idx, state, 5,
            impl="pallas")

    compiled = jax.jit(run, donate_argnums=10).lower(
        f32(T, h, d), f32(T, h, d), f32(T, h, d), f32(T, h, d), f32(T, h),
        i32(T), S((T,), jnp.bool_), i32(T), i32(T), i32(b),
        f32(20, b, h, d, d)).compile()
    assert "kda_ragged_scan" in compiled.as_text()
    # in place: the 2.01 GB of state is not copied beside itself
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.alias_size_in_bytes > 2.0e9


@pytest.mark.parametrize("T,has_ctx", [(48, True), (512, True),
                                       (512, False)])
def test_mla_kernel_compiles_at_32_heads(v5e, T, has_ctx):
    """`mla_ragged_attention` as kimi-longdoc runs it: 32 heads (256
    query rows an item, where dsv3-longchat has 1,024) on the same row
    of 640 lanes, the 7-layer pool of 16,384 pages whole with a traced
    index IN THE GROUP, 48 slots, a table 1,600 pages wide (25,600
    tokens)."""
    from ray_tpu.ops.mla_attention import mla_ragged_attention_pallas
    S = _on(v5e[0])
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(q, pool, layer, tables, slots, pos, valid, start, new):
        return mla_ragged_attention_pallas(
            q, pool, layer, tables, slots, pos, valid, start, new,
            dv=512, scale=192 ** -0.5, ctx_pages=-1 if has_ctx else 0)

    compiled = jax.jit(run).lower(
        S((T, 32, 576), jnp.bfloat16),
        S((7, 16384, PAGE, 1, 640), jnp.bfloat16), i32(),
        i32(48, 1600), i32(T), i32(T), S((T,), jnp.bool_), i32(48),
        S((T, 576), jnp.bfloat16)).compile()
    assert "mla_ragged_attention" in compiled.as_text()
    # the pool is read where it lies: no 2.35 GB copy of it
    assert compiled.memory_analysis().temp_size_in_bytes < 100 << 20


def _kimi_args(S, cfg, fam, T, impl="pallas", pages=16384):
    """The forwards' arguments at `kimi-longdoc`'s engine: 48 slots,
    pages of 16, a table 1,600 pages wide."""
    b, page, width = 48, 16, 1600
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: fam.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    made = [tuple(S(shape, dt) for shape, dt in g.array_shapes(
        pages, page, b)) for g in fam.cache_groups(cfg, impl)]
    kp = tuple(m[0] for m in made)
    vp = tuple(m[1] if len(m) > 1 else None for m in made)
    tables = S((b, width), jnp.int32)
    i32 = lambda n: S((n,), jnp.int32)
    if T:
        return (params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
                i32(b), i32(b), kp, vp, tables)
    return (params, i32(b), i32(b), S((b,), jnp.bool_), kp, vp, tables)


@pytest.mark.parametrize("T,temp_mb", [(0, 32), (512, 96)])
def test_kimis_scanned_forwards_compile_at_the_cells_sizes(v5e, T, temp_mb):
    """The whole forward at the published widths and `kimi-longdoc`'s
    pool and state (T 0: the decode tick of 48 slots): 27 layers as ONE
    scan over seven units with a loop over a unit's KDA layers inside;
    the latent pool (2.35 GB), the state (2.08 GB) and the held experts'
    stacks (5.9 GB) are not copied: it would show in the temporaries
    (2 and 55 MB as compiled)."""
    from ray_tpu.models import kimi_linear
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = kimi_linear.KimiLinearConfig(**_KIMI_CUT)
    fam = family_of(cfg)
    args = _kimi_args(S, cfg, fam, T)
    impl = "pallas"
    if T:
        def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
            return fam.ragged_forward(
                cfg, params, tok, slot, pos, valid, start, last, kp, vp,
                tables, ctx_pages=tables.shape[1], impl=impl)
    else:
        def run(params, tok, pos, active, kp, vp, tables):
            return fam.decode_step(cfg, params, tok, pos, kp, vp, tables,
                                   active, impl=impl)
    n = len(args)
    compiled = jax.jit(run, donate_argnums=(n - 3, n - 2)).lower(
        *args).compile()
    text = compiled.as_text()
    for kernel in ("kda_ragged_scan", "mla_ragged_attention",
                   "moe_grouped_up", "moe_grouped_down"):
        assert kernel in text, kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_mb << 20
    # the pool, the conv inputs and the state are updated in place
    assert mem.alias_size_in_bytes > 4.4e9
    # weights, pool and state: what the configuration's file reckons
    assert mem.argument_size_in_bytes == pytest.approx(13.03e9, rel=0.003)


def test_kimis_gather_path_fits_beside_the_engine_when_donated(v5e):
    """The checks' OTHER implementation at the cell's sizes, as
    `checks_kimi_linear._ticks` calls it: the pool and the state DONATED
    and handed back (not donated, 4.5 GB more: the pool's and the
    state's copies, which do not fit beside 13.0 GB). What is left
    (382 MB as compiled) is the latent gather's blocks of 2^18 context
    rows and a layer's experts cut out of their stacks."""
    from ray_tpu.models import kimi_linear
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = kimi_linear.KimiLinearConfig(**_KIMI_CUT)
    fam = family_of(cfg)

    def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
        return fam.ragged_forward(
            cfg, params, tok, slot, pos, valid, start, last, kp, vp,
            tables, ctx_pages=1024, impl="gather")

    # the engine's own pool: rows of 640 lanes, as the kernels keep it
    args = _kimi_args(S, cfg, fam, 512, "pallas")
    compiled = jax.jit(run, donate_argnums=(7, 8)).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 600 << 20


# ---- the GraniteHybrid family (`granite-concurrent`) --------------------

@pytest.mark.parametrize("T", [48, 512])
def test_ssd_scan_kernel_compiles_at_one_group_of_64_heads(v5e, T):
    """`ssd_ragged_scan` at 64 heads of 64 in ONE group, N 128, 48 slots,
    the 36 layers' state whole and aliased in place: the grid's first
    axis is eight head tiles, a step's state block [8, 64, 128] as at
    Nemotron's eight groups; the decode tick's T and a chunk's."""
    from ray_tpu.ops import selective_scan as ssm
    from ray_tpu.ops import ssd_scan
    S = _on(v5e[0])
    h, p, g, n, b = 64, 64, 1, 128, 48
    f32 = lambda *shape: S(shape, jnp.float32)
    i32 = lambda *shape: S(shape, jnp.int32)

    def run(x, dt, a, bm, cm, d, slots, valid, first, last, last_idx,
            state):
        marks = ssm.Marks(first, first, last, last_idx >= 0)
        return ssd_scan.ssd_ragged_scan(
            x, dt, a, bm, cm, d, marks, slots, valid, last_idx, state, 20,
            impl="pallas")

    compiled = jax.jit(run, donate_argnums=11).lower(
        S((T, h, p), jnp.bfloat16), f32(T, h), f32(h), f32(T, g, n),
        f32(T, g, n), f32(h), i32(T), S((T,), jnp.bool_), i32(T), i32(T),
        i32(b), f32(36, b, h, p, n)).compile()
    assert "ssd_ragged_scan" in compiled.as_text()
    # in place: the 3.6 GB of state is not copied beside itself
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.alias_size_in_bytes > 3.6e9


def _granite_args(S, cfg, fam, T):
    """The forwards' arguments at `granite-concurrent`'s engine: 48
    slots, 12,288 pages of 16, a table 192 pages wide."""
    b, page, pages, width = 48, 16, 12288, 192
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: fam.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    made = [tuple(S(shape, dt) for shape, dt in g.array_shapes(
        pages, page, b)) for g in fam.cache_groups(cfg, "pallas")]
    kp, vp = tuple(m[0] for m in made), tuple(m[1] for m in made)
    tables = S((b, width), jnp.int32)
    i32 = lambda n: S((n,), jnp.int32)
    if T:
        return (params, i32(T), i32(T), i32(T), S((T,), jnp.bool_),
                i32(b), i32(b), kp, vp, tables)
    return (params, i32(b), i32(b), S((b,), jnp.bool_), kp, vp, tables)


@pytest.mark.parametrize("T,impl,temp_mb", [
    (0, "pallas", 64), (512, "pallas", 112), (0, "gather", 224)],
    ids=["decode", "chunk", "the checks' gather decode"])
def test_granites_scanned_forwards_copy_no_state(v5e, T, impl, temp_mb):
    """The whole model at the published widths and `granite-concurrent`'s
    pools (T 0: the decode tick of 48 slots): 40 layers as ONE scan over
    36 units with a cond on the attention layer that takes the residual
    stream alone. The state (3.67 GB) and the pools (1.6 GB) are the
    scan's carry, donated, aliased and updated in place: a copy of the
    state would show in the temporaries (42, 75 and 145 MB as compiled;
    with the state handed through the cond's branches the attention
    branch copied it, `copy` of f32[36,48,64,64,128], 1.2 GB of
    temporaries). The gather path is the checks' other implementation,
    donated as `checks_granite_hybrid._ticks` hands it."""
    from ray_tpu.models import granite_hybrid
    from ray_tpu.models.family import family_of
    S = _on(v5e[0])
    cfg = granite_hybrid.GraniteHybridConfig()
    fam = family_of(cfg)
    args = _granite_args(S, cfg, fam, T)
    if T:
        def run(params, tok, slot, pos, valid, start, last, kp, vp, tables):
            return fam.ragged_forward(
                cfg, params, tok, slot, pos, valid, start, last, kp, vp,
                tables, ctx_pages=tables.shape[1], impl=impl)
    else:
        def run(params, tok, pos, active, kp, vp, tables):
            return fam.decode_step(cfg, params, tok, pos, kp, vp, tables,
                                   active, impl=impl)
    n = len(args)
    compiled = jax.jit(run, donate_argnums=(n - 3, n - 2)).lower(
        *args).compile()
    text = compiled.as_text()
    if impl == "pallas":
        for kernel in ("ssd_ragged_scan", "ragged_paged_attention"):
            assert kernel in text, kernel
    # nothing of the state's shape is made anew
    assert " copy(" not in "".join(
        line for line in text.splitlines()
        if "= f32[36,48,64,64,128]" in line)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_mb << 20
    assert mem.alias_size_in_bytes > 5.2e9
    # weights, pools and state: what the configuration's file reckons
    assert mem.argument_size_in_bytes == pytest.approx(11.665e9, rel=0.003)
