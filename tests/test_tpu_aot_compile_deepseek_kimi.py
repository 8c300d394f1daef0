"""The compiler gate (`test_tpu_aot_compile.py`), the latent-attention
families' part: `mla_ragged_attention` and the grouped experts at
`dsv3-longchat`'s shapes, and Kimi Linear's KDA scan, kernel and whole
forwards at `kimi-longdoc`'s.
"""

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import PAGE, _on, v5e

pytestmark = pytest.mark.usefixtures("no_compile_cache")  # aot_v5e.py


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
