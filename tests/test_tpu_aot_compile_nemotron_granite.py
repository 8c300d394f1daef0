"""The compiler gate (`test_tpu_aot_compile.py`), the SSD-scan families'
part: NemotronH (`nemotron-agent`) and GraniteHybrid
(`granite-concurrent`).
"""

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import _on, _row_write_is_one_scatter, v5e

pytestmark = pytest.mark.usefixtures("no_compile_cache")  # aot_v5e.py


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
