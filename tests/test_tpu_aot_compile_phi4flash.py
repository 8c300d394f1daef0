"""The compiler gate (`test_tpu_aot_compile.py`), Phi4Flash's part:
`phi4flash-reason`'s paired-head attention kernels on merged-rows pages,
the selective scan, the row write of every merged-rows family, and the
whole scanned forwards at the cell's sizes.
"""

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import PAGE, _on, _row_write_is_one_scatter, v5e
from ray_tpu.ops.ragged_paged_attention import ragged_paged_attention_pallas

pytestmark = pytest.mark.usefixtures("no_compile_cache")  # aot_v5e.py


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
