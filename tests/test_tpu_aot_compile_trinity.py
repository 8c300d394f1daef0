"""The compiler gate (`test_tpu_aot_compile.py`), Trinity's part:
`trinity-mixed`'s window kernel at a group of 6, and the work-list
kernel's paired-head loads at every cell's head geometry.
"""

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import PAGE, _on, v5e
from ray_tpu.ops.ragged_paged_attention import ragged_paged_attention_pallas

pytestmark = pytest.mark.usefixtures("no_compile_cache")  # aot_v5e.py


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
