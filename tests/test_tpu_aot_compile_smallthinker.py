"""The compiler gate (`test_tpu_aot_compile.py`), SmallThinker's part:
`smallthinker-assist`'s kernels and whole forwards.
"""

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import PAGE, _on, _row_write_is_one_scatter, v5e
from ray_tpu.ops.ragged_paged_attention import ragged_paged_attention_pallas

pytestmark = pytest.mark.usefixtures("no_compile_cache")  # aot_v5e.py


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
