"""DeepSeek-V3 through the engine's seam, at a small size with seeded
weights on the CPU: the system against the plain reference
(benchmarks/lib/reference_deepseek_v3.py) for a ragged tick and for
prefill then decode through the latent cache; absorbed against
non-absorbed attention; the routing against the equations on hand-made
scores; the YaRN table; the chip's share against the uncut layer; the
cache-row description; the engine end to end, and what it refuses."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import functools

import numpy as np
import pytest

from benchmarks.lib import reference_deepseek_v3 as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.llm._internal.kv_cache import CacheRow
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import deepseek_v3 as ds
from ray_tpu.models import llama
from ray_tpu.models.family import family_of, resolve_config
from ray_tpu.ops import mla_attention as mla_ops
from ray_tpu.ops import moe

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def model_dict(cfg):
    """The published keys the reference reads, from a config."""
    return {
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group,
        "num_experts_per_tok": cfg.moe_top_k,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.rope_factor,
            "beta_fast": cfg.rope_beta_fast,
            "beta_slow": cfg.rope_beta_slow, "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim,
            "original_max_position_embeddings": cfg.rope_original_max},
    }


B, PAGE, MAXP = 4, 4, 16


def _tables():
    t = np.zeros((B, MAXP), np.int32)
    t[:3] = 1 + np.arange(3 * MAXP).reshape(3, MAXP)
    return jnp.asarray(t)


def _gather_latent(pool, page_tables, width):
    """pool: [L, P, page, 1, W]; page_tables: [B, n] -> each slot's
    rows in position order, [L, B, n * page, width]: the whole-table
    gather the dense comparison takes."""
    l, _, page, _, w = pool.shape
    b, n = page_tables.shape
    return pool[:, page_tables].reshape(l, b, n * page, w)[..., :width]


def _pack(rng, cfg, history, plan, t):
    toks, slot, pos = (np.zeros(t, np.int32) for _ in range(3))
    valid = np.zeros(t, bool)
    start, last = np.zeros(B, np.int32), np.zeros(B, np.int32)
    cur = 0
    for s, st, n in plan:
        new = rng.integers(3, cfg.vocab_size, n)
        history[s].extend(int(x) for x in new)
        toks[cur:cur + n] = new
        slot[cur:cur + n] = s
        pos[cur:cur + n] = np.arange(st, st + n)
        valid[cur:cur + n] = True
        start[s], last[s] = st, cur + n - 1
        cur += n
    return tuple(jnp.asarray(a) for a in
                 (toks, slot, pos, valid, start, last))


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, ctx_pages):
    """The family's forwards as ONE program each, as the engine runs
    them (eagerly, every primitive of a forward is compiled by itself);
    `ctx_pages` None is the decode tick."""
    if ctx_pages is None:
        return jax.jit(functools.partial(ds.decode_step, cfg, impl=impl))
    return jax.jit(functools.partial(ds.ragged_forward, cfg,
                                     ctx_pages=ctx_pages, impl=impl))


def _reference_rows(cfg, params, seqs):
    """The reference's logits of every sequence, [len(seqs), n, vocab].
    It is causal, so all go in padded to ONE length n and a row is read
    at its own position (eagerly, each primitive of the reference is
    compiled again at every new length: most of these tests' time)."""
    m = model_dict(cfg)
    n = -(-max(map(len, seqs)) // 32) * 32
    return np.stack([np.asarray(ref.logits(
        m, params, jnp.asarray(list(s) + [0] * (n - len(s))), cfg.held))
        for s in seqs])


def _reference_last(cfg, params, history):
    rows = _reference_rows(cfg, params, history)
    return np.stack([r[len(h) - 1] for r, h in zip(rows, history)])


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_ragged_tick_then_decode_through_the_cache_match_the_reference(
        impl):
    """Prefill, a mixed tick (a decode row, a chunk against its cached
    context, a new prompt) and a decode tick through the latent cache,
    against the reference's full forward of each history; the held
    experts are a strict share (4..11 of 16)."""
    cfg = ds.config("debug", experts_held=(4, 12), **F32)
    params = ds.init_params(cfg, jax.random.PRNGKey(3))
    width = mla_ops.latent_row_width(cfg.kv_lora_rank,
                                     cfg.qk_rope_head_dim, impl)
    pool = jnp.zeros((cfg.n_layers, 64, PAGE, 1, width), jnp.float32)
    tables = _tables()
    rng = np.random.default_rng(0)
    history = [[], [], []]
    batch = _pack(rng, cfg, history, [(0, 0, 21), (1, 0, 9)], 32)
    _, pool, none, _ = _tick_fn(cfg, impl, 0)(
        params, *batch, pool, None, tables)
    assert none is None                    # one pool: no second one back
    batch = _pack(rng, cfg, history, [(0, 21, 1), (1, 9, 13), (2, 0, 7)],
                  32)
    logits, pool, _, counts = _tick_fn(cfg, impl, 8)(
        params, *batch, pool, None, tables)
    want = _reference_last(cfg, params, history)
    np.testing.assert_allclose(np.asarray(logits)[:3], want, atol=2e-5)
    # 21 valid tokens, 4 picks each, 8 of 16 experts held: some land
    assert counts.shape == (cfg.n_moe_layers, cfg.n_held)
    assert 0 < int(counts.sum()) <= 21 * cfg.moe_top_k * cfg.n_moe_layers

    toks = rng.integers(3, cfg.vocab_size, B)
    posn = np.zeros(B, np.int32)
    posn[:3] = [len(h) for h in history]
    for s in range(3):
        history[s].append(int(toks[s]))
    logits, pool, _, counts = _tick_fn(cfg, impl, None)(
        params, jnp.asarray(toks, jnp.int32), jnp.asarray(posn),
        pool, None, tables, jnp.asarray(np.arange(B) < 3))
    want = _reference_last(cfg, params, history)
    np.testing.assert_allclose(np.asarray(logits)[:3], want, atol=2e-5)
    # the inactive slot's token routes nowhere
    assert int(counts.sum()) <= 3 * cfg.moe_top_k * cfg.n_moe_layers


def test_absorbed_attention_equals_the_non_absorbed_form():
    """One layer's attention block: the system's absorbed form over
    latent rows (no cache: everything in the tick) against the
    reference's per-head keys and values."""
    cfg = ds.config("debug", **F32)
    layer = ds.init_params(cfg, jax.random.PRNGKey(1))["layers"][0]
    s = 19
    x = jax.random.normal(jax.random.PRNGKey(2), (s, cfg.hidden))
    pos = jnp.arange(s)
    q, rows = ds.mla_project(cfg, layer, x, *ds.rope_cos_sin(cfg, pos))
    zeros = jnp.zeros((s,), jnp.int32)
    o_lat = mla_ops.mla_attention_gather(
        q, jnp.zeros((1, 0, cfg.latent_width)), rows, zeros, pos,
        jnp.ones((s,), bool), jnp.zeros((1,), jnp.int32),
        dv=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    got = ds.mla_output(cfg, layer, o_lat)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(model_dict(cfg), layer, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def _tick(plan, t, n_slots):
    """Token arrays and starts of a tick packed in `plan`'s order."""
    slot, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    start = np.zeros(n_slots, np.int32)
    cur = 0
    for s, st, n in plan:
        slot[cur:cur + n], pos[cur:cur + n] = s, np.arange(st, st + n)
        valid[cur:cur + n] = True
        start[s] = st
        cur += n
    return (jnp.asarray(slot), jnp.asarray(pos), jnp.asarray(valid),
            jnp.asarray(start))


def _kernel_against_gather(rng, pool, tables, args, layer, *, heads, w,
                           dv, atol):
    """The interpreted kernel beside the dense gather on one tick, at
    random float32 queries and rows; returns the kernel's output."""
    t = args[0].shape[0]
    q = jnp.asarray(rng.normal(size=(t, heads, w)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(t, w)), jnp.float32)
    want = mla_ops.mla_attention_gather(
        q, _gather_latent(pool, tables, w)[layer], new, *args, dv=dv,
        scale=0.3)
    run = lambda tb: mla_ops.mla_ragged_attention_pallas(
        q, pool, layer, tb, *args, new, dv=dv, scale=0.3, interpret=True)
    got = run(tables)
    valid = np.asarray(args[2])
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want) * valid[:, None, None],
        atol=atol)
    return got, run


# heads x tokens an item, row width, value width: the debug preset's, a
# value width of whole lane tiles (the cells' 512 of 640), and both
# cells' items of 1,024 query rows (8 tokens x 128 heads, 32 x 32)
@pytest.mark.parametrize("heads,w,dv", [
    (4, 24, 16), (4, 640, 512), (128, 24, 16), (32, 24, 16)])
def test_kernel_interpret_matches_gather_on_a_mixed_tick(heads, w, dv):
    rng = np.random.default_rng(5)
    t = 32
    pool = jnp.asarray(rng.normal(size=(2, 40, PAGE, 1, w)), jnp.float32)
    plan = [(0, 13, 1), (1, 5, 19), (2, 0, 4)]
    args = _tick(plan, t, B)
    _kernel_against_gather(rng, pool, _tables(), args, 1, heads=heads,
                           w=w, dv=dv, atol=4e-6)
    # the host's count of the kernel's work: 1 + ceil(19 / q_blk) + 1
    segs = [(st, n) for _, st, n in plan]
    q_blk = mla_ops.mla_q_block(t, heads)
    assert mla_ops.mla_work_counts(segs, t, PAGE, MAXP, heads)[0] == (
        2 + -(-19 // q_blk))


LONG_PAGE = 16      # the cell's page size: 8 pages a block of 128 keys


def _long_tables(n_slots, pages_per_slot):
    return jnp.asarray((1 + np.arange(n_slots * pages_per_slot,
                                      dtype=np.int32)
                        ).reshape(n_slots, pages_per_slot))


# the chunk's cached length: 4 blocks of 128 keys and 18 keys, 4 blocks
# to the key (every context block whole: no mask anywhere), and one key
# past them (a last block of one key)
@pytest.mark.parametrize("chunk_ctx,ctx_blocks", [(530, 5), (512, 4),
                                                   (513, 5)])
def test_kernel_interpret_sweeps_a_context_of_several_kv_blocks(
        chunk_ctx, ctx_blocks):
    """Contexts of 3, 4 and 5 blocks of 128 keys (8 pages a block): the
    double-buffered page DMA with its prefetch of the next block, a last
    block that is partly filled (the `last_page` clamp: 401 = 25 pages
    and a token, 530 = 4 blocks and 18 keys) or filled to the key, a
    chunk of three query blocks against one, and decode rows, against
    the dense gather."""
    rng = np.random.default_rng(11)
    heads, w, dv, t, per = 4, 24, 16, 32, 40          # 640 tokens a slot
    plan = [(0, 401, 1), (1, chunk_ctx, 19), (2, 384, 1), (3, 0, 5)]
    tables, args = _long_tables(4, per), _tick(plan, t, 4)
    pool = jnp.asarray(rng.normal(size=(2, 4 * per + 2, LONG_PAGE, 1, w)),
                       jnp.float32)
    got, run = _kernel_against_gather(rng, pool, tables, args, 1,
                                      heads=heads, w=w, dv=dv, atol=3e-6)
    # the host's count: 1 + 1 + 1 + 1 items (19 tokens are one item at
    # 4 heads); context blocks 4 + the chunk's + 3 + 0, and one
    # in-batch block an item
    segs = [(st, n) for _, st, n in plan]
    assert mla_ops.mla_work_counts(segs, t, LONG_PAGE, per, heads) == (
        4, 7 + ctx_blocks + 4)
    assert mla_ops.mla_work_counts(segs, t, LONG_PAGE, per) == (
        6, 7 + 3 * ctx_blocks + 6)
    # a moved page table row or one key more or less of context shows
    moved = tables.at[1, 30].set(int(tables[0, 3]))
    assert float(jnp.abs(run(moved) - got)[1:20].max()) > 1e-3


# decode rows packed first, and the long chunk's length: 300 tokens are
# items of whole blocks, of a diagonal block and a short last item; 257
# end in an item of ONE token behind two whole blocks of in-batch keys
@pytest.mark.parametrize("lead_rows,chunk,counts", [
    (0, 300, None), (1, 300, None), (1, 257, (15, 45)), (2, 290, None)])
def test_kernel_interpret_counts_each_in_batch_key_once(lead_rows, chunk,
                                                        counts):
    """A chunk longer than one block of in-batch keys (128): a block is
    one aligned read of 128 flat rows, the first from the aligned row at
    or before the chunk's first, and no key may be counted in two
    blocks. With decode rows packed first the chunk starts off the
    16-row alignment (found on the chip at the cell's sizes, PR 27:
    every token past a chunk's first 128 saw 16 keys twice). At 512
    flat tokens every block is a whole lane tile: the row's sum is kept
    a lane, as on the chip."""
    rng = np.random.default_rng(14)
    heads, w, dv, t, per = 2, 24, 16, 512, 40
    plan = [(0, 130, 1), (3, 77, 1)][:lead_rows] + [(1, 200, chunk),
                                                    (2, 0, 150)]
    tables, args = _long_tables(4, per), _tick(plan, t, 4)
    pool = jnp.asarray(rng.normal(size=(1, 4 * per + 2, LONG_PAGE, 1, w)),
                       jnp.float32)
    _kernel_against_gather(rng, pool, tables, args, 0, heads=heads, w=w,
                           dv=dv, atol=3e-6)
    if counts:
        # items of 32 tokens (2 heads): 1 + 9 + 5; the decode row 2
        # context blocks and 1 of its own; the chunk 9 x 2 and, one
        # flat row off the alignment, 1 1 1 2 2 2 2 3 3 (15 were it
        # aligned); the last chunk none and 1 1 1 2 2
        segs = [(st, n) for _, st, n in plan]
        assert mla_ops.mla_work_counts(segs, t, LONG_PAGE, per,
                                       heads) == counts


def _kernel_jaxpr(t, heads):
    """The kernel's own jaxpr (the body of its pallas_call; its inputs
    are the kernel's refs, scratch included) for a tick of t tokens."""
    w, dv = 24, 16
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    traced = jax.make_jaxpr(
        lambda q, pool, tables, slot, pos, valid, start, new:
        mla_ops.mla_ragged_attention_pallas(
            q, pool, 0, tables, slot, pos, valid, start, new, dv=dv,
            scale=0.3))(
        f32(t, heads, w), f32(1, 42, LONG_PAGE, 1, w), i32(4, 10), i32(t),
        i32(t), jax.ShapeDtypeStruct((t,), jnp.bool_), i32(4), f32(t, w))

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = find(sub)
                if found is not None:
                    return found

    return find(traced.jaxpr)


@pytest.mark.parametrize("t,heads", [(512, 128), (512, 32), (64, 128)])
def test_kernel_keeps_its_statistics_lane_wide_and_divides_once(t, heads):
    """What set the kernel's pace until PR 49, read off its jaxpr: the
    running maximum and sum lie on all 128 lanes of their row (a
    statistic ONE lane wide costs a lane broadcast a vector register at
    each use), and no loop body (a flash step runs inside the context's
    and the in-batch loop) divides a vector of integers: the rows' token
    offsets are made once an item."""
    kernel = _kernel_jaxpr(t, heads)
    rows = mla_ops.mla_q_block(t, heads) * heads
    shapes = [tuple(v.aval.shape) for v in kernel.invars]
    assert shapes.count((rows, 128)) == 3          # m, l, the offsets
    assert not [s for s in shapes if len(s) == 2 and s[1] == 1]

    def vector_divisions(jaxpr, in_loop):
        found = []
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if (in_loop and name in ("div", "rem")
                    and eqn.outvars[0].aval.shape
                    and jnp.issubdtype(eqn.outvars[0].aval.dtype,
                                       jnp.integer)):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += vector_divisions(
                    sub, in_loop or name in ("while", "scan"))
        return found

    assert vector_divisions(kernel, False) == []


def test_gather_in_token_blocks_equals_the_whole_tick(monkeypatch):
    """`mla_attention_gather_paged` cuts a tick into blocks of tokens
    when its gathered context would not fit; the sums are the same."""
    rng = np.random.default_rng(12)
    heads, w, dv, t, per = 4, 24, 16, 32, 40
    plan = [(0, 401, 1), (1, 530, 19), (2, 384, 1), (3, 0, 5)]
    tables, args = _long_tables(4, per), _tick(plan, t, 4)
    pool = jnp.asarray(rng.normal(size=(2, 4 * per + 2, LONG_PAGE, 1, 128)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(t, heads, w)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(t, w)), jnp.float32)
    kw = dict(width=w, dv=dv, scale=0.3)
    whole = mla_ops.mla_attention_gather_paged(
        q, pool, 1, tables, new, *args, **kw)
    want = mla_ops.mla_attention_gather(
        q, _gather_latent(pool, tables, w)[1], new, *args, dv=dv,
        scale=0.3)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=1e-6)
    # 640 context rows a token: blocks of 4 tokens (2,560 rows), and of
    # 1 when even one token's context is over the budget
    for budget in (3000, 100):
        monkeypatch.setattr(mla_ops, "GATHER_ROWS", budget)
        cut = mla_ops.mla_attention_gather_paged(
            q, pool, 1, tables, new, *args, **kw)
        np.testing.assert_allclose(np.asarray(cut), np.asarray(whole),
                                   atol=1e-6)


def test_ragged_tick_over_a_three_block_context_matches_the_reference():
    """The model through the interpreted kernel with 400 and 290 tokens
    cached (4 and 3 blocks of 128 keys), past the YaRN table's original
    length of 32: a chunk and a decode row against the reference's full
    forward."""
    cfg = ds.config("debug", max_seq=512, **F32)
    params = ds.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(13)
    per, t = 32, 32
    tables = jnp.asarray(
        (1 + np.arange(4 * per, dtype=np.int32)).reshape(4, per))
    pool = jnp.zeros((cfg.n_layers, 4 * per + 2, LONG_PAGE, 1, 128),
                     jnp.float32)
    history = [[], [], [], []]
    tick = jax.jit(functools.partial(
        ds.ragged_forward, cfg, ctx_pages=per, impl="pallas_interpret"))
    target = (400, 290)
    while any(len(history[s]) < target[s] for s in (0, 1)):
        plan = [(s, len(history[s]),
                 min(t // 2, target[s] - len(history[s])))
                for s in (0, 1) if len(history[s]) < target[s]]
        _, pool, _, _ = tick(params, *_pack(rng, cfg, history, plan, t),
                             pool, None, tables)
    batch = _pack(rng, cfg, history, [(0, 400, 1), (1, 290, 11), (2, 0, 3)],
                  t)
    logits, pool, _, _ = tick(params, *batch, pool, None, tables)
    want = _reference_last(cfg, params, history[:3])
    np.testing.assert_allclose(np.asarray(logits)[:3], want, atol=5e-5)


# ---- routing -----------------------------------------------------------

def _route_by_hand(scores, bias, n_group, topk_group, top_k, scale):
    """The equations, one token at a time, in plain Python."""
    picks, weights = [], []
    e = len(bias)
    per = e // n_group
    for row in scores:
        choice = [s + b for s, b in zip(row, bias)]
        groups = []
        for g in range(n_group):
            best = sorted(choice[g * per:(g + 1) * per], reverse=True)[:2]
            groups.append(sum(best))
        kept = sorted(range(n_group), key=lambda g: (-groups[g], g)
                      )[:topk_group]
        cand = [i for i in range(e) if i // per in kept]
        top = sorted(cand, key=lambda i: (-choice[i], i))[:top_k]
        total = sum(row[i] for i in top)
        picks.append(top)
        weights.append([row[i] / total * scale for i in top])
    return np.array(picks), np.array(weights)


def _route_system(scores, bias, **kw):
    """`sigmoid_group_routing` on given SCORES: an identity router fed
    their logits."""
    scores = np.asarray(scores, np.float64)
    logit = np.log(scores / (1 - scores)).astype(np.float32)
    w, idx = moe.sigmoid_group_routing(
        jnp.asarray(logit), jnp.eye(scores.shape[1], dtype=jnp.float32),
        jnp.asarray(bias, jnp.float32), **kw)
    return np.asarray(idx), np.asarray(w)


ROUTE = dict(n_group=4, topk_group=2, top_k=3, scale=2.5)


def test_routing_follows_the_equations_on_hand_made_scores():
    rng = np.random.default_rng(7)
    scores = rng.uniform(0.05, 0.95, size=(9, 16))
    bias = rng.normal(scale=0.1, size=16)
    idx, w = _route_system(scores, bias, **ROUTE)
    want_idx, want_w = _route_by_hand(scores.tolist(), bias.tolist(),
                                      *ROUTE.values())
    assert (idx == want_idx).all()
    np.testing.assert_allclose(w, want_w, rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    ridx = np.asarray(ref.route(
        {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
         "routed_scaling_factor": 2.5}, jnp.asarray(scores, jnp.float32),
        jnp.asarray(bias, jnp.float32))[1])
    assert (ridx == want_idx).all()


def test_routing_cuts_a_group_whose_one_score_is_the_largest():
    """Group 3 holds the single largest score but its two best sum to
    less than groups 0 and 1: it is cut and none of it is picked."""
    scores = np.full((1, 16), 0.1)
    scores[0, 12] = 0.9                     # group 3: 0.9 + 0.1
    scores[0, [0, 1]] = 0.6, 0.55           # group 0: 1.15
    scores[0, [4, 5]] = 0.7, 0.5            # group 1: 1.2
    idx, w = _route_system(scores, np.zeros(16), **ROUTE)
    assert sorted(idx[0]) == [0, 1, 4]
    np.testing.assert_allclose(sorted(w[0]), np.sort(
        np.array([0.55, 0.6, 0.7]) / 1.85 * 2.5), rtol=1e-5)


def test_routing_bias_changes_the_choice_but_not_the_weight():
    scores = np.full((1, 16), 0.1)
    scores[0, [0, 1, 2, 3]] = 0.8, 0.7, 0.6, 0.5
    scores[0, [4, 5]] = 0.4, 0.4
    plain_idx, plain_w = _route_system(scores, np.zeros(16), **ROUTE)
    assert sorted(plain_idx[0]) == [0, 1, 2]
    bias = np.zeros(16)
    bias[3] = 0.25                          # 0.5 + 0.25 beats 0.6 and 0.7
    idx, w = _route_system(scores, bias, **ROUTE)
    assert sorted(idx[0]) == [0, 1, 3]
    by_expert = dict(zip(idx[0].tolist(), w[0].tolist()))
    # the weight is the SCORE 0.5, not the biased 0.75
    np.testing.assert_allclose(by_expert[3], 0.5 / 2.0 * 2.5, rtol=1e-5)


def test_routing_ties_go_to_the_lower_index():
    scores = np.full((2, 16), 0.3)          # everything ties
    idx, w = _route_system(scores, np.zeros(16), **ROUTE)
    assert (idx == [[0, 1, 2]] * 2).all()   # groups 0, 1 kept; 0, 1, 2
    np.testing.assert_allclose(w, 2.5 / 3, rtol=1e-5)
    want_idx, _ = _route_by_hand(scores.tolist(), [0.0] * 16,
                                 *ROUTE.values())
    assert (want_idx == idx).all()


# ---- rope --------------------------------------------------------------

def test_yarn_table_blends_between_the_correction_dims():
    cfg = ds.DeepseekV3Config()             # the published numbers
    got = np.asarray(ds.yarn_inv_freq(cfg), np.float64)
    d, base, orig, factor = 64, 10000.0, 4096, 40.0
    dim_of = lambda rot: d * math.log(orig / (rot * 2 * math.pi)) / (
        2 * math.log(base))
    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (10, 23)
    for i in range(32):
        plain = base ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain / factor * ramp + plain * (1 - ramp)
        assert got[i] == pytest.approx(want, rel=1e-5)
    assert got[5] == pytest.approx(base ** (-10 / 64), rel=1e-5)
    assert got[30] == pytest.approx(base ** (-60 / 64) / 40, rel=1e-5)
    # s = 192^-1/2 * m^2 with m = 0.1 ln 40 + 1; cos/sin factor 1
    m = 0.1 * math.log(40) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = ds.rope_cos_sin(cfg, jnp.asarray([0, 7]))
    np.testing.assert_allclose(np.asarray(cos[0]), 1.0)
    np.testing.assert_allclose(np.asarray(sin[1]), np.sin(7 * got),
                               rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(model_dict(cfg))), got, rtol=1e-6)
    assert ref.softmax_scale(model_dict(cfg)) == pytest.approx(
        cfg.softmax_scale)


# ---- the chip's share --------------------------------------------------

@pytest.mark.parametrize("rows", [24, 96])
def test_the_shares_routed_parts_plus_the_shared_expert_once_equal_the_uncut_layer(
        rows):
    """The model-configs guide's section 4: over the 4 shares of 4
    experts each, the routed parts add up, with the shared expert
    counted once, to what the uncut reference gives for the whole layer
    (24 rows, and 96 of which an expert here takes over 64)."""
    whole = ds.config("debug", **F32)
    layer = ds.init_params(whole, jax.random.PRNGKey(4))["layers"][1]
    y = jax.random.normal(jax.random.PRNGKey(5), (rows, whole.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(model_dict(whole), layer, y, (0, 16))
    shared = ds.swiglu(layer["shared"], y)
    total = shared
    landed = 0
    for lo in range(0, 16, 4):
        cfg = dataclasses.replace(whole, experts_held=(lo, lo + 4))
        part = {**layer, "experts": jax.tree.map(
            lambda a: a[lo:lo + 4], layer["experts"])}
        out, counts = ds.moe_block(cfg, part, y)
        total = total + (out - shared)
        landed += int(counts.sum())
        # the reference given the same share agrees with the system
        with jax.default_matmul_precision("highest"):
            ref_part = ref.experts(model_dict(cfg), part, y, (lo, lo + 4))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_part),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=5e-5)
    assert landed == rows * whole.moe_top_k      # no pick lost or doubled


# ---- the cache row and the engine --------------------------------------

def test_cache_row_gives_todays_numbers_for_the_dense_family():
    cfg = llama.config("debug")             # 2 layers, 2 kv heads of 32
    fam = family_of(cfg)
    row = fam.cache_row(cfg, "gather", "f32")
    assert row == CacheRow(kind="kv", pools=2, heads=2, width=32,
                           padded_width=32, dtype=cfg.dtype)
    assert row.bytes_per_token_layer == 2 * 2 * 32 * 2
    assert row.pool_shape(2, 64, 16) == (2, 64, 16, 2, 32)
    padded = fam.cache_row(cfg, "pallas", "f32")
    assert padded.padded_width == 128 and padded.width == 32
    quant = fam.cache_row(cfg, "gather", "int8")
    assert quant.bytes_per_token_layer == 2 * 2 * (32 * 1 + 4)
    eng = InferenceEngine(EngineConfig(model="debug", num_pages=32))
    assert eng.k_pages.shape == eng.v_pages.shape == (2, 32, 16, 2, 32)
    assert eng.stats()["kv_page_bytes"] == 2 * 256 * 16
    assert eng.stats()["moe"] is None
    # A9: the cost model prices KV at the POOL's row
    assert CostModel(cfg, 16).kv_bytes_per_token == 2 * 256
    assert CostModel(cfg, 16, cache_row=padded).kv_bytes_per_token == (
        2 * 2 * 2 * 128 * 2)


def test_latent_cache_row_and_cost_model():
    cfg = ds.DeepseekV3Config(n_layers=5, first_k_dense=1,
                              experts_held=(0, 16), vocab_size=16160)
    row = family_of(cfg).cache_row(cfg, "pallas", "f32")
    assert (row.kind, row.pools, row.heads) == ("latent", 1, 1)
    assert (row.width, row.padded_width, row.value_width) == (576, 640,
                                                              512)
    assert row.bytes_per_token_layer == 1280
    assert row.pool_shape(5, 16384, 16) == (5, 16384, 16, 1, 640)
    assert 5 * 16384 * 16 * 1280 == 1_677_721_600       # 1.68 GB
    assert cfg.num_params() == 4_565_630_976            # ISSUE 27: 4,566M
    cm = CostModel(cfg, 16, cache_row=row)
    assert cm.kv_bytes_per_token == 6400
    assert cm.weight_bytes == 2 * cfg.num_params()
    # 128 heads x (576 + 512) x 2 a pair a layer
    assert cm.attn_flops_per_pair == 5 * 128 * 1088 * 2
    assert cm.decode_cost(3000)["bytes_kv_read"] == 6400 * 3008


def test_engine_serves_the_family_and_counts_its_experts():
    cfg = ds.config("debug", experts_held=(0, 8), **F32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=4, page_size=4, num_pages=128,
        max_seq_len=128, max_prefill_tokens=16))
    assert eng.v_pages is None and eng.k_pages.shape == (3, 128, 4, 1, 24)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 250, n)]
               for n in (5, 23, 40)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=5,
                                                temperature=0.0))
    # greedy continuation against the reference, token by token: the
    # reference is causal, so ONE call on a request's whole sequence has
    # the row that predicted each generated token (a call a token, at a
    # new length each, compiled every primitive again)
    every = _reference_rows(cfg, eng.params, [
        list(p) + list(r.output_tokens) for r, p in zip(outs, prompts)])
    for req, prompt, rows in zip(outs, prompts, every):
        for i, tok in enumerate(req.output_tokens):
            lg = rows[len(prompt) - 1 + i]
            top2 = np.sort(lg)[-2:]
            if top2[1] - top2[0] > 1e-3:    # not a rounding tie
                assert tok == int(lg.argmax())
    st = eng.stats()
    assert st["cache_row"]["kind"] == "latent"
    assert st["kv_page_bytes"] == 3 * 24 * 4 * 4
    moe_st = st["moe"]
    assert moe_st["experts_held"] == [0, 8]
    assert moe_st["tokens_routed"] >= sum(map(len, prompts))
    assert moe_st["assignments_landed"] == sum(map(sum, moe_st["landed"]))
    assert 0 < moe_st["assignments_landed"] <= (
        moe_st["tokens_routed"] * cfg.moe_top_k * cfg.n_moe_layers)
    assert moe_st["experts_with_tokens"] <= 16
    assert moe_st["busiest_over_mean"] >= 1.0
    # one decode program whatever fed it, programs keep their names
    assert eng._decode_fn.__name__ == "step"
    assert resolve_config("deepseek_v3:debug") == ds.config("debug")


@pytest.mark.parametrize("option, kwargs", [
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("enable_kv_offload", {"enable_kv_offload": True}),
    ("mesh_shape", {"mesh_shape": (1, 1)}),
    ("checkpoint", {"checkpoint": "/nowhere"}),
])
def test_engine_refuses_what_the_family_does_not_compose_with(option,
                                                              kwargs):
    with pytest.raises(ValueError, match=option):
        InferenceEngine(EngineConfig(model=ds.config("debug"),
                                     num_pages=32, **kwargs))


def test_engine_refuses_lora_and_session_shipping_for_the_family():
    eng = InferenceEngine(EngineConfig(model=ds.config("debug"),
                                       num_pages=32, max_seq_len=64))
    with pytest.raises(ValueError, match="lora"):
        eng.register_lora("a", {})
    with pytest.raises(ValueError, match="session_shipping"):
        eng.export_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="session_shipping"):
        eng.import_session({"request_id": "r", "seed": 1})


def test_free_pages_count_matches_a_walk_of_the_cache():
    """`PageAllocator.free_pages` is a count kept as references move
    (it was a walk of the whole prefix cache, a dozen times a tick): on
    random traffic it equals the walk at every step."""
    from ray_tpu.llm._internal.kv_cache import PageAllocator
    rng = np.random.default_rng(0)
    alloc = PageAllocator(num_pages=65, page_size=4)
    walk = lambda: len(alloc._free) + sum(
        1 for p in alloc._cache.values() if alloc._rc.get(p, 0) == 1)
    prompts = [[int(t) for t in rng.integers(0, 5, rng.integers(4, 40))]
               for _ in range(12)]
    live = []
    for step in range(400):
        if live and (rng.random() < 0.45 or alloc.free_pages < 12):
            alloc.free(live.pop(int(rng.integers(len(live)))))
        else:
            prompt = prompts[int(rng.integers(len(prompts)))]
            shared, matched = alloc.match_prefix(prompt)
            need = alloc.pages_needed(len(prompt) + 3) - len(shared)
            if need > alloc.free_pages:
                alloc.free(shared)
            else:
                pages = shared + alloc.allocate_pages(need)
                alloc.register_prefix(prompt, pages)
                live.append(pages)
        if step % 97 == 0:
            alloc.clear_cache()
        assert alloc.free_pages == walk(), step
        assert alloc.used_pages == alloc.num_usable - walk()
    for pages in live:
        alloc.free(pages)
    assert alloc.free_pages == walk() == alloc.num_usable
