"""The Trinity family (`models/trinity.py`): sliding-window and
full-attention layers over two page groups in one cache manager
(`kv_cache.CacheManager`), the window-aware ragged kernel, gated
QK-normed GQA attention and sigmoid top-k routing with the experts held
here, against the plain float32 reference the benchmark keeps
(`benchmarks/lib/reference_trinity.py`), at a toy size on the CPU: a
window of 8 and sequences past two windows, in float32, so that an edge
off by one key fails exactly."""

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program_trinity, reference_trinity as ref
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.llm._internal.kv_cache import CacheManager
from ray_tpu.llm._internal.perfmodel import CostModel
from ray_tpu.models import llama, trinity
from ray_tpu.models.cache_row import CacheGroup, CacheRow
from ray_tpu.models.family import family_of, resolve_config
from ray_tpu.ops import moe
from ray_tpu.ops import ragged_paged_attention as rpa

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
PAGE = 4


def _cfg(**over):
    return trinity.config("debug", **{**F32, **over})


def _jittered(params, seed=7):
    """Norm weights and biases off 1.0 / 0.0, so that each one matters."""
    key = jax.random.PRNGKey(seed)

    def jitter(path, a):
        name = str(path[-1])
        if "norm" in name or "ln_" in name:
            k = jax.random.fold_in(key, zlib.crc32(str(path).encode()))
            return 1.0 + 0.3 * jax.random.normal(k, a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(jitter, params)


def _rel(got, want, scale=None):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = want if scale is None else scale
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(np.asarray(scale) ** 2)))


# ---- the configuration --------------------------------------------------

def test_the_cut_holds_the_issues_parameter_count():
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * 3
    cut = trinity.TrinityConfig(
        vocab_size=25024, n_layers=9, n_dense_layers=1,
        experts_held=(0, 16), layer_types=tuple(kinds[:9]))
    # 176.16 + 8 x 545.00 + 153.75 million (ISSUE 31)
    assert cut.num_params() == 4_689_887_232
    assert cut.layers_of(trinity.FULL) == (3, 7)
    assert cut.layers_of(trinity.SLIDING) == (0, 1, 2, 4, 5, 6, 8)
    assert [cut.group_index(i) for i in range(9)] == [
        0, 1, 2, 0, 3, 4, 5, 1, 6]
    whole = trinity.TrinityConfig()
    assert whole.kinds.count(trinity.FULL) == 15 and len(whole.kinds) == 60
    with pytest.raises(ValueError, match="layer_types"):
        trinity.TrinityConfig(n_layers=4, n_dense_layers=1,
                              layer_types=("sliding_attention",) * 3)
    with pytest.raises(ValueError, match="full-attention"):
        trinity.TrinityConfig(n_layers=2, n_dense_layers=1,
                              layer_types=("sliding_attention",) * 2)
    assert isinstance(resolve_config("trinity:debug"),
                      trinity.TrinityConfig)


def test_family_describes_two_groups_and_the_others_one():
    cfg = _cfg()
    full, window = family_of(cfg).cache_groups(cfg, "gather")
    row = CacheRow(kind="kv", pools=2, heads=2, width=16, padded_width=16,
                   dtype=jnp.float32)
    assert full == CacheGroup("full", row, (3, 7))
    assert window == CacheGroup("window", row, (0, 1, 2, 4, 5, 6, 8), 8)
    assert window.bytes_per_token == 7 * 2 * 2 * 16 * 4
    assert family_of(cfg).cache_row(cfg, "pallas").padded_width == 128
    dense = llama.config("debug")
    (only,) = family_of(dense).cache_groups(dense, "gather")
    assert (only.name, only.layers, only.window) == ("all", (0, 1), None)
    assert only.row == family_of(dense).cache_row(dense, "gather")


# ---- system against reference ------------------------------------------

def _pools(cfg, impl, pages):
    groups = family_of(cfg).cache_groups(cfg, impl)
    make = lambda: tuple(
        jnp.zeros(g.row.pool_shape(len(g.layers), n, PAGE), g.row.dtype)
        for g, n in zip(groups, pages))
    return groups, make(), make()


@functools.lru_cache(maxsize=None)
def _tick_fn(cfg, impl, ctx_pages):
    """The family's forwards as ONE program each, as the engine runs
    them (eagerly, every primitive of a forward is compiled by itself:
    714 programs a test); `ctx_pages` None is the decode tick."""
    if ctx_pages is None:
        return jax.jit(functools.partial(trinity.decode_step, cfg,
                                         impl=impl))
    return jax.jit(functools.partial(trinity.ragged_forward, cfg,
                                     ctx_pages=ctx_pages, impl=impl))


def _reference_logits(cfg, model, params, seqs, **kw):
    """The reference's logits of each sequence, [len(s), vocab] each. It
    is causal, so all go in padded to ONE length and are cut back
    (eagerly, each primitive of the reference is compiled again at every
    new length)."""
    n = -(-max(map(len, seqs)) // 16) * 16
    return [np.asarray(ref.logits(
        model, params, jnp.array(np.pad(np.asarray(s, np.int32),
                                        (0, n - len(s)))),
        cfg.held, **kw))[:len(s)] for s in seqs]


def _tick(cfg, params, impl, kp, vp, tables, rows, seqs, t, slots):
    """One ragged tick. rows: [(slot, first position, tokens)] of the
    sequences `seqs`. Returns (logits by slot, kp, vp)."""
    tok = np.zeros(t, np.int32)
    sid = np.zeros(t, np.int32)
    pos = np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    start = np.zeros(slots, np.int32)
    last = np.zeros(slots, np.int32)
    cur = 0
    for s, pos0, n in rows:
        tok[cur:cur + n] = seqs[s][pos0:pos0 + n]
        sid[cur:cur + n] = s
        pos[cur:cur + n] = np.arange(pos0, pos0 + n)
        valid[cur:cur + n] = True
        start[s], last[s] = pos0, cur + n - 1
        cur += n
    has_ctx = any(pos0 for _, pos0, _ in rows)
    lg, kp, vp, _ = _tick_fn(cfg, impl, -1 if has_ctx else 0)(
        params, jnp.array(tok), jnp.array(sid), jnp.array(pos),
        jnp.array(valid), jnp.array(start), jnp.array(last), kp, vp,
        tuple(jnp.array(t_) for t_ in tables))
    return np.asarray(lg), kp, vp


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_ragged_ticks_through_both_groups_match_the_reference(impl):
    """Two sequences of 40 and 23 tokens (a window of 8: past two
    windows, and across one) prefilled in chunks of 10 and 7 in the same
    ticks, then a decode tick of both, THROUGH THE CACHE MANAGER with a
    window pool so small that the pages one sequence hands back are the
    ones the other is given: every compared row equals the reference's
    forward of the whole sequence."""
    cfg = _cfg(experts_held=(0, 8))
    params = _jittered(trinity.init_params(cfg, jax.random.PRNGKey(3)))
    model = program_trinity.published_keys(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 255, n).astype(np.int32) for n in (41, 24)]
    want = _reference_logits(cfg, model, params, seqs)
    slots, t = 2, 32
    # the window group holds what the two reserve and no page more (9
    # pages: 8 + 17 + 8 tokens; 7: all 25), where they claim 18 in all
    groups, kp, vp = _pools(cfg, impl, (40, 17))
    cache = CacheManager(groups, (40, 17), PAGE, slots, 16,
                         tick_tokens=17, enable_prefix_caching=False)
    win = cache.groups[1]
    for s, seq in enumerate(seqs):
        assert cache.can_admit(len(seq) + 1)
        cache.admit(s, len(seq) + 1)
    done = [0, 0]
    given: set = set()          # pages handed back so far
    reused = 0
    while min(done[0] - 40, done[1] - 23) < 0:
        rows = [(s, done[s], min(n, lim - done[s])) for s, n, lim
                in ((0, 10, 40), (1, 7, 23)) if done[s] < lim]
        lg, kp, vp = _tick(cfg, params, impl, kp, vp, cache.tables, rows,
                           seqs, t, slots)
        for s, pos0, n in rows:
            done[s] = pos0 + n
            assert _rel(lg[s], want[s][done[s] - 1], want[s]) < 2e-5, (
                s, done[s])
        held = [set(win.tables[s, win.lo[s]:win.hi[s]].tolist())
                for s in range(slots)]
        cache.advance(list(enumerate(done)))
        after = [set(win.tables[s, win.lo[s]:win.hi[s]].tolist())
                 for s in range(slots)]
        for s in range(slots):
            reused += len((after[s] - held[s]) & given)
            given |= held[s] - after[s]
    assert win.returned >= 8 and reused > 0
    # the decode tick: one token a slot, the ragged tick of T = slots
    toks = jnp.array([seqs[0][40], seqs[1][23]], jnp.int32)
    lg, _, _, counts = _tick_fn(cfg, impl, None)(
        params, toks, jnp.array(done, jnp.int32), kp, vp,
        tuple(jnp.array(t_) for t_ in cache.tables),
        jnp.ones(slots, bool))
    for s, p in enumerate(done):
        assert _rel(lg[s], want[s][p], want[s]) < 2e-5
    assert counts.shape == (cfg.n_moe_layers, cfg.n_held)
    # the comparison has teeth: one key off, or one thing left out
    for variant, least in (("all_full", 0.3), ("all_window", 0.1),
                           ("rope_on_full", 0.1), ("no_gate", 0.3),
                           ("no_qk_norm", 0.3), ("no_route_scale", 0.1),
                           ("no_embed_scale", 0.3)):
        off, = _reference_logits(cfg, model, params, seqs[:1],
                                 variant=(variant,))
        assert _rel(off[-8:], want[0][-8:]) > least, variant
    one_off = {**model, "sliding_window": 7}
    off, = _reference_logits(cfg, one_off, params, seqs[:1])
    assert _rel(off[-8:], want[0][-8:]) > 1e-2


def test_engine_greedy_tokens_are_the_references():
    """Prefill then decode through the ENGINE (admission in both groups,
    chunked prefill, decode ticks, pages handed back at tick boundaries)
    in float32: every token it gives is the reference's largest logit
    given the tokens before it."""
    cfg = _cfg(experts_held=(0, 8))
    eng = InferenceEngine(EngineConfig(
        model=cfg, num_pages=64, num_pages_by_group={"window": 28},
        max_batch_size=3, page_size=PAGE, max_seq_len=64,
        max_prefill_tokens=8, max_num_batched_tokens=12, seed=5))
    model = program_trinity.published_keys(cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 255, n).tolist() for n in (5, 21, 30, 17)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=12))
    every = _reference_logits(cfg, model, eng.params, [
        req.prompt_tokens + req.output_tokens for req in outs])
    for req, lg in zip(outs, every):
        n = len(req.prompt_tokens)
        for i, tok in enumerate(req.output_tokens):
            row = lg[n + i - 1]
            assert row[tok] >= np.sort(row)[-1] - 1e-4, (n, i)
    st = eng.stats()
    full, window = st["cache_groups"]
    assert window["pages_returned"] > 0 and window["pages_used"] == 0
    assert full["pages_used"] == 0 and full["pages_peak"] > 0
    assert st["prefix_cache"].startswith("off")
    assert st["cache_row"] == full["row"]
    assert st["moe"]["assignments_landed"] > 0


# ---- the window kernel against the gather path -------------------------

def _window_oracle(c, window):
    """Numpy, per token: the ragged rule with a band of `window` keys."""
    q, t = c["q"], c["q"].shape[0]
    kvh = c["k_new"].shape[1]
    group = q.shape[1] // kvh
    out = np.zeros_like(q)
    for i in range(t):
        if not c["valid"][i]:
            continue
        s, p = int(c["slot_ids"][i]), int(c["positions"][i])
        lo = max(p - window + 1, 0)
        ctx = range(lo, int(c["start"][s]))
        mates = [j for j in range(t) if c["valid"][j]
                 and c["slot_ids"][j] == s
                 and lo <= c["positions"][j] <= p]
        kk = np.concatenate([c["dense_k"][s, list(ctx)], c["k_new"][mates]])
        vv = np.concatenate([c["dense_v"][s, list(ctx)], c["v_new"][mates]])
        kk, vv = np.repeat(kk, group, 1), np.repeat(vv, group, 1)
        sc = np.einsum("hd,nhd->hn", q[i], kk) / np.sqrt(q.shape[-1])
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        out[i] = np.einsum("hn,nhd->hd", pr / pr.sum(-1, keepdims=True),
                           vv)
    return out


def _case(rng, segs, window, kvh=2, group=3, d=8, width=80, pad=0):
    """A ragged batch over a paged pool whose tables are `width` pages
    wide (so that the kernel's context blocks are 128 keys). Pages
    wholly behind every query's window hold garbage and their table
    entries point at one garbage page: handed back and written by
    another sequence."""
    b, h = len(segs), kvh * group
    num_pages = b * width + 2
    garbage = num_pages - 2
    k_pages = np.zeros((num_pages, PAGE, kvh, d), np.float32)
    v_pages = np.zeros((num_pages, PAGE, kvh, d), np.float32)
    k_pages[garbage] = v_pages[garbage] = 1e3
    tables = np.arange(b * width, dtype=np.int32).reshape(b, width)
    ctx = max(max(s for s, _ in segs), 1)
    dense_k = rng.normal(size=(b, ctx, kvh, d)).astype(np.float32)
    dense_v = rng.normal(size=(b, ctx, kvh, d)).astype(np.float32)
    for s, (start, _) in enumerate(segs):
        for p in range(start):
            k_pages[tables[s, p // PAGE], p % PAGE] = dense_k[s, p]
            v_pages[tables[s, p // PAGE], p % PAGE] = dense_v[s, p]
        gone = max(start - window + 1, 0) // PAGE
        tables[s, :gone] = garbage
    t = sum(n for _, n in segs) + pad
    slot_ids, positions = np.zeros(t, np.int32), np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    cur = 0
    for s, (start, n) in enumerate(segs):
        slot_ids[cur:cur + n] = s
        positions[cur:cur + n] = np.arange(start, start + n)
        valid[cur:cur + n] = True
        cur += n
    return dict(
        q=rng.normal(size=(t, h, d)).astype(np.float32),
        k_new=rng.normal(size=(t, kvh, d)).astype(np.float32),
        v_new=rng.normal(size=(t, kvh, d)).astype(np.float32),
        k_pages=k_pages, v_pages=v_pages, tables=tables,
        slot_ids=slot_ids, positions=positions, valid=valid,
        start=np.asarray([s for s, _ in segs], np.int32),
        dense_k=dense_k, dense_v=dense_v)


WINDOW_CASES = [
    # name, window, [(cached tokens, tokens this tick)], padding rows
    ("edge_inside_a_context_block", 100, [(300, 1), (200, 5)], 2),
    ("edge_on_a_block_edge", 45, [(300, 1), (172, 1)], 0),
    ("edge_one_past_a_block_edge", 44, [(300, 1), (129, 3)], 0),
    ("edge_inside_the_in_batch_keys", 8, [(300, 20), (0, 20)], 0),
    ("second_query_block_starts_later", 100, [(150, 140)], 0),
    ("window_wider_than_every_context", 4096, [(300, 1), (40, 9)], 3),
    ("window_of_one", 1, [(9, 1), (0, 4)], 0),
]


@pytest.mark.parametrize("name,window,segs,pad", WINDOW_CASES,
                         ids=[c[0] for c in WINDOW_CASES])
def test_window_kernel_and_gather_paths_match_the_band(name, window, segs,
                                                       pad):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    c = _case(rng, segs, window, pad=pad)
    want = _window_oracle(c, window)
    arr = {k: jnp.asarray(v) for k, v in c.items()}
    args = (arr["q"], arr["k_pages"], arr["v_pages"], arr["tables"],
            arr["slot_ids"], arr["positions"], arr["valid"], arr["start"],
            arr["k_new"], arr["v_new"])
    kernel = np.asarray(rpa.ragged_paged_attention_pallas(
        *args, window=window, interpret=True))
    gather = np.asarray(rpa.ragged_paged_prefill_decode_attention(
        *args, window=window))
    blocked = np.asarray(rpa.ragged_gather_paged_blocked(
        arr["q"], arr["k_pages"][None], arr["v_pages"][None], 0,
        arr["tables"], arr["slot_ids"], arr["positions"], arr["valid"],
        arr["start"], arr["k_new"], arr["v_new"], window=window,
        gather_rows=640))                      # blocks of 2 tokens
    ok = c["valid"]
    for got in (kernel, gather, blocked):
        np.testing.assert_allclose(got[ok], want[ok], rtol=2e-4,
                                   atol=2e-5)
    assert not kernel[~ok].any()


def test_window_work_counts_by_hand():
    # page 16, a table of 64 pages: blocks of 128 keys and 128 queries
    count = lambda segs, t, w: rpa.ragged_work_counts(
        segs, t, 16, 64, kvh=8, row_width=128, itemsize=2, window=w)
    # a decode row at 1,000 cached tokens: 8 context blocks and its own;
    # a window of 300 starts at key 701: block 5, so 3 context blocks
    assert count([(1000, 1)], 8, None) == (1, 9)
    assert count([(1000, 1)], 8, 300) == (1, 4)
    assert count([(1000, 1)], 8, 233) == (1, 3)        # key 768: block 6
    # a 300-token chunk at 1,000: 3 query blocks, 8 context blocks each
    # and 1 + 2 + 3 in-batch; windowed, the blocks start at keys 701,
    # 829, 957: blocks 5, 6, 7
    assert count([(1000, 300)], 512, None) == (3, 30)
    assert count([(1000, 300)], 512, 300) == (3, 30 - 18)
    # no context at all
    assert count([(0, 5)], 8, 4) == (1, 1)
    cfg = _cfg()
    got = trinity.span_counts(cfg, [(20, 1), (3, 10), (0, 4)],
                              [True, False, False])
    # window 8. decode row at 20: 8 keys, 8 pairs. chunk of 10 at 3: its
    # queries keep 4, 5, 6, 7, 8, 8, 8, 8, 8, 8 = 70 and read keys 0..12;
    # a prompt of 4: 1 + 2 + 3 + 4 and keys 0..3
    assert got == {"win_kv_tokens": 8 + 13 + 4,
                   "win_attn_pairs": 8 + 70 + 10, "win_decode_pairs": 8}


# ---- routing and the chip's share --------------------------------------

def test_routing_on_hand_made_scores():
    e = 8
    eye = jnp.eye(e, dtype=jnp.float32)
    route = lambda logits, bias, **kw: moe.sigmoid_group_routing(
        jnp.asarray(logits, jnp.float32), eye,
        jnp.asarray(bias, jnp.float32), n_group=1, topk_group=1, top_k=2,
        **{"scale": 1.0, **kw})
    sig = lambda x: 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))
    zero = np.zeros(e)
    # ties go to the lower index
    w, idx = route([[1.0, 3.0, 3.0, 3.0, 0.0, 0.0, 0.0, 0.0]], zero)
    assert idx.tolist() == [[1, 2]]
    np.testing.assert_allclose(w, [[0.5, 0.5]], rtol=1e-6)
    # the bias changes the pick, the weight stays the unbiased score's
    logits = [[2.0, 1.0, 0.0, -1.0, 0.0, 0.0, 0.0, 0.0]]
    bias = [0.0, 0.0, 0.0, 0.9, 0.0, 0.0, 0.0, 0.0]
    w, idx = route(logits, bias)
    assert idx.tolist() == [[3, 0]]       # 0.269 + 0.9 beats 0.881
    s = sig([-1.0, 2.0])
    np.testing.assert_allclose(w, [s / s.sum()], rtol=1e-6)
    # `route_scale` multiplies, `route_norm` off leaves the scores
    w2, _ = route(logits, bias, scale=2.448)
    np.testing.assert_allclose(w2, 2.448 * w, rtol=1e-6)
    w3, _ = route(logits, bias, normalize=False)
    np.testing.assert_allclose(w3, [s], rtol=1e-6)
    # the reference routes alike
    model = {"num_experts_per_tok": 2, "route_norm": True,
             "route_scale": 2.448}
    rw, ridx = ref.route(model, jnp.asarray(sig(logits), jnp.float32),
                         jnp.asarray(bias, jnp.float32))
    assert ridx.tolist() == idx.tolist()
    np.testing.assert_allclose(rw, w2, rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's section 4: the routed parts that the
    shares give, with what every chip computes alike (the shared
    expert) counted once, are the uncut layer's output."""
    whole = _cfg()                                   # holds all 16
    layer = trinity.init_params(whole, jax.random.PRNGKey(2))["layers"][1]
    y = jax.random.normal(jax.random.PRNGKey(4), (70, whole.hidden))
    uncut, landed = trinity.moe_block(whole, layer, y)
    shared = trinity.swiglu(layer["shared"], y)
    total, counts = shared, []
    for lo in range(0, 16, 4):
        cfg = _cfg(experts_held=(lo, lo + 4))
        share = {**layer, "experts": jax.tree.map(
            lambda a: a[lo:lo + 4], layer["experts"])}
        out, c = trinity.moe_block(cfg, share, y)
        total = total + (out - shared)
        counts.append(c)
    assert _rel(total, uncut) < 1e-5
    assert jnp.concatenate(counts).tolist() == landed.tolist()
    assert int(landed.sum()) == 70 * whole.moe_top_k     # nothing dropped
    want = ref.experts(program_trinity.published_keys(whole), layer, y,
                       (0, 16))
    assert _rel(uncut, want) < 1e-5


# ---- the cache manager -------------------------------------------------

ROW = CacheRow(kind="kv", pools=2, heads=2, width=16, padded_width=16,
               dtype=jnp.bfloat16)


def _manager(full_pages=64, window_pages=64, window=8, tick=4, slots=3):
    groups = (CacheGroup("full", ROW, (3,)),
              CacheGroup("window", ROW, (0, 1, 2), window))
    return CacheManager(groups, (full_pages, window_pages), PAGE, slots,
                        table_width=32, tick_tokens=tick)


def test_pages_go_back_exactly_when_no_later_query_sees_them():
    cache = _manager()
    win = cache.groups[1]
    scratch, w, tick = win.num_pages - 1, 8, 4
    pages = cache.admit(0, 100)
    assert len(pages) == 25 and win.reserve[0] == 5     # 8 + 4 + 2 pages
    back = 0
    for pos in range(0, 96):
        handed, _ = cache.advance([(0, pos)])
        back += handed
        row = win.tables[0]
        for page in range(32):
            first, last = page * PAGE, page * PAGE + PAGE - 1
            seen = last > pos - w             # some query from pos on sees it
            written = first < min(pos + tick + 2, 100)
            if written and seen:
                assert row[page] != scratch, (pos, page)
                assert row[page] not in win.allocator._free
            elif not seen and first < 100:
                assert row[page] == scratch, (pos, page)
        assert win.hi[0] - win.lo[0] <= win.reserve[0]
    assert back == win.returned == (95 - w + 1) // PAGE
    cache.first.free(pages)
    cache.vacate(0)
    assert win.allocator.used_pages == 0 and cache.first.used_pages == 0
    assert not win.tables.any() and not cache.groups[0].tables.any()


def test_a_page_handed_back_serves_another_sequence():
    cache = _manager(window_pages=9)            # 8 usable: 5 + 3
    win = cache.groups[1]
    cache.admit(0, 100)                         # reserves 5
    assert cache.can_admit(12)                  # 3 pages
    cache.admit(1, 12)
    assert not cache.can_admit(12)              # 8 reserved of 8
    first = set(win.tables[0, :2].tolist())
    for pos in range(0, 40, 4):
        cache.advance([(0, pos), (1, min(pos, 11))])
    assert win.returned > 0
    # the free list turns over: slot 0's later pages are ones it, or
    # slot 1, once held
    assert set(win.tables[0, win.lo[0]:win.hi[0]].tolist()) & first
    st = cache.stats()
    # slot 1 has claimed its last page and handed its first back: of its
    # 3 reserved it holds 2 and wants no more, so 7 of 8 are spoken for
    assert (win.lo[1], win.hi[1], win.final[1]) == (1, 3, 3)
    assert st["cache_groups"][1]["pages_reserved"] == 7
    assert st["cache_groups"][1]["pages_used"] <= 7
    assert (st["free_pages"], st["total_pages"]) == (1, 8)
    assert st["occupancy"] == 7 / 8             # the group that gates
    assert cache.can_admit(4) and not cache.can_admit(8)


def test_admission_refuses_when_either_group_is_short():
    assert _manager().can_admit(100)
    assert not _manager(full_pages=20).can_admit(100)    # 25 of 19
    assert _manager(full_pages=20).can_admit(60)
    assert not _manager(window_pages=5).can_admit(100)   # 5 of 4
    assert _manager(window_pages=5).can_admit(16)
    assert "group 'full'" in _manager(full_pages=20).fits(100)
    assert "group 'window'" in _manager(window_pages=5).fits(100)
    assert _manager().fits(100) is None
    # the engine: a request whose window reservation never fits is
    # refused when it is queued, and one that fits waits for pages
    eng = InferenceEngine(EngineConfig(
        model="trinity:debug", num_pages=64,
        num_pages_by_group={"window": 6}, max_batch_size=2,
        page_size=PAGE, max_seq_len=64, max_num_batched_tokens=8))
    with pytest.raises(ValueError, match="group 'window'"):
        eng.add_request(Request("big", [1] * 30,
                                SamplingParams(max_tokens=4)))
    with pytest.raises(ValueError, match="num_pages_by_group names"):
        InferenceEngine(EngineConfig(model="trinity:debug",
                                     num_pages_by_group={"latent": 8}))
    for groups in ((CacheGroup("w", ROW, (0,), 8),),
                   (CacheGroup("a", ROW, (0,)), CacheGroup("b", ROW, (1,)))):
        with pytest.raises(ValueError, match="first cache group"):
            CacheManager(groups, (8,) * len(groups), PAGE, 1, 8,
                         tick_tokens=4)


def test_one_group_families_numbers_are_todays():
    eng = InferenceEngine(EngineConfig(model="debug", num_pages=32,
                                       max_batch_size=2))
    assert eng.cache.first is eng.allocator
    assert eng._page_tables is eng.cache.groups[0].tables
    assert not eng.cache.windowed and eng.cache.advance([(0, 5)]) == (0, 0)
    assert eng.k_pages.shape == eng.v_pages.shape == (2, 32, 16, 2, 32)
    out = eng.generate([[5, 6, 7, 8] * 5], SamplingParams(max_tokens=3))
    assert len(out[0].output_tokens) == 3
    st = eng.stats()
    assert (st["free_pages"], st["total_pages"], st["used_pages"]) == (
        31, 31, 0)
    assert st["occupancy"] == 0.0 and st["prefix_cache"] == "on"
    assert st["cached_pages"] == 1 and st["kv_page_bytes"] == 2 * 256 * 16
    assert st["kv_device_bytes_used"] == 0
    (group,) = st["cache_groups"]
    assert group["row"] == st["cache_row"]
    assert (group["name"], group["layers"], group["window"]) == (
        "all", [0, 1], None)
    assert (group["pages_total"], group["pages_used"]) == (31, 0)
    assert group["pages_peak"] == group["pages_at_peak"] == 2
    assert "pages_returned" not in group
    span = eng._tick_carried or {}
    assert "win_kv_tokens" not in span


def test_a_family_with_a_window_group_matches_no_prefix():
    """The rule of ISSUE 31 section 2, the second way: window-group pages
    stay out of the prefix cache, so such a family resumes nowhere, and
    says so."""
    eng = InferenceEngine(EngineConfig(
        model="trinity:debug", num_pages=64, max_batch_size=2,
        page_size=PAGE, max_seq_len=64, enable_prefix_caching=True))
    prompt = list(range(1, 25))
    a = eng.generate([prompt], SamplingParams(max_tokens=4))[0]
    b = eng.generate([prompt], SamplingParams(max_tokens=4))[0]
    assert a.output_tokens == b.output_tokens
    st = eng.stats()
    assert st["cache_hit_tokens"] == 0 and st["cached_pages"] == 0
    assert st["prefix_cache"].startswith("off: a window group")
    assert st["prefill"]["prefill_tokens_dispatched"] == 2 * len(prompt)


@pytest.mark.parametrize("kw,what", [
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"enable_kv_offload": True}, "enable_kv_offload"),
    ({"mesh_shape": (1, 2)}, "mesh_shape"),
    ({"checkpoint": "/nowhere"}, "checkpoint"),
])
def test_pairings_nobody_built_are_refused_with_the_reason(kw, what):
    with pytest.raises(ValueError) as e:
        InferenceEngine(EngineConfig(model="trinity:debug", **kw))
    assert trinity.TRINITY_REFUSES[what] in str(e.value)


def test_entry_points_nobody_built_are_refused():
    eng = InferenceEngine(EngineConfig(model="trinity:debug",
                                       num_pages=32, max_seq_len=64))
    with pytest.raises(ValueError, match="does not compose with lora"):
        eng.register_loras({"a": {}})
    assert set(trinity.TRINITY_REFUSES) == {
        "lora", "kv_dtype", "enable_kv_offload", "mesh", "mesh_shape",
        "checkpoint", "session_shipping"}


def test_cost_model_prices_the_cache_a_group():
    cfg = dataclasses.replace(trinity.config("debug"),
                              sliding_window=32)
    cm = CostModel(cfg, 16)
    row = 2 * 2 * 16 * 2                         # K and V, 2 heads of 16
    assert cm.kv_bytes_per_token == 9 * row
    # a decode token at context 100: the 2 full layers read 99 keys (7
    # pages), the 7 window layers 32 (2 pages)
    d = cm.decode_cost(100)
    assert d["bytes_kv_read"] == 2 * row * 112 + 7 * row * 32
    per_layer = cm.attn_flops_per_pair / 9
    assert d["flops_attn"] == pytest.approx(
        per_layer * (2 * 100 + 7 * 32))
    # under the window the two kinds cost alike
    assert cm.decode_cost(20)["bytes_kv_read"] == 9 * row * 32
    # a chunk of 16 at 40: full 16 x 40 + 136 pairs, window 16 x 32
    c = cm.chunk_cost(40, 16)
    assert c["flops_attn"] == pytest.approx(
        per_layer * (2 * (640 + 136) + 7 * 512))
    assert c["bytes_kv_read"] == 2 * row * 48 + 7 * row * 48
    # the dense family: one group, today's numbers
    dense = CostModel(llama.config("debug"), 16)
    assert dense.decode_cost(100)["bytes_kv_read"] == (
        dense.kv_bytes_per_token * 112)
    assert dense.decode_cost(100)["flops_attn"] == (
        dense.attn_flops_per_pair * 100)
