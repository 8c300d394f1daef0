"""Unified ragged prefill+decode step (ISSUE 1 / Ragged Paged
Attention, PAPERS.md) and its Pallas kernel (ISSUE 2).

Gates:
- the ragged paged op matches its CPU-exact dense oracle across ragged
  shapes (pure decode, pure prefill, mixed, single-token prompts,
  page-boundary-straddling chunks, padding rows);
- the Pallas ragged kernel (interpret mode — the same program compiles
  on TPU) matches the oracle across GQA group widths, partial last
  pages, decode-only rows, all-padding rows, and start=0 slots;
- the engine's tokens are a greedy decode by the training forward
  (llama.forward) over the full history, under every packing the
  scheduler's options produce (with and without repetition penalty),
  and decode_impl=pallas_interpret is token-exact vs the gather path;
- a mixed prefill+decode workload costs exactly ONE compiled dispatch
  per engine tick, and a steady-state decode run holds the jit-cache
  compile counter flat (no bucket-churn recompile storms).
"""

import dataclasses
import re
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.ops.ragged_paged_attention import (
    ragged_attention_dense_oracle, ragged_block_sizes, ragged_item_bound,
    ragged_paged_attention_pallas, ragged_paged_prefill_decode_attention,
    ragged_work_counts, ragged_work_list)


# ------------------------------------------------------------ op vs oracle

def _ragged_case(rng, segs, page_size=4, kvh=2, group=2, d=8, pad=0,
                 extra_pages=0):
    """Build a ragged batch from [(start, n_tokens)] per slot, scatter
    each slot's context into a paged pool, and return everything both
    the op and the oracle need. extra_pages widens the page table past
    what any slot holds."""
    b = len(segs)
    h = kvh * group
    max_ctx = max((s for s, _ in segs), default=0)
    max_pages = max(-(-max(s + n for s, n in segs) // page_size), 1)
    max_pages += extra_pages
    num_pages = b * max_pages + 1
    k_pages = np.zeros((num_pages, page_size, kvh, d), np.float32)
    v_pages = np.zeros((num_pages, page_size, kvh, d), np.float32)
    tables = np.arange(b * max_pages, dtype=np.int32).reshape(b, max_pages)
    dense_k = rng.normal(size=(b, max(max_ctx, 1), kvh, d)).astype(
        np.float32)
    dense_v = rng.normal(size=(b, max(max_ctx, 1), kvh, d)).astype(
        np.float32)
    for s in range(b):
        for p in range(segs[s][0]):
            page = tables[s, p // page_size]
            k_pages[page, p % page_size] = dense_k[s, p]
            v_pages[page, p % page_size] = dense_v[s, p]
    t = sum(n for _, n in segs) + pad
    slot_ids = np.zeros(t, np.int32)
    positions = np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    cur = 0
    for s, (start, n) in enumerate(segs):
        slot_ids[cur:cur + n] = s
        positions[cur:cur + n] = np.arange(start, start + n)
        valid[cur:cur + n] = True
        cur += n
    q = rng.normal(size=(t, h, d)).astype(np.float32)
    k_new = rng.normal(size=(t, kvh, d)).astype(np.float32)
    v_new = rng.normal(size=(t, kvh, d)).astype(np.float32)
    start = np.asarray([s for s, _ in segs], np.int32)
    return dict(q=q, k_pages=k_pages, v_pages=v_pages, tables=tables,
                slot_ids=slot_ids, positions=positions, valid=valid,
                start=start, k_new=k_new, v_new=v_new,
                dense_k=dense_k, dense_v=dense_v)


@pytest.mark.parametrize("name,segs,pad", [
    ("pure_decode", [(5, 1), (11, 1), (3, 1)], 0),
    ("pure_prefill", [(0, 6), (0, 3), (0, 9)], 0),
    ("mixed", [(7, 1), (0, 5), (12, 1), (4, 6)], 0),
    ("single_token_prompts", [(0, 1), (0, 1), (9, 1)], 0),
    # chunks whose (start, start+n) straddle page boundaries (page=4)
    ("page_straddle", [(3, 6), (6, 5), (2, 1)], 0),
    ("padding_rows", [(5, 1), (0, 4)], 7),
])
def test_ragged_op_matches_dense_oracle(name, segs, pad):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    c = _ragged_case(rng, segs, pad=pad)
    out = np.asarray(ragged_paged_prefill_decode_attention(
        jnp.asarray(c["q"]), jnp.asarray(c["k_pages"]),
        jnp.asarray(c["v_pages"]), jnp.asarray(c["tables"]),
        jnp.asarray(c["slot_ids"]), jnp.asarray(c["positions"]),
        jnp.asarray(c["valid"]), jnp.asarray(c["start"]),
        jnp.asarray(c["k_new"]), jnp.asarray(c["v_new"])))
    ref = ragged_attention_dense_oracle(
        c["q"], c["dense_k"], c["dense_v"], c["k_new"], c["v_new"],
        c["slot_ids"], c["positions"], c["valid"], c["start"])
    np.testing.assert_allclose(out[c["valid"]], ref[c["valid"]],
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------------ pallas kernel vs oracle

def _kernel_out(c, **kw):
    return np.asarray(ragged_paged_attention_pallas(
        jnp.asarray(c["q"]), jnp.asarray(c["k_pages"]),
        jnp.asarray(c["v_pages"]), jnp.asarray(c["tables"]),
        jnp.asarray(c["slot_ids"]), jnp.asarray(c["positions"]),
        jnp.asarray(c["valid"]), jnp.asarray(c["start"]),
        jnp.asarray(c["k_new"]), jnp.asarray(c["v_new"]), **kw))


def _oracle_out(c, window=None):
    return ragged_attention_dense_oracle(
        c["q"], c["dense_k"], c["dense_v"], c["k_new"], c["v_new"],
        c["slot_ids"], c["positions"], c["valid"], c["start"], window)


# The cells' head geometries, in the cells' type: bf16 pools with an even
# number of kv heads reach the MXU through `split_heads` (a bitcast view
# of the page block, a strided load of 32-bit words a pair of heads), in
# both pool forms; the interpreter runs that path as the chip does.
_TRINITY = dict(kvh=8, group=6)                      # tile form
_SMALLTHINKER = dict(kvh=4, group=7, merged=True)    # merged rows
_NEMOTRON = dict(kvh=2, group=16, merged=True)
_PHI4FLASH = dict(kvh=10, group=4, merged=True)


def _typed_case(rng, segs, *, kvh, group, merged=False, d=128,
                dtype=jnp.bfloat16, **kw):
    """A `_ragged_case` in the kernel's `dtype`, its pools merged-rows if
    asked, and the same case with those VALUES in float32 for the
    oracle."""
    c = _ragged_case(rng, segs, kvh=kvh, group=group, d=d, **kw)
    floats = [k for k, v in c.items() if v.dtype == np.float32]
    chip = dict(c, **{k: jnp.asarray(c[k], dtype) for k in floats})
    c.update({k: np.asarray(chip[k].astype(jnp.float32)) for k in floats})
    if merged:
        pages, page = c["k_pages"].shape[:2]
        for k in ("k_pages", "v_pages"):
            chip[k] = chip[k].reshape(pages, page * kvh, d)
    return chip, c


def _bf16_agrees(name, segs, geo, window=None, **case_kw):
    """The interpreted kernel on bf16 arrays against the float32 oracle
    on the same values: the operands' and the output's rounding is all
    that parts them."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    chip, c = _typed_case(rng, segs, **geo, **case_kw)
    out = _kernel_out(chip, interpret=True, window=window,
                      merged_rows=geo.get("merged", False)
                      ).astype(np.float32)
    ref = _oracle_out(c, window)
    ok = c["valid"]
    np.testing.assert_allclose(out[ok], ref[ok], rtol=3e-2, atol=3e-2)
    assert np.all(np.isfinite(out)) and not out[~ok].any()


@pytest.mark.parametrize("name,segs,pad,kvh,group", [
    # every row decodes (1 token each, ragged contexts)
    ("decode_only", [(5, 1), (11, 1), (3, 1), (8, 1)], 0, 2, 2),
    ("mixed", [(7, 1), (0, 5), (12, 1), (4, 6)], 0, 2, 2),
    # GQA head-group sweep: 1 query head per kv head and a wide group
    ("gqa_group1", [(6, 2), (0, 3), (10, 1)], 0, 3, 1),
    ("gqa_group4", [(6, 2), (0, 3), (10, 1)], 0, 2, 4),
    # contexts ending mid-page (page_size=4): the kernel must mask the
    # tail of the last streamed page
    ("partial_last_page", [(5, 3), (9, 1), (1, 2), (6, 1)], 0, 2, 2),
    # fresh slots: no cached context, in-batch causal only
    ("start_zero", [(0, 1), (0, 4), (0, 1)], 0, 2, 2),
    ("padding_rows", [(5, 1), (0, 4)], 7, 2, 2),
    # a slot with zero tokens this tick + nothing but padding rows
    ("all_padding", [(0, 0)], 6, 2, 2),
    # the cells' geometries in bf16 (kvh names the geometry)
    ("trinity_mixed", [(7, 1), (0, 5), (12, 1), (4, 6)], 0, _TRINITY, 0),
    ("smallthinker_mixed", [(7, 1), (0, 5), (12, 1), (4, 6)], 3,
     _SMALLTHINKER, 0),
    ("nemotron_decode_only", [(5, 1), (11, 1), (3, 1), (8, 1)], 0,
     _NEMOTRON, 0),
    ("phi4flash_partial_last_page", [(5, 3), (9, 1), (1, 2), (6, 1)], 0,
     _PHI4FLASH, 0),
    # a window whose lower edge falls inside a context block (keys
    # 201..300 of a decode row at 300), group 6; and inside the
    # in-batch keys, group 16 on merged rows
    ("window_edge_in_a_context_block", [(300, 1), (200, 5)], 2, _TRINITY,
     100),
    ("window_edge_in_the_batch", [(300, 20), (0, 20)], 0, _NEMOTRON, 8),
])
def test_pallas_ragged_kernel_matches_oracle(name, segs, pad, kvh, group):
    if isinstance(kvh, dict):
        return _bf16_agrees(name, segs, kvh, window=group or None, pad=pad)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    c = _ragged_case(rng, segs, pad=pad, kvh=kvh, group=group)
    out = _kernel_out(c, interpret=True)
    ref = _oracle_out(c)
    np.testing.assert_allclose(out[c["valid"]], ref[c["valid"]],
                               rtol=2e-3, atol=2e-3)
    # invalid rows must come back exact zeros (finite downstream)
    if (~c["valid"]).any():
        assert np.all(out[~c["valid"]] == 0.0)


# every edge of the kernel's work list (one (slot, query block) item
# per grid step, `ragged_work_list`); T > 128 makes q_blk 128, so a
# longer chunk spans several items, and page 4 makes a context block
# 128 keys of 32 pages
_WORK_LIST_EDGES = [
    # name, segs [(cached, tokens)], pad, extra table pages
    ("slot_without_tokens", [(5, 1), (0, 0), (3, 2), (0, 0)], 0, 0),
    ("lone_decode_row", [(9, 1)], 0, 0),
    ("all_32_slots_live",
     [(3 * s % 11, 1 + (s % 5 == 0) * 6) for s in range(32)], 0, 0),
    ("chunk_not_a_multiple_of_the_block", [(6, 1), (20, 200), (2, 1)],
     0, 0),
    ("context_zero", [(0, 150), (0, 1)], 0, 0),
    ("context_not_a_multiple_of_the_kv_block", [(130, 3), (257, 1)],
     0, 0),
    ("context_bucket_wider_than_any_context", [(5, 2), (9, 1)], 0, 40),
    # 129 = one full block and one token over: every slot adds its
    # partial block, which is the most a list can hold
    ("item_list_full", [(4, 129), (0, 129), (7, 1), (2, 1)], 0, 0),
    ("padding_between_the_bound_and_the_tokens", [(3, 140)], 9, 0),
    # bf16 at the cells' geometries (`extra` names geometry and window).
    # A 140-token chunk at 300 cached tokens: its first item sweeps two
    # interior context blocks, the boundary block that holds keys
    # 256..299 and its diagonal block; its second item (12 rows: the
    # `few` body) a whole in-batch block under the diagonal one
    ("interior_boundary_and_diagonal_blocks", [(300, 140), (130, 3)], 0,
     (_TRINITY, None)),
    # the same sweep under a window that cuts inside block 1
    ("window_sweep_starts_inside_a_block", [(300, 140), (130, 3)], 5,
     (_SMALLTHINKER, 200)),
    ("merged_rows_two_heads_chunk_over_blocks",
     [(6, 1), (20, 200), (2, 1)], 0, (_NEMOTRON, None)),
    ("merged_rows_ten_heads_context_off_the_block",
     [(130, 3), (257, 1)], 0, (_PHI4FLASH, None)),
]


@pytest.mark.parametrize("name,segs,pad,extra", _WORK_LIST_EDGES,
                         ids=[c[0] for c in _WORK_LIST_EDGES])
def test_pallas_ragged_kernel_work_list_edges(name, segs, pad, extra):
    if isinstance(extra, tuple):
        geo, window = extra
        return _bf16_agrees(name, segs, geo, window=window, pad=pad)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    c = _ragged_case(rng, segs, pad=pad, extra_pages=extra)
    t = len(c["valid"])
    q_blk, _ = ragged_block_sizes(t, 4, c["tables"].shape[1])
    live, _ = ragged_work_counts(segs, t, 4, c["tables"].shape[1])
    bound = ragged_item_bound(t, len(segs), q_blk)
    assert live < bound
    if name == "item_list_full":
        assert live == bound - 1 and q_blk == 128
    items, seg_rows = (np.asarray(a) for a in ragged_work_list(
        jnp.asarray(c["slot_ids"]), jnp.asarray(c["valid"]),
        jnp.asarray(c["start"]), q_blk))
    assert items.shape == (3, bound)
    assert (items[0] >= 0).sum() == live
    assert np.all(items[0, live:] == -1)       # live items first
    assert np.all(np.diff(items[2, :live]) > 0)    # in flat order
    np.testing.assert_array_equal(seg_rows[1], [n for _, n in segs])
    out = _kernel_out(c, interpret=True)
    ref = _oracle_out(c)
    np.testing.assert_allclose(out[c["valid"]], ref[c["valid"]],
                               rtol=2e-3, atol=2e-3)
    assert np.all(np.isfinite(out))
    if (~c["valid"]).any():
        assert np.all(out[~c["valid"]] == 0.0)


@pytest.mark.parametrize("page_size,pad,ctx_pages", [
    # (q_blk, pages per context block) as ragged_block_sizes derives
    # them from T, the page size and the table's width
    (4, 0, -1),      # q_blk 13 = T, one block of the 4 table pages
    (4, 150, -1),    # T 163: q_blk 128
    (2, 0, -1),      # 7 pages of 2
    (1, 30, 13),     # 13 pages of 1, the table cut to what exists
    (4, 0, 4),       # the static ctx_pages bound covering the data
    # bf16 pairs of heads off blocks narrower than 128 keys
    (4, 0, _TRINITY), (2, 150, _SMALLTHINKER), (1, 30, _NEMOTRON),
    (4, 150, _PHI4FLASH),
])
def test_pallas_ragged_kernel_blocking_invariance(page_size, pad,
                                                  ctx_pages):
    """Online softmax must be exact under any blocking: whatever block
    sizes the kernel derives, it agrees with the oracle; and the static
    ctx_pages bound does not change the math when it covers the live
    data."""
    if isinstance(ctx_pages, dict):
        return _bf16_agrees("blocking", [(7, 1), (0, 5), (12, 1), (4, 6)],
                            ctx_pages, page_size=page_size, pad=pad)
    rng = np.random.default_rng(12)
    c = _ragged_case(rng, [(7, 1), (0, 5), (12, 1), (4, 6)],
                     page_size=page_size, pad=pad)
    out = _kernel_out(c, interpret=True, ctx_pages=ctx_pages)
    np.testing.assert_allclose(out[c["valid"]], _oracle_out(c)[c["valid"]],
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype,merged,paired", [
    ("bfloat16", False, True), ("bfloat16", True, True),
    ("float32", False, False), ("float32", True, False)])
def test_only_bf16_pages_take_the_paired_load(dtype, merged, paired):
    """Which load path a kernel takes is a static fact of its arguments,
    and the interpreter runs the one the chip runs: bf16 pools read
    words of two heads (a shift for the low half: kvh / 2 pairs, K and
    V, context and in-batch), and nothing casts a page block to float32
    whole; float32 pools have no words to split."""
    kvh, page, d = 4, 4, 16
    arr, c = _typed_case(np.random.default_rng(3), [(7, 1), (4, 6)],
                         kvh=kvh, group=2, merged=merged, d=d,
                         dtype=dtype, page_size=page)
    arr = {k: jnp.asarray(v) for k, v in arr.items()}
    text = str(jax.make_jaxpr(
        lambda a: ragged_paged_attention_pallas(
            a["q"], a["k_pages"], a["v_pages"], a["tables"],
            a["slot_ids"], a["positions"], a["valid"], a["start"],
            a["k_new"], a["v_new"], interpret=True,
            merged_rows=merged))(arr))
    assert text.count("shift_left") == (4 * kvh // 2 if paired else 0)
    # ... a float32 value as large as a page block of 3 pages
    whole = c["tables"].shape[1] * page * kvh * d
    casts = re.findall(r"f32\[([\d,]+)\] = convert_element_type", text)
    sizes = {int(np.prod([int(n) for n in dims.split(",")]))
             for dims in casts}
    assert whole not in sizes


@pytest.mark.parametrize("t,page_size,table,kvh,expect", [
    (512, 16, 128, 8, (128, 8)),     # the chat-open cell
    (64, 16, 16, 8, (64, 8)),
    (8, 16, 4, 2, (8, 4)),           # a table narrower than a block
    (256, 128, 64, 8, (128, 1)),     # a page as wide as a block
    (512, 16, 128, 64, (128, 4)),    # 64 kv heads: VMEM halves the block
])
def test_ragged_block_sizes_follow_the_shapes(t, page_size, table, kvh,
                                              expect):
    assert ragged_block_sizes(t, page_size, table, kvh, 128, 2) == expect


def test_ragged_op_ctx_bucketing_matches_full_table():
    """ctx_pages bounds the gather to the pages that exist — same
    output as gathering the whole table."""
    rng = np.random.default_rng(0)
    c = _ragged_case(rng, [(6, 1), (0, 3), (5, 4)])
    args = (jnp.asarray(c["q"]), jnp.asarray(c["k_pages"]),
            jnp.asarray(c["v_pages"]), jnp.asarray(c["tables"]),
            jnp.asarray(c["slot_ids"]), jnp.asarray(c["positions"]),
            jnp.asarray(c["valid"]), jnp.asarray(c["start"]),
            jnp.asarray(c["k_new"]), jnp.asarray(c["v_new"]))
    full = np.asarray(ragged_paged_prefill_decode_attention(*args))
    bucketed = np.asarray(ragged_paged_prefill_decode_attention(
        *args, ctx_pages=2))            # 2 pages cover start=6
    np.testing.assert_allclose(full[c["valid"]], bucketed[c["valid"]],
                               rtol=1e-6, atol=1e-7)


# ------------------------------- the engine against the training forward

_CFG = llama.config("debug", dtype=jnp.float32)


def _engine(**over):
    kw = dict(model=_CFG, max_batch_size=3, page_size=8, num_pages=64,
              max_prefill_tokens=16, seed=9)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _drive(eng, prompts, **sp):
    """Staggered mixed workload: more requests than slots, added while
    earlier ones decode — every tick mixes prefill chunks and decode."""
    reqs = [Request(f"r{i}", list(p), SamplingParams(**sp))
            for i, p in enumerate(prompts)]
    for r in reqs[:2]:
        eng.add_request(r)
    for r in reqs[2:]:
        eng.step()
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    return [r.output_tokens for r in reqs]


def _prompts():
    rng = np.random.default_rng(3)
    # longer than the 16-token chunk (chunked prefill), plus short and
    # single-token prompts
    lens = (40, 23, 1, 33, 7, 19)
    return [rng.integers(2, 250, n).tolist() for n in lens]


_ORACLE_LEN = 64        # every history here is shorter; one program


@jax.jit
def _training_logits(params, tokens):
    return llama.forward(_CFG, params, tokens)


def _assert_greedy_by_training_forward(eng, prompts, outs, n,
                                       penalty=1.0, tol=1e-4):
    """Every request's tokens are a greedy decode by `llama.forward`,
    the training forward, over its full history: an oracle that shares
    nothing with the serving path (no paged cache, no `_layer_body`, no
    `_sample`, no packing). The causal forward over prompt + output
    gives at position len(prompt) - 1 + i the logits that output i was
    drawn from, so one call holds a whole request; the CTRL penalty is
    applied to them here in numpy. A token that is not the argmax may
    only be a near tie: its logit within `tol` of the best."""
    for prompt, out in zip(prompts, outs):
        assert len(out) == n, (len(prompt), out)
        hist = list(prompt) + list(out)
        toks = np.zeros((1, _ORACLE_LEN), np.int32)
        toks[0, :len(hist)] = hist
        logits = np.asarray(_training_logits(eng.params,
                                             jnp.asarray(toks)))[0]
        for i, tok in enumerate(out):
            row = logits[len(prompt) - 1 + i].astype(np.float64)
            if penalty != 1.0:
                seen = np.unique(hist[:len(prompt) + i])
                row[seen] = np.where(row[seen] > 0, row[seen] / penalty,
                                     row[seen] * penalty)
            best = int(np.argmax(row))
            assert tok == best or row[best] - row[tok] <= tol, (
                f"prompt of {len(prompt)}: output {i} is {tok} "
                f"(logit {row[tok]:.6f}), the training forward says "
                f"{best} ({row[best]:.6f})")


@pytest.mark.parametrize("penalty", [1.0, 1.3],
                         ids=["greedy", "penalty"])
@pytest.mark.parametrize("async_readback", [True, False],
                         ids=["async", "sync"])
@pytest.mark.parametrize("budget", [0, 24])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_engine_tokens_are_the_training_forwards(chunk, budget,
                                                 async_readback, penalty):
    """The staggered workload under every packing the scheduler's
    options produce (chunk cap x token budget: prompts cut into 8s and
    16s or taken whole, a budget that splits a chunk across ticks or
    never binds) with the fold a tick late or not, greedy and with the
    seen bookkeeping of the repetition penalty (chunk tokens before
    sampling, emitted samples after)."""
    eng = _engine(max_prefill_tokens=chunk,
                  max_num_batched_tokens=budget,
                  async_readback=async_readback)
    prompts = _prompts()
    outs = _drive(eng, prompts, max_tokens=12,
                  repetition_penalty=penalty)
    _assert_greedy_by_training_forward(eng, prompts, outs, 12, penalty)
    assert eng.stats()["dispatches_per_step"] == 1.0


@pytest.mark.parametrize("penalty", [1.0, 1.3],
                         ids=["greedy", "penalty"])
def test_cold_batch_tokens_are_the_training_forwards(penalty):
    """All six prompts admitted at once on six slots: a tick packs
    several prompts' chunks, and the batch turns to pure decode
    together."""
    eng = _engine(max_batch_size=6)
    prompts = _prompts()
    outs = [r.output_tokens for r in eng.generate(
        [list(p) for p in prompts],
        SamplingParams(max_tokens=10, repetition_penalty=penalty))]
    _assert_greedy_by_training_forward(eng, prompts, outs, 10, penalty)


def test_prefix_cache_tokens_are_the_training_forwards():
    """A prompt that starts on another's cached pages prefills only its
    tail, and decodes what the training forward does over the whole."""
    rng = np.random.default_rng(5)
    shared = rng.integers(2, 250, 24).tolist()
    prompts = [shared + [5], shared + [9, 11]]
    eng = _engine(enable_prefix_caching=True)
    outs = [eng.generate([list(p)], SamplingParams(max_tokens=8)
                         )[0].output_tokens for p in prompts]
    assert eng.allocator.cache_hit_tokens >= 16
    _assert_greedy_by_training_forward(eng, prompts, outs, 8)


def test_pipeline_parallel_mesh_is_refused():
    from ray_tpu.parallel import MeshSpec
    with pytest.raises(ValueError, match="pipeline-parallel serving "
                                         "was removed"):
        _engine(mesh=MeshSpec(dp=1, fsdp=1, sp=1, tp=1, pp=2))
    with pytest.raises(TypeError):
        EngineConfig(unified_step=True)


def test_unified_step_one_dispatch_per_tick():
    """The tentpole contract: a mixed prefill+decode workload costs
    exactly ONE compiled dispatch per engine tick."""
    eng = _engine()
    for i, p in enumerate(_prompts()):
        eng.add_request(Request(f"d{i}", list(p),
                                SamplingParams(max_tokens=8)))
    steps = 0
    d0 = eng.dispatches
    while eng.has_work():
        eng.step()
        steps += 1
    assert steps > 0
    assert eng.dispatches - d0 == steps
    assert eng.stats()["dispatches_per_step"] == 1.0


def test_unified_step_pallas_interpret_token_exact():
    """decode_impl=pallas_interpret routes the ragged tick through the
    Pallas ragged kernel AND the pure-decode tick through the paged
    decode kernel (interpret mode): greedy output must be token-exact
    vs the dense gather engine on a mixed staggered workload."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 250, n).tolist() for n in (40, 23, 1, 19)]
    out_g = _drive(_engine(decode_impl="gather"),
                   [list(p) for p in prompts], max_tokens=6)
    out_p = _drive(_engine(decode_impl="pallas_interpret"),
                   [list(p) for p in prompts], max_tokens=6)
    assert out_g == out_p


def test_jit_cache_counter_stable_in_steady_state():
    """Engine.stats() exposes the live jit-cache buckets and a
    cumulative compile counter; once a decode batch reaches steady
    state, further ticks must not build new programs (bucket churn
    would show up as a recompile storm here)."""
    eng = _engine()
    rng = np.random.default_rng(7)
    for i in range(3):
        eng.add_request(Request(
            f"c{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=30)))
    while any(s.request is not None and not s.ready
              for s in eng.slots) or eng.waiting:
        eng.step()
    for _ in range(3):                    # settle the decode loop
        eng.step()
    st0 = eng.stats()["jit_cache"]
    assert st0["compiled_programs"] > 0
    assert st0["ragged_buckets"] == len(eng._ragged_fns)
    for _ in range(12):                   # steady-state decode
        eng.step()
    st1 = eng.stats()["jit_cache"]
    assert st1["compiled_programs"] == st0["compiled_programs"]
    assert st1["ragged_buckets"] == st0["ragged_buckets"]


# a small engine of each family: (model, what its cache groups need)
_FAMILIES = {
    "llama": ("debug", {}),
    "deepseek_v3": ("deepseek_v3:debug", {}),
    "trinity": ("trinity:debug", {"num_pages_by_group": {"window": 20}}),
    "phi4flash": ("phi4flash:debug",
                  {"num_pages_by_group": {"window": 20}}),
    "nemotron_h": ("nemotron_h:tiny", {}),
}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_kernel_path_keeps_one_program_a_token_bucket(family):
    """`_ctx_bucket`, the context key of a ragged tick's program: the
    gather path cuts the tables to a power of two of pages and keeps a
    program a bucket; every family's kernels read a row's own pages off
    the whole table, so off the gather path the key is the whole table
    or none. The rule reads the implementation that runs, no family's
    flag."""
    model, groups = _FAMILIES[family]
    eng = InferenceEngine(EngineConfig(
        model=model, num_pages=64, max_batch_size=2, page_size=4,
        max_seq_len=256, max_prefill_tokens=8, **groups))
    assert eng.family.name == family
    assert eng._resolve_impl() == "gather"
    assert [eng._ctx_bucket(n) for n in (0, 1, 9, 33)] == [0, 1, 4, 16]
    # past the table's width the bucket is the table
    assert eng._ctx_bucket(255) == eng.max_pages_per_seq == 64
    eng.config = dataclasses.replace(eng.config,
                                     decode_impl="pallas_interpret")
    whole = eng.max_pages_per_seq
    assert [eng._ctx_bucket(n) for n in (0, 1, 9, 33)] == [
        0, whole, whole, whole]


def test_contexts_of_three_buckets_share_one_kernel_program():
    """Three prompts whose last chunks meet cached contexts of 2, 4 and
    8 pages, every chunk one token bucket: under the kernels the engine
    builds ONE ragged program with a context and one without, whatever
    comes after the first prompt, where the gather path builds one a
    context bucket; and a prompt's greedy tokens are those it gives
    when it runs alone."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, 250, n).tolist() for n in (32, 48, 80)]
    sp = dict(max_tokens=4, temperature=0.0)

    def alone(eng, prompt):
        return eng.generate([list(prompt)],
                            SamplingParams(**sp))[0].output_tokens

    eng = _engine(decode_impl="pallas_interpret")
    whole = eng.max_pages_per_seq
    outs = [alone(eng, prompts[0])]
    jc = eng.stats()["jit_cache"]
    assert sorted(eng._ragged_fns) == [(16, 0, True), (16, whole, True)]
    assert jc["ragged_buckets"] == 2
    outs += [alone(eng, p) for p in prompts[1:]]
    # contexts of 4 and 8 pages landed on the program 2 pages built
    assert eng.stats()["jit_cache"] == jc
    assert sorted(eng._ragged_fns) == [(16, 0, True), (16, whole, True)]
    gather = _engine(decode_impl="gather")
    assert [alone(gather, p) for p in prompts] == outs
    assert sorted(gather._ragged_fns) == [
        (16, c, True) for c in (0, 2, 4, 8)]
    # together: the same tokens, and still context or none a bucket
    both = _engine(decode_impl="pallas_interpret")
    reqs = both.generate([list(p) for p in prompts], SamplingParams(**sp))
    assert [r.output_tokens for r in reqs] == outs
    assert {c for _, c, _ in both._ragged_fns} <= {0, whole}


def test_unified_step_multi_lora_mixed_batch():
    """Per-token adapter indices: a batch mixing base and a strong
    adapter through the ragged step reproduces each request's solo
    output."""
    cfg = llama.config("debug", dtype=jnp.float32)
    eng = _engine(model=cfg, max_batch_size=4)
    L, h, q_dim, r = cfg.n_layers, cfg.hidden, cfg.q_dim, 4
    rng = np.random.default_rng(1)
    eng.register_lora("strong", {
        "wq": (rng.normal(0, 0.5, (L, h, r)),
               rng.normal(0, 0.5, (r, q_dim)) * np.ones((L, 1, 1)))})
    prompt = list(rng.integers(2, 250, 20))   # > chunk: ragged ticks
    sp = SamplingParams(max_tokens=6)

    def solo(lora, rid):
        req = Request(rid, list(prompt), sp, lora=lora)
        eng.add_request(req)
        while not req.finished:
            eng.step()
        return req.output_tokens

    base, strong = solo(None, "b"), solo("strong", "s")
    assert base != strong
    r1 = Request("mb", list(prompt), sp)
    r2 = Request("ms", list(prompt), sp, lora="strong")
    eng.add_request(r1)
    eng.add_request(r2)
    while not (r1.finished and r2.finished):
        eng.step()
    assert r1.output_tokens == base
    assert r2.output_tokens == strong
