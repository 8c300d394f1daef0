"""Pallas paged-decode kernel vs the dense-gather reference, and the
kernel-backed decode_step vs the gather-backed one (interpret mode — the
same kernel compiles on TPU).

Pool layout: [n_layers, num_pages, page_size, KVH, D]; single-layer
slices passed to the kernel are [num_pages, page_size, KVH, D].
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama_infer import decode_step, ragged_forward
from ray_tpu.ops import kv_quant
from ray_tpu.ops import paged_attention as pa


def _pool(rng, num_pages=32, page_size=16, kvh=4, d=64):
    k = jnp.asarray(rng.normal(size=(num_pages, page_size, kvh, d)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(num_pages, page_size, kvh, d)),
                    jnp.float32)
    return k, v


def _dense(pages, tables):
    """[pages, page, KVH, D] + [B, P] -> [B, P*page, KVH, D]"""
    g = pages[tables]                       # [B, P, page, KVH, D]
    b, p, s, h, d = g.shape
    return g.reshape(b, p * s, h, d)


def test_kernel_matches_dense_gather():
    rng = np.random.default_rng(0)
    B, H, KVH, D = 3, 8, 4, 64
    num_pages, page_size, max_pages = 32, 16, 8
    k_pages, v_pages = _pool(rng, num_pages, page_size, KVH, D)
    tables = jnp.asarray(
        rng.permutation(num_pages - 1)[:B * max_pages].reshape(B, max_pages),
        jnp.int32)
    seq_lens = jnp.asarray([5, 37, 128], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)

    ref = pa.paged_attention_on_gathered(
        q, _dense(k_pages, tables), _dense(v_pages, tables), seq_lens)
    out = pa.paged_decode_attention(
        q, k_pages, v_pages, tables, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=2e-5, rtol=2e-5)


def test_kernel_new_token_merge():
    rng = np.random.default_rng(1)
    B, H, KVH, D = 2, 8, 4, 64
    num_pages, page_size, max_pages = 16, 16, 4
    k_pages, v_pages = _pool(rng, num_pages, page_size, KVH, D)
    tables = jnp.asarray(
        rng.permutation(num_pages - 1)[:B * max_pages].reshape(B, max_pages),
        jnp.int32)
    seq_lens = jnp.asarray([0, 23], jnp.int32)   # incl. empty-cache case
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, KVH, D)), jnp.float32)

    k_full = jnp.concatenate([_dense(k_pages, tables), k_new[:, None]],
                             axis=1)
    v_full = jnp.concatenate([_dense(v_pages, tables), v_new[:, None]],
                             axis=1)
    ref = pa.paged_attention_on_gathered(q, k_full, v_full, seq_lens,
                                         append_len=1)
    out = pa.paged_decode_with_new_token(
        q, k_pages, v_pages, tables, seq_lens, k_new, v_new, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=2e-5, rtol=2e-5)


def test_decode_step_kernel_matches_gather():
    cfg = llama.config("debug", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    B, page_size, num_pages, max_pages = 2, 16, 16, 4
    kv_shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads,
                cfg.head_dim)
    k_pages = jnp.zeros(kv_shape, cfg.dtype)
    v_pages = jnp.zeros(kv_shape, cfg.dtype)
    tables = jnp.asarray(
        np.arange(B * max_pages).reshape(B, max_pages), jnp.int32)

    # fill the cache: one segment a sequence, 8 and 5 tokens, nothing
    # cached before them
    true_lens = jnp.asarray([8, 5], jnp.int32)
    _, k_pages, v_pages = ragged_forward(
        cfg, params,
        jnp.asarray(rng.integers(0, cfg.vocab_size, 13), jnp.int32),
        jnp.asarray([0] * 8 + [1] * 5, jnp.int32),
        jnp.asarray(list(range(8)) + list(range(5)), jnp.int32),
        jnp.ones(13, bool), jnp.zeros(B, jnp.int32),
        jnp.asarray([7, 12], jnp.int32), k_pages, v_pages, tables,
        ctx_pages=0)

    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
    active = jnp.asarray([True, True])
    ref_logits, rk, rv = decode_step(
        cfg, params, tokens, true_lens, k_pages, v_pages, tables, active,
        impl="gather")
    out_logits, ok, ov = decode_step(
        cfg, params, tokens, true_lens, k_pages, v_pages, tables, active,
        impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(ref_logits),
                               np.asarray(out_logits), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(ok),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kind", ["f32", "int8"])
@pytest.mark.parametrize("forward", ["decode_step", "ragged_forward"])
def test_kernel_forwards_read_each_layers_own_pages(forward, kind):
    """The kernel path hands the kernels the pools of all layers whole
    and a table shifted to the layer's pages. Three layers whose pools
    hold DIFFERENT rows and tables over the pool's LAST pages: a layer
    that read another's pages, or pages past its own, would leave the
    gather path's logits."""
    cfg = dataclasses.replace(llama.config("debug", dtype=jnp.float32),
                              n_layers=3)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    B, page_size, num_pages, max_pages = 2, 16, 12, 4
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads,
             cfg.head_dim)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    kw = {}
    if kind != "f32":
        k_pages, k_scales = kv_quant.quantize_rows(k_pages, kind)
        v_pages, v_scales = kv_quant.quantize_rows(v_pages, kind)
        kw = dict(kv_kind=kind, k_scales=k_scales, v_scales=v_scales)
    # pages 3..10 in a random order; 11 is the scatter's scratch page
    tables = jnp.asarray(
        num_pages - 2 - rng.permutation(B * max_pages).reshape(
            B, max_pages), jnp.int32)
    cached = jnp.asarray([2 * page_size + 3, 5], jnp.int32)
    if forward == "decode_step":
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
        run = lambda impl: decode_step(
            cfg, params, tokens, cached, k_pages, v_pages, tables,
            jnp.ones(B, bool), impl=impl, **kw)
    else:
        # a decode row of slot 0 and a 6-token chunk of slot 1
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, 7), jnp.int32)
        run = lambda impl: ragged_forward(
            cfg, params, tokens, jnp.asarray([0] + [1] * 6, jnp.int32),
            jnp.asarray([2 * page_size + 3] + list(range(5, 11)),
                        jnp.int32),
            jnp.ones(7, bool), cached, jnp.asarray([0, 6], jnp.int32),
            k_pages, v_pages, tables, ctx_pages=3, impl=impl, **kw)
    ref, out = run("gather"), run("pallas_interpret")
    assert len(ref) == len(out) == (3 if kind == "f32" else 5)
    np.testing.assert_allclose(np.asarray(ref[0]), np.asarray(out[0]),
                               atol=1e-4, rtol=1e-4)
    for r, o in zip(ref[1:], out[1:]):          # pools (and scales)
        assert r.shape == o.shape and r.dtype == o.dtype
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(o, np.float32),
                                   atol=1e-4, rtol=1e-4)


def test_multipage_kernel_matches_dense_gather():
    """The multi-page manual-DMA kernel (the TPU decode hot path) in
    interpret mode vs the dense reference — including partial blocks,
    a zero-length row, and full-context rows."""
    from ray_tpu.ops.paged_attention import _paged_decode_multipage

    rng = np.random.default_rng(3)
    B, H, KVH, D = 3, 8, 4, 64
    num_pages, page_size, max_pages = 100, 8, 32
    k_pages = jnp.asarray(
        rng.normal(size=(num_pages, page_size, KVH, D)), jnp.float32)
    v_pages = jnp.asarray(
        rng.normal(size=(num_pages, page_size, KVH, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(num_pages - 1)[:B * max_pages].reshape(
            B, max_pages), jnp.int32)
    # 0 (inactive slot), mid partial block, exactly full context
    seq_lens = jnp.asarray([0, 77, page_size * max_pages], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)

    out, m, l = _paged_decode_multipage(
        q, k_pages, v_pages, tables, seq_lens, ppb=4, interpret=True)
    ref = pa.paged_attention_on_gathered(
        q, _dense(k_pages, tables), _dense(v_pages, tables),
        jnp.maximum(seq_lens, 1))   # kernel clamps 0 -> 1 page row
    np.testing.assert_allclose(
        np.asarray(out).reshape(B, H, D)[1:], np.asarray(ref)[1:],
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind", ["f32", "int8"])
@pytest.mark.parametrize("B", [3, 8, 32])
def test_decode_step_is_the_ragged_tick_of_one_token_a_slot(B, kind):
    """On the kernel path the dense family's decode tick IS
    `ragged_forward` of one token a slot (PR 50), bit for bit: logits,
    pools and scale pools, with a LoRA adapter a slot and some slots
    inactive, whose pool rows nobody writes. And it stays within the
    band of the gather path, which keeps a dense forward of its own."""
    cfg = llama.config("debug", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(50 + B)
    page_size, max_pages = 16, 4
    num_pages = B * max_pages + 1           # the last: the scratch page
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads,
             cfg.head_dim)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    kw = {}
    if kind != "f32":
        k_pages, k_scales = kv_quant.quantize_rows(k_pages, kind)
        v_pages, v_scales = kv_quant.quantize_rows(v_pages, kind)
        kw = dict(kv_kind=kind, k_scales=k_scales, v_scales=v_scales)
    tables = jnp.asarray(
        rng.permutation(B * max_pages).reshape(B, max_pages), jnp.int32)
    # contexts from none to one short of the table's width
    cached = jnp.asarray(
        rng.integers(0, max_pages * page_size - 1, B), jnp.int32)
    active = jnp.asarray(np.arange(B) % 3 != 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    adapters, rank = 3, 4                   # adapter 0: the zero adapter
    stack = lambda i, o: {
        "a": jnp.asarray(rng.normal(size=(cfg.n_layers, adapters, i, rank)),
                         jnp.float32).at[:, 0].set(0.0) * 0.1,
        "b": jnp.asarray(rng.normal(size=(cfg.n_layers, adapters, rank, o)),
                         jnp.float32).at[:, 0].set(0.0) * 0.1}
    kw["lora"] = {"wq": stack(cfg.hidden, cfg.q_dim),
                  "wo": stack(cfg.q_dim, cfg.hidden)}
    kw["lora_idx"] = jnp.asarray(np.arange(B) % adapters, jnp.int32)

    out = decode_step(cfg, params, tokens, cached, k_pages, v_pages, tables,
                      active, impl="pallas_interpret", **kw)
    slots = jnp.arange(B, dtype=jnp.int32)
    same = ragged_forward(
        cfg, params, tokens, slots, cached, active, cached, slots,
        k_pages, v_pages, tables, ctx_pages=-1, impl="pallas_interpret",
        **kw)
    ref = decode_step(cfg, params, tokens, cached, k_pages, v_pages, tables,
                      active, impl="gather", **kw)
    assert len(out) == len(same) == len(ref) == (3 if kind == "f32" else 5)
    act = np.asarray(active)
    for o, s, r in zip(out, same, ref):
        assert o.shape == s.shape == r.shape and o.dtype == s.dtype == r.dtype
        o, s, r = (np.asarray(a, np.float32) for a in (o, s, r))
        np.testing.assert_array_equal(o, s)
        # an inactive slot's logits are nobody's to read, and its rows
        # go to the scratch page
        o, r = (o[act], r[act]) if o.ndim == 2 else (o[:, :-1], r[:, :-1])
        np.testing.assert_allclose(r, o, atol=1e-4, rtol=1e-4)
    # an inactive slot's row of the pools is as it was
    pos = np.asarray(cached)
    page = np.asarray(tables)[np.arange(B), pos // page_size]
    for before, after in zip((k_pages, v_pages), out[1:3]):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(
            after[:, page[~act], pos[~act] % page_size],
            before[:, page[~act], pos[~act] % page_size])
        assert (after[:, page[act], pos[act] % page_size]
                != before[:, page[act], pos[act] % page_size]).any()
