"""Quantized KV serving end-to-end (ISSUE 16).

Gates, layer by layer:
- `ops/kv_quant.py` quantize/dequantize row properties: per-(row,
  head) scales, bounded relative error, exact zeros, byte accounting;
- the quantized ragged Pallas kernel (interpret mode — the same
  program compiles on TPU) matches the f32 dense oracle FED THE SAME
  DEQUANTIZED VALUES across GQA widths, partial last pages,
  decode-only batches, padding rows, per-page scale extremes, and
  block-size choices — the fused dequant must be exact, quantization
  error lives only in the (tested) quantizer;
- quantize-at-append (`scatter_kv_quant`) writes only its target rows
  and round-trips through `gather_kv_quant` within the quantizer's
  error bound;
- engine-level: int8/fp8 preempt/restore and session migration are
  token-exact vs a same-kind oracle (quantization changes tokens;
  moving pages must not), kind-mismatched imports are REJECTED, and
  the byte gauges report the configured page dtype;
- wire v2: scale arrays + kv_dtype round-trip byte-exact, v1 frames
  still decode as f32, corruption anywhere in the scale region raises
  the transport-error family, self-inconsistent quant frames are bad
  payloads;
- EQuARX-style quantized collectives match the f32 lax collectives
  within per-kind tolerance on a multi-device CPU mesh.
"""

import dataclasses
import functools
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.models import llama
from ray_tpu.ops import kv_quant
from ray_tpu.ops import quantized_collectives as qcoll
from ray_tpu.ops.paged_attention import gather_kv_quant, scatter_kv_quant
from ray_tpu.ops.ragged_paged_attention import (
    ragged_attention_dense_oracle, ragged_paged_attention_pallas)
from ray_tpu.serve.llm import kv_transport as kvt

QUANT_KINDS = ("int8", "fp8")
# quantizer round-trip bounds: int8 has 7 value bits per row-scaled
# lane; fp8 e4m3 carries ~3 mantissa bits
RT_RTOL = {"int8": 0.01, "fp8": 0.07}


# ------------------------------------------------------- kv_quant unit

@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quantize_rows_roundtrip_bounded(kind):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 7, 3, 16)).astype(np.float32)
                    * 4.0)
    q, s = kv_quant.quantize_rows(x, kind)
    assert q.dtype == kv_quant.storage_dtype(kind)
    assert s.shape == x.shape[:-1] and s.dtype == jnp.float32
    y = kv_quant.dequantize_rows(q, s, kind)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < RT_RTOL[kind], (kind, rel)


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quantize_rows_zero_rows_exact_and_no_nan(kind):
    x = jnp.zeros((3, 4, 2, 8), jnp.float32)
    q, s = kv_quant.quantize_rows(x, kind)
    y = kv_quant.dequantize_rows(q, s, kind)
    assert float(jnp.max(jnp.abs(y))) == 0.0
    assert not bool(jnp.any(jnp.isnan(y)))


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quantize_rows_scale_extremes(kind):
    """Rows spanning 8 orders of magnitude: per-row scales keep the
    RELATIVE error flat across the range (one global scale would
    crush the small rows to zero)."""
    rng = np.random.default_rng(1)
    base = rng.normal(size=(8, 1, 1, 16)).astype(np.float32)
    mags = (10.0 ** np.arange(-4, 4)).reshape(8, 1, 1, 1)
    x = jnp.asarray(base * mags)
    y = kv_quant.dequantize_rows(*kv_quant.quantize_rows(x, kind),
                                 kind=kind)
    for i in range(8):
        num = float(jnp.linalg.norm(y[i] - x[i]))
        den = float(jnp.linalg.norm(x[i]))
        assert num / den < RT_RTOL[kind], (kind, i, num / den)


def test_kv_quant_kind_table_and_bytes():
    assert kv_quant.validate_kind("f32") == "f32"
    with pytest.raises(ValueError):
        kv_quant.validate_kind("int4")
    with pytest.raises(ValueError):
        kv_quant.quantize_rows(jnp.zeros((2, 4)), "f32")
    # token_row_bytes: f32 rows are 4 B/value; quant rows are 1
    # B/value + one 4 B scale per head
    assert kv_quant.token_row_bytes("f32", 2, 32) == 2 * 32 * 4
    for kind in QUANT_KINDS:
        assert kv_quant.token_row_bytes(kind, 2, 32) == 2 * 32 + 2 * 4
    # >= 1.9x footprint/read-bytes (the perf_opt headline) at every
    # realistic head_dim
    for d in (32, 64, 128, 256):
        assert (kv_quant.token_row_bytes("f32", 1, d)
                / kv_quant.token_row_bytes("int8", 1, d)) >= 1.9


# ---------------------------------------- quantized kernel vs oracle

def _quant_case(rng, segs, kind, page_size=4, kvh=2, group=2, d=8,
                pad=0, mags=None):
    """A ragged batch whose PAGED context is quantized storage. The
    oracle sees the DEQUANTIZED values (quantize_rows is per-(token,
    head) on both layouts, so quantizing the dense context gives
    byte-identical values to quantizing the pages) — any kernel/
    oracle gap is a fused-dequant bug, not quantization error."""
    b = len(segs)
    h = kvh * group
    max_ctx = max((s for s, _ in segs), default=0)
    max_pages = max(-(-max(s + n for s, n in segs) // page_size), 1)
    num_pages = b * max_pages + 1
    dense_k = rng.normal(size=(b, max(max_ctx, 1), kvh, d)).astype(
        np.float32)
    dense_v = rng.normal(size=(b, max(max_ctx, 1), kvh, d)).astype(
        np.float32)
    if mags is not None:                  # per-position magnitude ramp
        dense_k = dense_k * mags
        dense_v = dense_v * mags
    kq, ks_d = kv_quant.quantize_rows(jnp.asarray(dense_k), kind)
    vq, vs_d = kv_quant.quantize_rows(jnp.asarray(dense_v), kind)
    dense_k_dq = np.asarray(kv_quant.dequantize_rows(kq, ks_d, kind))
    dense_v_dq = np.asarray(kv_quant.dequantize_rows(vq, vs_d, kind))
    k_pages = np.zeros((num_pages, page_size, kvh, d),
                       np.asarray(kq).dtype)
    v_pages = np.zeros_like(k_pages)
    k_scales = np.zeros((num_pages, page_size, kvh), np.float32)
    v_scales = np.zeros_like(k_scales)
    tables = np.arange(b * max_pages, dtype=np.int32).reshape(
        b, max_pages)
    for s in range(b):
        for p in range(segs[s][0]):
            page, row = tables[s, p // page_size], p % page_size
            k_pages[page, row] = np.asarray(kq)[s, p]
            v_pages[page, row] = np.asarray(vq)[s, p]
            k_scales[page, row] = np.asarray(ks_d)[s, p]
            v_scales[page, row] = np.asarray(vs_d)[s, p]
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
    return dict(q=q, k_pages=k_pages, v_pages=v_pages,
                k_scales=k_scales, v_scales=v_scales, tables=tables,
                slot_ids=slot_ids, positions=positions, valid=valid,
                start=start, k_new=k_new, v_new=v_new,
                dense_k=dense_k_dq, dense_v=dense_v_dq)


def _quant_kernel_out(c, **kw):
    return np.asarray(ragged_paged_attention_pallas(
        jnp.asarray(c["q"]), jnp.asarray(c["k_pages"]),
        jnp.asarray(c["v_pages"]), jnp.asarray(c["tables"]),
        jnp.asarray(c["slot_ids"]), jnp.asarray(c["positions"]),
        jnp.asarray(c["valid"]), jnp.asarray(c["start"]),
        jnp.asarray(c["k_new"]), jnp.asarray(c["v_new"]),
        k_scales=jnp.asarray(c["k_scales"]),
        v_scales=jnp.asarray(c["v_scales"]), **kw))


def _oracle_out(c):
    return ragged_attention_dense_oracle(
        c["q"], c["dense_k"], c["dense_v"], c["k_new"], c["v_new"],
        c["slot_ids"], c["positions"], c["valid"], c["start"])


@pytest.mark.parametrize("kind", QUANT_KINDS)
@pytest.mark.parametrize("name,segs,pad,kvh,group", [
    ("decode_only", [(5, 1), (11, 1), (3, 1), (8, 1)], 0, 2, 2),
    ("mixed", [(7, 1), (0, 5), (12, 1), (4, 6)], 0, 2, 2),
    ("gqa_group1", [(6, 2), (0, 3), (10, 1)], 0, 3, 1),
    ("gqa_group4", [(6, 2), (0, 3), (10, 1)], 0, 2, 4),
    ("partial_last_page", [(5, 3), (9, 1), (1, 2), (6, 1)], 0, 2, 2),
    ("padding_rows", [(5, 1), (0, 4)], 7, 2, 2),
])
def test_quant_kernel_matches_dequant_oracle(name, segs, pad, kvh,
                                             group, kind):
    rng = np.random.default_rng(zlib.crc32(f"{name}/{kind}".encode()))
    c = _quant_case(rng, segs, kind, pad=pad, kvh=kvh, group=group)
    out = _quant_kernel_out(c, interpret=True)
    ref = _oracle_out(c)
    np.testing.assert_allclose(out[c["valid"]], ref[c["valid"]],
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quant_kernel_per_page_scale_extremes(kind):
    """Context whose magnitude ramps 6 orders across positions: the
    per-(row, head) scales land per PAGE in storage, and the fused
    dequant must reproduce every page's range exactly (a kernel that
    mixed up scale rows would be off by orders of magnitude, not
    epsilons)."""
    rng = np.random.default_rng(7)
    segs = [(12, 1), (9, 2)]
    mags = (10.0 ** rng.uniform(-3, 3, size=(1, 12, 1, 1))).astype(
        np.float32)
    c = _quant_case(rng, segs, kind, mags=mags)
    out = _quant_kernel_out(c, interpret=True)
    ref = _oracle_out(c)
    np.testing.assert_allclose(out[c["valid"]], ref[c["valid"]],
                               rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quant_kernel_block_size_invariance(kind):
    """The kernel derives its block sizes from T: whatever the padding
    of the batch makes them (q_blk 13, 40, 128), the fused dequant
    agrees with the oracle."""
    segs = [(7, 1), (0, 5), (12, 1), (4, 6)]
    for pad in (0, 27, 150):
        c = _quant_case(np.random.default_rng(11), segs, kind, pad=pad)
        out = _quant_kernel_out(c, interpret=True)
        np.testing.assert_allclose(
            out[c["valid"]], _oracle_out(c)[c["valid"]],
            rtol=2e-4, atol=2e-5)


# -------------------------------------- quantize-at-append round trip

@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_scatter_gather_quant_roundtrip(kind):
    rng = np.random.default_rng(3)
    L, P, page, kvh, d = 2, 6, 4, 2, 8
    kp = jnp.zeros((L, P, page, kvh, d), kv_quant.storage_dtype(kind))
    vp = jnp.zeros_like(kp)
    ks = jnp.zeros((L, P, page, kvh), jnp.float32)
    vs = jnp.zeros_like(ks)
    n = 5
    k_new = jnp.asarray(rng.normal(size=(n, L, kvh, d))
                        .astype(np.float32) * 2.0)
    v_new = jnp.asarray(rng.normal(size=(n, L, kvh, d))
                        .astype(np.float32) * 2.0)
    tables = jnp.asarray(np.tile(np.array([[0, 1]], np.int32), (n, 1)))
    positions = jnp.asarray(np.arange(n, dtype=np.int32))
    valid = jnp.asarray(np.array([1, 1, 1, 1, 0], bool))
    kp, vp, ks, vs = scatter_kv_quant(kp, vp, ks, vs, k_new, v_new,
                                      tables, positions, valid, kind)
    got_k, got_v = gather_kv_quant(kp, vp, ks, vs,
                                   jnp.asarray([[0, 1]], np.int32))
    want_k = kv_quant.dequantize_rows(
        *kv_quant.quantize_rows(k_new, kind), kind=kind)
    for i in range(n):
        row = np.asarray(got_k)[:, 0, i]            # [L, kvh, d]
        if bool(valid[i]):
            np.testing.assert_allclose(row, np.asarray(want_k)[i],
                                       rtol=1e-6, atol=1e-7)
        else:
            assert float(np.abs(row).max()) == 0.0  # scratch-paged


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_scatter_quant_write_only_append(kind):
    """Appending must not re-quantize or disturb neighbor rows: rows
    written earlier keep their exact stored bytes."""
    rng = np.random.default_rng(4)
    L, P, page, kvh, d = 1, 3, 4, 1, 8
    kp = jnp.zeros((L, P, page, kvh, d), kv_quant.storage_dtype(kind))
    vp = jnp.zeros_like(kp)
    ks = jnp.zeros((L, P, page, kvh), jnp.float32)
    vs = jnp.zeros_like(ks)

    def append(kp, vp, ks, vs, pos):
        kn = jnp.asarray(rng.normal(size=(1, L, kvh, d))
                         .astype(np.float32))
        return scatter_kv_quant(
            kp, vp, ks, vs, kn, kn,
            jnp.asarray([[0, 1]], np.int32),
            jnp.asarray([pos], np.int32), jnp.ones(1, bool), kind)

    kp, vp, ks, vs = append(kp, vp, ks, vs, 0)
    before = np.asarray(kp[0, 0, 0]).copy()
    sbefore = np.asarray(ks[0, 0, 0]).copy()
    kp, vp, ks, vs = append(kp, vp, ks, vs, 1)
    np.testing.assert_array_equal(np.asarray(kp[0, 0, 0]), before)
    np.testing.assert_array_equal(np.asarray(ks[0, 0, 0]), sbefore)


# ------------------------------------------------------- engine level

_COMMON = dict(model="debug", num_pages=64, page_size=4,
               max_batch_size=3)
_PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8], [9, 8, 7, 6, 5, 4, 3, 2],
            [11, 12, 13, 14, 15, 16, 17, 18]]


def _run(eng):
    while eng.has_work():
        eng.step()


def _mk(kind, **kw):
    c = dict(_COMMON)
    c.update(kw)
    eng = InferenceEngine(EngineConfig(kv_dtype=kind, **c))
    reqs = [Request(f"q{i}", list(p), SamplingParams(max_tokens=20))
            for i, p in enumerate(_PROMPTS)]
    for r in reqs:
        eng.add_request(r)
    return eng, reqs


def test_engine_rejects_quant_composition():
    with pytest.raises(ValueError):
        InferenceEngine(EngineConfig(model="debug", kv_dtype="int4"))


@pytest.mark.parametrize("kind", QUANT_KINDS)
def test_quant_preempt_restore_token_exact_vs_same_kind_oracle(kind):
    """THE quantized-hierarchy gate: quantization legitimately changes
    tokens, so the oracle is a never-preempted engine of the SAME
    kind — spill/restore must move the narrow pages + scales
    bit-exact and resume the identical stream."""
    ora, oreqs = _mk(kind)
    _run(ora)
    eng, reqs = _mk(kind, enable_kv_offload=True)
    while len(reqs[1].output_tokens) < 5:
        eng.step()
    assert eng.preempt("q1", reason="manual")
    assert eng.host_tier.spills_total == 1
    parked = eng.host_tier.entries()[0]
    assert parked.kv_kind == kind
    _run(eng)
    assert eng.host_tier.restores_total == 1
    for o, r in zip(oreqs, reqs):
        assert o.output_tokens == r.output_tokens, r.request_id


def test_quant_parked_payload_bytes_count_scales():
    eng, reqs = _mk("int8", enable_kv_offload=True)
    while len(reqs[1].output_tokens) < 3:
        eng.step()
    assert eng.preempt("q1", reason="manual")
    parked = eng.host_tier.entries()[0]
    assert parked.kv_kind == "int8"
    assert parked.k_scales_pending is not None or (
        parked.k_scales_host is not None)
    # values (1 B) + scales (4 B/head) per token row, k and v, every
    # layer — exactly the engine's configured page byte size
    mc = eng.model_cfg
    row = 2 * mc.n_layers * kv_quant.token_row_bytes(
        "int8", mc.n_kv_heads, mc.head_dim)
    want = parked.n_pages * row * _COMMON["page_size"]
    assert parked.payload_bytes() == want
    assert eng.host_tier.used_bytes == want
    assert want == parked.n_pages * eng.stats()["kv_page_bytes"]


def test_quant_session_migration_token_exact_and_kind_gated():
    """Disagg-handoff gate: export on one int8 engine, ship through
    the v2 wire, import on another — token-exact vs an uninterrupted
    same-kind engine; the same frame is REJECTED by engines of any
    other kind (engine-level ValueError, transport-level
    TransportError)."""
    kind = "int8"
    e1 = InferenceEngine(EngineConfig(kv_dtype=kind,
                                      enable_kv_offload=True,
                                      **_COMMON))
    r = Request("mig", list(_PROMPTS[0]), SamplingParams(max_tokens=20))
    e1.add_request(r)
    for _ in range(8):
        e1.step()
    assert e1.preempt("mig", reason="ship")
    state = e1.export_session("mig")
    assert state["kv_dtype"] == kind
    assert state["k_scales"].shape == state["k"].shape[:-1]
    blob = kvt.encode_session(state)
    shipped = kvt.decode_session(blob)
    assert shipped["k"].tobytes() == np.ascontiguousarray(
        state["k"]).tobytes()
    assert shipped["k_scales"].tobytes() == np.ascontiguousarray(
        state["k_scales"]).tobytes()

    e2 = InferenceEngine(EngineConfig(kv_dtype=kind,
                                      enable_kv_offload=True,
                                      **_COMMON))
    req2 = e2.import_session(shipped)
    _run(e2)
    e3 = InferenceEngine(EngineConfig(kv_dtype=kind, **_COMMON))
    r3 = Request("mig", list(_PROMPTS[0]), SamplingParams(max_tokens=20))
    e3.add_request(r3)
    _run(e3)
    assert req2.output_tokens == r3.output_tokens

    for other in ("f32", "fp8"):
        bad = InferenceEngine(EngineConfig(kv_dtype=other,
                                           enable_kv_offload=True,
                                           **_COMMON))
        with pytest.raises(ValueError):
            bad.import_session(dict(shipped))
        with pytest.raises(kvt.TransportError):
            kvt.ship_kind_compatible(shipped["kv_dtype"], other)


def test_quant_prefix_export_import_and_kind_gate():
    kind = "int8"
    sys_prefix = list(range(2, 2 + 16))          # 4 full pages
    a = InferenceEngine(EngineConfig(kv_dtype=kind, **_COMMON))
    ra = Request("p0", sys_prefix + [100, 101, 102],
                 SamplingParams(max_tokens=4))
    a.add_request(ra)
    _run(a)
    exp = a.export_prefix(sys_prefix)
    assert exp is not None and exp["kv_dtype"] == kind
    assert exp["k_scales"].shape == exp["k"].shape[:-1]
    pfx = kvt.decode_prefix(kvt.encode_prefix(
        exp["tokens"], exp["k"], exp["v"], k_scales=exp["k_scales"],
        v_scales=exp["v_scales"], kv_dtype=kind))
    assert pfx["kv_dtype"] == kind

    b = InferenceEngine(EngineConfig(kv_dtype=kind, **_COMMON))
    assert b.import_prefix(pfx["tokens"], pfx["k"], pfx["v"],
                           k_scales=pfx["k_scales"],
                           v_scales=pfx["v_scales"],
                           kv_dtype=kind) == 4
    # token-exact continuation vs an engine that prefilled it itself
    suffix = [110, 111, 112]
    rb = Request("pb", sys_prefix + suffix,
                 SamplingParams(max_tokens=8))
    b.add_request(rb)
    _run(b)
    ora = InferenceEngine(EngineConfig(kv_dtype=kind, **_COMMON))
    ro = Request("po", sys_prefix + suffix,
                 SamplingParams(max_tokens=8))
    ora.add_request(ro)
    _run(ora)
    assert rb.output_tokens == ro.output_tokens

    c = InferenceEngine(EngineConfig(**_COMMON))       # f32 engine
    with pytest.raises(ValueError):
        c.import_prefix(pfx["tokens"], pfx["k"], pfx["v"],
                        k_scales=pfx["k_scales"],
                        v_scales=pfx["v_scales"], kv_dtype=kind)


def test_quant_stats_report_configured_dtype_bytes():
    mc = llama.config("debug")
    row_f32 = (2 * mc.n_layers * mc.n_kv_heads * mc.head_dim
               * jnp.dtype(mc.dtype).itemsize)
    row_i8 = 2 * mc.n_layers * kv_quant.token_row_bytes(
        "int8", mc.n_kv_heads, mc.head_dim)
    for kind, row in (("f32", row_f32), ("int8", row_i8)):
        eng, _ = _mk(kind)
        for _ in range(3):
            eng.step()
        st = eng.stats()
        assert st["kv_dtype"] == kind
        assert st["kv_page_bytes"] == row * _COMMON["page_size"]
        assert st["kv_device_bytes_used"] == (
            eng.allocator.used_pages * st["kv_page_bytes"])


def test_cost_model_kv_dtype_parametrization():
    from ray_tpu.llm._internal.perfmodel import CostModel
    cfg = dataclasses.replace(llama.config("debug"),
                              dtype=jnp.float32)
    f32 = CostModel(cfg, page_size=8)
    for kind in QUANT_KINDS:
        q = CostModel(cfg, page_size=8, kv_dtype=kind)
        assert (f32.kv_bytes_per_token / q.kv_bytes_per_token) >= 1.9
        # scale overhead is real traffic: narrower than f32, wider
        # than values alone
        values_only = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
        assert q.kv_bytes_per_token > values_only
        assert q.page_bytes == q.kv_bytes_per_token * 8
    with pytest.raises(ValueError):
        CostModel(cfg, page_size=8, kv_dtype="int4")


# --------------------------------------------------------- wire v2

def _int8_session_frame():
    e1 = InferenceEngine(EngineConfig(kv_dtype="int8",
                                      enable_kv_offload=True,
                                      **_COMMON))
    r = Request("w", list(_PROMPTS[0]), SamplingParams(max_tokens=12))
    e1.add_request(r)
    for _ in range(8):
        e1.step()
    e1.preempt("w", reason="ship")
    return kvt.encode_session(e1.export_session("w"))


def test_wire_v2_corruption_over_scale_region():
    """crc32 covers the scale arrays too: flipping any byte across
    the scale region (the tail arrays of a v2 quant frame) raises the
    transport-error family, never garbage pages."""
    blob = _int8_session_frame()
    st = kvt.decode_session(blob)
    scale_bytes = st["k_scales"].nbytes + st["v_scales"].nbytes
    scale_start = len(blob) - 4 - scale_bytes
    for off in (scale_start, scale_start + scale_bytes // 3,
                scale_start + scale_bytes // 2,
                len(blob) - 5):
        bad = bytearray(blob)
        bad[off] ^= 0xFF
        with pytest.raises(kvt.TransportError):
            kvt.decode_session(bytes(bad))


def test_wire_v1_frames_still_decode_as_f32():
    rng = np.random.default_rng(5)
    k = rng.standard_normal((2, 2, 4, 2, 8)).astype(np.float32)
    orig = kvt.WIRE_VERSION
    kvt.WIRE_VERSION = 1
    try:
        blob = kvt.encode_prefix([1, 2, 3], k, k)
    finally:
        kvt.WIRE_VERSION = orig
    pfx = kvt.decode_prefix(blob)
    assert pfx["kv_dtype"] == "f32"
    assert pfx["k_scales"] is None and pfx["v_scales"] is None
    assert pfx["k"].tobytes() == k.tobytes()
    with pytest.raises(kvt.TransportError):
        # an unknown FUTURE version still refuses
        kvt.WIRE_VERSION = 9
        try:
            bad = kvt.encode_prefix([1], k, k)
        finally:
            kvt.WIRE_VERSION = orig
        kvt.decode_prefix(bad)


def test_wire_v2_inconsistent_quant_frames_rejected():
    rng = np.random.default_rng(6)
    k = rng.integers(-127, 127, (2, 2, 4, 2, 8)).astype(np.int8)
    s = np.abs(rng.standard_normal((2, 2, 4, 2))).astype(np.float32)
    # quant frame missing its scales
    with pytest.raises(kvt.TransportError):
        kvt.decode_prefix(kvt.encode_prefix([1], k, k,
                                            kv_dtype="int8"))
    # scale shape disagreeing with the pages
    with pytest.raises(kvt.TransportError):
        kvt.decode_prefix(kvt.encode_prefix(
            [1], k, k, k_scales=s[:, :1], v_scales=s,
            kv_dtype="int8"))
    # f32 frame smuggling scale arrays
    kf = k.astype(np.float32)
    with pytest.raises(kvt.TransportError):
        kvt.decode_prefix(kvt.encode_prefix(
            [1], kf, kf, k_scales=s, v_scales=s, kv_dtype="f32"))


# ---------------------------------------------- quantized collectives

def _tp_mesh(n=4):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


@pytest.mark.parametrize("kind,psum_tol,ag_tol", [
    ("int8", 0.02, 0.01), ("fp8", 0.08, 0.05), ("f32", 1e-6, 1e-6),
])
def test_quantized_collectives_match_f32_oracle(kind, psum_tol,
                                                ag_tol):
    """EQuARX tolerance oracle: both hops quantized, error bounded
    per kind vs the lax collectives on a 4-device tp mesh (f32 pass-
    through is exact)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _tp_mesh()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 37, 19)).astype(np.float32)
                    * 3.0)

    got = shard_map(functools.partial(qcoll.quantized_psum,
                                      axis_name="tp", kind=kind),
                    mesh, in_specs=P("tp"), out_specs=P("tp"))(x)
    want = shard_map(lambda v: jax.lax.psum(v, "tp"), mesh,
                     in_specs=P("tp"), out_specs=P("tp"))(x)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < psum_tol, (kind, rel)

    got = shard_map(functools.partial(qcoll.quantized_all_gather,
                                      axis_name="tp", kind=kind),
                    mesh, in_specs=P("tp"),
                    out_specs=P(None, "tp"))(x)
    want = shard_map(lambda v: jax.lax.all_gather(v, "tp"), mesh,
                     in_specs=P("tp"), out_specs=P(None, "tp"))(x)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < ag_tol, (kind, rel)


def test_quantized_collective_payload_accounting():
    n = 37 * 19
    assert qcoll.payload_bytes(n, "f32") == n * 4
    blocks = -(-n // qcoll.DEFAULT_BLOCK)
    assert qcoll.payload_bytes(n, "int8") == n + blocks * 4
    assert (qcoll.payload_bytes(n, "f32")
            / qcoll.payload_bytes(n, "int8")) >= 3.5


def test_engine_quantized_collectives_knob():
    """The config knob arms the ops-layer helpers; it must construct
    cleanly beside kv_dtype (the llama path is GSPMD — no call site
    swaps, correctness is the oracle above)."""
    eng = InferenceEngine(EngineConfig(
        model="debug", kv_dtype="int8", quantized_collectives=True,
        num_pages=32, page_size=4, max_batch_size=2))
    out = eng.generate([[1, 2, 3, 4]], SamplingParams(max_tokens=4))
    assert len(out[0].output_tokens) == 4
