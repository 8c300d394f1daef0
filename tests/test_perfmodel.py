"""Per-dispatch perf accounting (ISSUE 11, llm/_internal/perfmodel).

Gates:
- closed-form unit checks: the CostModel's per-token GEMM/attention
  FLOPs and KV bytes against hand-derived formulas for a known config;
- engine integration: every tick records a PerfSample, token totals
  reconcile with the requests' actual output (modulo the async
  pipeline's <=1-token over-generation per finished request),
  stats()["perf"] / fleet_stats carry MFU/MBU/roofline, and disabling
  accounting removes the surface without touching behavior;
- offload traffic: spill/restore moves show up as d2h/h2d bytes;
- the slow-marked analytic-vs-XLA cross-check: the model's full-
  forward FLOPs against jax.jit(...).lower().cost_analysis() at the
  one sanctioned compile — the drift alarm for the cost formulas.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.llm._internal.perfmodel import (ENVELOPES, CostModel,
                                             PerfAccountant,
                                             detect_envelope)
from ray_tpu.models import llama


def _engine(**over):
    kw = dict(model=llama.config("debug", dtype=jnp.float32),
              max_batch_size=3, page_size=8, num_pages=64,
              max_prefill_tokens=16,
              seed=9, enable_prefix_caching=False)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


# ------------------------------------------------------- closed forms

def test_gemm_flops_per_token_closed_form():
    cfg = llama.config("debug")
    cm = CostModel(cfg, page_size=8)
    h = cfg.hidden
    qkvo = 2 * h * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * h
    mlp = 3 * 2 * h * cfg.ffn
    assert cm.gemm_flops_per_token == cfg.n_layers * (qkvo + mlp)
    assert cm.head_flops == 2 * h * cfg.vocab_size
    assert cm.attn_flops_per_pair == (4 * cfg.n_layers * cfg.n_heads
                                      * cfg.head_dim)


def test_kv_bytes_and_page_granularity():
    cfg = llama.config("debug")         # bf16 pools (2 bytes)
    cm = CostModel(cfg, page_size=8)
    per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    assert cm.kv_bytes_per_token == per_tok
    # decode at ctx=1 has nothing cached to read, writes one row
    c = cm.decode_cost(1)
    assert c["bytes_kv_read"] == 0
    assert c["bytes_kv_write"] == per_tok
    # ctx=9 spans 2 pages of 8 -> reads 16 page-resident rows (the
    # kernel streams whole pages; ctx-1=8 cached rounds to 8)
    assert cm.decode_cost(10)["bytes_kv_read"] == 16 * per_tok


def test_chunk_cost_matches_tokenwise_sum():
    """A chunk of n tokens at context `start` must attend to exactly
    the pairs the per-token causal rule implies."""
    cfg = llama.config("debug")
    cm = CostModel(cfg, page_size=8)
    start, n = 7, 5
    pairs = sum(start + i + 1 for i in range(n))
    c = cm.chunk_cost(start, n)
    assert c["flops_attn"] == cm.attn_flops_per_pair * pairs
    assert c["flops_gemm"] == n * cm.gemm_flops_per_token + cm.head_flops
    assert c["bytes_kv_write"] == n * cm.kv_bytes_per_token


def test_moe_counts_active_experts_only():
    dense = CostModel(llama.config("debug"), page_size=8)
    moe = CostModel(llama.config("debug_moe"), page_size=8)
    cfg = llama.config("debug_moe")
    # top-2 of 4 experts: per-token FFN flops = 2 dense FFNs + router
    h = cfg.hidden
    expect_mlp = 2 * h * cfg.n_experts + 2 * 3 * 2 * h * cfg.ffn
    dense_mlp = 3 * 2 * h * cfg.ffn
    assert (moe.gemm_flops_per_token - dense.gemm_flops_per_token
            == cfg.n_layers * (expect_mlp - dense_mlp))


def test_envelope_detection_and_override():
    assert detect_envelope(name="cpu") is ENVELOPES["cpu"]
    assert detect_envelope(name="tpu-v5e").peak_flops == 197e12
    with pytest.raises(ValueError, match="unknown perf envelope"):
        detect_envelope(name="tpu-v99")
    # CPU backend autodetects the calibrated CPU envelope
    assert detect_envelope(jax.devices()[0]).name == "cpu"
    # accelerators resolve by exact device_kind; one the peaks table
    # does not name is an error, never a default
    import types
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert detect_envelope(v5e) is ENVELOPES["tpu-v5e"]
    with pytest.raises(ValueError, match="no peaks for device_kind"):
        detect_envelope(types.SimpleNamespace(
            platform="tpu", device_kind="TPU v99"))


def test_accountant_window_and_totals():
    cm = CostModel(llama.config("debug"), page_size=8)
    acct = PerfAccountant(cm, ENVELOPES["cpu"])
    acct.add("decode", cm.decode_cost(5), decode_tokens=1)
    acct.commit(2.0)
    acct.add("ragged", cm.chunk_cost(0, 8), prefill_tokens=8)
    acct.note_offload(d2h=1024.0)
    acct.commit(3.0)
    t = acct.totals()
    assert t["samples"] == 2
    assert t["decode_tokens"] == 1 and t["prefill_tokens"] == 8
    assert t["bytes_d2h"] == 1024.0
    assert t["bytes_weights"] == 2 * cm.weight_bytes
    s = acct.summary()
    assert s["window"] == 2 and s["busy_s"] == pytest.approx(5e-3)
    assert s["mfu"] > 0 and s["roof"] in ("compute", "memory")
    # an empty pending commit records nothing
    acct.commit(1.0)
    assert acct.totals()["samples"] == 2


def test_accountant_abort_drops_pending():
    cm = CostModel(llama.config("debug"), page_size=8)
    acct = PerfAccountant(cm, ENVELOPES["cpu"])
    acct.add("decode", cm.decode_cost(5), decode_tokens=1)
    acct.abort_tick()
    acct.commit(1.0)
    assert acct.totals()["samples"] == 0


# -------------------------------------------------- engine integration

@pytest.mark.parametrize("async_rb", [True, False],
                         ids=["pipelined", "sync"])
def test_engine_records_every_tick_and_reconciles_tokens(async_rb):
    eng = _engine(async_readback=async_rb)
    rng = np.random.default_rng(5)
    reqs = [Request(f"p{i}", rng.integers(2, 250, 12).tolist(),
                    SamplingParams(max_tokens=16))
            for i in range(3)]
    for r in reqs:
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    perf = eng.stats()["perf"]
    assert perf["enabled"]
    tot = perf["totals"]
    # every tick committed a sample (window == tick count here)
    assert tot["samples"] == eng.ticks
    # prefill accounted every prompt token exactly once
    assert tot["prefill_tokens"] == sum(len(r.prompt_tokens)
                                        for r in reqs)
    # decode accounting covers emitted tokens minus the prefill-emitted
    # first token per request, plus at most one discarded
    # over-generation per finished request (the async pipeline)
    emitted = sum(len(r.output_tokens) for r in reqs)
    lo = emitted - len(reqs)
    assert lo <= tot["decode_tokens"] <= lo + len(reqs)
    assert tot["flops"] > 0 and tot["bytes_weights"] > 0
    assert 0 < perf["mfu"] <= 1.0
    assert 0 < perf["mbu"] <= 1.0
    assert perf["roof"] in ("compute", "memory")
    assert perf["busy_s"] <= perf["span_s"] * 1.001


def test_engine_single_request_matches_closed_form_sync():
    """One request, sync engine: totals equal the replayed closed
    form (one whole-prompt chunk + G-1 decode ticks at growing
    context) to the float. The same identity the bench gate asserts."""
    P, G = 12, 8
    eng = _engine(async_readback=False)
    rng = np.random.default_rng(7)
    req = Request("solo", rng.integers(2, 250, P).tolist(),
                  SamplingParams(max_tokens=G))
    eng.add_request(req)
    while eng.has_work():
        eng.step()
    cm = eng.perf.model
    expect = {"flops_gemm": 0.0, "flops_attn": 0.0,
              "bytes_kv_read": 0.0, "bytes_kv_write": 0.0}
    for k, v in cm.chunk_cost(0, P).items():
        expect[k] += v
    for i in range(G - 1):
        for k, v in cm.decode_cost(P + 1 + i).items():
            expect[k] += v
    tot = eng.stats()["perf"]["totals"]
    assert tot["flops_gemm"] == pytest.approx(expect["flops_gemm"])
    assert tot["flops_attn"] == pytest.approx(expect["flops_attn"])
    assert tot["bytes_kv_read"] == pytest.approx(expect["bytes_kv_read"])
    assert tot["bytes_kv_write"] == pytest.approx(
        expect["bytes_kv_write"])
    assert tot["decode_tokens"] == G - 1
    assert tot["prefill_tokens"] == P


def test_engine_accounting_disabled_removes_surface():
    eng = _engine(enable_perf_accounting=False)
    rng = np.random.default_rng(5)
    req = Request("off", rng.integers(2, 250, 12).tolist(),
                  SamplingParams(max_tokens=8))
    eng.add_request(req)
    while eng.has_work():
        eng.step()
    assert eng.perf is None
    assert eng.stats()["perf"] == {"enabled": False}
    assert len(req.output_tokens) == 8      # behavior untouched


def test_engine_perf_envelope_override_and_chrome_counters():
    eng = _engine(perf_envelope="tpu-v5e")
    rng = np.random.default_rng(5)
    eng.add_request(Request("e0", rng.integers(2, 250, 12).tolist(),
                            SamplingParams(max_tokens=8)))
    while eng.has_work():
        eng.step()
    perf = eng.stats()["perf"]
    assert perf["envelope"] == "tpu-v5e"
    assert perf["peak_flops"] == 197e12
    # counter tracks ride /debug/trace beside the request rows
    tr = eng.chrome_trace()
    counters = [e for e in tr["traceEvents"] if e.get("ph") == "C"]
    assert len(counters) >= 2 * eng.ticks - 2
    names = {e["name"] for e in counters}
    assert names == {"perf:utilization", "perf:tokens_per_tick"}
    assert all("mfu" in e["args"] for e in counters
               if e["name"] == "perf:utilization")


def test_spill_restore_traffic_accounted():
    eng = _engine(enable_kv_offload=True)
    rng = np.random.default_rng(5)
    for i in range(3):
        eng.add_request(Request(
            f"o{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=48)))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(4):
        eng.step()
    assert eng.preempt("o1", reason="manual")
    while eng.parked:
        eng.step()
    while eng.has_work():
        eng.step()
    tot = eng.stats()["perf"]["totals"]
    # one spill + one restore, bucketed pages each way, K+V both
    assert tot["bytes_d2h"] > 0
    assert tot["bytes_h2d"] > 0
    assert tot["bytes_d2h"] == tot["bytes_h2d"]
    page_bytes = eng.perf.model.page_bytes
    assert tot["bytes_d2h"] % page_bytes == 0


def test_fleet_stats_carries_perf_brief():
    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.serve.llm.router import ReplicaSnapshot

    srv = LLMServerImpl({"model_id": "pm",
                         "model_source": llama.config("debug"),
                         "engine_kwargs": dict(
                             max_batch_size=2, page_size=8,
                             num_pages=64, metrics_replica_id="r0")})
    rng = np.random.default_rng(5)
    srv.engine.add_request(Request(
        "f0", rng.integers(2, 250, 12).tolist(),
        SamplingParams(max_tokens=8)))
    while srv.engine.has_work():
        srv.engine.step()
    stats = srv._fleet_stats_sync()
    brief = stats["perf"]
    assert set(brief) == {"mfu", "mbu", "roof", "decode_tokens_per_s",
                          "prefill_tokens_per_s", "envelope"}
    assert 0 < brief["mfu"] <= 1.0
    snap = ReplicaSnapshot.from_stats(stats)
    assert snap.mfu == brief["mfu"]
    assert snap.roof in ("compute", "memory")
    assert snap.decode_tps == brief["decode_tokens_per_s"]


def test_tick_times_summary_percentiles():
    eng = _engine()
    rng = np.random.default_rng(5)
    eng.add_request(Request("t0", rng.integers(2, 250, 12).tolist(),
                            SamplingParams(max_tokens=16)))
    while eng.has_work():
        eng.step()
    tt = eng.stats()["tick_times"]
    for name in ("wall_ms", "host_ms", "device_ms"):
        p50, p95, p99 = (tt[f"{name}_p50"], tt[f"{name}_p95"],
                         tt[f"{name}_p99"])
        assert 0.0 <= p50 <= p95 <= p99
    # the wall percentiles are real observations: the window max
    # bounds p99, and the mean sits between p50-ish and the max
    assert tt["wall_ms_p99"] > 0
    assert tt["wall_ms_p50"] <= tt["wall_ms_avg"] <= tt["wall_ms_p99"]


# --------------------------------------- analytic vs XLA cost_analysis

@pytest.mark.slow
def test_analytic_flops_match_xla_cost_analysis():
    """The drift alarm: the cost model's full-forward FLOPs vs XLA's
    own cost_analysis() of the jitted llama forward at the one
    sanctioned compile.

    The model must be SINGLE-layer: XLA's cost analysis counts a
    lax.scan body ONCE regardless of trip count (verified by lowering
    1/2/4-layer configs — identical flops), so only at n_layers=1
    does the lowered program's cost equal the model's. The analytic
    side counts causal attention pairs and skips elementwise work
    while XLA counts the full S^2 matmuls plus softmax/norm flops, so
    the comparison carries a modest tolerance — the GEMMs dominate at
    this shape and the two agree within ~5%. A formula regression
    (dropped term, wrong 2x factor, missing projection) lands far
    outside the band."""
    cfg = llama.config("tiny", n_layers=1, remat=False)
    B, S = 2, 128
    cm = CostModel(cfg, page_size=8)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, S), jnp.int32)
    lowered = jax.jit(
        lambda p, t: llama.forward(cfg, p, t)).lower(params, tokens)
    cost = lowered.cost_analysis()
    if isinstance(cost, list):          # older jax returns [dict]
        cost = cost[0]
    xla_flops = float(cost["flops"])
    analytic = cm.forward_flops(B, S)
    assert xla_flops > 0
    ratio = analytic / xla_flops
    assert 0.8 <= ratio <= 1.2, (
        f"analytic {analytic:.3e} vs XLA {xla_flops:.3e} "
        f"(ratio {ratio:.3f}) — the cost model drifted from the "
        f"program it describes")
