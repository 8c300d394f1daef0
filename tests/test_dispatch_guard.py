"""Runtime dispatch-discipline guard (ISSUE 3, ray_tpu/util/jax_guard).

Gates:
- steady-state decode runs 32 consecutive engine ticks under an armed
  guard with ZERO host->device transfers and ZERO new XLA
  compilations — the mechanical form of PR 1/2's "one dispatch per
  tick, zero recompiles" contract (extends the jit_cache stability
  test, which only watched the engine's own counter);
- the guard itself: a seeded h2d transfer raises at the transfer
  site, a seeded compile raises GuardViolation on exit, an explicit
  compile budget admits warmup, and the per-tick d2h token readback
  stays sanctioned.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.util.jax_guard import GuardViolation, dispatch_guard


def _engine(tp=1, **over):
    kw = dict(model=llama.config("debug", dtype=jnp.float32),
              max_batch_size=3, page_size=8, num_pages=64,
              max_prefill_tokens=16,
              seed=9)
    if tp > 1:
        # explicit-tp pod slice (ISSUE 17) on the conftest's emulated
        # CPU devices: the shard_map'd collective-bearing tick must
        # hold the exact same dispatch discipline
        kw["mesh_shape"] = (1, tp)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _warmed_engine(async_readback=True, enable_metrics=True, tp=1,
                   **sp_over):
    """Engine with 3 in-flight requests past prefill, decode loop
    settled (all shape buckets built, device-resident state live)."""
    eng = _engine(async_readback=async_readback,
                  enable_metrics=enable_metrics, tp=tp)
    rng = np.random.default_rng(5)
    sp = dict(max_tokens=64)
    sp.update(sp_over)
    for i in range(3):
        eng.add_request(Request(
            f"g{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(**sp)))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(4):
        eng.step()
    return eng


@pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "tp2"])
@pytest.mark.parametrize("metrics", [True, False],
                         ids=["metrics", "no_metrics"])
@pytest.mark.parametrize("async_rb", [True, False],
                         ids=["pipelined", "sync"])
@pytest.mark.parametrize("sp", [
    {},                                                  # greedy
    {"temperature": 0.8, "top_k": 20, "top_p": 0.9,
     "repetition_penalty": 1.2},                         # full sampler
], ids=["greedy", "sampled_penalized"])
def test_steady_state_decode_zero_transfers_zero_compiles(
        sp, async_rb, metrics, tp):
    """32 consecutive decode ticks: no h2d upload (the loop state is
    device-resident and feeds back on device — the guard raises at
    the offending line otherwise) and no new compiled program (shape
    buckets are warm; the sentinel counts XLA builds). Holds with
    the ISSUE 4 pipeline ON (lagged folds are pure d2h + host work:
    the async copy, the one sanctioned readback and the discard mask
    add zero uploads and zero programs) and OFF — and with the
    ISSUE 5 request-lifecycle instrumentation ENABLED (its zero-sync
    contract: TTFT/ITL observation and flight recording are host-only
    Python on the fold path) as well as disabled. Parametrized over
    tp (ISSUE 17): at tp=2 the tick is one shard_map'd
    collective-bearing program over the named mesh, and the identical
    discipline must hold."""
    eng = _warmed_engine(async_readback=async_rb,
                         enable_metrics=metrics, tp=tp, **sp)
    comp0 = eng.stats()["jit_cache"]["compiled_programs"]
    disp0 = eng.dispatches
    with dispatch_guard() as rep:
        for _ in range(32):
            eng.step()
    assert rep.n_compiles == 0
    assert eng.stats()["jit_cache"]["compiled_programs"] == comp0
    assert eng.dispatches - disp0 == 32      # one dispatch per tick
    # nothing finished inside the window (no refresh ran, so the
    # guarded ticks really were the steady-state path)
    assert all(s.request is not None and s.ready for s in eng.slots)
    if async_rb:
        # the guarded ticks really ran pipelined: every one of them
        # folded its predecessor a tick late, with zero drains
        assert eng.stats()["tick_times"]["lagged_ticks"] >= 32
        assert eng.stats()["tick_times"]["drains"] == 0
    if metrics:
        # the instrumentation really was live inside the window (the
        # zero-transfer result is not vacuous): ~3 tokens/tick folded
        # through on_token (the async pipeline may hold one tick)
        assert eng.telemetry.summary()["generated_tokens"] >= 90
    # ISSUE 11: perf accounting is ON by default and recorded a
    # sample for every guarded tick — its host arithmetic added zero
    # transfers and zero compiles to the window above
    perf = eng.stats()["perf"]
    assert perf["enabled"] and perf["window"] >= 32
    assert perf["totals"]["flops"] > 0
    assert 0 < perf["mfu"] <= 1.0
    # ISSUE 13: attribution + anomaly detection are ON by default and
    # were LIVE inside the guarded window — per-request receipts grew
    # (3 decode tokens charged per tick) and the detector observed
    # every tick — while adding zero transfers and zero compiles
    attrib = eng.stats()["attribution"]
    assert attrib["enabled"] and attrib["live"] == 3
    assert attrib["ticks_total"] >= 32
    assert attrib["totals"]["decode_tokens"] >= 96
    anomaly = eng.stats()["anomaly"]
    assert anomaly["enabled"] and anomaly["ticks"] >= 32
    assert anomaly["anomalies_total"] == 0      # steady state IS steady


@pytest.mark.parametrize("kv_dtype", ["f32", "int8", "fp8"])
@pytest.mark.parametrize("sp", [
    {},                                                  # greedy
    {"temperature": 0.8, "top_k": 20, "top_p": 0.9},     # sampled
], ids=["greedy", "sampled"])
def test_steady_state_decode_offload_engine_clean(sp, kv_dtype):
    """ISSUE 10: the KV memory hierarchy lives entirely on the
    structural path. An offload-ENABLED engine whose host tier has
    already been exercised — one victim spilled (async d2h page
    gather) and restored (h2d page scatter) before the window — still
    runs 32 steady-state decode ticks at 0 h2d transfers / 0 compiles
    / 1 dispatch per tick: spill/restore ride drained structural
    events exactly like admission uploads, never the decode loop.

    Parametrized over kv_dtype (ISSUE 16): quantized pools thread two
    extra scale arrays through every decode/spill/restore program, and
    quantize-at-append rides the SAME single dispatch — the narrow
    pages must not cost a tick, a transfer, or a compile."""
    eng = _engine(enable_kv_offload=True, async_readback=True,
                  kv_dtype=kv_dtype)
    rng = np.random.default_rng(5)
    for i in range(3):
        eng.add_request(Request(
            f"g{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=96, **sp)))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(4):
        eng.step()
    # exercise the tier: spill one victim, let the engine restore it
    assert eng.preempt("g1", reason="manual")
    assert len(eng.parked) == 1
    while eng.parked:
        eng.step()
    assert eng.host_tier.spills_total == 1
    assert eng.host_tier.restores_total == 1
    for _ in range(4):
        eng.step()                       # settle the pipeline again
    comp0 = eng.stats()["jit_cache"]["compiled_programs"]
    disp0 = eng.dispatches
    with dispatch_guard() as rep:
        for _ in range(32):
            eng.step()
    assert rep.n_compiles == 0
    assert eng.stats()["jit_cache"]["compiled_programs"] == comp0
    assert eng.dispatches - disp0 == 32      # one dispatch per tick
    assert all(s.request is not None and s.ready for s in eng.slots)
    # the tier really was active across the window
    assert eng.host_tier is not None
    assert eng.stats()["spills_total"] == 1


def test_batch_lane_steady_state_clean():
    """ISSUE 14: the batch lane is pure host-side scheduling. An
    engine running a MIXED residency — an interactive request beside
    a batch-lane request that was priority-preempted and restored
    before the window — still decodes 32 steady ticks at 1
    dispatch/tick, 0 h2d transfers, 0 compiles: lane accounting,
    priority victim choice, and the inversion guards all live on the
    structural path."""
    eng = _engine(enable_kv_offload=True, async_readback=True)
    rng = np.random.default_rng(7)
    for i in range(3):               # every slot holds batch work
        eng.add_request(Request(
            f"b{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=96), priority=0, lane="batch"))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(4):
        eng.step()
    # an interactive arrival preempts one batch resident (priority
    # path), finishes, and the trough restores the victim
    eng.add_request(Request(
        "i0", rng.integers(2, 250, 8).tolist(),
        SamplingParams(max_tokens=8), priority=1))
    while any(s.request is not None and s.request.request_id == "i0"
              for s in eng.slots) or eng.waiting:
        eng.step()
    assert eng.preempt_counts.get("priority", 0) >= 1
    while eng.parked:
        eng.step()                   # restore the batch victim
    assert eng.host_tier.restores_total >= 1
    for _ in range(4):
        eng.step()                   # settle the pipeline again
    comp0 = eng.stats()["jit_cache"]["compiled_programs"]
    disp0 = eng.dispatches
    with dispatch_guard() as rep:
        for _ in range(32):
            eng.step()
    assert rep.n_compiles == 0
    assert eng.stats()["jit_cache"]["compiled_programs"] == comp0
    assert eng.dispatches - disp0 == 32      # one dispatch per tick
    # both lanes really decoded inside the window
    lanes = eng.lane_counts()
    assert lanes["active_batch"] >= 1
    assert eng.telemetry.summary()["batch"]["generated_tokens"] > 0


def test_disaggregated_import_steady_state_clean():
    """ISSUE 12: the fleet KV transport lives entirely on the
    structural path. Prefill-on-A, ship, decode-on-B: engine A runs
    the prompt and exports the parked session, engine B imports it
    (host-tier park + the sanctioned restore scatter — a structural
    h2d like admission uploads), and once B's pipeline settles,
    steady-state decode on B is STILL 1 dispatch/tick, 0 h2d
    transfers, 0 compiles for 32 ticks — importing a session leaves
    no residue on the decode loop."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 250, 12).tolist() for _ in range(3)]

    # engine A: prefill + a few decode ticks, then export
    a = _engine(enable_kv_offload=True)
    a.add_request(Request("ship0", list(prompts[0]),
                          SamplingParams(max_tokens=96)))
    while len(a.slots[0].request.output_tokens
              if a.slots[0].request else []) < 3 \
            and a.has_work():
        a.step()
    state = a.export_session("ship0", reason="disagg")
    assert state is not None and state["n_pages"] > 0

    # engine B: warm resident batch (decode buckets compiled), then
    # import the shipped session into the free slot
    b = _engine(enable_kv_offload=True, async_readback=True)
    for i in range(2):
        b.add_request(Request(
            f"g{i}", list(prompts[i + 1]),
            SamplingParams(max_tokens=96)))
    while b.waiting or any(s.request is not None and not s.ready
                           for s in b.slots):
        b.step()
    for _ in range(4):
        b.step()
    req = b.import_session(state)
    while b.parked:
        b.step()                 # restore (structural h2d scatter)
    assert b.host_tier.restores_total == 1
    assert any(s.request is req and s.ready for s in b.slots)
    for _ in range(4):
        b.step()                 # settle the pipeline again
    comp0 = b.stats()["jit_cache"]["compiled_programs"]
    disp0 = b.dispatches
    with dispatch_guard() as rep:
        for _ in range(32):
            b.step()
    assert rep.n_compiles == 0
    assert b.stats()["jit_cache"]["compiled_programs"] == comp0
    assert b.dispatches - disp0 == 32        # one dispatch per tick
    assert all(s.request is not None and s.ready for s in b.slots)
    # the imported session really decoded inside the window
    assert len(req.output_tokens) >= 32


def test_guard_raises_on_seeded_h2d_transfer():
    with pytest.raises(Exception, match="host-to-device"):
        with dispatch_guard():
            jnp.asarray(np.ones(4))          # the classic stray upload


def test_guard_raises_on_seeded_compile():
    f = jax.jit(lambda x: x * 3)
    f(jax.device_put(jnp.ones(8)))           # warm one bucket
    fresh = jax.device_put(jnp.ones(16))     # a NEW shape bucket
    with pytest.raises(GuardViolation, match="compilation"):
        with dispatch_guard():
            f(fresh)


def test_guard_compile_budget_admits_warmup():
    f = jax.jit(lambda x: x - 1)
    fresh = jax.device_put(jnp.ones(24))
    with dispatch_guard(max_compiles=8) as rep:
        f(fresh)
    assert 1 <= rep.n_compiles <= 8
    assert any("Compiling" in m for m in rep.compiles)


def test_guard_report_only_mode_collects_without_raising():
    """Observability mode must not crash on EITHER violation kind:
    transfers downgrade to 'log' levels, compiles only count."""
    f = jax.jit(lambda x: x + 2)
    fresh = jax.device_put(jnp.ones(48))
    with dispatch_guard(raise_on_violation=False) as rep:
        f(fresh)                         # compile: counted, no raise
        jnp.asarray(np.ones(4))          # h2d: logged, no raise
    assert rep.n_compiles >= 1


def test_guard_allows_d2h_readback():
    f = jax.jit(lambda x: x + 1)
    x = jax.device_put(jnp.ones(8))
    f(x)                                     # warm
    with dispatch_guard():
        out = np.asarray(f(x))               # the sanctioned readback
    assert out.shape == (8,)


def test_guard_fails_closed_when_logging_muted():
    """A host app that muted logging must not blind the compile
    sentinel (the guard would otherwise pass a recompile storm)."""
    import logging
    f = jax.jit(lambda x: x * 5)
    fresh = jax.device_put(jnp.ones(56))
    logging.disable(logging.CRITICAL)
    try:
        with pytest.raises(GuardViolation):
            with dispatch_guard():
                f(fresh)
    finally:
        logging.disable(logging.NOTSET)


def test_guard_violation_lands_in_flight_recorder():
    """ISSUE 5: given a flight recorder, a compile-budget violation is
    recorded as a structured guard_violation event BEFORE the raise —
    post-mortem dumps (GET /debug/events) keep it even when a retry
    layer swallows the exception. Report-only mode records without
    raising."""
    from ray_tpu.llm._internal.telemetry import FlightRecorder

    rec = FlightRecorder()
    f = jax.jit(lambda x: x * 7)
    fresh = jax.device_put(jnp.ones(40))
    with pytest.raises(GuardViolation):
        with dispatch_guard(recorder=rec):
            f(fresh)
    evs = [e for e in rec.events() if e["event"] == "guard_violation"]
    assert evs and evs[0]["cause"] == "compile"
    assert evs[0]["n_compiles"] >= 1 and evs[0]["budget"] == 0

    rec2 = FlightRecorder()
    fresh2 = jax.device_put(jnp.ones(72))
    with dispatch_guard(raise_on_violation=False, recorder=rec2):
        f(fresh2)
    assert any(e["event"] == "guard_violation" for e in rec2.events())


def test_guard_restores_log_compiles_config():
    prev = bool(jax.config.jax_log_compiles)
    with dispatch_guard(max_compiles=10**6):
        assert bool(jax.config.jax_log_compiles) is True
    assert bool(jax.config.jax_log_compiles) is prev
