"""Fleet traffic simulator gates (ISSUE 14).

The acceptance contract: the simulator drives the PRODUCTION policy
classes (asserted by identity), the same seed + trace produce a
byte-identical run summary, >=1M simulated sessions replay with fleet
SLO assertions and an emitted capacity-curve artifact, autoscaler
hysteresis stays bounded over >=24h of simulated diurnal time, and
the committed CPU calibration pins sim predictions against a real
engine within the tolerance band.
"""

import dataclasses
import json
import os

import pytest

jax = pytest.importorskip("jax")

from ray_tpu.serve import llm as serve_llm  # noqa: E402
from ray_tpu.serve.llm import (AdmissionConfig,  # noqa: E402
                               AdmissionController, AutoscaleConfig,
                               FleetAutoscaler, FleetRouter,
                               CircuitBreaker, SLOBurnWatchdog)
from ray_tpu.serve.llm.sim import (CALIBRATION_BAND,  # noqa: E402
                                   FleetSimulator, SimCalibration,
                                   SimFleetConfig, TraceConfig,
                                   VirtualClock, assert_slos,
                                   batch_backlog, capacity_curve,
                                   chaos_overlay,
                                   default_cpu_calibration, generate,
                                   write_artifact)

CALIB = default_cpu_calibration()


def _cfg(**kw):
    base = dict(replicas=4, min_replicas=2, slots_per_replica=8,
                pages_per_replica=2048, calibration=CALIB, seed=3,
                admission=AdmissionConfig(max_concurrent=96,
                                          max_queue=256,
                                          queue_wait_slo_s=5.0))
    base.update(kw)
    return SimFleetConfig(**base)


def _trace(**kw):
    base = dict(kind="diurnal", sessions=20_000, duration_s=7200.0,
                seed=3, prefix_groups=64, prompt_tokens_mean=24,
                prompt_tokens_max=96, out_tokens_mean=12,
                out_tokens_max=48)
    base.update(kw)
    return TraceConfig(**base)


# ------------------------------------------------ the policy identity
def test_simulator_drives_production_policy_classes():
    """THE anti-fork gate: the objects inside the simulator ARE the
    production classes, imported from their production modules — a
    policy bug the sim finds is a bug the fleet ships."""
    sim = FleetSimulator(generate(_trace(sessions=10)), _cfg())
    assert type(sim.router) is FleetRouter
    assert type(sim.admission) is AdmissionController
    assert type(sim.autoscaler) is FleetAutoscaler
    assert type(sim.watchdog) is SLOBurnWatchdog
    assert all(type(b) is CircuitBreaker for b in sim.breakers)
    # and they are the very classes serve.llm exports
    assert sim.router.__class__ is serve_llm.FleetRouter
    assert sim.admission.__class__ is serve_llm.AdmissionController
    assert sim.autoscaler.__class__ is serve_llm.FleetAutoscaler
    assert sim.watchdog.__class__ is serve_llm.SLOBurnWatchdog
    # virtual-clocked, not wall-clocked (the ISSUE 14 satellite):
    # every policy's injected clock is a bound method of THE sim
    # clock (bound-method objects differ per access; the receiver
    # identity is the contract)
    for obj in (sim.router, sim.admission, sim.autoscaler,
                sim.watchdog, *sim.breakers):
        assert getattr(obj._clock, "__self__", None) is sim.clock


def test_virtual_clock_only_time_source():
    """A run must never consult the wall clock: freezing real time
    has no effect, and the summary's virtual span tracks the trace's
    duration, not host time."""
    tc = _trace(sessions=2000, duration_s=3600.0)
    sim = FleetSimulator(generate(tc), _cfg())
    s = sim.run()
    # virtual span tracks the trace (last arrival + drain), far past
    # anything host time could reach in this test
    assert s["sim"]["virtual_s"] >= 0.9 * 3600.0
    assert s["sessions"]["completed"] > 0


# ---------------------------------------------------- determinism gate
def test_same_seed_byte_identical_summary():
    tc = _trace(sessions=8_000)
    jobs = batch_backlog(200, out_tokens=16)
    a = FleetSimulator(generate(tc), _cfg(), batch_jobs=jobs)
    a.run()
    b = FleetSimulator(generate(tc), _cfg(),
                       batch_jobs=batch_backlog(200, out_tokens=16))
    b.run()
    assert a.summary_json() == b.summary_json()


def test_different_seed_diverges():
    a = FleetSimulator(generate(_trace(sessions=5000, seed=3)),
                       _cfg(seed=3))
    b = FleetSimulator(generate(_trace(sessions=5000, seed=4)),
                       _cfg(seed=4))
    a.run()
    b.run()
    assert a.summary_json() != b.summary_json()


def test_trace_generator_deterministic_and_sorted():
    tc = _trace(sessions=5000)
    a = list(generate(tc))
    b = list(generate(tc))
    assert [(s.at, s.tenant, s.group, s.prompt_tokens, s.out_tokens)
            for s in a] == \
           [(s.at, s.tenant, s.group, s.prompt_tokens, s.out_tokens)
            for s in b]
    assert all(x.at <= y.at for x, y in zip(a, b[1:]))
    assert a[-1].at <= tc.duration_s


# ------------------------------------------------------- traffic shapes
def test_flash_crowd_concentrates_arrivals():
    tc = _trace(kind="flash_crowd", sessions=20_000, crowds=2,
                crowd_fraction=0.5, crowd_width_s=120.0)
    arrivals = [s.at for s in generate(tc)]
    # half the mass lands inside ~2*120s of a 7200s trace
    windows = sorted(arrivals)
    from collections import Counter
    by_bin = Counter(int(a // 120) for a in arrivals)
    top2 = sum(c for _, c in by_bin.most_common(4))
    assert top2 >= 0.4 * len(arrivals)


def test_tenant_skew_zipf_weighted():
    tc = _trace(kind="tenant_skew", sessions=20_000, tenants=6)
    from collections import Counter
    c = Counter(s.tenant for s in generate(tc))
    assert c["t0"] > 2 * c["t5"]


# -------------------------------------------------- chaos + breakers
def test_chaos_death_drives_breaker_eviction_and_recovery():
    # death at the diurnal PEAK (duration/2) of a hot trace, so the
    # victim is guaranteed residents to fail over
    tc = _trace(sessions=40_000, duration_s=3600.0,
                out_tokens_mean=32)
    chaos = [serve_llm.sim.ChaosEvent(at=1800.0, replica=1,
                                      kind="die", duration_s=600.0)]
    sim = FleetSimulator(generate(tc), _cfg(replicas=3,
                                            min_replicas=3),
                         chaos=chaos)
    s = sim.run()
    assert s["health"]["evictions"] >= 1
    assert s["health"]["readmissions"] >= 1
    assert s["sessions"]["failed_over"] >= 1
    assert_slos(s, min_completion_rate=0.99)


def test_chaos_overlay_seeded():
    tc = _trace(sessions=100)
    a = chaos_overlay(tc, replicas=4, events=3)
    b = chaos_overlay(tc, replicas=4, events=3)
    assert [(e.at, e.replica, e.kind) for e in a] == \
           [(e.at, e.replica, e.kind) for e in b]


# ------------------------------------- autoscaler hysteresis property
def test_autoscaler_hysteresis_bounded_over_24h_diurnal():
    """Satellite gate: >=24h of simulated diurnal traffic, replica
    count stays within [min,max] and the transition count is bounded
    (no flapping) — at most a few scale events per diurnal swing."""
    tc = _trace(sessions=80_000, duration_s=86_400.0,
                diurnal_amplitude=0.9)
    cfg = _cfg(replicas=8, min_replicas=2,
               autoscale=AutoscaleConfig(
                   min_replicas=2, max_replicas=8,
                   upscale_delay_s=30.0, downscale_delay_s=300.0),
               control_period_s=5.0, autoscale_period_s=15.0)
    sim = FleetSimulator(generate(tc), cfg)
    s = sim.run()
    assert 2 <= s["autoscale"]["active_min"] \
        <= s["autoscale"]["active_max"] <= 8
    # bounded transitions: one diurnal cycle should cost at most a
    # handful of scale events each way, never a flap storm
    assert s["autoscale"]["events"] <= 24, s["autoscale"]
    assert_slos(s, min_completion_rate=0.99)


# --------------------------------------------------- the million gate
def test_million_sessions_with_slos_and_capacity_artifact(tmp_path):
    """THE scale gate: >=1M simulated sessions replay on CPU with
    fleet SLO assertions, and the capacity sweep emits its artifact
    (replicas vs p99 TTFT)."""
    tc = _trace(sessions=1_000_000, duration_s=86_400.0, seed=14,
                tenants=8, prefix_groups=512)
    cfg = _cfg(replicas=12, min_replicas=6, slots_per_replica=16,
               pages_per_replica=4096, seed=14,
               control_period_s=10.0, autoscale_period_s=30.0,
               admission=AdmissionConfig(max_concurrent=384,
                                         max_queue=1024,
                                         queue_wait_slo_s=5.0))
    sim = FleetSimulator(generate(tc), cfg,
                         batch_jobs=batch_backlog(2000,
                                                  out_tokens=16))
    s = sim.run()
    assert s["sessions"]["arrived"] >= 1_000_000
    assert_slos(s, max_shed_rate=0.05, min_completion_rate=0.99)
    assert s["batch"]["completed"] == 2000
    assert s["batch"]["tokens"] > 0

    # capacity curve over a downsampled replay of the same shape
    curve = capacity_curve(
        dataclasses.replace(tc, sessions=30_000,
                            duration_s=3600.0),
        _cfg(slots_per_replica=16, pages_per_replica=4096),
        replica_counts=[2, 4, 8])
    path = write_artifact(curve,
                          os.path.join(tmp_path, "capacity.json"))
    doc = json.loads(open(path).read())
    assert doc["object"] == "capacity_curve"
    assert [p["replicas"] for p in doc["points"]] == [2, 4, 8]
    # more replicas never makes the tail WORSE on the same traffic
    p99 = [p["p99_ttft_ms"] for p in doc["points"]]
    assert p99[-1] <= p99[0]


# --------------------------------------------- slice topology (ISSUE 17)
def test_sim_chips_scale_tick_rate():
    """A 2-chip slice replica decodes ~2x faster (the calibration's
    single-chip tick duration divides by the slice size): same trace
    and seed, chips_per_replica=2 must tighten the interactive ITL
    materially while completing at least as many sessions."""
    tc = _trace(sessions=4000, duration_s=3600.0)
    one = FleetSimulator(generate(tc), _cfg()).run()
    two = FleetSimulator(generate(tc),
                         _cfg(chips_per_replica=2)).run()
    assert two["sim"]["chips_per_replica"] == 2
    assert (two["sessions"]["completed"]
            >= one["sessions"]["completed"])
    itl1 = one["latency"]["itl"]["mean_ms"]
    itl2 = two["latency"]["itl"]["mean_ms"]
    assert itl2 < 0.75 * itl1, (itl1, itl2)


def test_capacity_curve_prices_per_chip():
    """The sweep prices every operating point per chip: a 2-chip
    slice that doesn't buy the tail is capacity the per-replica view
    would hide."""
    curve = capacity_curve(
        _trace(sessions=2000, duration_s=1800.0),
        _cfg(chips_per_replica=2), replica_counts=[2, 4])
    assert curve["fleet"]["chips_per_replica"] == 2
    pts = curve["points"]
    assert [p["chips"] for p in pts] == [4, 8]
    for p in pts:
        assert p["tokens_per_chip_s"] > 0
        assert p["chip_s_per_1k_tokens"] > 0
    # same traffic over 2x the chips: per-chip throughput drops, so
    # the chip-seconds cost of 1k tokens rises — the cost metric
    # really is per chip, not per replica
    assert pts[1]["tokens_per_chip_s"] < pts[0]["tokens_per_chip_s"]


# --------------------------------------------- batch soak inside sim
def test_sim_batch_lane_soaks_trough_without_regression():
    """The simulator models the lane the fleet ships: batch backlog
    soaks the diurnal trough, interactive tails unchanged vs a
    lane-off A/B on the same seed."""
    tc = _trace(sessions=15_000, duration_s=14_400.0)

    def run(jobs):
        sim = FleetSimulator(generate(tc), _cfg(), batch_jobs=jobs)
        return sim.run()

    off = run([])
    on = run(batch_backlog(400, out_tokens=24))
    assert on["batch"]["completed"] == 400
    assert on["batch"]["tokens"] >= 400 * 24 * 0.9
    # interactive TAIL unchanged: one 1.15x log-histogram bin of p99
    # slack (bin quantization only). The mean may shift by a couple
    # of tick-times — co-residency with soaked batch work runs
    # interactive sessions in a larger batch — so it is bounded
    # absolutely (4 full-batch ticks), never relatively
    p99_off = off["latency"]["ttft"]["p99_ms"]
    p99_on = on["latency"]["ttft"]["p99_ms"]
    assert p99_on <= p99_off * 1.16 + 1.0, (p99_off, p99_on)
    mean_off = off["latency"]["ttft"]["mean_ms"]
    mean_on = on["latency"]["ttft"]["mean_ms"]
    assert mean_on <= mean_off + 4 * CALIB.tick_point(8, "p50"), (
        mean_off, mean_on)
    # the engine-level gate pins the token-exact preemption path
    # (test_batch_lane); here the lane must only soak, not regress


# ----------------------------------------------- calibration fidelity
def test_calibration_roundtrip_and_fallbacks():
    c = SimCalibration(
        name="t", decode_tick_ms={"2": {"p50": 1.0, "p95": 2.0,
                                        "p99": 3.0}},
        prefill_ms_per_token=0.1, prefill_chunk_tokens=64)
    c2 = SimCalibration.from_json(c.to_json())
    assert dataclasses.asdict(c2) == dataclasses.asdict(c)
    # bucket fallbacks: below -> nearest, above -> linear scale
    assert c.tick_point(1, "p50") == 1.0
    assert c.tick_point(8, "p50") == 4.0
    assert c.prefill_ticks(129) == 3
    assert c.draw_tick_ms(2, 0, 0.0) == 1.0
    assert c.draw_tick_ms(2, 0, 0.999) == 3.0
    assert c.draw_tick_ms(2, 10, 0.0) == 2.0


def test_committed_cpu_calibration_loads():
    assert CALIB.decode_tick_ms, "calibration_cpu.json is empty"
    assert CALIB.page_size > 0
    p50 = CALIB.tick_point(1, "p50")
    assert 0.01 <= p50 <= 1000.0


@pytest.mark.slow
def test_sim_vs_real_calibration_band():
    """The A/B that keeps the committed file honest: drive a real
    debug engine through a small workload, replay the same workload
    through the simulator under the committed calibration, and pin
    the predicted mean e2e within CALIBRATION_BAND of measured.
    Slow-marked: the real half builds and runs an engine (~tens of
    seconds)."""
    import time as _t
    from tools.simcal import build_engine, check_against
    from ray_tpu.llm._internal.engine import Request, SamplingParams

    n, prompt_len, out = 12, 24, 16
    eng = build_engine(offload=False)
    # warm the compile caches so measurement is steady-state
    warm = Request("warm", list(range(2, 2 + prompt_len)),
                   SamplingParams(max_tokens=4))
    eng.add_request(warm)
    while not warm.finished:
        eng.step()
    reqs = [Request(f"w{i}", list(range(2 + i, 2 + i + prompt_len)),
                    SamplingParams(max_tokens=out))
            for i in range(n)]
    t0 = _t.monotonic()
    for r in reqs:
        eng.add_request(r)
    while not all(r.finished for r in reqs):
        eng.step()
    real_e2e = (_t.monotonic() - t0)  # batch wall ~ mean e2e (all
    #                                   arrive at once, finish near
    #                                   together)

    sessions = [serve_llm.sim.SimSession(0.0, "t", i, prompt_len,
                                         out, sid=i)
                for i in range(n)]
    sim = FleetSimulator(iter(sessions),
                         _cfg(replicas=1, min_replicas=1,
                              slots_per_replica=8,
                              control_period_s=0.05))
    s = sim.run()
    verdict = check_against(CALIB, s, real_e2e)
    assert verdict["within_band"], verdict


# ---------------------------------------------- sync admission surface
def test_admission_sync_twin_matches_policy():
    """The clock-driven admission surface the simulator relies on:
    submit/grant/shed with an injected virtual clock, same counters
    as the async path."""
    clock = VirtualClock()
    adm = AdmissionController(
        AdmissionConfig(max_concurrent=2, max_queue=2,
                        queue_wait_slo_s=1.0),
        clock=clock.now)
    t1 = adm.submit("a")
    t2 = adm.submit("a")
    assert [t.granted for t in (t1, t2)] == [True, True]
    assert len(adm.granted_sync()) == 2
    t3 = adm.submit("a")
    t4 = adm.submit("b")
    assert not t3.granted and not t4.granted
    with pytest.raises(serve_llm.AdmissionRejected) as ei:
        adm.submit("a")
    assert ei.value.reason == "queue_full"
    # SLO timer in virtual time
    clock.t = 2.0
    shed = adm.shed_expired()
    assert {t.tenant for t in shed} == {"a", "b"}
    assert adm.rejected["queue_wait_slo"] == 2
    assert adm.shed_total == 2
    # release grants nothing (queue empty), counters consistent
    adm.release()
    assert adm.granted_sync() == []
    assert adm.stats()["queued"] == 0


def test_admission_sync_weighted_fair_order():
    clock = VirtualClock()
    adm = AdmissionController(
        AdmissionConfig(max_concurrent=1, max_queue=16,
                        tenant_weights={"heavy": 4.0}),
        clock=clock.now)
    first = adm.submit("x")          # takes the slot
    assert first.granted
    adm.granted_sync()
    order = []
    for i in range(3):
        adm.submit("light")
        adm.submit("heavy")
        adm.submit("heavy")
    for _ in range(9):
        adm.release()
        order += [t.tenant for t in adm.granted_sync()]
    # stride scheduling: heavy (weight 4) drains ~2 per light
    assert order.count("heavy") == 6 and order.count("light") == 3
    assert order[:3].count("heavy") >= 2
