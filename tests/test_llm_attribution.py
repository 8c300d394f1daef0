"""Per-request cost attribution + tick-anomaly analyzer (ISSUE 13).

The load-bearing gate is CONSERVATION: on a seeded mixed
prefill+decode workload with spills/restores, greedy AND sampled, the
summed per-request receipts must equal the PerfAccountant's cumulative
tick totals EXACTLY (closed form, not banded) — integer equality, not
an approx comparison. Everything else (receipts in the finish event
and usage.cost, tenant rollups and their Prometheus counters, the
anomaly detector's classification and auto-capture) layers on that.

Every engine gets a UNIQUE Prometheus model tag so samples from other
tests sharing the process registry can never leak in.
"""

import uuid

import numpy as np
import jax.numpy as jnp
import pytest

import time

from ray_tpu.llm._internal.anomaly import (AnomalyConfig,
                                           TickAnomalyDetector,
                                           own_program_ms)
from ray_tpu.llm._internal.attribution import (CONSERVED_FIELDS,
                                               ReceiptLedger,
                                               _largest_remainder_split)
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.models import llama


def make_engine(**over):
    cfg = llama.config("debug", dtype=jnp.float32)
    kw = dict(model=cfg, max_batch_size=3, page_size=8, num_pages=64,
              max_prefill_tokens=16,
              seed=11,
              metrics_model_id=f"at{uuid.uuid4().hex[:10]}")
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _drive_mixed(eng, sampled: bool, n_req: int = 10,
                 preempt_at: int = 12):
    """Seeded bursty mixed prefill+decode workload; preempts one
    running request mid-flight so spill/restore d2h/h2d traffic is
    part of the conservation sum. Returns the requests."""
    rng = np.random.default_rng(7)
    reqs = [Request(
        f"c{i}", rng.integers(2, 250, 12 + 4 * (i % 3)).tolist(),
        SamplingParams(
            max_tokens=16 + 8 * (i % 2),
            temperature=(0.8 if sampled and i % 2 else 0.0),
            top_k=(20 if sampled and i % 2 else 0)),
        tenant=("acme" if i % 3 == 0 else ""))
        for i in range(n_req)]
    pending = list(reqs)
    steps = 0
    preempted = False
    while eng.has_work() or pending:
        if pending and steps % 4 == 0:
            for r in pending[:3]:
                eng.add_request(r)
            pending = pending[3:]
        eng.step()
        steps += 1
        if steps >= preempt_at and not preempted:
            # spill whichever request currently decodes; keep trying
            # each tick (a victim can finish inside the drain fold
            # preempt() runs first, making that attempt a no-op)
            for s in eng.slots:
                if s.request is not None and s.ready:
                    preempted = eng.preempt(s.request.request_id,
                                            reason="manual")
                    break
    assert all(r.finished for r in reqs)
    return reqs


# --------------------------------------------------------- conservation

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_receipt_conservation_exact(sampled):
    """THE gate: summed receipts == accountant totals, integer-exact,
    on a mixed prefill+decode+spill workload (acceptance criterion)."""
    eng = make_engine(enable_kv_offload=True)
    _drive_mixed(eng, sampled=sampled)
    assert eng.host_tier.spills_total >= 1      # the spill really ran
    assert eng.host_tier.restores_total >= 1
    pt = eng.perf.totals()
    at = eng.attrib.totals()
    for key, _ in CONSERVED_FIELDS:
        assert pt[key] == at[key], (
            f"conservation failed for {key}: "
            f"perf={pt[key]} receipts={at[key]}")
    # offload traffic was part of the sum, not vacuously zero
    assert at["bytes_d2h"] > 0 and at["bytes_h2d"] > 0
    # every request ended with a CLOSED receipt
    summ = eng.attrib.summary()
    assert summ["live"] == 0
    assert summ["requests_total"] == 10
    # time shares exist and sum to (at most) the engine's busy time:
    # every charged tick contributed its wall once
    total_wall = sum(r["wall_ms"] for r in summ["top"])
    assert total_wall > 0


def test_receipt_time_and_queue_shares():
    """Wall-time shares over all receipts re-sum to the committed
    ticks' walls; queue wait lands on the receipt at admission."""
    eng = make_engine()
    _drive_mixed(eng, sampled=False, preempt_at=10**9)
    ledger = eng.attrib
    rows = [ledger.receipt(f"c{i}") for i in range(10)]
    assert all(r is not None and r.finished for r in rows)
    wall_sum = sum(r.wall_ms for r in rows)
    # sum of committed PerfSample walls == sum of receipt shares
    # (float pro-rata split; tolerance for accumulation only)
    sample_wall = sum(t.wall_ms for t in eng.perf.window())
    assert wall_sum == pytest.approx(sample_wall, rel=1e-6)
    assert all(r.queue_ms >= 0.0 for r in rows)
    assert all(r.kv_page_ticks > 0 for r in rows)
    assert all(r.ticks > 0 for r in rows)


def test_largest_remainder_split_exact():
    """The weight-byte splitter: shares always re-sum to the total,
    are proportional, and degrade to equal split on zero weights."""
    rng = np.random.default_rng(3)
    for _ in range(200):
        n = int(rng.integers(1, 9))
        total = int(rng.integers(0, 10**12))
        weights = [int(w) for w in rng.integers(0, 10**6, n)]
        shares = _largest_remainder_split(total, weights)
        assert sum(shares) == total
        assert all(s >= 0 for s in shares)
        wsum = sum(weights)
        if wsum:
            for s, w in zip(shares, weights):
                assert abs(s - total * w / wsum) <= 1
    assert _largest_remainder_split(10, [0, 0, 0]) == [4, 3, 3]
    assert _largest_remainder_split(7, []) == []


def test_finish_tick_late_charges_fold_into_done_receipt():
    """A request's FINAL tick is charged before its finish lands but
    the ledger commits at step end — the late charges must fold into
    the finished receipt, never a zombie live one (conservation
    depends on it)."""
    eng = make_engine()
    rng = np.random.default_rng(5)
    eng.add_request(Request("solo", rng.integers(2, 250, 12).tolist(),
                            SamplingParams(max_tokens=6)))
    while eng.has_work():
        eng.step()
    assert eng.attrib.summary()["live"] == 0
    rec = eng.attrib.receipt("solo")
    assert rec is not None and rec.finished
    assert rec.decode_tokens == 6
    assert rec.prefill_tokens == 12
    pt = eng.perf.totals()
    assert rec.flops == pt["flops_gemm"] + pt["flops_attn"]


# --------------------------------------------- surfaces: events + usage

def test_finish_event_and_stats_carry_receipt():
    """The retirement flight-recorder event carries the cost brief;
    stats()["attribution"] ranks receipts and rolls up tenants."""
    eng = make_engine()
    _drive_mixed(eng, sampled=False, preempt_at=10**9)
    retirements = [e for e in eng.telemetry.recorder.events()
                   if e["event"] == "retirement"]
    assert retirements and all("cost" in e for e in retirements)
    c = retirements[-1]["cost"]
    for key in ("flops", "hbm_bytes", "kv_page_ticks", "wall_ms",
                "queue_ms", "decode_tokens", "prefill_tokens"):
        assert key in c
    s = eng.stats()["attribution"]
    assert s["enabled"] and s["requests_total"] == 10
    assert s["top"] and s["top"][0]["flops"] >= s["top"][-1]["flops"]
    assert set(s["tenants"]) == {"default", "acme"}
    assert s["tenants"]["acme"]["requests"] == 4
    assert s["tenants"]["default"]["requests"] == 6
    # the same doc serves GET /debug/attribution
    assert eng.attribution_summary(top_k=2)["top"] == s["top"][:2]


def test_attribution_disabled_is_inert():
    eng = make_engine(enable_attribution=False,
                      enable_anomaly_detection=False)
    _drive_mixed(eng, sampled=False, preempt_at=10**9)
    assert eng.attrib is None and eng.anomaly is None
    assert eng.stats()["attribution"] == {"enabled": False}
    assert eng.stats()["anomaly"] == {"enabled": False}


def test_attribution_requires_perf_accounting():
    eng = make_engine(enable_perf_accounting=False)
    assert eng.attrib is None and eng.anomaly is None


def test_usage_cost_block_via_server():
    """The OpenAI response's usage.cost extension (server layer)."""
    import asyncio

    from ray_tpu.llm._internal.server import LLMServerImpl

    async def main():
        server = LLMServerImpl({
            "model_id": f"uc{uuid.uuid4().hex[:8]}",
            "engine_kwargs": {"max_batch_size": 2, "page_size": 8,
                              "num_pages": 64}})
        out = await server.completions(
            {"prompt": "hello cost", "max_tokens": 6,
             "user": "tenant-x"})
        return out, server

    out, server = asyncio.new_event_loop().run_until_complete(main())
    cost = out["usage"]["cost"]
    assert cost["flops"] > 0 and cost["hbm_bytes"] > 0
    assert cost["decode_tokens"] == out["usage"]["completion_tokens"]
    # the tenant rode admission -> Request -> receipt
    tenants = server.engine.attrib.tenants()
    assert "tenant-x" in tenants


# ------------------------------------------------- anomaly: unit tests

class _GcStub:
    def __init__(self):
        self.total = 0.0
        self.collections = 0

    def snapshot(self):
        return self.total


def _warm_detector(cfg=None, n=32, wall=2.0):
    det = TickAnomalyDetector(cfg or AnomalyConfig(
        warmup_ticks=16, z_threshold=6.0, min_wall_ms=0.1))
    det._gc = _GcStub()
    det._gc_prev = 0.0

    class S:        # a PerfSample-shaped stub
        flops = 2e9
        hbm_bytes = 1e9
        bytes_h2d = 0.0
        bytes_d2h = 0.0
        kind = "decode"
        dispatches = 1
        decode_tokens = 3
        prefill_tokens = 0

    for _ in range(n):
        ev = det.observe(S(), wall, 0.2, 0.1, compiles=5,
                         peak_flops=1e12, peak_bytes=1e12)
        assert ev is None, ev
    return det, S


def test_anomaly_detector_silent_on_steady_ticks():
    det, _ = _warm_detector(n=64)
    assert det.stats()["anomalies_total"] == 0
    assert det.stats()["warmed"]
    assert det.rate() == 0.0


def test_anomaly_classification_priority():
    """Each evidence channel classifies; priority order holds."""
    det, S = _warm_detector()
    # 1) compile delta wins
    ev = det.observe(S(), 40.0, 0.2, 0.1, compiles=6,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "recompile"
    assert ev["compile_delta"] == 1
    # 2) h2d bytes
    s = S()
    s.bytes_h2d = 4096.0
    ev = det.observe(s, 40.0, 0.2, 0.1, compiles=6,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "h2d_transfer"
    assert ev["composition"]["bytes_h2d"] == 4096
    # 3) gc pause overlapping the tick
    det._gc.total += 0.030
    ev = det.observe(S(), 40.0, 0.2, 0.1, compiles=6,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "gc_pause"
    assert ev["gc_pause_ms"] == pytest.approx(30.0, abs=0.5)
    # 4) host-fold stall (host share far above its baseline)
    ev = det.observe(S(), 40.0, 36.0, 0.1, compiles=6,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "host_fold_stall"
    # 5) device straggler
    ev = det.observe(S(), 40.0, 0.2, 30.0, compiles=6,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "device_straggler"
    # 6) no fingerprint
    ev = det.observe(S(), 40.0, 0.2, 0.1, compiles=6,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "unknown"
    st = det.stats()
    assert st["anomalies_total"] == 6
    assert set(st["by_kind"]) == {
        "recompile", "h2d_transfer", "gc_pause", "host_fold_stall",
        "device_straggler", "unknown"}
    assert st["rate"] > 0


def test_anomaly_capture_rate_limits():
    """arm_profile/dump resolve True once per interval, not per
    anomaly — an anomaly storm must not storm the spool."""
    det, S = _warm_detector(AnomalyConfig(
        warmup_ticks=16, z_threshold=6.0, min_wall_ms=0.1,
        profile_min_interval_s=3600.0, dump_min_interval_s=3600.0))
    ev1 = det.observe(S(), 40.0, 0.2, 0.1, compiles=5,
                      peak_flops=1e12, peak_bytes=1e12)
    ev2 = det.observe(S(), 40.0, 0.2, 0.1, compiles=5,
                      peak_flops=1e12, peak_bytes=1e12)
    assert ev1["arm_profile"] and ev1["dump"]
    assert not ev2["arm_profile"] and not ev2["dump"]


def test_anomaly_baseline_is_the_tick_kinds_own():
    """A kind the cost model prices 4.6x lower than another (a v5e's
    512-token ragged tick among decode ticks, PR 28) is a straggler
    against the pooled baseline only: once it has its own warm-up it is
    judged against itself, and a real stall of it still triggers."""
    det, S = _warm_detector(n=64)

    class R(S):
        kind = "ragged"

    def tick(sample, wall):
        return det.observe(sample(), wall, 0.2, 0.1, compiles=5,
                           peak_flops=1e12, peak_bytes=1e12)

    # one ragged tick in eight, 9.2 ms against decode's 2.0 for the
    # same bound: pooled, each is an anomaly (z 6.9 at the MAD floor)
    flagged = 0
    for i in range(16 * 8):
        if i % 8 == 7:
            flagged += tick(R, 9.2) is not None
        else:
            assert tick(S, 2.0) is None
    assert flagged == 16                   # the kind's own warm-up
    for _ in range(32):
        assert tick(R, 9.2) is None        # its own baseline now
        assert tick(S, 2.0) is None
    ev = tick(R, 46.0)                     # 5x its own kind
    assert ev is not None and ev["composition"]["tick_kind"] == "ragged"
    assert tick(S, 9.2) is not None        # decode at ragged's wall


def test_anomaly_unwarmed_never_triggers():
    det = TickAnomalyDetector(AnomalyConfig(warmup_ticks=1000))
    det._gc = _GcStub()
    det._gc_prev = 0.0

    class S:
        flops, hbm_bytes, bytes_h2d, bytes_d2h = 1e9, 1e9, 0.0, 0.0
        kind, dispatches, decode_tokens, prefill_tokens = "d", 1, 1, 0

    for i in range(100):
        wall = 1.0 if i % 10 else 500.0          # wild outliers
        assert det.observe(S(), wall, 0.1, 0.1, compiles=i,
                           peak_flops=1e12, peak_bytes=1e12) is None


# ------------------------------------------------ anomaly: engine e2e

def _steady_engine(z_threshold=6.0, **over):
    """Warmed engine in steady decode with a FAST anomaly warmup.
    Batch 4 with 3 warm requests: one slot stays free, so the test's
    injected long prompt admits (and recompiles) immediately."""
    eng = make_engine(
        max_batch_size=4, num_pages=128,
        anomaly={"warmup_ticks": 16, "z_threshold": z_threshold,
                 "min_wall_ms": 0.0,
                 "profile_min_interval_s": 0.0,
                 "dump_min_interval_s": 0.0},
        **over)
    rng = np.random.default_rng(5)
    for i in range(3):
        eng.add_request(Request(
            f"s{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=200)))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(40):          # past the 16-tick warmup, baseline set
        eng.step()
    return eng


def test_forced_recompile_produces_classified_capture(no_compile_cache):
    """Acceptance criterion: an injected stall (forced recompile — a
    cold prefill bucket mid-steady-state) produces a classified
    tick_anomaly event, an auto-armed profile capture, and a black-box
    bundle in the spool.

    What it races on: the stall against the baseline's spread. The stall
    is a REAL compile (the run's compile cache is off here: a hit is
    milliseconds and no stall), and the threshold is 3, not 6: beside
    five other workers the warm ticks' log-residuals spread to a MAD of
    ~0.5, where z >= 6 asks for a tick ~85 x the median and a debug
    model's compile is ~80 x; z >= 3 asks for ~9 x. The detector's own
    sensitivity is tested above on given walls; this test is about what
    follows a flag."""
    eng = _steady_engine(z_threshold=3.0)
    assert eng.anomaly.stats()["warmed"]
    base_anoms = eng.anomaly.anomalies_total
    # force a recompile: a prompt far past every warmed bucket
    rng = np.random.default_rng(9)
    eng.add_request(Request("long", rng.integers(2, 250, 60).tolist(),
                            SamplingParams(max_tokens=4)))
    comp0 = eng.compiles
    for _ in range(30):
        eng.step()
        # (a loaded host can have another tick flagged first)
        if eng.anomaly.by_kind.get("recompile"):
            break
    assert eng.compiles > comp0           # the recompile really ran
    assert eng.anomaly.anomalies_total > base_anoms
    events = eng.telemetry.recorder.events()
    anoms = [e for e in events if e["event"] == "tick_anomaly"
             and e["anomaly_kind"] == "recompile"]
    assert anoms, "no classified tick_anomaly flight event"
    ev = anoms[0]
    assert ev["compile_delta"] >= 1
    assert ev["wall_ms"] > ev["predicted_ms"]
    assert "composition" in ev and ev["composition"]["dispatches"] >= 1
    # auto-armed profile capture (trigger recorded)
    armed = [e for e in events if e["event"] == "profile_armed"
             and e.get("trigger") == "tick_anomaly"]
    assert armed, "profile capture was not auto-armed"
    # black-box bundle dropped (by the writer thread, off the step
    # lock) and fetchable from the spool
    assert eng.wait_for_profile(60)
    bundles = eng.blackbox.list()
    causes = {b["cause"] for b in bundles}
    assert "tick_anomaly" in causes
    bundle = next(
        b for b in (eng.blackbox.read(b["id"]) for b in bundles
                    if b["cause"] == "tick_anomaly")
        if b["anomaly_event"]["kind"] == "recompile")
    # the triggering event must not displace the detector's stats
    assert bundle["anomaly"]["anomalies_total"] >= 1
    assert bundle["attribution"] is not None
    # anomaly state rides stats() and the fleet snapshot brief
    assert eng.stats()["anomaly"]["anomalies_total"] >= 1
    assert eng.stats()["anomaly"]["by_kind"].get("recompile", 0) >= 1
    # leave no profiler session open for the tests after this one
    assert eng.wait_for_profile(60)
    eng._profile_abort()
    assert eng.wait_for_profile(60)


def test_anomaly_profile_arm_does_not_wedge_manual_arming():
    """After an auto-armed capture completes, POST /debug/profile
    (profile_next_ticks) still works — and an auto-arm while a manual
    capture is pending is a silent no-op, not a crash."""
    eng = _steady_engine()
    eng.profile_next_ticks(2)
    assert eng._arm_profile_locked(2) is None      # already armed
    _run_capture(eng)
    assert eng._profile is None                    # capture completed
    assert eng.profile_next_ticks(1)               # manual re-arm ok
    _run_capture(eng)


def _run_capture(eng, max_ticks=16):
    """Step an armed capture to its end: the writer thread starts the
    trace, the ticks that begin after that count, the thread stops and
    writes it."""
    assert eng.wait_for_profile(60)                # started off-tick
    for _ in range(max_ticks):
        if eng._profile is None or eng._profile["state"] == "writing":
            break
        eng.step()
    assert eng.wait_for_profile(60)                # written off-tick
    assert eng._profile is None


# ------------------------ a tick is judged on its own program (PR 39)

def _pipeline(programs):
    """A two-deep pipeline's timeline. `programs`: (kind, device ms) in
    dispatch order; every call dispatches its tick in 1 ms of host time
    and then waits for the tick BEFORE (lagged folds, the ragged tick's
    too: the wait for a 40 ms program falls in the next call). Returns
    for each tick but the last (kind, the wall of the call that
    dispatched it, its own program's ms by `own_program_ms`)."""
    t = dev_free = read_before = 0.0
    rows = []
    for i, (kind, ms) in enumerate(programs):
        t0 = t
        t += 1e-3                                   # host: dispatch
        dispatched = t
        done = max(dev_free, dispatched) + ms * 1e-3
        dev_free = done
        if i:
            t = max(t, rows[-1]["done"])            # wait for tick i-1
            rows[-1]["read"] = t
            rows[-1]["own_ms"] = own_program_ms(
                rows[-1]["dispatched"], read_before, t)
            read_before = t
        rows.append({"kind": kind, "dispatched": dispatched,
                     "done": done, "wall_ms": (t - t0) * 1e3})
    return rows[:-1]


def test_a_tick_behind_a_long_program_is_judged_on_its_own():
    """Decode programs of 5 ms, every tenth tick a ragged program of
    40 ms whose wait falls in the NEXT call. Fed the call's wall (what
    the engine handed over until PR 39) every decode tick behind a
    ragged one reads as a device straggler; fed the tick's own program
    nothing does, and a decode program that itself takes 40 ms still
    does."""
    programs = [("ragged" if i % 10 == 9 else "decode",
                 40.0 if i % 10 == 9 else 5.0) for i in range(400)]
    rows = _pipeline(programs)
    behind = [i for i in range(1, len(rows))
              if rows[i]["kind"] == "decode"
              and rows[i - 1]["kind"] == "ragged"]
    assert len(behind) >= 38

    def feed(walls):
        det = TickAnomalyDetector(AnomalyConfig(
            warmup_ticks=16, min_wall_ms=0.1))
        det._gc = _GcStub()
        det._gc_prev = 0.0

        class S:
            flops, hbm_bytes, bytes_h2d, bytes_d2h = 2e9, 1e9, 0.0, 0.0
            dispatches, decode_tokens, prefill_tokens = 1, 3, 0

        flagged = []
        for i, (row, (wall, device)) in enumerate(zip(rows, walls)):
            s = S()
            s.kind = row["kind"]
            ev = det.observe(s, wall, 0.1, device, compiles=0,
                             peak_flops=1e12, peak_bytes=1e12)
            if ev is not None:
                flagged.append((i, ev["kind"]))
        return det, flagged

    # the parent's rule: the call's wall, its readback wait as device
    _, flagged = feed([(r["wall_ms"], r["wall_ms"] - 1.0) for r in rows])
    warmed = [i for i in behind if i > 200]
    assert {i for i, _ in flagged} >= set(warmed)
    assert all(k == "device_straggler" for _, k in flagged)
    # the tick's own program, plus its call's host millisecond: no
    # decode tick is flagged; the ragged ticks are (40 ms among 5), as
    # long as their kind is judged against the pooled window
    det, flagged = feed([(1.0 + r["own_ms"], r["own_ms"]) for r in rows])
    assert [i for i, _ in flagged if rows[i]["kind"] == "decode"] == []
    assert len(flagged) <= 16 and flagged[-1][0] < 170

    class Long:
        flops, hbm_bytes, bytes_h2d, bytes_d2h = 2e9, 1e9, 0.0, 0.0
        dispatches, decode_tokens, prefill_tokens = 1, 3, 0
        kind = "decode"

    ev = det.observe(Long(), 41.0, 0.1, 40.0, compiles=0,
                     peak_flops=1e12, peak_bytes=1e12)
    assert ev is not None and ev["kind"] == "device_straggler"


def test_a_swell_of_load_is_not_a_run_of_stragglers():
    """The prediction holds flat (the weights' one read) while a decode
    tick's program grows with its rows: 4.5 ms for one row, 18 ms for
    twelve. Twelve-row ticks are judged against twelve-row ticks, so a
    swell after a quiet stretch flags at most its size's own warm-up
    against the pooled window, not every tick until the median moves;
    a twelve-row tick that stalls is still seen."""
    det, S = _warm_detector(n=0)

    def tick(rows, wall):
        s = S()
        s.decode_tokens = rows
        return det.observe(s, wall, 0.1, wall - 1.0, compiles=5,
                           peak_flops=1e12, peak_bytes=1e12)

    for _ in range(300):
        assert tick(1, 4.5) is None
    flagged = [tick(12, 18.0) is not None for _ in range(300)]
    assert sum(flagged) <= 16 and not any(flagged[16:])
    assert tick(1, 4.5) is None and tick(2, 5.0) is None
    ev = tick(12, 90.0)
    assert ev is not None and ev["kind"] == "device_straggler"
    assert TickAnomalyDetector._like(S()) == "decode/4"      # 3 tokens


def test_own_program_ms_books_a_wait_to_the_tick_waited_for():
    # dispatched at 1.0, the tick before read at 1.5, its own at 1.6
    assert own_program_ms(1.0, 1.5, 1.6) == pytest.approx(100.0)
    # synchronous: nothing read since its dispatch
    assert own_program_ms(1.0, 0.7, 1.25) == pytest.approx(250.0)
    assert own_program_ms(2.0, 1.0, 1.9) == 0.0


def test_a_ragged_tick_between_decode_ticks_flags_nothing():
    """Lagged folds on: the calls around an admission wait for other
    ticks' programs (the drain before the ragged tick, the retirement's
    double fold). Each tick is judged on its own, so a prompt of a shape
    the engine has run before leaves the detector silent."""
    eng = make_engine(
        max_batch_size=4, num_pages=128,
        anomaly={"warmup_ticks": 16, "min_wall_ms": 0.0})
    assert eng._async
    rng = np.random.default_rng(5)

    def prompt(rid, n, max_tokens):
        eng.add_request(Request(rid, rng.integers(2, 250, n).tolist(),
                                SamplingParams(max_tokens=max_tokens)))

    for i in range(3):
        prompt(f"s{i}", 12, 230)
    for round_ in range(6):
        prompt(f"w{round_}", 12, 2)
        for _ in range(12):
            eng.step()
    assert eng.anomaly.stats()["warmed"]
    assert eng.stats()["tick_times"]["lagged_ticks"] > 40
    compiles, base = eng.compiles, eng.anomaly.anomalies_total
    judged = eng.anomaly.ticks
    for round_ in range(4):
        prompt(f"p{round_}", 12, 2)
        for _ in range(12):
            eng.step()
    assert eng.compiles == compiles
    assert eng.anomaly.ticks >= judged + 40     # every tick is judged
    assert eng.anomaly.anomalies_total == base, eng.anomaly.stats()
    assert not eng._judged or max(eng._judged) == eng.ticks


def test_reactions_to_a_flag_run_off_the_step_lock(monkeypatch):
    """`start_trace` and the bundle's dump each sleep 0.2 s here: the
    tick after a flag still returns at once, and the step lock was held
    for the arming alone."""
    import contextlib
    from ray_tpu.util import profiling

    @contextlib.contextmanager
    def slow_trace(log_dir):
        time.sleep(0.2)
        yield

    # a detector that never judges: the flag below is the only one
    eng = make_engine(max_batch_size=4, num_pages=128,
                      anomaly={"warmup_ticks": 10 ** 6})
    for i in range(3):
        eng.add_request(Request(f"s{i}", list(range(2, 14)),
                                SamplingParams(max_tokens=200)))
    for _ in range(8):
        eng.step()
    monkeypatch.setattr(profiling, "trace", slow_trace)
    dump = eng.blackbox.dump
    monkeypatch.setattr(eng.blackbox, "dump", lambda cause, bundle: (
        time.sleep(0.2), dump(cause, bundle))[1])
    eng._held(eng._on_tick_anomaly, {
        "kind": "unknown", "arm_profile": True, "dump": True})
    longest = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        eng.step()
        longest = max(longest, time.perf_counter() - t0)
    assert longest < 0.1
    assert eng.wait_for_profile(60)
    _run_capture(eng)
    sc = eng.stats()["self_captures"]
    assert sc["profiles_armed"] == {"tick_anomaly": 1}
    assert sc["profiles_started"] == 1
    assert sc["blackbox_dumps"] == {"tick_anomaly": 1}
    # the arming's microseconds (10 ms on an idle host; a loaded one
    # has read 12), of the 0.4 s that the two stubs slept
    assert 0.0 < sc["lock_hold_s"] < 0.05


@pytest.mark.parametrize("started", [True, False],
                         ids=["trace running", "start in flight"])
def test_a_mid_tick_raise_leaves_no_profiler_session(monkeypatch,
                                                     started):
    """`_profile_abort` after a raise inside a tick: a running capture
    is stopped in line, a start the writer thread is in the middle of
    is stopped by the thread as soon as it is made; either way no
    session stays open and the next capture can be armed."""
    import contextlib
    from ray_tpu.util import profiling
    eng = _steady_engine()
    if not started:
        real = profiling.trace

        @contextlib.contextmanager
        def slow_trace(log_dir):
            time.sleep(0.3)
            with real(log_dir):
                yield

        monkeypatch.setattr(profiling, "trace", slow_trace)
    eng.profile_next_ticks(4)
    if started:
        assert eng.wait_for_profile(60)
        assert eng._profile["cm"] is not None

    def boom(touched):
        raise RuntimeError("mid-tick")

    monkeypatch.setattr(eng, "_step_tick", boom)
    with pytest.raises(RuntimeError, match="mid-tick"):
        eng.step()
    monkeypatch.undo()
    assert eng._profile is None
    assert eng.wait_for_profile(60)
    assert not profiling.session_open()
    events = [e["event"] for e in eng.telemetry.recorder.events()]
    assert "profile_aborted" in events
    assert eng.stats()["self_captures"]["profiles_started"] == int(started)
    assert eng.profile_next_ticks(1)
    _run_capture(eng)
    assert not profiling.session_open()


# --------------------------------------------------- ledger edge cases

def test_ledger_finish_before_first_commit():
    """An imported session (restarts >= 1, so no queue-note receipt)
    finishing inside its FIRST charged tick: the receipt must be
    issued at finish, the tick's pending charges must fold into it at
    commit, and NO zombie live receipt may leak."""
    ledger = ReceiptLedger()

    class R:
        request_id = "imported"
        tenant = "t1"
        finish_reason = "stop"

    class S:
        bytes_weights = 100.0
        wall_ms = 1.0

    r = R()
    ledger.charge(r, {"flops_gemm": 40.0}, decode_tokens=2)
    got = ledger.finish(r)                   # before any commit
    assert got is not None and got.finished
    ledger.commit(S())                       # late charges fold in
    assert ledger.summary()["live"] == 0     # no zombie
    rec = ledger.receipt("imported")
    assert rec is got
    assert rec.flops_gemm == 40 and rec.decode_tokens == 2
    assert rec.bytes_weights == 100
    t = ledger.totals()
    assert t["flops_gemm"] == 40 and t["bytes_weights"] == 100
    assert ledger.tenants()["t1"]["requests"] == 1


def test_ledger_migrated_close_not_counted_as_request():
    """An export-side 'migrated' close folds its costs into the
    tenant rollup but NOT into `requests` — the request finishes for
    real on the importing engine, and fleet-summed demand curves must
    count it once."""
    ledger = ReceiptLedger()

    class R:
        request_id = "m1"
        tenant = ""
        finish_reason = "migrated"

    class S:
        bytes_weights = 10.0
        wall_ms = 1.0

    r = R()
    ledger.charge(r, {"flops_gemm": 5.0}, prefill_tokens=1)
    ledger.commit(S())
    ledger.finish(r)
    t = ledger.tenants()["default"]
    assert t["requests"] == 0 and t["migrated"] == 1
    assert t["flops"] == 5                   # the cost still rolls up


def test_ledger_done_ring_eviction_keeps_totals():
    """Receipts displaced from the finished ring still count into
    totals() — conservation never decays with traffic volume."""
    ledger = ReceiptLedger(done_ring=4)

    class R:
        def __init__(self, rid):
            self.request_id = rid
            self.tenant = ""
            self.finish_reason = "stop"

    class S:
        bytes_weights = 100.0
        wall_ms = 1.0

    for i in range(10):
        r = R(f"r{i}")
        ledger.charge(r, {"flops_gemm": 50.0}, decode_tokens=1)
        ledger.commit(S())
        ledger.finish(r)
    t = ledger.totals()
    assert t["flops_gemm"] == 500
    assert t["bytes_weights"] == 1000
    assert t["decode_tokens"] == 10
    assert ledger.summary()["finished_retained"] == 4
    # tenant rollup saw all ten
    assert ledger.tenants()["default"]["requests"] == 10
