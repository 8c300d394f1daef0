"""Fleet-wide observability layer (ISSUE 7).

Unit tier for the new pieces, cheapest first:

- util/tracing ring (satellite): a full ring keeps the NEWEST events
  and counts what it displaced (the old `len < cap` check silently
  dropped all new events forever), surfaced in /debug/trace metadata;
- telemetry clocks (satellite): durations come from time.monotonic —
  an NTP step in time.time() mid-run must not skew TTFT/e2e or
  flight-recorder ordering;
- SLOBurnWatchdog: multi-window burn-rate math over monotone totals,
  page/clear transitions with hysteresis, gauges + alert events;
- AdmissionController brownout: the watchdog's shed signal tightens
  the queue bound without touching already-queued requests;
- BlackboxSpool: bounded (count + bytes), atomic, fetch-by-id,
  traversal-safe;
- engine black-box triggers: a mid-tick crash and a guard violation
  each snapshot a bundle with the replica's last moments;
- trace merge/filter: request_id/trace_id filtering keeps exactly one
  request's events (plus its thread metadata rows), dedup collapses
  the shared in-process tracing ring.

The end-to-end half (one trace id across ingress/router/replica over
real engines, watchdog driving autoscaler + brownout, fleet bundle
fetch) lives in test_serve_llm_fleet.py with the other e2e tests.
"""

import json
import time
import uuid

import numpy as np
import jax.numpy as jnp
import pytest

from ray_tpu.llm._internal.blackbox import BlackboxSpool
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.llm._internal.telemetry import FlightRecorder
from ray_tpu.models import llama
from ray_tpu.serve.llm import (AdmissionConfig, AdmissionController,
                               AdmissionRejected, IngressTraceBuffer,
                               SLOBurnWatchdog, WatchdogConfig,
                               filter_trace, merge_fleet_traces,
                               merge_flight_recorders)
from ray_tpu.serve.llm.tracemerge import request_events
from ray_tpu.util import metrics as metrics_api
from ray_tpu.util import tracing


def make_engine(**over):
    cfg = llama.config("debug", dtype=jnp.float32)
    kw = dict(model=cfg, max_batch_size=4, page_size=8, num_pages=64,
              metrics_model_id=f"obs{uuid.uuid4().hex[:10]}")
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


# ------------------------------------------------- tracing ring satellite

def test_tracing_ring_keeps_newest_and_counts_drops(monkeypatch):
    """The regression: a full ring must displace the OLDEST event, not
    silently refuse every new one (the seed kept startup spam forever
    and lost the events that matter)."""
    monkeypatch.setattr(tracing, "_ring", tracing.BoundedRing(4))
    tracing.enable()
    try:
        for i in range(10):
            with tracing.span(f"s{i}", "t"):
                pass
    finally:
        tracing.disable()
    names = [e["name"] for e in tracing.get_events()]
    assert names == ["s6", "s7", "s8", "s9"]      # newest survive
    assert tracing.ring_stats() == {"capacity": 4, "events": 4,
                                    "total": 10, "dropped": 6}
    # incremental flush addressing survives displacement: only the
    # resident tail comes back, with the advanced total
    tail, total = tracing._ring.tail_since(0)
    assert total == 10 and [e["name"] for e in tail] == names
    assert tracing._ring.tail_since(10) == ([], 10)


def test_tracing_ring_stats_surfaced_in_debug_trace():
    eng = make_engine()
    meta = eng.chrome_trace()["metadata"]
    assert {"dropped", "events", "total",
            "capacity"} <= set(meta["tracing_ring"])
    assert isinstance(meta["wall_anchor_s"], float)
    assert meta["replica"] == ""


# ---------------------------------------------------- clock satellite

def test_latencies_immune_to_wall_clock_step(monkeypatch):
    """An NTP step of +1h mid-generation must not land in the SLO
    histograms or reorder the flight recorder (everything times off
    time.monotonic now; time.time is only an anchor at import)."""
    eng = make_engine()
    rng = np.random.default_rng(0)
    real_time = time.time
    monkeypatch.setattr(time, "time", lambda: real_time() + 3600.0)
    eng.generate([rng.integers(2, 200, 8).tolist()],
                 SamplingParams(max_tokens=3))
    s = eng.stats()["requests"]
    assert 0 < s["ttft_ms_avg"] < 600_000         # not +3600s
    assert 0 < s["e2e_ms_avg"] < 600_000
    evs = eng.telemetry.recorder.events()
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    # recorder timestamps are monotone in seq order (anchored mono)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_request_submitted_at_is_monotonic_clock():
    r = Request("r", [1, 2], SamplingParams())
    assert abs(r.submitted_at - time.monotonic()) < 60.0


# ------------------------------------------------------------ watchdog

def _wd(**over):
    kw = dict(short_window_s=10.0, long_window_s=60.0,
              min_observations=5, objective=0.9, page_burn_rate=2.0,
              warn_burn_rate=1.0, slos=("ttft",))
    kw.update(over)
    rec = FlightRecorder(capacity=64)
    return SLOBurnWatchdog(WatchdogConfig(**kw), recorder=rec), rec


def test_watchdog_pages_on_multiwindow_burn_and_clears():
    wd, rec = _wd()
    wd.observe({"ttft_n": 0.0, "ttft_bad": 0.0}, now=0.0)
    assert not wd.paging                      # no history, no burn
    # 10 of 20 requests blew the SLO: burn = 0.5 / 0.1 = 5x in both
    # windows -> page, alert event, counter
    r = wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=5.0)
    assert r["ttft"]["state"] == "page" and wd.paging
    assert r["ttft"]["burn_short"] == pytest.approx(5.0)
    assert wd.alerts_total == 1
    kinds = [e["event"] for e in rec.events()]
    assert kinds.count("slo_alert") == 1
    # 100 healthy requests cool the short window -> page clears
    wd.observe({"ttft_n": 120.0, "ttft_bad": 10.0}, now=16.0)
    assert wd.state["ttft"] == "ok" and not wd.paging
    assert "slo_clear" in [e["event"] for e in rec.events()]
    # gauges landed in the process registry
    text = metrics_api.export_prometheus()
    assert 'ray_tpu_llm_slo_burn_rate{slo="ttft",window="short"}' \
        in text
    assert 'ray_tpu_llm_slo_alerts_total{slo="ttft"}' in text


def test_watchdog_page_is_sticky_until_short_window_cools():
    """Hysteresis: once paging, a short-window burn still over the
    WARN threshold keeps the page — recovery needs real cooling, not
    one good second."""
    wd, _ = _wd()
    wd.observe({"ttft_n": 0.0, "ttft_bad": 0.0}, now=0.0)
    wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=5.0)
    assert wd.paging
    # window grows but stays dirty: 6 more requests, 1 bad ->
    # short burn vs t=0 baseline is 11/26/0.1 = 4.2 >= warn
    wd.observe({"ttft_n": 26.0, "ttft_bad": 11.0}, now=8.0)
    assert wd.state["ttft"] == "page"


def test_watchdog_holds_page_through_total_stall():
    """A paged fleet that then serves ZERO requests is the outage at
    its worst — an empty short window must hold the page (no evidence
    of recovery), not clear it and release brownout mid-outage."""
    wd, rec = _wd()
    wd.observe({"ttft_n": 0.0, "ttft_bad": 0.0}, now=0.0)
    wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=5.0)
    assert wd.paging
    # total stall: totals frozen, short window drains to n=0
    wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=20.0)
    assert wd.state["ttft"] == "page" and wd.paging
    assert "slo_clear" not in [e["event"] for e in rec.events()]
    # traffic resumes healthy: NOW it clears
    wd.observe({"ttft_n": 120.0, "ttft_bad": 10.0}, now=25.0)
    assert not wd.paging


def test_watchdog_clears_page_when_fleet_is_demand_idle():
    """The ISSUE 14 trough: a page latched at the end of a burst must
    CLEAR once the caller vouches there is no interactive demand left
    anywhere (`idle=True`) — an empty short window over an empty
    fleet is a healthy trough, and a held page would wedge brownout
    shut with nobody left to shed (it starved the batch-lane soak
    governor forever). Without the idle vouch the stall hold stays."""
    wd, rec = _wd()
    wd.observe({"ttft_n": 0.0, "ttft_bad": 0.0}, now=0.0)
    wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=5.0)
    assert wd.paging
    # totals frozen but NOT vouched idle: stall semantics, page holds
    wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=20.0)
    assert wd.paging
    # same frozen totals, fleet vouched demand-idle: trough, clears
    wd.observe({"ttft_n": 20.0, "ttft_bad": 10.0}, now=21.0,
               idle=True)
    assert not wd.paging and wd.state["ttft"] == "ok"
    assert "slo_clear" in [e["event"] for e in rec.events()]
    # a dirty short window still pages even when idle is claimed
    # (evidence of bad traffic beats the vouch)
    wd.observe({"ttft_n": 40.0, "ttft_bad": 30.0}, now=22.0,
               idle=True)
    assert wd.paging


def test_watchdog_rejects_unknown_slo_at_construction():
    with pytest.raises(ValueError, match="unknown watchdog slo"):
        SLOBurnWatchdog(WatchdogConfig(slos=("ttft", "itl")))


def test_watchdog_quiet_window_judges_nothing():
    """Fewer than min_observations in the window -> burn 0: two bad
    requests out of three must not page a fleet."""
    wd, rec = _wd()
    wd.observe({"ttft_n": 0.0, "ttft_bad": 0.0}, now=0.0)
    wd.observe({"ttft_n": 3.0, "ttft_bad": 3.0}, now=5.0)
    assert not wd.paging and wd.alerts_total == 0
    assert rec.events() == []


# ----------------------------------------------------- admission brownout

def test_admission_brownout_tightens_queue_bound():
    import asyncio

    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=8, queue_wait_slo_s=30.0,
            brownout_queue_factor=0.25))
        await adm.acquire("hog")
        w1 = asyncio.create_task(adm.acquire("a"))
        w2 = asyncio.create_task(adm.acquire("b"))
        await asyncio.sleep(0.01)                 # both queued
        assert not adm.would_reject()             # 2 < 8
        assert adm.set_brownout(True)
        assert not adm.set_brownout(True)         # idempotent
        assert adm.stats()["effective_max_queue"] == 2
        assert adm.would_reject()                 # 2 >= 8 * 0.25
        with pytest.raises(AdmissionRejected) as ei:
            await adm.acquire("c")
        assert ei.value.reason == "brownout"      # not queue_full:
        assert adm.rejected["brownout"] == 1      # the full bound had
        assert adm.rejected["queue_full"] == 0    # room
        # queued waiters are untouched: they drain normally
        adm.set_brownout(False)
        adm.release()
        await w1
        adm.release()
        await w2
        adm.release()
        assert adm.admitted == 3
    asyncio.run(main())


# ------------------------------------------------------- black-box spool

def test_blackbox_spool_bounded_atomic_fetchable(tmp_path):
    sp = BlackboxSpool(str(tmp_path / "spool"), capacity=3)
    ids = [sp.dump(f"cause{i}", {"i": i}) for i in range(5)]
    assert all(ids)
    lst = sp.list()
    assert len(lst) == 3                          # count-bounded
    assert [e["id"] for e in lst] == ids[2:]      # oldest pruned
    doc = sp.read(ids[-1])
    assert doc["i"] == 4 and doc["cause"] == "cause4"
    assert doc["id"] == ids[-1] and doc["ts"] > 0
    assert sp.read(ids[0]) is None                # pruned
    assert sp.read("../../etc/passwd") is None    # traversal-safe
    # byte bound prunes too
    sp2 = BlackboxSpool(str(tmp_path / "small"), capacity=100,
                        max_bytes=400)
    for i in range(5):
        sp2.dump("c", {"pad": "x" * 100})
    assert sum(e["bytes"] for e in sp2.list()) <= 400


def test_engine_crash_dumps_blackbox(tmp_path, monkeypatch):
    """A mid-tick exception black-boxes the replica's last moments:
    config, counters, flight recorder, in-flight request states."""
    eng = make_engine(blackbox_dir=str(tmp_path / "bb"))
    rng = np.random.default_rng(1)
    eng.add_request(Request("crashy", rng.integers(2, 200, 6).tolist(),
                            SamplingParams(max_tokens=8)))
    eng.step()

    def boom(touched):
        raise RuntimeError("tick exploded")

    monkeypatch.setattr(eng, "_step_tick", boom)
    with pytest.raises(RuntimeError, match="tick exploded"):
        eng.step()
    monkeypatch.undo()
    bundles = eng.blackbox.list()
    assert len(bundles) == 1
    assert bundles[0]["cause"] == "engine_crash"
    doc = eng.blackbox.read(bundles[0]["id"])
    assert "tick exploded" in doc["error"]
    assert doc["engine_config"]["max_batch_size"] == 4
    assert doc["counters"]["ticks"] >= 1
    assert any(e["event"] == "admission"
               for e in doc["flight_recorder"])
    assert any(r["request_id"] == "crashy"
               for r in doc["in_flight_requests"])
    assert "ray_tpu_llm_ttft_seconds" in doc["metrics_exposition"]
    # the dump itself landed in the recorder (postmortem breadcrumb)
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    assert "blackbox_dump" in kinds
    # engine still usable: deliver or abort the in-flight request
    eng.abort("crashy")


def test_guard_violation_dumps_blackbox(tmp_path):
    """The acceptance path: a forced compile inside dispatch_guard
    lands a guard_violation in the flight recorder, whose alert hook
    snapshots a fetchable postmortem bundle."""
    import jax
    from ray_tpu.util.jax_guard import GuardViolation, dispatch_guard

    eng = make_engine(blackbox_dir=str(tmp_path / "bb"))
    with pytest.raises(GuardViolation):
        with dispatch_guard(max_compiles=0,
                            recorder=eng.telemetry.recorder):
            jax.jit(lambda x: x * 2 + 1)(jnp.arange(7.0))
    bundles = eng.blackbox.list()
    assert len(bundles) == 1
    assert bundles[0]["cause"] == "guard_violation"
    doc = eng.blackbox.read(bundles[0]["id"])
    assert doc["alert_event"]["event"] == "guard_violation"
    assert doc["alert_event"]["n_compiles"] >= 1


def test_blackbox_disabled_is_inert(tmp_path, monkeypatch):
    eng = make_engine(enable_blackbox=False,
                      blackbox_dir=str(tmp_path / "bb"))
    assert eng.dump_blackbox("manual") is None
    assert eng.blackbox.list() == []


def test_guard_violation_blackboxes_even_with_metrics_off(tmp_path):
    """enable_metrics=False disables the flight-recorder RING, not the
    black box: a guard violation must still snapshot a bundle."""
    import jax
    from ray_tpu.util.jax_guard import GuardViolation, dispatch_guard

    eng = make_engine(enable_metrics=False,
                      blackbox_dir=str(tmp_path / "bb"))
    with pytest.raises(GuardViolation):
        with dispatch_guard(max_compiles=0,
                            recorder=eng.telemetry.recorder):
            jax.jit(lambda x: x * 5)(jnp.arange(3.0))
    bundles = eng.blackbox.list()
    assert len(bundles) == 1
    assert bundles[0]["cause"] == "guard_violation"
    assert eng.telemetry.recorder.events() == []   # ring stays inert


def test_blackbox_oversized_bundle_keeps_itself(tmp_path):
    """The newest bundle is exempt from its own byte-bound prune:
    dump() must never return an id a follow-up fetch 404s."""
    sp = BlackboxSpool(str(tmp_path / "big"), capacity=8,
                       max_bytes=200)
    bid = sp.dump("giant", {"pad": "x" * 1000})
    assert bid is not None
    assert sp.read(bid)["cause"] == "giant"       # survived its prune
    # the NEXT dump evicts it (oldest-first) and keeps itself
    bid2 = sp.dump("giant2", {"pad": "y" * 1000})
    assert sp.read(bid) is None
    assert sp.read(bid2)["cause"] == "giant2"


# --------------------------------------------- request-id replay defense

def test_replayed_request_id_cannot_collide():
    """Security regression (ISSUE 7 review): `_request_id` doubles as
    the engine request id, so a client replaying another request's id
    must get a FRESH id instead of overwriting the victim's token
    queue and aborting its stream on teardown."""
    import asyncio

    from ray_tpu.llm._internal.server import LLMServerImpl

    srv = LLMServerImpl({
        "model_id": "m", "model_source": "debug",
        "engine_kwargs": dict(
            max_batch_size=4, page_size=8, num_pages=64,
            metrics_model_id=f"rid{uuid.uuid4().hex[:8]}")})

    async def main():
        a, b = await asyncio.gather(
            srv.completions({"prompt": "first", "max_tokens": 2,
                             "_request_id": "victim"}),
            srv.completions({"prompt": "second", "max_tokens": 2,
                             "_request_id": "victim"}))
        if srv._pump is not None:
            srv._pump.cancel()
        return a, b

    a, b = asyncio.run(main())
    # both complete, under DISTINCT engine ids
    assert a["choices"][0]["finish_reason"] is not None
    assert b["choices"][0]["finish_reason"] is not None
    assert a["id"] != b["id"]
    # the fleet ingress mints its own ids — a client-supplied value
    # never reaches the replica
    from ray_tpu.serve.llm import FleetManager, LocalReplicaClient
    fleet = FleetManager([LocalReplicaClient("r0", object())])
    body, rec = fleet._trace_begin(
        "completions", {"prompt": "x", "_request_id": "victim"})
    assert body["_request_id"] != "victim"
    assert rec["rid"] == body["_request_id"]


# -------------------------------------------------- trace merge / filter

def _ingress_events(rid, trace_id, flow_id, tid=1):
    return request_events(
        tid, rid, {"trace_id": trace_id, "span_id": "s0",
                   "flow_id": flow_id},
        t_queued=100.0, t_admitted=100.01, t_routed=100.02,
        t_done=101.0, replica="r0", outcome="affinity",
        method="completions", tenant="default", status="ok")


def test_request_events_shape_and_flow_start():
    evs = _ingress_events("reqA", "tA", "fA")
    by_name = {e["name"]: e for e in evs}
    assert {"thread_name", "fleet_request", "admission_wait",
            "routing_decision", "route"} <= set(by_name)
    span = by_name["fleet_request"]
    assert span["ph"] == "X" and span["dur"] == pytest.approx(1e6)
    assert span["args"]["trace_id"] == "tA"
    assert span["args"]["replica"] == "r0"
    flow = by_name["route"]
    assert flow["ph"] == "s" and flow["id"] == "fA"
    assert flow["args"]["request_id"] == "reqA"
    rd = by_name["routing_decision"]
    assert rd["args"]["outcome"] == "affinity"
    # flow-start sits at the routing span's start (binds to it)
    assert flow["ts"] == rd["ts"]
    assert flow["pid"] == rd["pid"] and flow["tid"] == rd["tid"]


def test_filter_trace_keeps_one_request_and_its_meta():
    evs = (_ingress_events("reqA", "tA", "fA", tid=1)
           + _ingress_events("reqB", "tB", "fB", tid=2))
    only_a = filter_trace(evs, request_id="reqA")
    assert only_a                                 # non-empty
    for e in only_a:
        if e["ph"] == "M":
            assert e["tid"] == 1                  # only A's label row
        else:
            assert e["args"]["request_id"] == "reqA"
    # trace-id filtering is equivalent addressing
    assert len(filter_trace(evs, trace_id="tB")) \
        == len(filter_trace(evs, request_id="reqB"))
    # no filter = passthrough
    assert filter_trace(evs) == evs


def test_merge_fleet_traces_dedups_shared_ring_and_carries_meta():
    buf = IngressTraceBuffer(capacity=128)
    buf.add(*_ingress_events("reqA", "tA", "fA"))
    shared = {"name": "ring_span", "cat": "task", "ph": "X",
              "ts": 1.0, "dur": 2.0, "pid": 1, "tid": 1, "args": {}}
    doc_r0 = {"traceEvents": [dict(shared)],
              "metadata": {"replica": "r0", "wall_anchor_s": 1.0,
                           "tracing_ring": {"dropped": 0}}}
    doc_r1 = {"traceEvents": [dict(shared)],
              "metadata": {"replica": "r1", "wall_anchor_s": 1.0,
                           "tracing_ring": {"dropped": 3}}}
    doc = merge_fleet_traces({"r0": doc_r0, "r1": doc_r1}, buf)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("ring_span") == 1          # deduped
    assert "fleet_request" in names
    meta = doc["metadata"]
    assert meta["replicas"]["r1"]["tracing_ring"]["dropped"] == 3
    assert meta["ingress"]["buffer"]["events"] == 5
    # a broken replica degrades to an error row, not a crash
    doc = merge_fleet_traces({"r0": doc_r0,
                              "rX": {"error": "timeout"}}, buf)
    assert meta["ingress"]
    assert doc["metadata"]["replicas"]["rX"] == {"error": "timeout"}


def test_ingress_buffer_bounded_with_drop_count():
    buf = IngressTraceBuffer(capacity=4)
    for i in range(10):
        buf.add({"name": f"e{i}", "ph": "X"})
    assert [e["name"] for e in buf.events()] \
        == ["e6", "e7", "e8", "e9"]
    assert buf.stats() == {"capacity": 4, "events": 4, "total": 10,
                           "dropped": 6}


def test_merge_flight_recorders_time_aligned_and_filtered():
    reps = {"r0": [{"seq": 1, "ts": 10.0, "event": "admission",
                    "request_id": "a"},
                   {"seq": 2, "ts": 30.0, "event": "retirement",
                    "request_id": "a"}],
            "r1": [{"seq": 1, "ts": 20.0, "event": "admission",
                    "request_id": "b"}]}
    ingress = [{"seq": 1, "ts": 5.0, "event": "slo_alert"}]
    merged = merge_flight_recorders(reps, ingress)
    assert [e["ts"] for e in merged] == [5.0, 10.0, 20.0, 30.0]
    assert merged[0]["replica"] == "ingress"
    assert merged[1]["replica"] == "r0"
    only_a = merge_flight_recorders(reps, ingress, request_id="a")
    assert len(only_a) == 2
    assert {e["request_id"] for e in only_a} == {"a"}
    # an errored fan-out row degrades instead of crashing the merge
    merged = merge_flight_recorders(
        {"rX": {"error": "timeout"}}, [])
    assert merged[0]["event"] == "collect_error"
