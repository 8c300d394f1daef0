"""Preemptible batch-inference lane gates (ISSUE 14).

The acceptance contract: an interactive burst preempts batch jobs
token-exact vs a never-preempted oracle (greedy AND sampled), batch
jobs complete after the trough returns, interactive latency is
unchanged vs a batch-lane-off A/B, and the admission/autoscaler/
watchdog planes exclude batch-lane depth from their overload and
burn signals.
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")

from ray_tpu.llm._internal.engine import (EngineConfig,  # noqa: E402
                                          InferenceEngine, Request,
                                          SamplingParams)
from ray_tpu.llm._internal.server import LLMServerImpl  # noqa: E402
from ray_tpu.serve.llm import (AdmissionConfig,  # noqa: E402
                               AutoscaleConfig, BatchLaneConfig,
                               FleetAutoscaler, FleetManager,
                               FleetMetrics, LocalReplicaClient,
                               ReplicaSnapshot)
from ray_tpu.serve.llm.batch import (BATCH_PRIORITY,  # noqa: E402
                                     INTERACTIVE_PRIORITY)


def _engine(**kw):
    cfg = dict(model="debug", max_batch_size=2, num_pages=64,
               page_size=16, enable_kv_offload=True,
               host_kv_pages=256, kv_watermark_tokens=16,
               enable_metrics=True, enable_blackbox=False, seed=0)
    cfg.update(kw)
    return InferenceEngine(EngineConfig(**cfg))


def _req(rid, prompt, out=24, priority=0, lane="interactive",
         temperature=0.0, seed=None):
    return Request(rid, list(prompt),
                   SamplingParams(max_tokens=out,
                                  temperature=temperature,
                                  seed=seed),
                   priority=priority, lane=lane)


# ------------------------------------------------- engine-level gates
@pytest.mark.parametrize("temperature,seed", [(0.0, None),
                                              (0.9, 1234)])
def test_interactive_preempts_batch_token_exact(temperature, seed):
    """THE gate: batch jobs fill the engine, an interactive burst
    preempts them (slot-priority preemption + spill), everyone
    completes, and the batch outputs are byte-identical to a
    never-preempted oracle — greedy and sampled."""
    def batch_reqs():
        return [_req(f"b{i}", range(2 + 4 * i, 10 + 4 * i), out=32,
                     priority=BATCH_PRIORITY, lane="batch",
                     temperature=temperature, seed=seed)
                for i in range(2)]

    eng = _engine()
    bs = batch_reqs()
    for r in bs:
        eng.add_request(r)
    for _ in range(6):
        eng.step()                      # decoding mid-flight
    burst = [_req(f"i{i}", range(40 + 8 * i, 46 + 8 * i), out=8,
                  priority=INTERACTIVE_PRIORITY,
                  temperature=temperature, seed=seed)
             for i in range(2)]
    for r in burst:
        eng.add_request(r)
    while not all(r.finished for r in burst):
        eng.step()
    # the burst claimed its slots by preempting batch work
    assert eng.preempt_counts.get("priority", 0) >= 1
    assert eng.host_tier.spills_total >= 1
    # trough: batch completes
    for _ in range(3000):
        if all(r.finished for r in bs):
            break
        eng.step()
    assert all(r.finished for r in bs)
    assert eng.host_tier.restores_total >= 1

    oracle = _engine()
    obs = batch_reqs()
    for r in obs:
        oracle.add_request(r)
    while not all(r.finished for r in obs):
        oracle.step()
    for got, want in zip(bs, obs):
        assert got.output_tokens == want.output_tokens, (
            temperature, got.request_id)


def test_parked_batch_never_blocks_interactive_admission():
    """The inversion guard: with a batch session PARKED (spilled),
    a fresh interactive request must admit past it instead of
    waiting for the restore (pre-ISSUE-14 parked-first would
    block)."""
    eng = _engine()
    bs = [_req(f"b{i}", range(2 + 4 * i, 10 + 4 * i), out=48,
               priority=BATCH_PRIORITY, lane="batch")
          for i in range(2)]
    for r in bs:
        eng.add_request(r)
    for _ in range(6):
        eng.step()
    first = _req("i0", range(60, 66), out=8,
                 priority=INTERACTIVE_PRIORITY)
    eng.add_request(first)
    while not first.finished:
        eng.step()
    assert len(eng.parked) >= 1        # batch is parked now
    nxt = _req("i1", range(70, 76), out=8,
               priority=INTERACTIVE_PRIORITY)
    eng.add_request(nxt)
    ticks = 0
    while not nxt.finished and ticks < 200:
        eng.step()
        ticks += 1
    assert nxt.finished and nxt.finish_reason == "length"
    # and the batch work still completes in the trough
    for _ in range(3000):
        if all(r.finished for r in bs):
            break
        eng.step()
    assert all(r.finished for r in bs)


def test_prefilling_batch_victim_requeues_behind_its_preemptor():
    """Review-hardening gate: a still-PREFILLING batch victim
    requeues (PR 10: no tokens emitted, nothing to spill) — but it
    must land BEHIND the interactive head that preempted it, not at
    waiting[0] where the very next admission would hand it the slot
    back (priority inversion; with prefix caching off, a
    preempt/readmit livelock that starves both requests forever)."""
    eng = _engine(enable_prefix_caching=False, max_batch_size=1,
                  max_prefill_tokens=16)
    b = _req("b0", range(2, 2 + 64), out=16, lane="batch",
             priority=BATCH_PRIORITY)
    eng.add_request(b)
    eng.step()                       # b0 holds the slot, prefilling
    assert any(s.request is b and not s.ready for s in eng.slots)
    i = _req("i0", range(100, 106), out=4,
             priority=INTERACTIVE_PRIORITY)
    eng.add_request(i)
    ticks = 0
    while not i.finished and ticks < 200:
        eng.step()
        ticks += 1
    assert i.finished and i.finish_reason == "length", (
        "interactive starved behind the batch victim it preempted")
    assert eng.preempt_counts.get("priority", 0) >= 1
    # and the requeued victim still completes in the trough
    ticks = 0
    while not b.finished and ticks < 2000:
        eng.step()
        ticks += 1
    assert b.finished and b.finish_reason == "length"


def test_parked_gate_is_per_head_not_unlocked_by_first_head():
    """Review-hardening gate: an interactive head outranking the
    parked work admits past it — but a BATCH request queued behind
    that head must NOT ride through the opened gate and claim the
    pages the earlier-arrived parked session needs (the PR 10
    parked-first invariant is per head, not per _admit call)."""
    eng = _engine()
    residents = [_req(f"b{i}", range(2 + 4 * i, 10 + 4 * i), out=48,
                      priority=BATCH_PRIORITY, lane="batch")
                 for i in range(2)]
    for r in residents:
        eng.add_request(r)
    for _ in range(6):
        eng.step()
    first = _req("i0", range(60, 66), out=8,
                 priority=INTERACTIVE_PRIORITY)
    eng.add_request(first)
    while not first.finished:
        eng.step()
    assert len(eng.parked) >= 1           # a batch resident spilled
    parked_ids = {p.request.request_id for p in eng.parked}
    # now an interactive head + a NEW batch request behind it
    i1 = _req("i1", range(70, 76), out=8,
              priority=INTERACTIVE_PRIORITY)
    late_batch = _req("b9", range(80, 88), out=8,
                      priority=BATCH_PRIORITY, lane="batch")
    eng.add_request(i1)
    eng.add_request(late_batch)
    eng.step()
    # the interactive head admitted; the late batch request did NOT
    # jump the parked session through the head's exception
    assert any(s.request is i1 for s in eng.slots)
    assert not any(s.request is late_batch for s in eng.slots)
    assert late_batch in eng.waiting
    # everyone still completes, parked-first order preserved for the
    # batch tier: the PARKED session resumes before the late one runs
    order = []
    seen = set()
    for _ in range(4000):
        if all(r.finished for r in (*residents, late_batch, i1)):
            break
        eng.step()
        for s in eng.slots:
            req = s.request
            if req is not None and req.lane == "batch" \
                    and req.request_id not in seen:
                seen.add(req.request_id)
                order.append(req.request_id)
    assert all(r.finished for r in (*residents, late_batch))
    resumed = [rid for rid in order if rid in parked_ids]
    assert resumed, "the parked session never resumed"
    assert order.index(resumed[0]) < order.index("b9"), order


def test_mixed_priority_parked_fifo_never_livelocks():
    """Review-hardening gate (confirmed livelock pre-fix): parked
    FIFO = [batch p0, interactive p1] with an interactive request
    waiting. The restore yield must SKIP the outranked batch head
    and restore the parked interactive behind it — a `break` there
    plus _admit's all-parked gate meant nothing restored and nothing
    admitted, forever."""
    eng = _engine()
    b = _req("b0", range(2, 10), out=48, lane="batch",
             priority=BATCH_PRIORITY)
    i0 = _req("i0", range(20, 28), out=48,
              priority=INTERACTIVE_PRIORITY)
    eng.add_request(b)
    eng.add_request(i0)
    for _ in range(6):
        eng.step()
    # park BOTH, batch first (FIFO head), interactive behind it
    assert eng.preempt("b0", reason="test")
    assert eng.preempt("i0", reason="test")
    ids = [p.request.request_id for p in eng.parked]
    assert ids == ["b0", "i0"]
    # a fresh interactive request arrives: it outranks b0 but NOT i0
    i1 = _req("i1", range(40, 46), out=8,
              priority=INTERACTIVE_PRIORITY)
    eng.add_request(i1)
    ticks = 0
    while not i1.finished and ticks < 400:
        eng.step()
        ticks += 1
    assert i1.finished, "mixed-priority parked FIFO livelocked"
    # and everything else still completes
    for _ in range(4000):
        if b.finished and i0.finished:
            break
        eng.step()
    assert b.finished and i0.finished


def test_fleet_clamps_client_priority_above_batch_tier():
    """Review-hardening gate: with the lane on, a client explicitly
    sending the pre-lane default priority 0 must be clamped UP — it
    would otherwise tie with batch jobs and never preempt them."""

    async def main():
        clients = [LocalReplicaClient("r0", _server("r0"))]
        fleet = _fleet(clients, lane=True)
        body, _ = fleet._trace_begin("completions",
                                     {"prompt": "x", "priority": 0})
        assert body["priority"] == INTERACTIVE_PRIORITY
        body2, _ = fleet._trace_begin("completions",
                                      {"prompt": "x", "priority": 3})
        assert body2["priority"] == 3           # tiers above survive
        bb, _ = fleet._trace_begin("completions",
                                   {"prompt": "x", "priority": 9},
                                   lane="batch")
        assert bb["priority"] == BATCH_PRIORITY  # forced down
        off = _fleet(clients, lane=False)
        body3, _ = off._trace_begin("completions", {"prompt": "x"})
        assert "priority" not in body3           # lane off: untouched
        await fleet.stop()

    asyncio.run(main())


def test_autoscaler_occupancy_excludes_batch_pages():
    """Review-hardening gate: a batch-soaked engine reports its
    displaceable page share, and the snapshot's interactive
    occupancy (the autoscaler's idle signal) excludes it — a fleet
    full of priority-0 work must still read as scale-downable."""
    eng = _engine()
    bs = [_req(f"b{i}", range(2 + 4 * i, 10 + 4 * i), out=48,
               priority=BATCH_PRIORITY, lane="batch")
          for i in range(2)]
    for r in bs:
        eng.add_request(r)
    for _ in range(6):
        eng.step()
    lanes = eng.lane_counts()
    assert lanes["batch_kv_pages"] > 0
    snap = ReplicaSnapshot.from_stats({
        "replica": "r0", "kv_occupancy": 0.8,
        "kv_occupancy_batch": 0.75})
    assert abs(snap.interactive_occupancy() - 0.05) < 1e-9
    for r in bs:
        eng.abort(r.request_id)


def test_batch_job_cancel():
    """POST /v1/batch/{id}/cancel semantics: unlaunched requests
    stop, completed results are kept, the pump drains cleanly."""

    async def main():
        clients = [LocalReplicaClient("r0", _server("r0"))]
        fleet = _fleet(clients, lane=True)
        await fleet.refresh()
        brief = fleet.batch.submit({"requests": [
            {"prompt": f"bulk {i}", "max_tokens": 8}
            for i in range(8)]})
        # let a couple launch, then cancel
        for _ in range(200):
            await asyncio.sleep(0.01)
            if fleet.batch.completed_requests >= 1:
                break
        doc = fleet.batch.cancel(brief["id"])
        assert doc["status"] == "cancelled"
        # pump drains: in-flight requests finish, queued ones never
        # launch
        for _ in range(400):
            await asyncio.sleep(0.01)
            if fleet.batch.inflight == 0 \
                    and fleet.batch._work.empty():
                break
        final = fleet.batch.get(brief["id"])
        assert final["status"] == "cancelled"
        assert 1 <= final["completed"] < 8
        done = [r for r in final["results"] if r is not None]
        assert len(done) == final["completed"]
        assert fleet.batch.cancel("nope") is None
        await fleet.stop()
        for c in clients:
            if c.server._pump is not None:
                c.server._pump.cancel()

    asyncio.run(main())


def test_cancel_is_final_even_when_all_requests_were_in_flight():
    """A job whose every request was already launched at cancel time
    must stay CANCELLED when the in-flight stragglers run to
    completion — _maybe_finish must not resurrect it as 'done' (the
    results themselves are kept)."""

    async def main():
        clients = [LocalReplicaClient("r0", _server("r0"))]
        fleet = _fleet(clients, lane=True)
        await fleet.refresh()
        brief = fleet.batch.submit({"requests": [
            {"prompt": f"bulk {i}", "max_tokens": 24}
            for i in range(2)]})
        # wait until BOTH are in flight (queue drained, none done)
        for _ in range(800):
            await asyncio.sleep(0.005)
            if fleet.batch._work.empty() \
                    and fleet.batch.inflight == 2:
                break
        doc = fleet.batch.cancel(brief["id"])
        if doc["status"] == "cancelled":     # lost the race = no-op
            for _ in range(800):
                await asyncio.sleep(0.01)
                if fleet.batch.inflight == 0:
                    break
            final = fleet.batch.get(brief["id"])
            assert final["status"] == "cancelled"
            kept = [r for r in final["results"] if r is not None]
            assert len(kept) == final["completed"]
        await fleet.stop()
        for c in clients:
            if c.server._pump is not None:
                c.server._pump.cancel()

    asyncio.run(main())


def test_equal_priority_never_preempts():
    """The pre-ISSUE-14 contract holds: equal-priority requests do
    head-of-line queueing, never preemption."""
    eng = _engine()
    residents = [_req(f"r{i}", range(2 + 4 * i, 10 + 4 * i), out=16)
                 for i in range(2)]
    for r in residents:
        eng.add_request(r)
    for _ in range(4):
        eng.step()
    peer = _req("peer", range(40, 46), out=8)     # same priority 0
    eng.add_request(peer)
    while not peer.finished:
        eng.step()
    assert eng.preempt_counts.get("priority", 0) == 0


def test_batch_lane_excluded_from_slo_totals():
    """Engine telemetry: batch-lane requests produce NO SLO
    observations (the watchdog/autoscaler inputs) while their tokens
    land in the batch counters."""
    eng = _engine()
    b = _req("b0", range(2, 10), out=8, lane="batch",
             priority=BATCH_PRIORITY)
    i = _req("i0", range(20, 26), out=8,
             priority=INTERACTIVE_PRIORITY)
    eng.add_request(b)
    eng.add_request(i)
    while not (b.finished and i.finished):
        eng.step()
    tot = eng.telemetry.slo_totals()
    assert tot["ttft_n"] == 1.0            # the interactive one only
    assert tot["queue_n"] == 1.0
    assert tot["e2e_n"] == 1.0
    summary = eng.telemetry.summary()
    assert summary["batch"]["generated_tokens"] == 8
    assert summary["batch"]["finished"] == {"length": 1}
    lanes = eng.lane_counts()
    assert lanes == {"waiting_batch": 0, "active_batch": 0,
                     "parked_batch": 0, "batch_kv_pages": 0}


def test_lane_rides_session_export_wire():
    """A migrated batch session stays batch on the importer (its SLO
    exclusion and victim priority must survive the hop)."""
    eng = _engine()
    b = _req("b0", range(2, 10), out=32, lane="batch",
             priority=BATCH_PRIORITY)
    eng.add_request(b)
    for _ in range(6):
        eng.step()
    state = eng.export_session("b0", "test")
    assert state is not None and state["lane"] == "batch"
    dst = _engine()
    req = dst.import_session(state)
    assert req.lane == "batch"
    assert req.priority == BATCH_PRIORITY


# ------------------------------------------------ control-plane gates
def test_autoscaler_ignores_batch_backlog():
    """A deep batch-lane queue must not breach the autoscaler while
    the same depth of interactive work must."""
    auto = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=4, upscale_delay_s=0.0))
    # all waiting is batch: FleetManager subtracts it => waiting=0
    m = FleetMetrics(waiting=0)
    assert auto.decide(m, active=2, now=100.0) == 2
    # the same depth interactive breaches (waiting > active)
    auto2 = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=4, upscale_delay_s=0.0))
    m2 = FleetMetrics(waiting=12)
    assert auto2.decide(m2, active=2, now=100.0) == 3


def test_router_treats_batch_depth_as_displaceable():
    """A replica soaking a deep batch queue still takes its affinity
    traffic (batch depth subtracted from the saturation check)."""
    from ray_tpu.serve.llm import FleetRouter, RouterConfig
    r = FleetRouter(RouterConfig(spill_waiting=4))
    r.set_replicas(["r0", "r1"])
    snaps = {
        "r0": ReplicaSnapshot(replica="r0", waiting=10,
                              waiting_batch=10),
        "r1": ReplicaSnapshot(replica="r1", waiting=0),
    }
    fp = "some-prefix"
    want = r.ring.preferred(fp)[0]
    rid, outcome = r.pick_ex(fp, snaps, {})
    assert rid == want and outcome == "affinity"


def test_snapshot_parses_lane_counts():
    snap = ReplicaSnapshot.from_stats(
        {"replica": "r0", "waiting": 7, "waiting_batch": 5,
         "active": 4, "active_batch": 3})
    assert snap.waiting_batch == 5 and snap.active_batch == 3


# ------------------------------------------------------ fleet-level A/B
def _server(rid):
    return LLMServerImpl({
        "model_id": "m", "model_source": "debug",
        "engine_kwargs": {"max_batch_size": 2, "num_pages": 64,
                          "page_size": 16, "enable_kv_offload": True,
                          "kv_watermark_tokens": 16,
                          "host_kv_pages": 256,
                          "metrics_replica_id": rid,
                          "enable_blackbox": False}})


def _fleet(clients, lane):
    return FleetManager(
        clients,
        admission=AdmissionConfig(max_concurrent=8, max_queue=32),
        batch_lane=(BatchLaneConfig(max_inflight=2) if lane
                    else None))


def test_batch_routes_through_serve_app():
    """The HTTP surface: FleetConfig(batch_lane=...) ->
    build_llm_fleet_app -> POST /v1/batch submits, GET /v1/batch and
    /v1/batch/{id} report, and the job completes through the lane."""
    import json
    import time
    import uuid

    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.serve._private.proxy import Request as HttpRequest
    from ray_tpu.serve.llm import FleetConfig, build_llm_fleet_app

    tag = f"batchapp{uuid.uuid4().hex[:8]}"
    app = build_llm_fleet_app(FleetConfig(
        llm_config=LLMConfig(
            model_id="mb", model_source="debug",
            engine_kwargs=dict(max_batch_size=4, page_size=8,
                               num_pages=96, seed=7,
                               metrics_model_id=tag)),
        min_replicas=1, max_replicas=1,
        admission=AdmissionConfig(max_concurrent=4, max_queue=8),
        batch_lane=BatchLaneConfig(max_inflight=2)))
    try:
        h = serve.run(app, name="batch-local",
                      local_testing_mode=True)

        def req(method, path, body=b""):
            return HttpRequest(method, path, {}, {}, body)

        brief = h.remote(req(
            "POST", "/v1/batch",
            json.dumps({"requests": [
                {"prompt": f"bulk {i}", "max_tokens": 4}
                for i in range(3)]}).encode())).result(timeout_s=180)
        assert brief["object"] == "batch" and brief["total"] == 3
        jid = brief["id"]
        deadline = time.monotonic() + 120
        doc = None
        while time.monotonic() < deadline:
            doc = h.remote(req("GET", f"/v1/batch/{jid}")).result(
                timeout_s=60)
            if doc["status"] in ("done", "failed"):
                break
            time.sleep(0.05)
        assert doc is not None and doc["status"] == "done", doc
        assert doc["completed"] == 3
        assert all(r["usage"]["completion_tokens"] == 4
                   for r in doc["results"])
        lst = h.remote(req("GET", "/v1/batch")).result(timeout_s=60)
        assert [j["id"] for j in lst["data"]] == [jid]
        assert lst["lane"]["recovered_tokens"] == 12
        missing = h.remote(req("GET", "/v1/batch/nope")).result(
            timeout_s=60)
        assert getattr(missing, "status", 200) == 404
    finally:
        serve.shutdown()


def test_fleet_batch_ab_recovers_tokens_without_regression():
    """The fleet A/B the bench gate mirrors: identical interactive
    traffic with the lane off vs on (plus a bulk backlog). The lane
    must recover batch tokens > 0, complete every job, keep every
    interactive latency sane, and keep the front door
    interactive-only."""
    def run(lane: bool):
        clients = [LocalReplicaClient(r, _server(r))
                   for r in ("r0", "r1")]
        fleet = _fleet(clients, lane)

        async def drive():
            await fleet.refresh()
            if lane:
                fleet.batch.submit({"requests": [
                    {"prompt": f"bulk {i}", "max_tokens": 16}
                    for i in range(6)]})
            outs = []
            for wave in range(3):
                outs += await asyncio.gather(*[
                    fleet.dispatch(
                        "completions",
                        {"prompt": f"wave {wave} user {i}",
                         "max_tokens": 8})
                    for i in range(4)])
                await asyncio.sleep(0.05)
            job = None
            if lane:
                for _ in range(800):
                    await asyncio.sleep(0.02)
                    await fleet.refresh()
                    job = fleet.batch.get("batch-1")
                    if job["status"] in ("done", "failed"):
                        break
            await fleet.stop()
            for c in clients:
                if c.server._pump is not None:
                    c.server._pump.cancel()
            return outs, job, fleet

        return asyncio.run(drive())

    outs_off, _, fleet_off = run(False)
    outs_on, job, fleet_on = run(True)
    # identical interactive traffic, identical outputs (the lane may
    # only change WHEN batch work runs, never what interactive sees)
    texts_off = [o["choices"][0]["text"] for o in outs_off]
    texts_on = [o["choices"][0]["text"] for o in outs_on]
    assert texts_on == texts_off
    # recovered throughput
    assert job is not None and job["status"] == "done"
    assert job["completed"] == 6
    assert fleet_on.batch.recovered_tokens > 0
    # the front door admitted interactive only (batch bypassed)
    assert fleet_on.admission.admitted == fleet_off.admission.admitted
