"""Multi-replica serve.llm fleet (ISSUE 6).

Layers under test, cheapest first:

- consistent-hash ring + prompt-prefix fingerprint (pure): the
  minimal-disruption property under replica add/remove, and chat
  canonicalization (shared system prompt + history = shared key);
- FleetRouter: prefix affinity is sticky, spills to the ring
  successor once the target saturates (KV occupancy / queue depth),
  and degrades to scored least-load when everything is saturated;
- AdmissionController: bounded queue, immediate 429 on queue_full,
  SLO-bounded shed of queued waiters (so EVERY request's queue wait
  is bounded), weighted fair dequeue across tenants;
- FleetAutoscaler: hysteresis on sustained breach / sustained idle;
- fleet /metrics: separate-registry scrapes get a `replica` label
  injected before the merge (the ISSUE 6 satellite) — identical
  series from different replicas must neither collide nor sum;
- serve.status() health detail: the replica metrics poll carries an
  optional health_detail() payload;
- end-to-end on TWO real in-process engine replicas (debug model,
  CPU): same-prefix requests co-locate and hit the prefix cache,
  overload answers 429 with bounded queue wait, scale-down drains a
  replica without dropping or corrupting an in-flight stream
  (token-exact vs a single-replica oracle), and each replica's
  engine still honors the dispatch contract (1 dispatch/tick, 0 h2d,
  0 compiles) in steady-state decode afterward.

Everything here is in-process (tier-1); process-spawning fleet tests
live behind the `slow` marker in this file's tail.
"""

import asyncio
import json
import time
import uuid

import numpy as np
import pytest

from ray_tpu.serve.llm import (AdmissionConfig, AdmissionController,
                               AdmissionRejected, AutoscaleConfig,
                               ChaosReplicaClient, ChaosSchedule,
                               CircuitBreaker, FleetAutoscaler,
                               FleetManager, FleetMetrics, FleetRouter,
                               HashRing, HealthConfig,
                               LocalReplicaClient, ReplicaSnapshot,
                               RouterConfig, StreamSevered,
                               WatchdogConfig, merge_fleet_traces,
                               prefix_fingerprint)
from ray_tpu.serve.llm.fleet import ACTIVE, DRAINING, STANDBY, UNHEALTHY
from ray_tpu.util import metrics as metrics_api


# ----------------------------------------------------------- hash ring

def _fps(n, salt=""):
    return [prefix_fingerprint({"prompt": f"{salt}prompt #{i} " * 4})
            for i in range(n)]


def test_ring_walk_covers_each_node_exactly_once():
    ring = HashRing(vnodes=16)
    for rid in ("r0", "r1", "r2", "r3"):
        ring.add(rid)
    for fp in _fps(50):
        walk = ring.preferred(fp)
        assert sorted(walk) == ["r0", "r1", "r2", "r3"]
        assert len(set(walk)) == 4


def test_ring_remove_is_minimal_disruption():
    """Removing a node only remaps keys it owned; re-adding restores
    the original assignment exactly (vnode points depend only on node
    names)."""
    ring = HashRing(vnodes=32)
    for rid in ("r0", "r1", "r2"):
        ring.add(rid)
    keys = _fps(300)
    before = {k: ring.preferred(k)[0] for k in keys}
    ring.remove("r1")
    after = {k: ring.preferred(k)[0] for k in keys}
    for k in keys:
        if before[k] == "r1":
            assert after[k] in ("r0", "r2")
        else:
            assert after[k] == before[k]     # untouched keys stay put
    assert any(before[k] == "r1" for k in keys)
    ring.add("r1")
    assert {k: ring.preferred(k)[0] for k in keys} == before


def test_ring_state_is_history_independent():
    """Property under random add/remove churn: the assignment depends
    only on the surviving node SET, never on the order of membership
    events — a rebuilt ring with the same nodes maps every key
    identically."""
    rng = np.random.default_rng(42)
    ring = HashRing(vnodes=16)
    live = set()
    pool = [f"n{i}" for i in range(8)]
    keys = _fps(80)
    for _ in range(60):
        rid = pool[rng.integers(len(pool))]
        if rid in live and rng.random() < 0.5:
            ring.remove(rid)
            live.discard(rid)
        else:
            ring.add(rid)
            live.add(rid)
        if not live:
            assert ring.preferred(keys[0]) == []
            continue
        fresh = HashRing(vnodes=16)
        for r in sorted(live):
            fresh.add(r)
        for k in keys:
            assert ring.preferred(k) == fresh.preferred(k)
        assert set(ring.nodes()) == live


def test_prefix_fingerprint_prompt_depth():
    shared = "x" * 300
    a = prefix_fingerprint({"prompt": shared + "tail A"})
    b = prefix_fingerprint({"prompt": shared + "completely other"})
    assert a == b                       # differ only beyond depth=256
    c = prefix_fingerprint({"prompt": "y" + shared})
    assert c != a                       # differ inside the prefix


def test_prefix_fingerprint_chat_canonicalization():
    sys_msg = {"role": "system", "content": "You are terse. " * 20}
    hist = [sys_msg, {"role": "user", "content": "earlier turn"}]
    a = prefix_fingerprint({"messages": hist + [
        {"role": "user", "content": "now do A"}]})
    b = prefix_fingerprint({"messages": hist + [
        {"role": "user", "content": "now do something else"}]})
    assert a == b                       # shared system+history wins
    c = prefix_fingerprint({"messages": [
        {"role": "system", "content": "You are verbose."}]})
    assert c != a
    # role changes inside the window change the key even when the
    # concatenated text would collide
    d = prefix_fingerprint({"messages": [
        {"role": "user", "content": sys_msg["content"]}]})
    e = prefix_fingerprint({"messages": [
        {"role": "system", "content": sys_msg["content"]}]})
    assert d != e


# ------------------------------------------------------------- router

def _snap(rid, occ=0.0, waiting=0, active=0):
    return ReplicaSnapshot(replica=rid, kv_occupancy=occ,
                           waiting=waiting, active=active)


def test_router_affinity_sticky_then_spills_then_scores():
    r = FleetRouter(RouterConfig(vnodes=16))
    r.set_replicas(["r0", "r1", "r2"])
    fp = prefix_fingerprint({"prompt": "the shared prefix " * 20})
    order = r.ring.preferred(fp)
    primary, second = order[0], order[1]
    empty = {rid: _snap(rid) for rid in order}
    # sticky: same fingerprint, same replica, counted as affinity
    for _ in range(5):
        assert r.pick(fp, empty, {}) == primary
    assert r.affinity_hits == 5 and r.spills == 0
    # primary saturated by occupancy -> deterministic ring successor
    sat = dict(empty)
    sat[primary] = _snap(primary, occ=0.95)
    for _ in range(3):
        assert r.pick(fp, sat, {}) == second
    assert r.spills == 3
    # saturation by queue depth spills too
    sat[primary] = _snap(primary, waiting=99)
    assert r.pick(fp, sat, {}) == second
    # everything saturated -> least-loaded by score
    allsat = {rid: _snap(rid, occ=0.99, waiting=10) for rid in order}
    allsat[order[2]] = _snap(order[2], occ=0.86, waiting=4)
    assert r.pick(fp, allsat, {}) == order[2]
    assert r.scored_fallbacks == 1


def test_router_inflight_counts_toward_saturation():
    """The router's own not-yet-visible in-flight count saturates a
    target before the replica's stats catch up (zero-lag signal)."""
    cfg = RouterConfig(vnodes=16, spill_waiting=4)
    r = FleetRouter(cfg)
    r.set_replicas(["r0", "r1"])
    fp = prefix_fingerprint({"prompt": "hot prefix " * 30})
    primary = r.ring.preferred(fp)[0]
    other = r.ring.preferred(fp)[1]
    snaps = {rid: _snap(rid) for rid in ("r0", "r1")}
    assert r.pick(fp, snaps, {primary: 3}) == primary
    assert r.pick(fp, snaps, {primary: 4}) == other


def test_router_round_robin_policy_cycles():
    r = FleetRouter(RouterConfig(policy="round_robin", vnodes=8))
    r.set_replicas(["r0", "r1"])
    fp = prefix_fingerprint({"prompt": "same " * 40})
    picks = [r.pick(fp, {}, {}) for _ in range(4)]
    assert sorted(picks[:2]) == ["r0", "r1"]
    assert picks[:2] == picks[2:]       # cycles, ignores the prefix


def test_router_empty_ring_returns_none():
    r = FleetRouter()
    assert r.pick("deadbeef", {}, {}) is None


# ---------------------------------------------------------- admission

def test_admission_queue_full_rejects_immediately():
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=1, queue_wait_slo_s=5.0))
        await adm.acquire("a")                       # dispatched
        waiter = asyncio.create_task(adm.acquire("b"))
        await asyncio.sleep(0.01)                    # b is queued
        with pytest.raises(AdmissionRejected) as ei:
            await adm.acquire("c")                   # queue is full
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after_s > 0
        assert adm.rejected["queue_full"] == 1
        adm.release()                                # grants b
        await waiter
        adm.release()
        assert adm.stats()["queued"] == 0
    asyncio.run(main())


def test_admission_slo_shed_bounds_every_queue_wait():
    """A queued request that cannot be granted within the SLO is shed
    with 429 — its wall-clock wait is bounded by the SLO, not by the
    backlog ahead of it."""
    async def main():
        slo = 0.15
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=4, queue_wait_slo_s=slo))
        await adm.acquire("hog")       # never released during the test
        t0 = time.monotonic()
        with pytest.raises(AdmissionRejected) as ei:
            await adm.acquire("victim")
        waited = time.monotonic() - t0
        assert ei.value.reason == "queue_wait_slo"
        assert slo * 0.5 <= waited <= slo + 0.5
        assert adm.shed_total == 1
    asyncio.run(main())


def test_admission_weighted_fair_dequeue():
    """Stride scheduling: tenant A (weight 3) drains ~3x faster than
    tenant B (weight 1) under contention; B is never starved."""
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=32, queue_wait_slo_s=30.0,
            tenant_weights={"A": 3.0, "B": 1.0}))
        await adm.acquire("hog")
        grants = []

        async def one(tenant, i):
            await adm.acquire(tenant)
            grants.append(tenant)

        tasks = []
        for i in range(6):
            tasks.append(asyncio.create_task(one("A", i)))
        for i in range(2):
            tasks.append(asyncio.create_task(one("B", i)))
        await asyncio.sleep(0.02)       # everyone queued
        for _ in range(8):
            adm.release()               # grant one; the grantee holds
            await asyncio.sleep(0.005)
        await asyncio.gather(*tasks)
        # vtimes: A at 1/3,2/3,1,4/3,5/3,2 ; B at 1,2 -> A gets 3 of
        # the first 4 grants, B's first inside the first 4
        assert grants[:3].count("A") == 3
        assert "B" in grants[:4]
        assert grants.count("A") == 6 and grants.count("B") == 2
    asyncio.run(main())


def test_admission_overload_p99_bounded():
    """Hammer the front door: every request either dispatches, gets
    queue_full instantly, or is shed by the SLO timer — no request
    waits unboundedly, and the admitted p99 stays under the SLO."""
    async def main():
        slo = 0.25
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=2, max_queue=3, queue_wait_slo_s=slo))
        done = {"ok": 0, "rejected": 0}
        waits = []

        async def one(i):
            t0 = time.monotonic()
            try:
                await adm.acquire(f"t{i % 3}")
            except AdmissionRejected:
                done["rejected"] += 1
                waits.append(time.monotonic() - t0)
                return
            try:
                await asyncio.sleep(0.03)
                done["ok"] += 1
            finally:
                waits.append(time.monotonic() - t0)
                adm.release()

        await asyncio.gather(*(one(i) for i in range(40)))
        assert done["ok"] + done["rejected"] == 40
        assert done["rejected"] > 0
        assert max(waits) <= slo + 0.6          # bounded, incl. sheds
        assert adm.queue_wait_p99_s() <= slo + 0.05
    asyncio.run(main())


def test_admission_tenant_state_bounded():
    """The stride scheduler's per-tenant pass dict is keyed by the
    CLIENT-controlled "user" field: a stream of unique tenant ids
    (millions of end users, or an attacker) must not accumulate one
    permanent entry each. Entries at/below the global vtime floor are
    semantically dead and get pruned."""
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=4, max_queue=4))
        for i in range(5000):
            await adm.acquire(f"user-{i}")
            adm.release()
        assert len(adm._pass) <= 1025
    asyncio.run(main())


def test_admission_shed_tickets_reaped_under_saturation():
    """Long-lived streams peg inflight at the cap, so _grant_next's
    capacity-gated pop never runs: shed tickets must be reaped by the
    mark-and-compact path instead, or an hour of sustained overload
    retains every ticket ever shed and admission degrades to O(dead)
    per call."""
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=2, max_queue=8, queue_wait_slo_s=0.01))
        await adm.acquire("s0")
        await adm.acquire("s1")                  # cap pegged
        for _ in range(30):
            results = await asyncio.gather(
                *(adm.acquire(f"u{i}") for i in range(8)),
                return_exceptions=True)
            assert all(isinstance(r, AdmissionRejected)
                       for r in results)
        assert len(adm._heap) <= 80              # 240 shed, reaped
        adm.release()
        adm.release()
    asyncio.run(main())


def test_admission_tenant_labeled_series():
    """ISSUE 13 satellite: queue-wait / shed / 429 series carry the
    tenant label — and the DEFAULT tenant exports with NO tenant
    label, so single-tenant scrapes stay byte-identical (the PR 6
    `replica` convention)."""
    import re

    from ray_tpu.util.metrics import export_prometheus

    tag = f"adm{uuid.uuid4().hex[:8]}"

    def sample(text, name, **tags):
        for line in text.splitlines():
            m = re.match(r"^([a-zA-Z0-9_]+)(?:\{(.*)\})? (.+)$", line)
            if m is None or m.group(1) != name:
                continue
            got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
            if got == {k: str(v) for k, v in tags.items()}:
                return float(m.group(3))
        return None

    async def main():
        adm = AdmissionController(
            AdmissionConfig(max_concurrent=1, max_queue=0),
            metrics_model_id=tag)
        await adm.acquire("default")          # default tenant admits
        with pytest.raises(AdmissionRejected) as e:
            await adm.acquire("noisy-tenant")  # full: immediate 429
        assert e.value.reason == "queue_full"
        adm.release()
        await adm.acquire("noisy-tenant")
        adm.release()

    asyncio.run(main())
    text = export_prometheus()
    # default tenant: label OMITTED
    assert sample(text, "ray_tpu_llm_fleet_queue_wait_seconds_count",
                  model=tag) == 1.0
    # explicit tenant: labeled, on both the wait and the 429 series
    assert sample(text, "ray_tpu_llm_fleet_queue_wait_seconds_count",
                  model=tag, tenant="noisy-tenant") == 1.0
    assert sample(text, "ray_tpu_llm_fleet_admission_rejected_total",
                  model=tag, tenant="noisy-tenant",
                  reason="queue_full") == 1.0
    # nothing leaked onto an unlabeled rejection series
    assert sample(text, "ray_tpu_llm_fleet_admission_rejected_total",
                  model=tag, reason="queue_full") is None


def test_watchdog_anomaly_precursor_hysteresis():
    """ISSUE 13: the fleet watchdog's tick-anomaly monitor — two
    consecutive high readings flag, the warn band holds state, and
    recovery under warn clears; alert/clear land in the recorder."""
    from ray_tpu.llm._internal.telemetry import FlightRecorder
    from ray_tpu.serve.llm.watchdog import (SLOBurnWatchdog,
                                            WatchdogConfig)

    rec = FlightRecorder()
    wd = SLOBurnWatchdog(WatchdogConfig(
        anomaly_rate_high=0.25, anomaly_rate_warn=0.10,
        anomaly_count=2), recorder=rec)
    assert not wd.observe_anomaly(0.3)          # 1st high: not yet
    assert wd.anomaly_state == "ok"
    assert wd.observe_anomaly(0.4)              # 2nd: flags
    assert wd.anomaly_state == "high"
    assert not wd.observe_anomaly(0.15)         # warn band: holds
    assert wd.anomaly_state == "high"
    assert wd.observe_anomaly(0.05)             # under warn: clears
    assert wd.anomaly_state == "ok"
    kinds = [e["event"] for e in rec.events()]
    assert kinds.count("anomaly_rate_alert") == 1
    assert kinds.count("anomaly_rate_clear") == 1


def test_admission_would_reject_preflight_matches():
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=1, queue_wait_slo_s=5.0))
        assert not adm.would_reject()
        await adm.acquire("a")
        assert not adm.would_reject()            # queue still empty
        t = asyncio.create_task(adm.acquire("b"))
        await asyncio.sleep(0.01)
        assert adm.would_reject()                # full: next is a 429
        adm.release()
        await t
        adm.release()
    asyncio.run(main())


# --------------------------------------------------------- autoscaler

def test_autoscaler_upscale_needs_sustained_breach():
    a = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=3, upscale_delay_s=3.0,
        downscale_delay_s=30.0, ttft_high_ms=1000.0))
    hot = FleetMetrics(ttft_ms=5000.0)
    assert a.decide(hot, active=1, now=100.0) == 1   # breach starts
    assert a.decide(hot, active=1, now=101.0) == 1   # not sustained
    assert a.decide(hot, active=1, now=103.5) == 2   # sustained -> +1
    # a calm tick resets the breach clock
    assert a.decide(FleetMetrics(ttft_ms=10.0, occupancy=0.5),
                    active=2, now=104.0) == 2
    assert a.decide(hot, active=2, now=105.0) == 2
    assert a.decide(hot, active=2, now=109.0) == 3
    assert a.decide(hot, active=3, now=120.0) == 3   # clamped at max


def test_autoscaler_shed_is_an_instant_breach_signal():
    a = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=2, upscale_delay_s=1.0))
    m = FleetMetrics(shed_delta=3)      # front door turned traffic away
    assert a.decide(m, active=1, now=10.0) == 1
    assert a.decide(m, active=1, now=11.5) == 2


def test_autoscaler_downscale_needs_sustained_idle_and_clamps():
    a = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=3, upscale_delay_s=1.0,
        downscale_delay_s=10.0, occupancy_low=0.3,
        queue_wait_low_ms=50.0))
    idle = FleetMetrics(ttft_ms=5.0, queue_wait_ms=1.0, occupancy=0.05)
    assert a.decide(idle, active=2, now=0.0) == 2
    assert a.decide(idle, active=2, now=5.0) == 2
    assert a.decide(idle, active=2, now=10.5) == 1
    # at min: stays clamped no matter how idle
    assert a.decide(idle, active=1, now=50.0) == 1
    assert a.decide(idle, active=1, now=100.0) == 1
    # busy-but-not-breached middle ground resets the idle clock
    mid = FleetMetrics(ttft_ms=5.0, queue_wait_ms=1.0, occupancy=0.6)
    a2 = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=3, downscale_delay_s=1.0))
    assert a2.decide(idle, active=2, now=0.0) == 2
    assert a2.decide(mid, active=2, now=0.9) == 2
    assert a2.decide(idle, active=2, now=1.5) == 2   # clock restarted


def test_autoscaler_decision_denominated_in_slices():
    """ISSUE 17: the decision stays replica-counted, but
    last_decision carries the chip-denominated view — one +1 buys a
    whole chips_per_slice slice, never a fraction."""
    a = FleetAutoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=3, upscale_delay_s=1.0,
        ttft_high_ms=1000.0))
    hot = FleetMetrics(ttft_ms=5000.0, chips_per_slice=2)
    assert a.decide(hot, active=2, now=0.0) == 2
    assert a.decide(hot, active=2, now=1.5) == 3
    d = a.last_decision
    assert d["chips_per_slice"] == 2
    assert d["active_chips"] == 4
    assert d["target_chips"] == 6


# ----------------------------------------- fleet /metrics aggregation

def test_relabel_exposition_injects_replica_tag():
    from ray_tpu.util.metrics import relabel_exposition
    text = ("# HELP t_total help\n"
            "# TYPE t_total counter\n"
            't_total{model="m"} 3\n'
            "plain_gauge 1.5\n"
            't_total{model="m",replica="keep"} 9\n')
    out = relabel_exposition(text, {"replica": "r7"})
    assert 't_total{model="m",replica="r7"} 3' in out
    assert 'plain_gauge{replica="r7"} 1.5' in out
    # a NON-empty existing label wins over the injected one
    assert 't_total{model="m",replica="keep"} 9' in out
    # headers untouched
    assert "# HELP t_total help" in out and "# TYPE t_total counter" in out


def test_empty_tag_value_is_omitted_from_exposition():
    """The Prometheus data model treats label="" as the label being
    absent — engines outside a fleet leave replica unset and render
    byte-identically to the pre-fleet format."""
    name = f"t_fleet_omit_{uuid.uuid4().hex[:8]}"
    g = metrics_api.Gauge(name, "d", tag_keys=("model", "replica"))
    g.set(4.0, {"model": "m", "replica": ""})
    text = metrics_api.export_prometheus()
    assert f'{name}{{model="m"}} 4.0' in text
    g.set(5.0, {"model": "m", "replica": "r1"})
    text = metrics_api.export_prometheus()
    assert f'{name}{{model="m",replica="r1"}} 5.0' in text


class _FakeClient:
    """Replica stub for fleet plumbing tests: canned fleet_stats /
    metrics_text / drain with call recording."""

    def __init__(self, replica_id, shares_registry=False,
                 metrics="", stats=None, drain_delay_s=0.0):
        self.replica_id = replica_id
        self.shares_registry = shares_registry
        self._metrics = metrics
        self._stats = stats or {}
        self._drain_delay_s = drain_delay_s
        self.calls = []

    async def call(self, method, *args):
        self.calls.append(method)
        if method == "fleet_stats":
            return {"replica": self.replica_id, **self._stats}
        if method == "metrics_text":
            return self._metrics
        if method == "drain":
            await asyncio.sleep(self._drain_delay_s)
            return {"replica": self.replica_id, "drained": True}
        raise AttributeError(method)

    def stream(self, method, body):
        raise NotImplementedError


def test_fleet_metrics_text_relabels_separate_registries():
    """True multi-process fleets: each replica renders the same series
    names from its OWN registry. The fleet scrape must attribute each
    to its replica — not collide, not silently sum."""
    exp = ("# HELP ray_tpu_llm_generated_tokens_total t\n"
           "# TYPE ray_tpu_llm_generated_tokens_total counter\n"
           'ray_tpu_llm_generated_tokens_total{model="m"} %d\n')

    async def main():
        fleet = FleetManager([
            _FakeClient("r0", metrics=exp % 7),
            _FakeClient("r1", metrics=exp % 11),
        ])
        return await fleet.metrics_text()

    out = asyncio.run(main())
    assert ('ray_tpu_llm_generated_tokens_total'
            '{model="m",replica="r0"} 7') in out
    assert ('ray_tpu_llm_generated_tokens_total'
            '{model="m",replica="r1"} 11') in out
    # ONE header pair for the family across both scrapes
    assert out.count("# TYPE ray_tpu_llm_generated_tokens_total") == 1


def test_fleet_metrics_text_shared_registry_renders_once():
    """In-process replicas share one registry: relabeling would lie
    (every scrape holds EVERY replica's series already) — the fleet
    returns one rendering instead of a merged duplicate."""
    exp = "# HELP x y\n# TYPE x gauge\nx 1\n"

    async def main():
        fleet = FleetManager([
            _FakeClient("r0", shares_registry=True, metrics=exp),
            _FakeClient("r1", shares_registry=True, metrics=exp),
        ])
        return await fleet.metrics_text()

    out = asyncio.run(main())
    assert out.count("x 1") == 1
    assert "replica=" not in out


# ------------------------------------------------ fleet state machine

def test_fleet_apply_target_activates_and_drains():
    async def main():
        clients = [_FakeClient(f"r{i}") for i in range(3)]
        fleet = FleetManager(
            clients,
            autoscale=AutoscaleConfig(min_replicas=1, max_replicas=3))
        assert fleet.replicas["r0"].status == ACTIVE
        assert fleet.replicas["r1"].status == STANDBY
        assert fleet.router.ring.nodes() == ["r0"]
        fleet._apply_target(3)
        assert [fleet.replicas[f"r{i}"].status for i in range(3)] \
            == [ACTIVE, ACTIVE, ACTIVE]
        assert fleet.router.ring.nodes() == ["r0", "r1", "r2"]
        # scale down: the victim leaves the ring IMMEDIATELY, drains
        # in the background, parks on standby
        fleet._apply_target(2)
        draining = [rid for rid, st in fleet.replicas.items()
                    if st.status == DRAINING]
        assert len(draining) == 1
        assert draining[0] not in fleet.router.ring.nodes()
        await fleet.replicas[draining[0]].drain_task
        assert fleet.replicas[draining[0]].status == STANDBY
        events = [e["event"] for e in fleet._scale_events]
        assert events.count("activate") == 2
        assert "drain_begin" in events and "drain_done" in events
    asyncio.run(main())


def test_fleet_drain_waits_for_inflight_streams():
    async def main():
        clients = [_FakeClient("r0"), _FakeClient("r1")]
        fleet = FleetManager(
            clients,
            autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2))
        fleet.replicas["r1"].inflight = 2       # live streams
        fleet._begin_drain("r1")
        await asyncio.sleep(0.05)
        assert fleet.replicas["r1"].status == DRAINING
        assert "drain" not in clients[1].calls  # still waiting on them
        fleet.replicas["r1"].inflight = 0
        await asyncio.wait_for(fleet.replicas["r1"].drain_task, 5)
        assert fleet.replicas["r1"].status == STANDBY
        assert "drain" in clients[1].calls      # engine-side drain ran
        done = [e for e in fleet._scale_events
                if e["event"] == "drain_done"]
        assert done and done[0]["clean"] is True
    asyncio.run(main())


def test_fleet_window_metrics_are_deltas_not_lifetime():
    """The autoscaler input is the RECENT window: a fleet that was
    slow an hour ago but fast now must read as fast now."""
    async def main():
        slow = {"slo_totals": {"ttft_s": 50.0, "ttft_n": 10.0,
                               "queue_s": 5.0, "queue_n": 10.0}}
        c = _FakeClient("r0", stats=slow)
        fleet = FleetManager(
            [c], autoscale=AutoscaleConfig(min_replicas=1,
                                           max_replicas=1))
        await fleet.refresh()
        m1 = fleet._window_metrics()
        assert m1.ttft_ms == pytest.approx(5000.0)
        # next window: 10 more requests at 10ms TTFT each
        c._stats = {"slo_totals": {"ttft_s": 50.1, "ttft_n": 20.0,
                                   "queue_s": 5.0, "queue_n": 10.0}}
        await fleet.refresh()
        m2 = fleet._window_metrics()
        assert m2.ttft_ms == pytest.approx(10.0, abs=1e-6)
        assert m2.queue_wait_ms == 0.0          # no new observations
    asyncio.run(main())


def test_fleet_window_metrics_survive_membership_changes():
    """Deltas are per replica id, not a diff of the fleet sum over the
    changing ACTIVE set: a replica parking to STANDBY must not read as
    a negative window (masking a real breach on the survivor), and a
    reactivated replica must contribute only growth since last seen —
    not its lifetime totals as one spurious breach window."""
    async def main():
        c0 = _FakeClient("r0", stats={"slo_totals": {
            "ttft_s": 1.0, "ttft_n": 10.0,
            "queue_s": 0.0, "queue_n": 10.0}})
        c1 = _FakeClient("r1", stats={"slo_totals": {
            "ttft_s": 40.0, "ttft_n": 20.0,
            "queue_s": 0.0, "queue_n": 20.0}})
        fleet = FleetManager(
            [c0, c1], autoscale=AutoscaleConfig(min_replicas=1,
                                                max_replicas=2))
        fleet.replicas["r1"].status = "ACTIVE"
        await fleet.refresh()
        fleet._window_metrics()                  # baseline window

        # r1 parks; r0 alone serves 10 slow requests (500ms TTFT).
        # With fleet-sum deltas the vanished r1 totals would swamp the
        # window negative and report 0.0 — the breach must survive.
        fleet.replicas["r1"].status = "STANDBY"
        c0._stats = {"slo_totals": {"ttft_s": 6.0, "ttft_n": 20.0,
                                    "queue_s": 0.0, "queue_n": 20.0}}
        await fleet.refresh()
        m = fleet._window_metrics()
        assert m.ttft_ms == pytest.approx(500.0)

        # r1 reactivates with unchanged lifetime totals: its history
        # must NOT re-enter as one giant window (fleet-sum deltas
        # would report (40s + r0 growth) / (20 + n) here)
        fleet.replicas["r1"].status = "ACTIVE"
        c0._stats = {"slo_totals": {"ttft_s": 6.1, "ttft_n": 30.0,
                                    "queue_s": 0.0, "queue_n": 30.0}}
        await fleet.refresh()
        m = fleet._window_metrics()
        assert m.ttft_ms == pytest.approx(10.0, abs=1e-6)
    asyncio.run(main())


# ------------------------------------- serve.status() health detail

def test_replica_metrics_surfaces_health_detail():
    """The controller's existing metrics poll piggybacks an optional
    health_detail() hook (sync or async); a broken hook never fails
    the probe."""
    from ray_tpu._private.serialization import serialize_code
    from ray_tpu.serve._private.replica import Replica
    from ray_tpu.serve._private.serialization_helpers import \
        serialize_args

    class WithDetail:
        async def health_detail(self):
            return {"waiting": 3, "kv_occupancy": 0.25}

        def __call__(self):
            return "ok"

    class WithBrokenDetail:
        def health_detail(self):
            raise RuntimeError("boom")

        def __call__(self):
            return "ok"

    class NoDetail:
        def __call__(self):
            return "ok"

    def build(cls):
        return Replica("app#d", "rid", serialize_code(cls),
                       serialize_args((), {}))

    async def main():
        m = await build(WithDetail).metrics()
        assert m["detail"] == {"waiting": 3, "kv_occupancy": 0.25}
        m = await build(WithBrokenDetail).metrics()
        assert "detail" not in m                # best-effort, no raise
        m = await build(NoDetail).metrics()
        assert "detail" not in m
        assert {"ongoing", "total", "qps_10s"} <= set(m)
    asyncio.run(main())


def test_llm_server_health_detail_shape(fleet_servers):
    srv = fleet_servers["r0"]

    async def main():
        return await srv.health_detail()

    d = asyncio.run(main())
    assert d["replica"] == "r0"
    assert {"active", "waiting", "kv_occupancy", "free_pages",
            "last_tick_age_s", "cache_hit_rate"} <= set(d)
    assert "slo_totals" not in d                # detail stays compact


# --------------------------------------------- e2e: real 2-replica fleet

_fleet_state = {}


def _make_server(rid, tag):
    from ray_tpu.llm._internal.server import LLMServerImpl
    return LLMServerImpl({
        "model_id": "m", "model_source": "debug",
        "engine_kwargs": dict(
            max_batch_size=4, page_size=8, num_pages=128, seed=7,
            max_prefill_tokens=32,
            metrics_model_id=tag, metrics_replica_id=rid),
    })


@pytest.fixture(scope="module")
def fleet_servers():
    """Two real engine replicas (debug model, CPU) shared across the
    e2e tests — engine construction and shape-bucket compiles are the
    expensive part, the tests themselves reuse the warm engines."""
    if "servers" not in _fleet_state:
        tag = f"fleet{uuid.uuid4().hex[:8]}"
        _fleet_state["tag"] = tag
        _fleet_state["servers"] = {
            rid: _make_server(rid, tag) for rid in ("r0", "r1")}
    return _fleet_state["servers"]


def _cancel_pumps(servers):
    """End-of-test hygiene: the engine pump task belongs to the test's
    asyncio.run loop — cancel it before the loop closes so teardown
    doesn't warn about destroyed pending tasks (each test's first
    request re-creates the pump on its own loop)."""
    for srv in servers.values():
        if srv._pump is not None:
            srv._pump.cancel()


def _fleet_over(servers, **over):
    kw = dict(
        router=RouterConfig(prefix_depth=64, spill_waiting=16),
        admission=AdmissionConfig(max_concurrent=8, max_queue=16,
                                  queue_wait_slo_s=30.0),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2))
    kw.update(over)
    return FleetManager(
        [LocalReplicaClient(rid, srv) for rid, srv in servers.items()],
        **kw)


# 64-char shared prefixes (= prefix_depth and a multiple of
# page_size=8, so followers share the leading prompt pages exactly)
_PREFIX_A = ("alpha context block that the whole tenant shares " +
             "a" * 14)[:64]
_PREFIX_B = ("bravo context block that another tenant shares " +
             "b" * 16)[:64]


def test_e2e_prefix_affinity_colocates_and_hits_cache(fleet_servers):
    """Same-prefix requests land on the same replica while distinct
    prefixes may split — and the co-located followers actually HIT
    the affine replica's prefix cache (the gauge the router's policy
    exists to maximize)."""
    fleet = _fleet_over(fleet_servers)

    async def group(prefix, n):
        picked = set()
        for i in range(n):
            body = {"prompt": prefix + f" req {i}", "max_tokens": 2}
            before = {rid: st.requests_total
                      for rid, st in fleet.replicas.items()}
            out = await fleet.dispatch("completions", body)
            assert out["choices"][0]["finish_reason"] is not None
            after = {rid: st.requests_total
                     for rid, st in fleet.replicas.items()}
            picked.update(rid for rid in after
                          if after[rid] != before[rid])
        return picked

    async def main():
        hit0 = {rid: srv.engine.allocator.cache_hit_rate
                for rid, srv in fleet_servers.items()}
        a = await group(_PREFIX_A, 3)
        b = await group(_PREFIX_B, 3)
        _cancel_pumps(fleet_servers)
        return a, b, hit0

    a, b, hit0 = asyncio.run(main())
    assert len(a) == 1, f"group A sprayed across {a}"
    assert len(b) == 1, f"group B sprayed across {b}"
    st = fleet.router.stats()
    assert st["picks"] == 6 and st["affinity_hits"] == 6
    assert st["spills"] == 0 and st["scored_fallbacks"] == 0
    # followers 2..n of each group hit their replica's prefix cache
    for rid in a | b:
        eng = fleet_servers[rid].engine
        assert eng.allocator.cache_hit_rate > hit0.get(rid, 0.0), (
            f"no prefix-cache hits on affine replica {rid}")


def test_e2e_slice_fleet_provisions_whole_slices():
    """ISSUE 17 acceptance: on a 2-chip-slice fleet every replica IS
    one tp-sharded engine over a named (1, 2) mesh — /fleet rows
    carry chips per replica, the autoscale block accounts in slice
    units, and a scale-up provisions a WHOLE 2-chip slice (the
    activated standby's engine already spans 2 emulated devices)."""
    from ray_tpu.llm._internal.server import LLMServerImpl

    servers = {}
    for rid in ("r0", "r1"):
        servers[rid] = LLMServerImpl({
            "model_id": "m", "model_source": "debug",
            "engine_kwargs": dict(
                max_batch_size=2, page_size=8, num_pages=64, seed=5,
                mesh_shape=(1, 2)),
        })
    fleet = FleetManager(
        [LocalReplicaClient(rid, srv)
         for rid, srv in servers.items()],
        autoscale=AutoscaleConfig(min_replicas=1, max_replicas=2))

    async def main():
        await fleet.refresh()
        st1 = await fleet.status()
        fleet._apply_target(2)          # the scale-up decision lands
        await fleet.refresh()
        st2 = await fleet.status()
        _cancel_pumps(servers)
        return st1, st2

    st1, st2 = asyncio.run(main())
    assert st1["replicas"]["r0"]["chips"] == 2
    assert st1["autoscale"]["chips_per_slice"] == 2
    assert st1["autoscale"]["active_chips"] == 2
    # the activated replica is itself a whole 2-chip slice
    assert servers["r1"].engine.n_chips == 2
    assert st2["replicas"]["r1"]["status"] == ACTIVE
    assert st2["replicas"]["r1"]["chips"] == 2
    assert st2["autoscale"]["active_chips"] == 4


def test_e2e_fleet_stats_and_status_surface(fleet_servers):
    fleet = _fleet_over(fleet_servers)

    async def main():
        await fleet.refresh()
        return await fleet.status(), await fleet.metrics_text()

    status, mtext = asyncio.run(main())
    for rid in ("r0", "r1"):
        row = status["replicas"][rid]
        assert row["status"] == ACTIVE
        assert {"active", "waiting", "kv_occupancy", "free_pages",
                "prefix_cache_hit_rate",
                "last_tick_age_s"} <= set(row)
    assert status["autoscale"]["active"] == 2
    # in-process replicas share the registry: one clean exposition
    tag = _fleet_state["tag"]
    assert f'model="{tag}"' in mtext
    assert mtext.count("# TYPE ray_tpu_llm_ttft_seconds histogram") == 1


def test_e2e_overload_429_with_bounded_wait(fleet_servers):
    """16 concurrent requests against max_concurrent=1/max_queue=1:
    the surplus gets an immediate 429 (queue_full) or an SLO-bounded
    shed — nobody waits unboundedly, admitted work completes."""
    fleet = _fleet_over(
        fleet_servers,
        admission=AdmissionConfig(max_concurrent=1, max_queue=1,
                                  queue_wait_slo_s=8.0))

    async def main():
        results = await asyncio.gather(
            *(fleet.dispatch(
                "completions",
                {"prompt": f"overload probe {i}", "max_tokens": 2})
              for i in range(16)),
            return_exceptions=True)
        _cancel_pumps(fleet_servers)
        return results

    t0 = time.monotonic()
    results = asyncio.run(main())
    elapsed = time.monotonic() - t0
    ok = [r for r in results if isinstance(r, dict)]
    rejected = [r for r in results if isinstance(r, AdmissionRejected)]
    other = [r for r in results
             if not isinstance(r, (dict, AdmissionRejected))]
    assert not other, other
    assert len(ok) + len(rejected) == 16
    assert len(rejected) >= 10          # the burst visibly sheds
    for r in rejected:
        assert r.retry_after_s > 0      # Retry-After hint populated
    assert len(ok) >= 1                 # admitted work completed
    adm = fleet.admission.stats()
    assert adm["rejected"]["queue_full"] >= 10
    # bounded: admitted queue waits obey the SLO; the whole burst
    # resolves in bounded time instead of queueing 16-deep
    assert adm["queue_wait_p99_s"] <= 8.0 + 0.5
    assert elapsed < 60.0


def test_e2e_scale_down_drains_streams_token_exact(fleet_servers):
    """Scale-down mid-stream: the victim leaves the ring, its live
    SSE streams run to completion, and every stream's text is
    token-exact vs a single-replica oracle — drain never drops or
    corrupts in-flight work."""
    fleet = _fleet_over(fleet_servers)
    gen_tokens = 12
    # choose prompts that provably put TWO live streams on EACH
    # replica (the ring is deterministic), so the drain victim —
    # whichever replica it is — has work on the wire
    by_rid = {rid: [] for rid in fleet.replicas}
    i = 0
    while any(len(v) < 2 for v in by_rid.values()):
        p = f"drain stream probe {i}"
        rid = fleet.router.ring.preferred(
            prefix_fingerprint({"prompt": p}, 64))[0]
        if len(by_rid[rid]) < 2:
            by_rid[rid].append(p)
        i += 1
    prompts = [p for group in by_rid.values() for p in group]

    async def consume(body, started):
        chunks = []
        async for chunk in fleet.dispatch_stream(
                "completions_stream", body):
            chunks.append(chunk)
            if len(chunks) == 1:
                started.set_result(None)
        return chunks

    async def main():
        loop = asyncio.get_running_loop()
        started = [loop.create_future() for _ in prompts]
        tasks = [
            asyncio.create_task(consume(
                {"prompt": p, "max_tokens": gen_tokens}, started[i]))
            for i, p in enumerate(prompts)]
        await asyncio.wait_for(asyncio.gather(*started), 120)
        # every stream is live on the wire: drop to ONE replica
        fleet._apply_target(1)
        draining = [rid for rid, st in fleet.replicas.items()
                    if st.status == DRAINING]
        assert len(draining) == 1
        assert fleet.router.ring.nodes() != []
        all_chunks = await asyncio.wait_for(asyncio.gather(*tasks), 120)
        # a post-drain request still works (routes to the survivor)
        out = await fleet.dispatch(
            "completions", {"prompt": "after drain", "max_tokens": 2})
        assert out["choices"][0]["finish_reason"] is not None
        await asyncio.wait_for(
            fleet.replicas[draining[0]].drain_task, 60)
        _cancel_pumps(fleet_servers)
        return draining[0], all_chunks

    victim, all_chunks = asyncio.run(main())
    assert fleet.replicas[victim].status == STANDBY
    done = [e for e in fleet._scale_events if e["event"] == "drain_done"]
    assert done and done[-1]["clean"] is True

    def sse_text(chunks):
        text = ""
        finishes = 0
        for c in chunks:
            payload = c[len("data: "):].strip()
            if payload == "[DONE]":
                continue
            d = json.loads(payload)
            text += d["choices"][0]["text"]
            finishes += d["choices"][0]["finish_reason"] is not None
        assert finishes == 1            # exactly one finish per stream
        return text

    # oracle: a fresh single replica with the same seed (greedy decode
    # is batching- and fleet-independent)
    oracle = _make_server("oracle", f"oracle{uuid.uuid4().hex[:6]}")

    async def oracle_text(p):
        out = await oracle.completions(
            {"prompt": p, "max_tokens": gen_tokens})
        return out["choices"][0]["text"]

    async def oracle_main():
        texts = []
        for p in prompts:
            texts.append(await oracle_text(p))
        _cancel_pumps({"oracle": oracle})
        return texts

    want = asyncio.run(oracle_main())
    got = [sse_text(c) for c in all_chunks]
    assert got == want, "drain corrupted an in-flight stream"


def test_e2e_dispatch_discipline_holds_per_replica(fleet_servers):
    """After fleet traffic, each replica's engine still honors the
    dispatch contract in steady-state decode: 16 consecutive ticks =
    16 dispatches, zero h2d transfers, zero new compiled programs
    under the armed runtime guard.

    ISSUE 7 acceptance: the replicas run with the FULL observability
    layer on — trace-context-tagged requests (the fleet prime below
    routes a traced request, and the direct guard requests carry
    trace contexts too), SLO targets recording bad counts, the fleet
    watchdog observing, black-box armed — and the tick cost is still
    1 dispatch / 0 h2d / 0 compiles, because all of it is host-side
    Python off the dispatch boundary."""
    from ray_tpu.llm._internal.engine import Request, SamplingParams
    from ray_tpu.util.jax_guard import dispatch_guard

    fleet = _fleet_over(fleet_servers)      # tracing + watchdog on

    async def prime():
        out = await fleet.dispatch(
            "completions", {"prompt": "guard trace probe",
                            "max_tokens": 2})
        assert out["choices"][0]["finish_reason"] is not None
        await fleet.autoscale_tick(now=0.0)   # watchdog observes
        _cancel_pumps(fleet_servers)

    asyncio.run(prime())
    assert fleet.enable_tracing and fleet.watchdog.config.enabled
    assert fleet.trace.stats()["events"] > 0   # the ingress traced it

    rng = np.random.default_rng(3)
    for rid, srv in fleet_servers.items():
        eng = srv.engine
        while eng.has_work():                # drain the primed work
            eng.step()
        rids = []
        for i in range(2):
            r = f"guard-{rid}-{i}"
            rids.append(r)
            eng.add_request(Request(
                r, rng.integers(2, 250, 12).tolist(),
                SamplingParams(max_tokens=64),
                trace={"trace_id": f"t-{r}", "span_id": f"s-{r}",
                       "flow_id": f"f-{r}"}))
        while eng.waiting or any(s.request is not None and not s.ready
                                 for s in eng.slots):
            eng.step()
        for _ in range(4):
            eng.step()                  # settle the pipeline
        comp0 = eng.stats()["jit_cache"]["compiled_programs"]
        disp0 = eng.dispatches
        # the guard RAISES at any h2d transfer site, so 16 clean ticks
        # prove 0 uploads; the sentinel counts XLA builds
        with dispatch_guard() as rep:
            for _ in range(16):
                eng.step()
        assert eng.dispatches - disp0 == 16, rid
        assert rep.n_compiles == 0, rid
        assert eng.stats()["jit_cache"]["compiled_programs"] == comp0
        for r in rids:
            eng.abort(r)
        while eng.has_work():           # deliver pending folds
            eng.step()


# ------------------------------- e2e: fleet observability (ISSUE 7)

def test_e2e_fleet_trace_one_trace_id_across_processes(fleet_servers):
    """Satellite + acceptance: one request through the fleet ingress
    produces spans sharing ONE trace id across ingress (fleet_request,
    admission_wait, routing_decision), router flow-start, and the
    replica's engine lifecycle (queued/prefill/decode), with the
    Perfetto flow arrow linking router to replica — and ?request_id=
    filtering returns exactly that request's lifecycle."""
    fleet = _fleet_over(fleet_servers)

    async def main():
        out = await fleet.dispatch(
            "completions",
            {"prompt": "distributed trace probe", "max_tokens": 3})
        _cancel_pumps(fleet_servers)
        return out

    out = asyncio.run(main())
    rid = out["id"][len("cmpl-"):]
    docs = {r: srv.engine.chrome_trace()
            for r, srv in fleet_servers.items()}
    doc = merge_fleet_traces(docs, fleet.trace, request_id=rid)
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert evs, "filter returned nothing for a served request"
    # exactly that request's lifecycle...
    for e in evs:
        assert e["args"]["request_id"] == rid
    # ...sharing ONE trace id across ingress and replica events
    trace_ids = {e["args"]["trace_id"] for e in evs
                 if "trace_id" in e["args"]}
    assert len(trace_ids) == 1
    names = {e["name"] for e in evs}
    assert {"fleet_request", "admission_wait", "routing_decision",
            "queued", "prefill", "decode"} <= names, names
    # the flow arrow: one start at the ingress routing span, one
    # finish on the replica's request row, same flow id
    flows = [e for e in evs if e.get("cat") == "flow"
             and e["name"] == "route"]
    starts = [e for e in flows if e["ph"] == "s"]
    finishes = [e for e in flows if e["ph"] == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    # the ingress span names the replica that served it, and that
    # replica's doc is where the lifecycle events came from
    span = next(e for e in evs if e["name"] == "fleet_request")
    assert span["args"]["status"] == "ok"
    served = span["args"]["replica"]
    assert served in fleet_servers
    # timestamps are epoch-aligned: the merged doc orders ingress
    # admission before the replica's prefill
    t_admit = next(e for e in evs if e["name"] == "admission_wait")
    t_prefill = next(e for e in evs if e["name"] == "prefill")
    assert t_admit["ts"] <= t_prefill["ts"] + 1e3   # <=1ms anchor slop
    # the UNFILTERED merge contains more than this one request
    # (the module fixture served earlier traffic)
    full = merge_fleet_traces(docs, fleet.trace)
    assert len(full["traceEvents"]) > len(doc["traceEvents"])
    assert full["metadata"]["ingress"]["buffer"]["events"] > 0


_WD_ZERO = {"ttft_s": 0.0, "ttft_n": 0.0, "ttft_bad": 0.0,
            "queue_s": 0.0, "queue_n": 0.0, "queue_bad": 0.0,
            "e2e_s": 0.0, "e2e_n": 0.0, "e2e_bad": 0.0}


def test_e2e_watchdog_pages_scales_up_and_brownouts():
    """Acceptance: synthetic SLO burn drives the watchdog to page —
    slo_alert lands in the fleet recorder, admission engages brownout,
    the autoscaler treats the page as an instant breach and adds a
    replica, a postmortem dump is triggered — and healthy traffic
    clears all of it."""
    async def main():
        c0 = _FakeClient("r0", stats={"slo_totals": dict(_WD_ZERO)})
        c1 = _FakeClient("r1", stats={"slo_totals": dict(_WD_ZERO)})
        fleet = FleetManager(
            [c0, c1],
            autoscale=AutoscaleConfig(min_replicas=1, max_replicas=2,
                                      upscale_delay_s=3.0),
            watchdog=WatchdogConfig(short_window_s=10.0,
                                    long_window_s=60.0,
                                    min_observations=5,
                                    page_burn_rate=2.0,
                                    warn_burn_rate=1.0))
        await fleet.autoscale_tick(now=0.0)
        assert not fleet.watchdog.paging
        assert not fleet.admission.brownout

        # 12 of 20 requests blow their TTFT target: burn 6x in both
        # windows -> page
        c0._stats = {"slo_totals": {**_WD_ZERO, "ttft_n": 20.0,
                                    "ttft_bad": 12.0,
                                    "ttft_s": 10.0}}
        await fleet.autoscale_tick(now=5.0)
        assert fleet.watchdog.paging
        assert fleet.admission.brownout              # shed early
        status = await fleet.status()
        assert status["watchdog"]["paging"] is True
        assert status["watchdog"]["state"]["ttft"] == "page"
        assert status["admission"]["brownout"] is True
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "slo_alert" in kinds and "brownout_on" in kinds
        # the page also black-boxed the fleet (FakeClients error out
        # of debug_dump, but the trigger breadcrumb must land)
        if fleet._page_dump_task is not None:
            await fleet._page_dump_task
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "postmortem_dump" in kinds

        # the page is an instant breach: sustained past the upscale
        # delay it adds the standby replica PRE-emptively
        target = await fleet.autoscale_tick(now=9.0)
        assert target == 2
        assert fleet.replicas["r1"].status == ACTIVE
        assert fleet.autoscaler.last_decision["slo_page"] is True

        # healthy traffic cools the short window: page clears,
        # brownout releases
        c0._stats = {"slo_totals": {**_WD_ZERO, "ttft_n": 140.0,
                                    "ttft_bad": 12.0,
                                    "ttft_s": 11.0}}
        await fleet.autoscale_tick(now=20.0)
        assert not fleet.watchdog.paging
        assert not fleet.admission.brownout
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "slo_clear" in kinds and "brownout_off" in kinds
    asyncio.run(main())


def test_e2e_guard_violation_bundle_fetchable_via_fleet(tmp_path):
    """Acceptance: a forced guard violation on a replica produces a
    postmortem bundle fetchable through the fleet surface, and
    POST /debug/dump snapshots on demand."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.util.jax_guard import GuardViolation, dispatch_guard

    srv = LLMServerImpl({
        "model_id": "bbm", "model_source": "debug",
        "engine_kwargs": dict(
            max_batch_size=2, page_size=8, num_pages=64,
            metrics_model_id=f"bb{uuid.uuid4().hex[:8]}",
            blackbox_dir=str(tmp_path / "bb"))})
    with pytest.raises(GuardViolation):
        with dispatch_guard(max_compiles=0,
                            recorder=srv.engine.telemetry.recorder):
            jax.jit(lambda x: x - 3)(jnp.arange(5.0))

    fleet = FleetManager([LocalReplicaClient("r0", srv)])

    async def main():
        listing = await fleet.replicas["r0"].client.call(
            "debug_bundles")
        assert listing, "guard violation produced no bundle"
        assert listing[-1]["cause"] == "guard_violation"
        bundle = await fleet.replicas["r0"].client.call(
            "debug_bundle", listing[-1]["id"])
        assert bundle["cause"] == "guard_violation"
        assert bundle["alert_event"]["event"] == "guard_violation"
        assert "metrics_exposition" in bundle
        # unknown id -> None (the ingress turns this into a 404)
        assert await fleet.replicas["r0"].client.call(
            "debug_bundle", "nope") is None
        # POST /debug/dump: on-demand snapshot adds a second bundle
        out = await fleet.debug_dump_all("manual_probe")
        assert out["r0"]["bundle"]
        return await fleet.replicas["r0"].client.call("debug_bundles")

    listing = asyncio.run(main())
    assert len(listing) == 2
    assert listing[-1]["cause"] == "manual_probe"
    kinds = [e["event"] for e in fleet.recorder.events()]
    assert "postmortem_dump" in kinds


# --------------------------------- e2e: fleet app through serve.run

def test_fleet_app_local_testing_mode(fleet_servers):
    """The full wiring — FleetConfig -> build_llm_fleet_app ->
    serve.run(local_testing_mode=True) -> ingress __call__ — serves
    completions, /fleet, and /metrics through deployment handles
    (in-process replicas, shared-registry scrape path)."""
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.serve._private.proxy import Request
    from ray_tpu.serve.llm import FleetConfig, build_llm_fleet_app

    tag = f"fleetapp{uuid.uuid4().hex[:8]}"
    app = build_llm_fleet_app(FleetConfig(
        llm_config=LLMConfig(
            model_id="mf", model_source="debug",
            engine_kwargs=dict(max_batch_size=4, page_size=8,
                               num_pages=96, seed=7,
                               metrics_model_id=tag)),
        min_replicas=2, max_replicas=2,
        admission=AdmissionConfig(max_concurrent=4, max_queue=8)))
    try:
        h = serve.run(app, name="fleet-local", local_testing_mode=True)

        def req(method, path, body=b""):
            return Request(method, path, {}, {}, body)

        out = h.remote(req(
            "POST", "/v1/completions",
            json.dumps({"prompt": "hello fleet",
                        "max_tokens": 3}).encode())).result(
                timeout_s=180)
        assert out["object"] == "text_completion"
        assert out["choices"][0]["finish_reason"] is not None

        models = h.remote(req("GET", "/v1/models")).result(timeout_s=30)
        assert models["data"][0]["id"] == "mf"

        fl = h.remote(req("GET", "/fleet")).result(timeout_s=30)
        assert set(fl["replicas"]) == {"r0", "r1"}
        assert fl["admission"]["admitted"] >= 1
        assert fl["autoscale"]["active"] == 2

        m = h.remote(req("GET", "/metrics")).result(timeout_s=30)
        assert m.status == 200
        assert f'model="{tag}"' in m.body

        # ISSUE 7 surface through the ingress: merged fleet trace
        # (ingress spans + replica lifecycles), merged flight
        # recorders, on-demand black-box dump, bundle listing
        tr = h.remote(req("GET", "/fleet/debug/trace")).result(
            timeout_s=60)
        names = {e["name"] for e in tr["traceEvents"]}
        assert {"fleet_request", "routing_decision"} <= names
        assert tr["metadata"]["ingress"]["buffer"]["events"] > 0
        rid_q = next(e["args"]["request_id"]
                     for e in tr["traceEvents"]
                     if e["name"] == "fleet_request")
        filt = h.remote(Request(
            "GET", "/fleet/debug/trace", {"request_id": rid_q}, {},
            b"")).result(timeout_s=60)
        assert filt["traceEvents"] and all(
            e["args"]["request_id"] == rid_q
            for e in filt["traceEvents"] if e.get("ph") != "M")

        ev = h.remote(req("GET", "/fleet/debug/events")).result(
            timeout_s=60)
        assert ev["object"] == "events"
        assert any(e["replica"] == "r0" for e in ev["events"])

        dmp = h.remote(req(
            "POST", "/debug/dump",
            json.dumps({"cause": "apptest"}).encode())).result(
                timeout_s=60)
        assert set(dmp["replicas"]) == {"r0", "r1"}
        assert all(v.get("bundle") for v in dmp["replicas"].values())

        bl = h.remote(req("GET", "/fleet/debug/bundles")).result(
            timeout_s=60)
        assert set(bl["replicas"]) == {"r0", "r1"}
        assert bl["replicas"]["r0"][-1]["cause"] == "apptest"
        one = h.remote(Request(
            "GET", "/fleet/debug/bundles",
            {"replica": "r0", "id": bl["replicas"]["r0"][-1]["id"]},
            {}, b"")).result(timeout_s=60)
        assert one["cause"] == "apptest"

        missing = h.remote(req("GET", "/no/such")).result(timeout_s=30)
        assert missing.status == 404

        bad = h.remote(req(
            "POST", "/v1/completions",
            json.dumps({"model": "nope", "prompt": "x"}).encode())
        ).result(timeout_s=30)
        assert bad.status == 404
    finally:
        serve.shutdown()


# ----------------------------- failure plane (ISSUE 9): unit layers

def test_circuit_breaker_state_machine():
    """closed -> open after consecutive probe failures (with eviction
    signal), cooldown -> half-open, successes close, a half-open
    failure re-opens with a backed-off cooldown."""
    cfg = HealthConfig(probe_failures=3, open_cooldown_s=1.0,
                       cooldown_backoff=2.0, max_cooldown_s=30.0,
                       half_open_probes=2)
    b = CircuitBreaker(cfg)
    assert b.state == "closed" and b.gauge() == 0
    assert not b.record_failure(now=0.0)
    assert not b.record_failure(now=0.1)
    assert b.record_failure(now=0.2)          # 3rd opens
    assert b.state == "open" and b.gauge() == 1 and b.trips == 1
    # inside the cooldown: no probes
    assert not b.should_probe(now=0.5)
    assert b.state == "open"
    # past it: half-open, probes admitted
    assert b.should_probe(now=1.3)
    assert b.state == "half_open" and b.gauge() == 2
    # one success isn't enough; the second closes
    assert not b.record_success()
    assert b.state == "half_open"
    assert b.record_success()
    assert b.state == "closed" and b.failures == 0
    # a hard failure (dispatch error) trips instantly from closed
    assert b.record_failure(now=2.0, hard=True)
    assert b.trips == 2
    assert b.cooldown_s() == pytest.approx(2.0)   # backed off
    assert b.should_probe(now=4.1)
    assert b.state == "half_open"
    # a half-open failure re-opens and backs off further
    assert b.record_failure(now=4.2)
    assert b.state == "open" and b.trips == 3
    assert b.cooldown_s() == pytest.approx(4.0)
    # a success once half-open again starts the count fresh
    assert b.should_probe(now=8.3)
    assert not b.record_success()
    assert b.record_success()
    assert b.state == "closed"


def test_chaos_schedule_fires_deterministically():
    """The harness contract: faults fire at exact per-method call
    indices, `count` times, and the fired log records them — the same
    schedule replays the same failure sequence every run."""
    async def main():
        sched = ChaosSchedule(seed=5)
        sched.fail_calls(method="completions", at_call=1, count=2)
        sched.timeout_probes(count=1)
        client = ChaosReplicaClient(_FakeClient("r0"), sched)
        assert client.replica_id == "r0"
        # call 0 passes, calls 1+2 raise, call 3 passes again
        with pytest.raises(AttributeError):
            await client.call("completions")   # fake has no method:
        for _ in range(2):                     # reaches the fake = pass
            with pytest.raises(Exception) as ei:
                await client.call("completions")
            assert "chaos" in str(ei.value)
        with pytest.raises(AttributeError):
            await client.call("completions")
        # fleet_stats: first probe times out, then flows again
        with pytest.raises(asyncio.TimeoutError):
            await client.call("fleet_stats")
        out = await client.call("fleet_stats")
        assert out["replica"] == "r0"
        kinds = [f["kind"] for f in sched.fired]
        assert kinds == ["call_error", "call_error", "probe_timeout"]
        assert [f["call"] for f in sched.fired] == [1, 2, 0]
    asyncio.run(main())


def test_chaos_severed_stream_closes_inner_generator():
    """A severed stream must close the replica-side generator (so the
    server aborts the engine request like a real disconnect) and then
    raise StreamSevered into the consumer."""
    closed = {"v": False}

    class StreamFake(_FakeClient):
        def stream(self, method, body):
            async def gen():
                try:
                    for i in range(10):
                        yield {"i": i, "toks": [i]}
                finally:
                    closed["v"] = True
            return gen()

    async def main():
        sched = ChaosSchedule().sever_stream(after_chunks=3)
        client = ChaosReplicaClient(StreamFake("r0"), sched)
        got = []
        with pytest.raises(StreamSevered):
            async for c in client.stream("completions_stream_tokens",
                                         {}):
                got.append(c["i"])
        assert got == [0, 1, 2]
        assert closed["v"], "inner stream generator was not closed"
    asyncio.run(main())


def test_chaos_wildcard_sever_waits_for_a_stream():
    """A wildcard-method stream_sever must NOT be consumed by the
    next unary call (e.g. a fleet_stats probe) — it waits for an
    actual stream; probe_timeout conversely never fires on streams."""

    class StreamFake(_FakeClient):
        def stream(self, method, body):
            async def gen():
                for i in range(5):
                    yield {"i": i, "toks": [i]}
            return gen()

    async def main():
        sched = ChaosSchedule().sever_stream(after_chunks=1)
        client = ChaosReplicaClient(StreamFake("r0"), sched)
        out = await client.call("fleet_stats")   # unary: not eaten
        assert out["replica"] == "r0"
        assert not sched.fired
        got = []
        with pytest.raises(StreamSevered):
            async for c in client.stream("completions_stream_tokens",
                                         {}):
                got.append(c["i"])
        assert got == [0]
        assert [f["kind"] for f in sched.fired] == ["stream_sever"]
    asyncio.run(main())


def test_ingress_relay_terminates_sse_on_exhausted_failover(
        fleet_servers):
    """When the failover budget runs out (every replica severs every
    stream), the ingress must still END the SSE stream per the
    convention — an error event then [DONE] — never a silent
    truncation the client can't tell from a transport blip."""
    from ray_tpu.serve.llm.deployment import LLMFleetIngressImpl

    schedules = {rid: ChaosSchedule() for rid in fleet_servers}
    for s in schedules.values():
        s.sever_stream(after_chunks=1, count=-1)
    fleet = FleetManager(
        [ChaosReplicaClient(LocalReplicaClient(rid, srv),
                            schedules[rid])
         for rid, srv in fleet_servers.items()],
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        health=HealthConfig(max_failovers=1, open_cooldown_s=30.0),
        model_id="m")
    ingress = LLMFleetIngressImpl.__new__(LLMFleetIngressImpl)
    ingress.model_id = "m"
    ingress.fleet = fleet

    async def main():
        chunks = []
        async for c in ingress._relay(
                "completions_stream",
                {"prompt": "doomed stream", "max_tokens": 6}):
            chunks.append(c)
        await fleet.stop()
        _cancel_pumps(fleet_servers)
        return chunks

    chunks = asyncio.run(main())
    assert chunks[-1] == "data: [DONE]\n\n"
    docs = [json.loads(c[6:]) for c in chunks
            if c.strip() != "data: [DONE]"]
    assert any(d.get("error", {}).get("type") == "upstream_failure"
               for d in docs), chunks
    # tokens that made it out before the failure still framed cleanly
    assert any("choices" in d for d in docs)


def test_e2e_anomaly_capture_fetchable_via_fleet(no_compile_cache):
    """ISSUE 13 acceptance: an injected stall (forced recompile — a
    cold prefill bucket mid-steady-state) on one replica produces a
    CLASSIFIED tick_anomaly event, an auto-armed profile capture, and
    a black-box bundle fetchable at GET /fleet/debug/bundles; the
    anomaly rate rides the replica's snapshot into the /fleet row,
    and GET /fleet/debug/attribution merges both replicas' cost
    receipts."""
    from ray_tpu.llm._internal.engine import Request, SamplingParams
    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.serve.llm.deployment import LLMFleetIngressImpl

    tag = f"anomfleet{uuid.uuid4().hex[:8]}"
    servers = {}
    for rid in ("r0", "r1"):
        servers[rid] = LLMServerImpl({
            "model_id": "m", "model_source": "debug",
            "engine_kwargs": dict(
                # batch 4: one slot stays FREE during the steady warm
                # phase, so the injected long prompt admits (and its
                # cold-bucket recompile fires) immediately
                max_batch_size=4, page_size=8, num_pages=128, seed=7,
                max_prefill_tokens=16,
                metrics_model_id=tag, metrics_replica_id=rid,
                # fast warmup + no capture rate limits: the test
                # injects exactly one stall and wants its evidence (a
                # REAL compile, `no_compile_cache`, judged at z >= 3: why,
                # `test_forced_recompile_produces_classified_capture`)
                anomaly={"warmup_ticks": 16, "min_wall_ms": 0.0,
                         "z_threshold": 3.0,
                         "profile_min_interval_s": 0.0,
                         "dump_min_interval_s": 0.0}),
        })
    fleet = FleetManager(
        [LocalReplicaClient(rid, srv) for rid, srv in servers.items()],
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        model_id="m")
    ingress = LLMFleetIngressImpl.__new__(LLMFleetIngressImpl)
    ingress.model_id = "m"
    ingress.fleet = fleet

    # warm r0 into steady decode past the detector warmup, then
    # inject the stall: a prompt far past every warmed bucket forces
    # a recompile mid-steady-state
    eng = servers["r0"].engine
    rng = np.random.default_rng(5)
    for i in range(3):
        eng.add_request(Request(
            f"w{i}", rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=200), tenant="tenant-a"))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(40):
        eng.step()
    assert eng.anomaly.stats()["warmed"]
    eng.add_request(Request(
        "stall", rng.integers(2, 250, 60).tolist(),
        SamplingParams(max_tokens=4)))
    for _ in range(30):
        eng.step()
        if eng.anomaly.anomalies_total:
            break
    assert eng.anomaly.anomalies_total >= 1
    assert eng.anomaly.stats()["by_kind"].get("recompile", 0) >= 1
    armed = [e for e in eng.telemetry.recorder.events()
             if e["event"] == "profile_armed"
             and e.get("trigger") == "tick_anomaly"]
    assert armed, "profile capture was not auto-armed"
    # drive a little work on r1 too so the merged attribution doc has
    # both replicas' receipts
    eng1 = servers["r1"].engine
    eng1.add_request(Request("other", rng.integers(2, 250, 12).tolist(),
                             SamplingParams(max_tokens=4)))
    while eng1.has_work():
        eng1.step()

    async def main():
        await fleet.refresh()
        status = await fleet.status()
        bundles = await ingress._handle_get("/fleet/debug/bundles", {})
        r0_bundles = bundles["replicas"]["r0"]
        bid = next(b["id"] for b in r0_bundles
                   if b["cause"] == "tick_anomaly")
        bundle = await ingress._handle_get(
            "/fleet/debug/bundles", {"replica": "r0", "id": bid})
        events = await ingress._handle_get("/fleet/debug/events", {})
        attribution = await ingress._handle_get(
            "/fleet/debug/attribution", {})
        return status, bundle, events, attribution

    status, bundle, events, attribution = asyncio.run(main())
    # the anomaly rate rode ReplicaSnapshot into the /fleet row
    row = status["replicas"]["r0"]
    assert row["anomalies_total"] >= 1
    assert row["anomaly_rate"] > 0
    assert row["anomaly_last_kind"] == "recompile"
    assert status["replicas"]["r1"].get("anomalies_total", 0) == 0
    assert "anomaly_state" in status["watchdog"]
    # the fetched bundle IS the anomaly postmortem: the triggering
    # event AND the detector's stats both survive
    assert bundle["anomaly_event"]["kind"] == "recompile"
    assert bundle["anomaly_event"]["compile_delta"] >= 1
    assert bundle["anomaly"]["anomalies_total"] >= 1
    assert bundle["attribution"] is not None
    # the classified event surfaces in the merged fleet event stream
    kinds = [e["event"] for e in events["events"]]
    assert "tick_anomaly" in kinds
    ev = next(e for e in events["events"]
              if e["event"] == "tick_anomaly")
    assert ev["anomaly_kind"] == "recompile"
    assert ev["composition"]["dispatches"] >= 1
    # merged attribution: both replicas' receipts, one fleet top-K,
    # summed tenant rollups
    assert set(attribution["replicas"]) == {"r0", "r1"}
    assert attribution["top"], "no receipts in the merged doc"
    assert {r["replica"] for r in attribution["top"]} <= {"r0", "r1"}
    # the warm decodes are still LIVE: their receipts rank in the
    # merged top-K under their tenant; rollups count finished ones
    assert any(r["tenant"] == "tenant-a" for r in attribution["top"])
    # r1's finished request rolled up fleet-wide
    assert attribution["tenants"]["default"]["requests"] >= 1
    _cancel_pumps(servers)


def test_fleet_evicts_on_probe_failures_then_readmits():
    """The tentpole's health state machine on the refresh loop:
    3 consecutive probe timeouts evict the replica from the ring
    within the probe cycle that trips the breaker; past the cooldown,
    half-open probes re-admit it. The healthy replica's snapshot
    stays fresh throughout."""
    async def main():
        sched = ChaosSchedule().timeout_probes(count=3)
        chaotic = ChaosReplicaClient(_FakeClient("r1"), sched)
        fleet = FleetManager(
            [_FakeClient("r0"), chaotic],
            autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
            health=HealthConfig(probe_failures=3,
                                open_cooldown_s=0.05,
                                half_open_probes=2))
        base = sum(v for _, v in
                   fleet.metrics["evictions"]._samples())
        await fleet.refresh()
        await fleet.refresh()
        assert fleet.replicas["r1"].status == ACTIVE     # not yet
        await fleet.refresh()                            # 3rd failure
        assert fleet.replicas["r1"].status == UNHEALTHY
        assert fleet.router.ring.nodes() == ["r0"]
        assert fleet.replicas["r1"].breaker.state == "open"
        assert sum(v for _, v in
                   fleet.metrics["evictions"]._samples()) == base + 1
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "replica_evicted" in kinds
        evs = [e["event"] for e in fleet._scale_events]
        assert "evict" in evs
        # healthy replica kept refreshing: snapshot is fresh
        assert fleet.replicas["r0"].snapshot is not None
        assert fleet.replicas["r0"].snapshot.age_s() < 5.0

        # inside the cooldown the dead replica is left alone
        calls_before = sched.stats()["calls"]["fleet_stats"]
        await fleet.refresh()
        assert sched.stats()["calls"]["fleet_stats"] == calls_before
        assert fleet.replicas["r1"].status == UNHEALTHY

        # past the cooldown: half-open probes (now healthy) re-admit
        # after half_open_probes consecutive successes
        await asyncio.sleep(0.06)
        await fleet.refresh()                  # success 1: half-open
        assert fleet.replicas["r1"].status == UNHEALTHY
        assert fleet.replicas["r1"].breaker.state == "half_open"
        await fleet.refresh()                  # success 2: closed
        assert fleet.replicas["r1"].status == ACTIVE
        assert fleet.replicas["r1"].breaker.state == "closed"
        assert fleet.router.ring.nodes() == ["r0", "r1"]
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "replica_readmitted" in kinds
        status = await fleet.status()
        assert status["replicas"]["r1"]["breaker"]["trips"] == 1
        await asyncio.sleep(0)                 # drain the dump task
    asyncio.run(main())


def test_request_faults_do_not_trip_the_breaker():
    """A malformed REQUEST (replica raises ValueError/TypeError —
    bad sampling params, unknown adapter) must neither evict the
    healthy replica nor burn failover retries: one poisoned body must
    not walk the ring evicting replicas."""

    class BadRequestClient(_FakeClient):
        async def call(self, method, *args):
            if method == "completions":
                raise ValueError("unknown model 'nope'")
            return await super().call(method, *args)

    async def main():
        fleet = FleetManager(
            [BadRequestClient("r0"), BadRequestClient("r1")],
            autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
            health=HealthConfig())
        with pytest.raises(ValueError):
            await fleet.dispatch("completions", {"prompt": "x"})
        for rid in ("r0", "r1"):
            assert fleet.replicas[rid].status == ACTIVE
            assert fleet.replicas[rid].breaker.state == "closed"
        assert sorted(fleet.router.ring.nodes()) == ["r0", "r1"]
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "failover" not in kinds and "replica_evicted" not in kinds
    asyncio.run(main())


def test_evicting_sole_active_replica_activates_a_standby():
    """With spare capacity parked on STANDBY, the sole active
    replica's death must not defer into a dead-replica-serves-all
    outage: a standby is activated as the replacement, THEN the dead
    one is evicted."""
    async def main():
        sched = ChaosSchedule().timeout_probes(count=1)
        fleet = FleetManager(
            [ChaosReplicaClient(_FakeClient("r0"), sched),
             _FakeClient("r1")],
            autoscale=AutoscaleConfig(min_replicas=1, max_replicas=2),
            health=HealthConfig(probe_failures=1))
        assert fleet.replicas["r1"].status == STANDBY
        await fleet.refresh()
        assert fleet.replicas["r0"].status == UNHEALTHY
        assert fleet.replicas["r1"].status == ACTIVE
        assert fleet.router.ring.nodes() == ["r1"]
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "failover_activate" in kinds
        await asyncio.sleep(0)         # drain the eviction dump task
    asyncio.run(main())


def test_deadline_sheds_do_not_feed_autoscaler_overload():
    """A deadline shed is the client's budget spent, not fleet
    overload: it must not count into shed_total (the autoscaler's
    strongest scale-up trigger would otherwise pin an idle fleet at
    max on expired-deadline traffic)."""
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=4, queue_wait_slo_s=5.0))
        for _ in range(3):
            with pytest.raises(AdmissionRejected):
                await adm.acquire("t", deadline=time.monotonic() - 1.0)
        assert adm.rejected["deadline"] == 3
        assert adm.shed_total == 0
    asyncio.run(main())


def test_unhealthy_replicas_stay_in_observability_fanouts():
    """An evicted replica must not vanish from /metrics and
    postmortem dumps mid-incident — that is exactly when its data is
    wanted (a dead one degrades to an error row under the timeout)."""
    async def main():
        sched = ChaosSchedule().timeout_probes(count=1)
        chaotic = ChaosReplicaClient(_FakeClient("r1"), sched)
        fleet = FleetManager(
            [_FakeClient("r0"), chaotic],
            autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
            health=HealthConfig(probe_failures=1,
                                open_cooldown_s=300.0))
        await fleet.refresh()
        assert fleet.replicas["r1"].status == UNHEALTHY
        await fleet.metrics_text()
        assert "metrics_text" in chaotic.inner.calls
        await fleet.debug_dump_all("probe")
        assert "debug_dump" in chaotic.inner.calls
    asyncio.run(main())


def test_fleet_never_evicts_last_active_replica():
    """A false-positive eviction of the ONLY active replica would turn
    an incident into a blackout: the breaker still opens (recovery
    stays gated on half-open probes) but the replica keeps its ring
    slot."""
    async def main():
        sched = ChaosSchedule().timeout_probes(count=1)
        fleet = FleetManager(
            [ChaosReplicaClient(_FakeClient("r0"), sched)],
            health=HealthConfig(probe_failures=1))
        await fleet.refresh()
        assert fleet.replicas["r0"].breaker.state == "open"
        assert fleet.replicas["r0"].status == ACTIVE
        assert fleet.router.ring.nodes() == ["r0"]
        kinds = [e["event"] for e in fleet.recorder.events()]
        assert "eviction_deferred" in kinds
    asyncio.run(main())


def test_router_deprioritizes_stale_snapshots():
    """ISSUE 9 satellite: a snapshot past snapshot_stale_s (its
    replica's probes keep failing) is treated as saturated by the
    affinity walk (spill to a replica with real numbers) and carries
    a flat score penalty in the all-saturated fallback."""
    cfg = RouterConfig(vnodes=16, snapshot_stale_s=0.5)
    r = FleetRouter(cfg)
    r.set_replicas(["r0", "r1"])
    fp = prefix_fingerprint({"prompt": "stale probe " * 10})
    primary, second = r.ring.preferred(fp)[:2]
    fresh = {rid: _snap(rid) for rid in ("r0", "r1")}
    assert r.pick(fp, fresh, {}) == primary
    stale = dict(fresh)
    stale[primary] = ReplicaSnapshot(
        replica=primary, mono_ts=time.monotonic() - 5.0)
    rid, outcome = r.pick_ex(fp, stale, {})
    assert rid == second and outcome == "spill"
    # scored fallback: staleness costs w_stale
    s_fresh = r.score(_snap("x"), 0)
    s_stale = r.score(ReplicaSnapshot(
        replica="x", mono_ts=time.monotonic() - 5.0), 0)
    assert s_stale == pytest.approx(s_fresh + cfg.w_stale)
    # fleet status surfaces the age
    assert stale[primary].age_s() > 4.0


def test_admission_deadline_sheds_before_queueing_and_in_queue():
    """ISSUE 9 deadline propagation, admission half: an
    already-expired request sheds instantly (reason "deadline"), and
    a queued request whose deadline lands before the queue-wait SLO
    sheds at the deadline, not the SLO."""
    async def main():
        adm = AdmissionController(AdmissionConfig(
            max_concurrent=1, max_queue=4, queue_wait_slo_s=5.0))
        # expired on arrival: zero work, instant shed
        with pytest.raises(AdmissionRejected) as ei:
            await adm.acquire("t", deadline=time.monotonic() - 1.0)
        assert ei.value.reason == "deadline"
        assert adm.rejected["deadline"] == 1
        # queued past its own (short) deadline: shed at the deadline
        await adm.acquire("hog")
        t0 = time.monotonic()
        with pytest.raises(AdmissionRejected) as ei:
            await adm.acquire("t", deadline=time.monotonic() + 0.1)
        waited = time.monotonic() - t0
        assert ei.value.reason == "deadline"
        assert waited < 1.0                  # the 5s SLO did NOT gate
        assert adm.rejected["deadline"] == 2
        adm.release()
    asyncio.run(main())


# ------------------------------- failure plane (ISSUE 9): chaos e2e

def _sse_transcript(chunks):
    """Parse fleet SSE chunks -> (token_ids, text, finish_reason);
    asserts exactly one finish."""
    toks, text, reasons = [], "", []
    for c in chunks:
        payload = c[len("data: "):].strip()
        if payload == "[DONE]":
            continue
        d = json.loads(payload)
        ch = d["choices"][0]
        toks += ch.get("token_ids") or []
        text += ch.get("text") or ch.get("delta", {}).get("content", "") or ""
        if ch["finish_reason"] is not None:
            reasons.append(ch["finish_reason"])
    assert len(reasons) == 1, reasons
    return toks, text, reasons[0]


def _chaos_fleet(servers, victim, after_chunks, **over):
    """Fleet over the shared servers with a chaos wrapper per replica;
    the victim's next token stream is severed after `after_chunks`."""
    schedules = {rid: ChaosSchedule(seed=11) for rid in servers}
    schedules[victim].sever_stream(
        after_chunks=after_chunks, method="completions_stream_tokens")
    kw = dict(
        router=RouterConfig(prefix_depth=64, spill_waiting=64),
        admission=AdmissionConfig(max_concurrent=8, max_queue=16,
                                  queue_wait_slo_s=30.0),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        health=HealthConfig(open_cooldown_s=30.0), model_id="m")
    kw.update(over)
    fleet = FleetManager(
        [ChaosReplicaClient(LocalReplicaClient(rid, srv),
                            schedules[rid])
         for rid, srv in servers.items()], **kw)
    return fleet, schedules


def _prompt_routed_to(fleet, rid, salt=""):
    i = 0
    while True:
        p = f"chaos stream probe {salt}{i}"
        if fleet.router.ring.preferred(
                prefix_fingerprint({"prompt": p}, 64))[0] == rid:
            return p
        i += 1


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_e2e_mid_stream_failover_token_exact(fleet_servers, sampled):
    """THE acceptance gate: a replica severed mid-stream (2 chunks
    delivered, more tokens in flight) is evicted from the ring, and
    the client stream still completes with a transcript token-exact
    vs a fresh single-replica oracle — greedy AND seeded-sampled —
    with exactly-once delivery and one finish."""
    gen = 12
    victim = "r0"
    fleet, schedules = _chaos_fleet(fleet_servers, victim,
                                    after_chunks=2)
    prompt = _prompt_routed_to(fleet, victim,
                               "S" if sampled else "G")
    body = {"prompt": prompt, "max_tokens": gen}
    if sampled:
        body.update(temperature=0.8, top_p=0.9, seed=4242)
    fo_base = sum(v for _, v in
                  fleet.metrics["failovers"]._samples())

    async def main():
        chunks = []
        async for c in fleet.dispatch_stream("completions_stream",
                                             dict(body)):
            chunks.append(c)
        # post-failover: the fleet still serves (survivor takes all)
        out = await fleet.dispatch(
            "completions", {"prompt": "after failover", "max_tokens": 2})
        assert out["choices"][0]["finish_reason"] is not None
        _cancel_pumps(fleet_servers)
        return chunks

    chunks = asyncio.run(main())
    toks, _, reason = _sse_transcript(chunks)
    assert reason in ("length", "stop")
    # the sever actually fired and the failover plane reacted
    assert [f["kind"] for f in schedules[victim].fired] \
        == ["stream_sever"]
    assert fleet.replicas[victim].status == UNHEALTHY
    assert fleet.router.ring.nodes() == ["r1"]
    kinds = [e["event"] for e in fleet.recorder.events()]
    assert "failover" in kinds and "replica_evicted" in kinds
    assert sum(v for _, v in
               fleet.metrics["failovers"]._samples()) == fo_base + 1

    # token-exact vs a fresh single-replica oracle (same weights seed)
    oracle = _make_server("oracle", f"oracle{uuid.uuid4().hex[:6]}")

    async def oracle_main():
        out = []
        async for c in oracle.completions_stream_tokens(dict(body)):
            out.append(c)
        _cancel_pumps({"oracle": oracle})
        return [t for c in out for t in c["toks"]]

    want = asyncio.run(oracle_main())
    assert len(want) == gen
    assert toks == want, (
        "failover transcript diverged from the single-replica oracle")


def test_e2e_hung_replica_stall_watchdog_fails_over(fleet_servers):
    """The ISSUE 9 motivating case the probes alone can't save a
    client from: a replica that HANGS mid-stream (no raise, no
    end-of-stream). The relay's stall watchdog detects the silence,
    fails over, and the transcript is still token-exact.

    The watchdog is a one-second timer, and the only silence it may
    meet is the hung replica's: a first call of a program compiles for
    longer than that on a loaded machine, on the victim ahead of the
    hang or on the survivor after it, and the watchdog then evicted a
    healthy replica. So the scenario is walked once before it counts,
    with the stream SEVERED at the same chunk (a failover that waits on
    no timer) under the default, generous watchdog, on a prompt of the
    same length that shares no cached page with the real one: both
    replicas have built every program the timed pass meets."""
    gen = 10
    victim = "r1"

    def chaos_fleet(fault, **health):
        schedules = {rid: ChaosSchedule() for rid in fleet_servers}
        getattr(schedules[victim], fault)(
            after_chunks=2, method="completions_stream_tokens")
        return schedules, FleetManager(
            [ChaosReplicaClient(LocalReplicaClient(rid, srv),
                                schedules[rid])
             for rid, srv in fleet_servers.items()],
            router=RouterConfig(prefix_depth=64, spill_waiting=64),
            autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
            health=HealthConfig(open_cooldown_s=300.0, **health),
            model_id="m")

    def routed_to_victim(fleet, salt):
        # the salt leads, so two salts share no page of the prefix
        # cache; the counter's width is fixed, so they share a length
        return next(
            p for p in (f"{salt}{i:03d} hung replica probe"
                        for i in range(1000))
            if fleet.router.ring.preferred(
                prefix_fingerprint({"prompt": p}, 64))[0] == victim)

    async def stream(fleet, body):
        chunks = []
        async for c in fleet.dispatch_stream("completions_stream",
                                             dict(body)):
            chunks.append(c)
        _cancel_pumps(fleet_servers)
        return chunks

    rehearsal, fleet = chaos_fleet("sever_stream")
    asyncio.run(stream(fleet, {
        "prompt": routed_to_victim(fleet, "W"), "max_tokens": gen}))
    assert [f["kind"] for f in rehearsal[victim].fired] \
        == ["stream_sever"]

    schedules, fleet = chaos_fleet("stall_stream",
                                   stream_stall_timeout_s=1.0)
    body = {"prompt": routed_to_victim(fleet, "H"), "max_tokens": gen}
    built = {rid: srv.engine.compiles
             for rid, srv in fleet_servers.items()}
    chunks = asyncio.run(stream(fleet, body))
    # the rehearsal was one: the timed pass built no program
    assert built == {rid: srv.engine.compiles
                     for rid, srv in fleet_servers.items()}
    toks, _, reason = _sse_transcript(chunks)
    assert reason in ("length", "stop")
    assert len(toks) == gen
    assert [f["kind"] for f in schedules[victim].fired] \
        == ["stream_stall"]
    assert fleet.replicas[victim].status == UNHEALTHY
    kinds = [e["event"] for e in fleet.recorder.events()]
    assert "failover" in kinds
    # the failover classified the stall, not a generic timeout
    fo = next(e for e in fleet.recorder.events()
              if e["event"] == "failover")
    assert "StreamStalled" in fo["error"]

    # token-exact vs the oracle despite the hang
    oracle = _make_server("oracle", f"oracle{uuid.uuid4().hex[:6]}")

    async def oracle_main():
        out = []
        async for c in oracle.completions_stream_tokens(dict(body)):
            out.append(c)
        _cancel_pumps({"oracle": oracle})
        return [t for c in out for t in c["toks"]]

    assert toks == asyncio.run(oracle_main())


def test_e2e_unary_hung_replica_bounded_by_deadline(fleet_servers):
    """A hung replica must not strand a deadline-carrying UNARY
    request (and its admission slot) forever: the ingress bounds the
    await at remaining-deadline + grace, the timeout counts SOFTLY
    toward the breaker (a tight client deadline must not evict a
    healthy-but-slow replica outright), and the retry lands on a
    healthy replica which sheds the expired request cleanly
    (finish_reason="deadline")."""
    schedules = {rid: ChaosSchedule() for rid in fleet_servers}
    fleet = FleetManager(
        [ChaosReplicaClient(LocalReplicaClient(rid, srv),
                            schedules[rid])
         for rid, srv in fleet_servers.items()],
        router=RouterConfig(prefix_depth=64, spill_waiting=64),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        health=HealthConfig(open_cooldown_s=300.0,
                            unary_deadline_grace_s=1.0),
        model_id="m")
    victim = "r0"
    prompt = _prompt_routed_to(fleet, victim, "U")
    schedules[victim].slow_calls(60.0, method="completions")

    async def main():
        t0 = time.monotonic()
        out = await fleet.dispatch(
            "completions", {"prompt": prompt, "max_tokens": 4,
                            "deadline_s": 0.3})
        dt = time.monotonic() - t0
        _cancel_pumps(fleet_servers)
        return out, dt

    out, dt = asyncio.run(main())
    assert out["choices"][0]["finish_reason"] == "deadline"
    assert dt < 10.0, dt                 # bounded, not the 60s hang
    # soft evidence: counted toward the threshold, not an instant
    # eviction — one tight deadline must not cost a ring slot
    assert fleet.replicas[victim].status == ACTIVE
    assert fleet.replicas[victim].breaker.failures >= 1
    kinds = [e["event"] for e in fleet.recorder.events()]
    assert "failover" in kinds


def test_e2e_deadline_propagation_through_fleet(fleet_servers):
    """ISSUE 9 deadline acceptance: an expired deadline sheds at the
    front door (zero engine work, counted per stage), and a live one
    rides the body into the engine, which aborts the stream at a fold
    boundary with finish_reason="deadline"."""
    fleet = _fleet_over(fleet_servers)

    def shed_count(stage):
        return sum(v for tags, v in
                   fleet.metrics["deadline_sheds"]._samples()
                   if tags.get("stage") == stage)

    adm0, eng0 = shed_count("admission"), shed_count("engine")

    async def main():
        with pytest.raises(AdmissionRejected) as ei:
            await fleet.dispatch(
                "completions",
                {"prompt": "already dead", "max_tokens": 2,
                 "deadline_s": -1.0})
        assert ei.value.reason == "deadline"

        # mid-generation expiry: way too many tokens for the budget
        chunks = []
        async for c in fleet.dispatch_stream(
                "completions_stream",
                {"prompt": "deadline stream probe", "max_tokens": 200,
                 "deadline_s": 0.2}):
            chunks.append(c)
        # unary path reports the deadline finish too (same prompt:
        # its greedy sequence provably runs past the deadline
        # without hitting a stop token)
        out = await fleet.dispatch(
            "completions",
            {"prompt": "deadline stream probe", "max_tokens": 200,
             "deadline_s": 0.2})
        _cancel_pumps(fleet_servers)
        return chunks, out

    chunks, out = asyncio.run(main())
    toks, _, reason = _sse_transcript(chunks)
    assert reason == "deadline"
    assert len(toks) < 200
    assert out["choices"][0]["finish_reason"] == "deadline"
    assert shed_count("admission") == adm0 + 1
    assert shed_count("engine") >= eng0 + 2
    # the replica recorded the engine-side abort
    kinds = [e["event"]
             for srv in fleet_servers.values()
             for e in srv.engine.telemetry.recorder.events()]
    assert "deadline_abort" in kinds


def test_e2e_dispatch_discipline_with_chaos_wrapper(fleet_servers):
    """ISSUE 9 acceptance: failure handling adds ZERO device work.
    With the chaos wrapper installed and a mid-stream failover
    already served, each replica's engine still measures 16
    consecutive steady-state decode ticks = 16 dispatches, 0 h2d
    transfers, 0 new compiles under the armed runtime guard."""
    from ray_tpu.llm._internal.engine import Request, SamplingParams
    from ray_tpu.util.jax_guard import dispatch_guard

    fleet, schedules = _chaos_fleet(fleet_servers, "r1",
                                    after_chunks=1)
    prompt = _prompt_routed_to(fleet, "r1", "D")

    async def prime():
        chunks = []
        async for c in fleet.dispatch_stream(
                "completions_stream",
                {"prompt": prompt, "max_tokens": 6}):
            chunks.append(c)
        _cancel_pumps(fleet_servers)
        return chunks

    chunks = asyncio.run(prime())
    toks, _, _ = _sse_transcript(chunks)
    assert len(toks) == 6
    assert schedules["r1"].fired          # the failover really ran

    rng = np.random.default_rng(9)
    for rid, srv in fleet_servers.items():
        eng = srv.engine
        while eng.has_work():
            eng.step()
        rids = []
        for i in range(2):
            r = f"chaosguard-{rid}-{i}"
            rids.append(r)
            eng.add_request(Request(
                r, rng.integers(2, 250, 12).tolist(),
                SamplingParams(max_tokens=64, temperature=0.7,
                               top_p=0.9, seed=17 + i)))
        while eng.waiting or any(s.request is not None and not s.ready
                                 for s in eng.slots):
            eng.step()
        for _ in range(4):
            eng.step()
        comp0 = eng.stats()["jit_cache"]["compiled_programs"]
        disp0 = eng.dispatches
        with dispatch_guard() as rep:
            for _ in range(16):
                eng.step()
        assert eng.dispatches - disp0 == 16, rid
        assert rep.n_compiles == 0, rid
        assert eng.stats()["jit_cache"]["compiled_programs"] == comp0
        for r in rids:
            eng.abort(r)
        while eng.has_work():
            eng.step()


# ----------------------------------- process-spawning (slow) coverage

@pytest.mark.slow
def test_serve_status_replica_details_llm(ray_start):
    """Real controller path: serve.status() surfaces each LLM
    replica's health_detail (queue depth, KV occupancy, last-tick
    age) collected on the controller's metrics poll. Process-spawning
    and poll-cadence bound -> slow tier."""
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_llm_deployment

    app = build_llm_deployment(LLMConfig(
        model_id="m0", model_source="debug",
        engine_kwargs=dict(max_batch_size=4, page_size=8,
                           num_pages=96),
        deployment_config=dict(health_check_period_s=0.5)))
    try:
        serve.run(app, name="llm-status", _start_http=False,
                  timeout_s=180)
        deadline = time.time() + 60
        details = {}
        while time.time() < deadline:
            st = serve.status()
            dep = st["applications"]["llm-status"]["deployments"]
            details = next(iter(dep.values()))["replica_details"]
            if details:
                break
            time.sleep(0.5)
        assert details, "no replica_details after 60s of polling"
        row = next(iter(details.values()))
        assert {"waiting", "kv_occupancy", "last_tick_age_s",
                "active"} <= set(row)
    finally:
        serve.shutdown()
