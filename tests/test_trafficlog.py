"""Production traffic capture + deterministic replay (ISSUE 20).

Gates:
- capture wire format: versioned, per-segment crc32, end-segment
  record count — corruption/truncation anywhere raises a typed
  CaptureError/CaptureChecksumError, never a crash or a silently
  short replay;
- privacy by construction: capture bytes never contain prompt text
  (the only body readers on the path are `sampling_brief`'s numeric
  allowlist and the prefix fingerprint);
- the always-on recorder: bounded ring + armed-capture record/byte
  bounds, capture controls (start/mark/stop), BlackboxSpool
  retention;
- incremental event polling (satellite): FlightRecorder `since`
  cursor semantics across ring wraparound, `/fleet/debug/events
  ?since=` high-water marks, `/fleet/debug/traffic` GET/POST;
- deterministic replay: a fleet-recorded capture replays through the
  real-objects simulator byte-identically (same capture -> identical
  summary JSON) with recorded-vs-sim p99 TTFT and prefix-hit rate
  inside CALIBRATION_BAND;
- the recorder's metric families in both fleet topologies
  (shared-registry dedup and cross-process relabel);
- dispatch discipline: the steady-state guard holds with a capture
  armed and recording (1 dispatch/tick, 0 h2d, 0 compiles).
"""

import asyncio
import json
import uuid

import numpy as np
import pytest

from ray_tpu.llm._internal.server import LLMServerImpl, parse_since
from ray_tpu.llm._internal.telemetry import FlightRecorder
from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                               FleetManager, LocalReplicaClient,
                               RouterConfig, WatchdogConfig)
from ray_tpu.serve.llm.deployment import LLMFleetIngressImpl
from ray_tpu.serve.llm.trafficlog import (CaptureChecksumError,
                                          CaptureError,
                                          TrafficRecorder,
                                          decode_capture,
                                          decode_segment,
                                          encode_segment,
                                          load_capture,
                                          sampling_brief)

SECRET = "zanzibar marmalade heliotrope"   # the privacy tripwire


# ----------------------------------------------------- capture codec

def _capture_text(n=3, marks=("phase",)):
    rec = TrafficRecorder(capacity=64, model_id="codec")
    rec.start_capture("unit")
    for i in range(n):
        rec.record(t_mono=float(i), rid=f"r{i}", fp="ab" * 20,
                   prompt_tokens=4 + i, out_tokens=2,
                   tenant="t", lane="interactive", params={"seed": i},
                   outcome={"status": "ok"})
    for m in marks:
        rec.mark(m)
    rec.stop_capture()
    return rec.export()


def test_segment_roundtrip():
    doc = {"kind": "record", "seq": 1, "fp": "abc", "n": 2.5}
    assert decode_segment(encode_segment(doc)) == doc


def test_capture_roundtrip_structure():
    text = _capture_text(n=3, marks=("a", "b"))
    cap = decode_capture(text)
    assert cap["header"]["kind"] == "header"
    assert cap["header"]["version"] == 1
    assert cap["header"]["capture_id"]
    assert isinstance(cap["header"]["mono_anchor"], float)
    assert isinstance(cap["header"]["wall_anchor"], float)
    assert len(cap["records"]) == 3
    assert [m["label"] for m in cap["marks"]] == ["a", "b"]
    assert cap["end"]["records"] == 3
    # bytes in, same result out (the HTTP download path)
    assert decode_capture(text.encode()) == cap


def test_corrupted_checksum_is_typed_error():
    lines = _capture_text().splitlines()
    tag, crc, payload = lines[1].split(" ", 2)
    lines[1] = f"{tag} {crc} {payload.replace('record', 'recorp', 1)}"
    with pytest.raises(CaptureChecksumError, match="segment 2"):
        decode_capture("\n".join(lines))


def test_truncated_capture_is_typed_error():
    lines = _capture_text().splitlines()
    # no end segment: cut mid-write
    with pytest.raises(CaptureError, match="no end segment"):
        decode_capture("\n".join(lines[:-1]))
    # end survives but a record was lost: count mismatch
    with pytest.raises(CaptureError, match="end segment says"):
        decode_capture("\n".join(lines[:1] + lines[2:]))


def test_malformed_segments_are_typed_errors():
    good = _capture_text().splitlines()[0]
    with pytest.raises(CaptureError, match="empty"):
        decode_capture("   \n")
    with pytest.raises(CaptureError, match="malformed"):
        decode_capture("RTTC1 deadbeef")
    with pytest.raises(CaptureError, match="bad magic"):
        decode_capture("XTTC1 00000000 {}")
    with pytest.raises(CaptureError, match="version"):
        decode_capture(good.replace("RTTC1", "RTTC9", 1))
    with pytest.raises(CaptureError, match="not a capture header"):
        decode_capture(encode_segment({"kind": "record"}))
    with pytest.raises(CaptureError, match="bad JSON"):
        bad = "[1, 2"
        import zlib
        crc = f"{zlib.crc32(bad.encode()) & 0xFFFFFFFF:08x}"
        decode_capture(f"RTTC1 {crc} {bad}")
    with pytest.raises(CaptureError, match="not utf-8"):
        decode_capture(b"\xff\xfe RTTC")


def test_load_capture_io_and_roundtrip(tmp_path):
    with pytest.raises(CaptureError, match="cannot read"):
        load_capture(str(tmp_path / "missing.jsonl"))
    p = tmp_path / "cap.jsonl"
    p.write_text(_capture_text(n=2))
    assert len(load_capture(str(p))["records"]) == 2


def test_sampling_brief_numeric_allowlist():
    brief = sampling_brief({
        "prompt": SECRET, "messages": [{"content": SECRET}],
        "stop": [SECRET], "user": "tenant-a",
        "max_tokens": 32, "temperature": 0.7, "top_p": 0.9,
        "top_k": 40, "seed": 1234,
        "stream": True,                  # bool: excluded
        "echo": True,
        "logit_bias": {"5": 10.0},       # non-scalar: excluded
    })
    assert brief == {"max_tokens": 32, "temperature": 0.7,
                     "top_p": 0.9, "top_k": 40, "seed": 1234}


# -------------------------------------------------------- the recorder

def test_ring_bounds_and_tail_since():
    rec = TrafficRecorder(capacity=4, model_id="ring")
    seqs = [rec.record(t_mono=float(i), fp="") for i in range(10)]
    assert seqs == list(range(1, 11))
    st = rec.stats()
    assert st == {"records": 4, "total": 10, "dropped": 6,
                  "capture": None, "last_capture": None}
    assert [r["seq"] for r in rec.tail(64)] == [7, 8, 9, 10]
    assert [r["seq"] for r in rec.tail(2)] == [9, 10]
    # the cursor discipline: only records newer than `since`
    assert [r["seq"] for r in rec.tail(64, since=8)] == [9, 10]
    assert rec.tail(64, since=10) == []


def test_capture_bounds_and_control_misuse(tmp_path):
    rec = TrafficRecorder(capacity=64, model_id="bounds",
                          max_capture_records=2)
    with pytest.raises(CaptureError, match="no active capture"):
        rec.mark("x")
    with pytest.raises(CaptureError, match="no active capture"):
        rec.stop_capture()
    with pytest.raises(CaptureError, match="no sealed capture"):
        rec.export()
    out = rec.start_capture("bounded")
    with pytest.raises(CaptureError, match="already active"):
        rec.start_capture("again")
    for i in range(5):
        rec.record(t_mono=float(i), fp="")
    st = rec.stats()
    assert st["capture"]["capture_id"] == out["capture_id"]
    assert st["capture"]["records"] == 2      # bound enforced
    assert st["capture"]["dropped"] == 3      # overage counted
    sealed = rec.stop_capture()
    assert sealed["records"] == 2 and sealed["dropped"] == 3
    assert sealed["spool_id"] is None         # no spool configured
    cap = decode_capture(rec.export())
    assert len(cap["records"]) == 2
    assert cap["end"]["dropped"] == 3
    # the ring kept everything the capture dropped
    assert rec.stats()["records"] == 5
    assert rec.stats()["last_capture"]["records"] == 2


def test_sealed_captures_spool_to_disk(tmp_path):
    rec = TrafficRecorder(capacity=16, model_id="spool",
                          spool_dir=str(tmp_path / "spool"))
    rec.start_capture("spooled")
    rec.record(t_mono=0.0, fp="")
    sealed = rec.stop_capture()
    assert sealed["spool_id"] is not None
    bundle = rec.spool.read(sealed["spool_id"])
    assert bundle["cause"] == "traffic-" + sealed["capture_id"]
    assert bundle["capture_id"] == sealed["capture_id"]
    # the spooled text IS the replayable artifact
    assert len(decode_capture(bundle["capture"])["records"]) == 1


# ------------------------------ incremental event cursors (satellite)

def test_parse_since_degrades_to_none():
    assert parse_since(None) is None
    assert parse_since("") is None
    assert parse_since("drop table") is None
    assert parse_since("12.5") is None
    assert parse_since("12") == 12
    assert parse_since(7) == 7


def test_flight_recorder_since_cursor_across_wraparound():
    """The satellite-1 regression: cursors are seq-based, so a poll
    loop never re-reads events it has seen, and a cursor that has
    fallen off the ring (reader slower than the wrap) degrades to
    'everything resident' — no gap is silently invented."""
    rec = FlightRecorder(capacity=4)
    for i in range(3):
        rec.record("e", i=i)
    evs = rec.events()
    high = rec.stats()["total"]
    assert [e["seq"] for e in evs] == [1, 2, 3] and high == 3
    # incremental poll: nothing new at the high-water mark
    assert rec.events(high) == []
    for i in range(3, 10):                   # wraps the 4-slot ring
        rec.record("e", i=i)
    # cursor still resident: only newer events come back
    assert [e["seq"] for e in rec.events(8)] == [9, 10]
    # cursor fell off the ring: every resident event returns (the
    # reader lost 4..6 to the wrap; stats witnesses the drop)
    assert [e["seq"] for e in rec.events(3)] == [7, 8, 9, 10]
    assert rec.stats()["total"] == 10
    assert rec.stats()["dropped"] >= 1
    # malformed cursor degrades to the full ring, never raises
    assert len(rec.events("garbage")) == 4


# --------------------------------------- fleet capture (real engines)

_state = {}


def _make_server(rid, tag):
    return LLMServerImpl({
        "model_id": "traffic", "model_source": "debug",
        "engine_kwargs": dict(
            max_batch_size=4, page_size=8, num_pages=96, seed=7,
            enable_blackbox=False, metrics_model_id=tag,
            metrics_replica_id=rid)})


@pytest.fixture(scope="module")
def traffic_servers():
    """Two real debug-model engines shared by the capture tests
    (construction + shape-bucket compiles are the expensive part)."""
    if "servers" not in _state:
        tag = f"tl{uuid.uuid4().hex[:8]}"
        _state["tag"] = tag
        _state["servers"] = {rid: _make_server(rid, tag)
                             for rid in ("r0", "r1")}
    return _state["servers"]


def _fleet_over(servers, **over):
    kw = dict(router=RouterConfig(prefix_depth=64),
              # wide-open front door: the burst gates deliberately
              # queue at the ENGINES (which the sim replica models),
              # not in the admission queue
              admission=AdmissionConfig(max_concurrent=16,
                                        max_queue=64),
              autoscale=AutoscaleConfig(min_replicas=2,
                                        max_replicas=2),
              watchdog=WatchdogConfig(enabled=False),
              model_id="traffic")
    kw.update(over)
    return FleetManager([LocalReplicaClient(rid, srv)
                         for rid, srv in servers.items()], **kw)


def _cancel_pumps(servers):
    for srv in servers.values():
        if srv._pump is not None:
            srv._pump.cancel()


def _stream_prompt(c):
    """Stream-chain prompts are IDENTICAL within a chain (requests
    differ by seed/tenant): prefix_fingerprint hashes the first
    prefix_depth chars, so identical prompts are the simplest way to
    give the capture a real prefix-chain structure — and they are
    TINY on purpose: the calibration prices prefill per token from
    chunk-scale measurements, so the replay band holds where latency
    is queue/decode-dominated, not short-prompt-prefill-dominated."""
    return f"s{c}"


def _unary_prompt(c):
    """The unary tail carries the privacy tripwire (latency of these
    four sequential requests never lands near the burst's p99)."""
    return f"u{c} {SECRET}"


def _warm_engine(srv):
    """Pre-compile EVERY jit shape the captured workload can hit,
    driving the engine directly (simcal-style): prefill programs
    cache per (packed width, length bucket) and decode per
    (token bucket, ctx-pages bucket, greedy), so a fleet-level
    warmup burst cannot deterministically cover the space — packing
    widths depend on arrival interleaving. A compile stall inside
    the capture would poison the recorded p99 the replay band
    checks."""
    from ray_tpu.llm._internal.engine import (Request as EngRequest,
                                              SamplingParams)
    eng = srv.engine
    seq = iter(range(1000))
    base = iter(range(2, 220, 2))

    def run(batch, prompt_len, out, tokens=None):
        # every prompt gets a DISTINCT token range: a shared range
        # would hit the engine's prefix cache and skip the very
        # prefill-bucket compile this warmup exists to trigger
        reqs = []
        for _ in range(batch):
            toks = tokens if tokens is not None else list(
                range((b := next(base)), b + prompt_len))
            reqs.append(EngRequest(
                f"shapewarm-{next(seq)}", list(toks),
                SamplingParams(max_tokens=out,
                               temperature=0.5, seed=5)))
        for r in reqs:
            eng.add_request(r)
        while not all(r.finished for r in reqs):
            eng.step()
        return reqs

    for batch in (4, 3, 2, 1):    # stream shape: 3-token prompts,
        run(batch, 3, 26)         # decode across every batch ramp
    for batch in (2, 1):          # unary shape: long-prompt bucket
        long = run(batch, 33, 10)
    # the capture's unary tail REPEATS prompts within a prefix chain:
    # the repeat serves its whole prefix from cached pages and decodes
    # in a ctx-pages-bucketed shape no fresh prefill ever compiles —
    # warm it by replaying one long prompt's exact token range
    run(1, 33, 10, tokens=long[0].prompt_tokens)


async def _drive_captured_workload(fleet):
    """The seeded 2-replica workload the replay gates consume
    (engines pre-warmed by _warm_engine): one OVERSUBSCRIBED burst —
    12 concurrent streams against 2x4 engine slots, so TTFT is
    queue-wait dominated on both the real and simulated side — plus
    a unary tail, over 3+3 prefix chains x 2 tenants."""
    async def stream_one(i):
        body = {"prompt": _stream_prompt(i % 3),
                "max_tokens": 24, "seed": 100 + i,
                "user": f"tenant-{i % 2}", "temperature": 0.5}
        async for _ in fleet.dispatch_stream(
                "completions_stream", body):
            pass

    fleet.traffic.start_capture("gate")
    await asyncio.gather(*(stream_one(i) for i in range(12)))
    for i in range(4):                       # unary tail
        await fleet.dispatch("completions", {
            "prompt": _unary_prompt(i % 3), "max_tokens": 8,
            "seed": 200 + i, "user": f"tenant-{i % 2}",
            "temperature": 0.5})
    fleet.traffic.mark("burst-done")
    return fleet.traffic.stop_capture()


@pytest.fixture(scope="module")
def captured(traffic_servers):
    """One sealed capture from a real 2-replica fleet run, shared by
    the privacy / structure / replay gates."""
    if "capture" not in _state:
        for srv in traffic_servers.values():
            _warm_engine(srv)
        fleet = _fleet_over(traffic_servers)

        async def main():
            sealed = await _drive_captured_workload(fleet)
            text = fleet.traffic.export()
            stats = fleet.traffic.stats()
            await fleet.stop()
            return sealed, text, stats

        sealed, text, stats = asyncio.run(main())
        _cancel_pumps(traffic_servers)
        _state["capture"] = (sealed, text, stats)
    return _state["capture"]


def test_fleet_capture_is_privacy_clean(captured):
    """THE privacy gate: no prompt substring survives into capture
    bytes, and no record carries any body-text field at all."""
    sealed, text, _ = captured
    assert SECRET not in text
    for word in SECRET.split():
        assert word not in text
    cap = decode_capture(text)
    assert len(cap["records"]) == sealed["records"] == 16
    for r in cap["records"]:
        assert "prompt" not in r and "messages" not in r
        assert set(r["params"]) <= {"max_tokens", "temperature",
                                    "top_p", "top_k", "seed"}


def test_fleet_capture_records_the_request_lifecycle(captured):
    sealed, text, stats = captured
    cap = decode_capture(text)
    streams = [r for r in cap["records"] if r["stream"]]
    unary = [r for r in cap["records"] if not r["stream"]]
    assert len(streams) == 12 and len(unary) == 4
    anchor = cap["header"]["mono_anchor"]
    for r in cap["records"]:
        assert r["t_mono"] >= anchor
        assert len(r["fp"]) == 40            # prefix-chain fingerprint
        assert r["tenant"].startswith("tenant-")
        assert r["lane"] == "interactive"
        assert r["prompt_tokens"] > 0 and r["out_tokens"] > 0
        assert r["params"]["seed"] >= 100    # per-request seed rides
        out = r["outcome"]
        assert out["status"] == "ok"
        assert out["finish"] in ("length", "stop")
        assert out["route"] in ("affinity", "spill", "scored")
        assert out["replica"] in ("r0", "r1")
        assert out["failovers"] == 0
        assert out["e2e_ms"] > 0
    for r in streams:                        # TTFT is only
        assert r["outcome"]["ttft_ms"] is not None   # measurable
        assert r["outcome"]["ttft_ms"] > 0           # streaming
        assert 0 < r["out_tokens"] <= 24
    for r in unary:
        assert r["outcome"]["ttft_ms"] is None
    assert [m["label"] for m in cap["marks"]] == ["burst-done"]
    # engine warmup drove the engines directly, so the recorder saw
    # exactly the captured requests
    assert stats["total"] == 16
    assert stats["last_capture"]["records"] == 16


def test_capture_replays_deterministically_and_in_band(captured):
    """The acceptance gates: (a) the same capture replayed twice
    through the simulator produces byte-identical summary JSON;
    (b) recorded-vs-sim p99 TTFT lands inside CALIBRATION_BAND and
    the prefix-hit rate inside the diff tolerance."""
    from ray_tpu.serve.llm.sim import (CALIBRATION_BAND,
                                       FleetSimulator, RecordedTrace,
                                       SimFleetConfig,
                                       default_cpu_calibration)
    from tools import tracereplay

    _, text, _ = captured
    cap = decode_capture(text)

    def run_once():
        sim = FleetSimulator(
            RecordedTrace(cap),
            SimFleetConfig(replicas=2, min_replicas=2,
                           slots_per_replica=4,
                           calibration=default_cpu_calibration()))
        sim.run()
        return sim.summary_json()

    j1, j2 = run_once(), run_once()
    assert j1 == j2                          # byte-identical
    summary = json.loads(j1)
    assert summary["provenance"]["capture_id"] == \
        cap["header"]["capture_id"]
    assert summary["sessions"]["arrived"] == 16

    diff = tracereplay.capture_diff(cap, summary)
    assert diff["pass"], diff["failures"]
    lo, hi = CALIBRATION_BAND
    rec_ttft = diff["recorded"]["latency"]["ttft"]["p99_ms"]
    sim_ttft = diff["replayed"]["latency"]["ttft"]["p99_ms"]
    assert rec_ttft > 0 and lo <= sim_ttft / rec_ttft <= hi
    assert abs(diff["recorded"]["prefix_hit_rate"]
               - diff["replayed"]["prefix_hit_rate"]) \
        <= tracereplay.RATE_TOLERANCE
    # the recorded trace carried the prefix-chain structure: the sim
    # router actually exercised affinity on the recorded groups
    assert diff["replayed"]["route_mix"].get("affinity", 0) > 0


def test_recorded_trace_shapes(captured):
    from ray_tpu.serve.llm.sim import RecordedTrace

    _, text, _ = captured
    trace = RecordedTrace(text)              # raw text accepted too
    assert len(trace) == 16
    sessions = list(trace)
    ats = [s.at for s in sessions]
    assert ats == sorted(ats)                # generator contract
    assert all(s.at >= 0 for s in sessions)
    assert {s.tenant for s in sessions} == {"tenant-0", "tenant-1"}
    # 3 stream chains + 3 unary chains
    assert len({s.group for s in sessions}) == 6
    # time-warp halves every arrival offset
    fast = list(RecordedTrace(text, speed=2.0))
    assert all(abs(f.at - s.at / 2.0) < 1e-9
               for f, s in zip(fast, sessions))
    # degenerate fingerprints collapse to group 0, never raise
    assert RecordedTrace.group_of("") == 0
    assert RecordedTrace.group_of("zzzz") == 0
    assert RecordedTrace.group_of("00ff00ff" + "a" * 32) == 0xff00ff


# ----------------------------------------- ingress endpoint surface

def _ingress_over(fleet):
    ingress = LLMFleetIngressImpl.__new__(LLMFleetIngressImpl)
    ingress.model_id = "traffic"
    ingress.fleet = fleet
    return ingress


def test_fleet_debug_traffic_endpoints(traffic_servers):
    """GET/POST /fleet/debug/traffic: capture controls through the
    ingress HTTP surface, ring tail with ?since=, the sealed capture
    download, and typed-error HTTP mapping (409 misuse, 400 unknown
    action, 404 no capture)."""
    from ray_tpu.serve._private.proxy import Request

    fleet = _fleet_over(traffic_servers)
    ingress = _ingress_over(fleet)

    def post(action, **extra):
        return ingress(Request(
            "POST", "/fleet/debug/traffic", {}, {},
            json.dumps({"action": action, **extra}).encode()))

    async def main():
        # no sealed capture yet -> 404, typed message
        resp = await ingress._handle_get(
            "/fleet/debug/traffic", {"capture": "1"})
        assert resp.status == 404
        # stop with nothing armed -> 409
        resp = await post("stop")
        assert resp.status == 409
        # unknown action -> 400
        resp = await post("rewind")
        assert resp.status == 400
        started = await post("start", note="endpoint")
        assert started["object"] == "traffic_control"
        assert started["active"] is True
        # double start -> 409 naming the active capture
        resp = await post("start")
        assert resp.status == 409
        await fleet.dispatch("completions", {
            "prompt": f"endpoint {SECRET}", "max_tokens": 4,
            "seed": 3})
        marked = await post("mark", label="mid")
        assert marked["marks"] == 1
        doc = await ingress._handle_get("/fleet/debug/traffic", {})
        assert doc["object"] == "traffic" and doc["enabled"]
        assert doc["stats"]["capture"]["records"] == 1
        assert doc["records"][-1]["outcome"]["status"] == "ok"
        high = doc["records"][-1]["seq"]
        newer = await ingress._handle_get(
            "/fleet/debug/traffic", {"since": str(high)})
        assert newer["records"] == []        # cursor drained
        stopped = await post("stop")
        assert stopped["records"] == 1 and stopped["marks"] == 1
        resp = await ingress._handle_get(
            "/fleet/debug/traffic", {"capture": "1"})
        assert resp.status == 200
        await fleet.stop()
        return resp.body

    text = asyncio.run(main())
    _cancel_pumps(traffic_servers)
    assert SECRET not in text
    cap = decode_capture(text)
    assert len(cap["records"]) == 1
    assert [m["label"] for m in cap["marks"]] == ["mid"]


def test_fleet_debug_events_since_cursor(traffic_servers):
    """/fleet/debug/events?since= returns only events newer than the
    cursor plus per-source high-water marks; polling at the returned
    marks drains to empty; omitting ?since keeps the legacy shape."""
    fleet = _fleet_over(traffic_servers)
    ingress = _ingress_over(fleet)

    async def main():
        await fleet.dispatch("completions", {
            "prompt": "events probe", "max_tokens": 4, "seed": 3})
        legacy = await ingress._handle_get("/fleet/debug/events", {})
        assert "high_water" not in legacy and "since" not in legacy
        assert legacy["events"]
        doc = await ingress._handle_get("/fleet/debug/events",
                                        {"since": "0"})
        assert doc["since"] == 0 and doc["events"]
        high = doc["high_water"]
        assert set(high) == {"r0", "r1", "ingress"}
        assert high["ingress"] == fleet.recorder.stats()["total"]
        # sources are independent counters: poll each at its mark
        for rid in ("r0", "r1"):
            row = await ingress._handle_get(
                "/debug/events", {"since": str(high[rid])})
            assert row["replicas"][rid]["events"] == []
            assert row["replicas"][rid]["high_water"] == high[rid]
        # new work advances exactly the touched sources
        await fleet.dispatch("completions", {
            "prompt": "events probe 2", "max_tokens": 4, "seed": 3})
        doc2 = await ingress._handle_get(
            "/fleet/debug/events",
            {"since": str(min(high[r] for r in ("r0", "r1")))})
        assert doc2["events"]                # only the new activity
        assert all(doc2["high_water"][k] >= high[k] for k in high)

    asyncio.run(main())
    _cancel_pumps(traffic_servers)


# ------------------------------------- metric families (satellite 4)

def _sample(text, name, **labels):
    for ln in text.splitlines():
        if not ln.startswith(name + "{"):
            continue
        if all(f'{k}="{v}"' in ln for k, v in labels.items()):
            return float(ln.rsplit(" ", 1)[1])
    return None


def test_traffic_metric_families_shared_registry():
    """In-process fleets share one registry: two recorders with
    distinct model tags land distinct series in one render, and
    merge_expositions dedups repeated renders to one series per
    identity with one HELP/TYPE per family."""
    from ray_tpu.util.metrics import (export_prometheus,
                                      merge_expositions)

    tag_a, tag_b = (f"tm{uuid.uuid4().hex[:10]}",
                    f"tm{uuid.uuid4().hex[:10]}")
    rec_a = TrafficRecorder(capacity=8, model_id=tag_a)
    rec_b = TrafficRecorder(capacity=8, model_id=tag_b)
    rec_a.start_capture("metrics")
    for _ in range(3):
        rec_a.record(t_mono=0.0, fp="")
    rec_a.stop_capture()
    rec_b.record(t_mono=0.0, fp="")
    text = export_prometheus()
    assert _sample(text, "ray_tpu_llm_traffic_captured_total",
                   model=tag_a) == 3
    assert _sample(text, "ray_tpu_llm_traffic_captured_total",
                   model=tag_b) == 1
    # capture bytes accrue only while a capture is armed
    assert _sample(text, "ray_tpu_llm_traffic_capture_bytes_total",
                   model=tag_a) > 0
    assert not _sample(text, "ray_tpu_llm_traffic_capture_bytes_total",
                       model=tag_b)
    merged = merge_expositions([text, export_prometheus()])
    assert merged.count(
        "# TYPE ray_tpu_llm_traffic_captured_total counter") == 1
    series = [ln.rsplit(" ", 1)[0] for ln in merged.splitlines()
              if ln.startswith("ray_tpu_llm_traffic_captured_total{")
              and (tag_a in ln or tag_b in ln)]
    assert len(series) == len(set(series)) == 2


def test_traffic_metric_families_cross_process_relabel():
    """Separate-registry fleets render identical series; the scrape
    relabels each exposition before merging and the families carry
    distinct per-source series instead of colliding."""
    from ray_tpu.util.metrics import (export_prometheus,
                                      merge_expositions,
                                      relabel_exposition)

    tag = f"tx{uuid.uuid4().hex[:10]}"
    rec = TrafficRecorder(capacity=8, model_id=tag)
    rec.record(t_mono=0.0, fp="")
    text = export_prometheus()
    merged = merge_expositions([
        relabel_exposition(text, {"replica": "iA"}),
        relabel_exposition(text, {"replica": "iB"}),
    ])
    for rid in ("iA", "iB"):
        assert _sample(merged, "ray_tpu_llm_traffic_captured_total",
                       model=tag, replica=rid) == 1
    assert _sample(merged, "ray_tpu_llm_traffic_captured_total",
                   model=tag) == 1           # first-wins kept iA's
    assert merged.count(
        "# TYPE ray_tpu_llm_traffic_captured_total counter") == 1


# -------------------------------- dispatch discipline (acceptance)

def test_dispatch_guard_steady_state_with_recorder_armed():
    """The recorder is host-only Python riding the serving path: 32
    steady-state decode ticks with a capture ARMED and a record
    appended per tick hold the exact PR 1/2 contract — one dispatch
    per tick, zero h2d transfers (the guard raises at the site
    otherwise), zero new compiles."""
    import jax.numpy as jnp

    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              Request,
                                              SamplingParams)
    from ray_tpu.models import llama
    from ray_tpu.util.jax_guard import dispatch_guard

    eng = InferenceEngine(EngineConfig(
        model=llama.config("debug", dtype=jnp.float32),
        max_batch_size=3, page_size=8, num_pages=64,
        max_prefill_tokens=16,
        seed=9))
    rng = np.random.default_rng(5)
    for i in range(3):
        eng.add_request(Request(f"g{i}",
                                rng.integers(2, 250, 12).tolist(),
                                SamplingParams(max_tokens=64)))
    while eng.waiting or any(s.request is not None and not s.ready
                             for s in eng.slots):
        eng.step()
    for _ in range(4):
        eng.step()

    rec = TrafficRecorder(capacity=64, model_id="guard")
    rec.start_capture("armed")
    comp0 = eng.stats()["jit_cache"]["compiled_programs"]
    disp0 = eng.dispatches
    with dispatch_guard() as rep:
        for i in range(32):
            eng.step()
            rec.record(t_mono=float(i), fp="ab" * 20,
                       prompt_tokens=12, out_tokens=i,
                       outcome={"status": "ok"})
    assert rep.n_compiles == 0
    assert eng.stats()["jit_cache"]["compiled_programs"] == comp0
    assert eng.dispatches - disp0 == 32      # one dispatch per tick
    assert rec.stop_capture()["records"] == 32
