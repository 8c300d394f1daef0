"""Fleet KV transport (ISSUE 12): wire format, session export/import,
disaggregated prefill/decode, live migration, fleet prefix store.

Gates:
- serialization property test: seeded roundtrip over ragged page
  shapes, partial last pages, and dtype variants (f32/f16/bf16)
  asserts BYTE-identical restore; corrupted/truncated payloads are
  rejected with TransportError/TransportChecksumError (the fleet
  falls back to replay — never a crash);
- THE disaggregation acceptance gate: prefill-on-A / decode-on-B via
  the fleet relay produces token-identical output (greedy AND
  sampled) vs a single-engine oracle;
- live migration: a drain mid-stream ships the session instead of
  replaying; severing the ship (chaos) and corrupting the payload
  both degrade to the PR 9 replay path, still token-exact with
  exactly-once delivery;
- failover-by-restore: a wedged replica whose session was already
  parked hands the pages over instead of forcing a full replay;
- fleet prefix store: a prefix prefilled on one replica seeds the
  next replica's cache (match_prefix hits, output still
  oracle-exact);
- host-tier byte accounting (`kv_host_bytes_used`) across stats,
  fleet_stats, the Prometheus gauge, and the /fleet snapshot row.

Everything here is in-process (LocalReplicaClient over real engines
on CPU) — no cross-process transport tests exist yet; any future ones
must take the `slow` marker so tier-1 stays in-process.
"""

import asyncio
import base64
import json
import uuid

import numpy as np
import jax.numpy as jnp
import pytest

from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.llm._internal.server import LLMServerImpl
from ray_tpu.models import llama
from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                               ChaosReplicaClient, ChaosSchedule,
                               FleetManager, HealthConfig,
                               LocalReplicaClient, RouterConfig,
                               TransportConfig)
from ray_tpu.serve.llm import kv_transport as kvt
from ray_tpu.serve.llm.router import ReplicaSnapshot, prefix_fingerprint

# ---------------------------------------------------------------- helpers

_ENGINE_KW = dict(max_batch_size=4, page_size=8, num_pages=128, seed=7,
                  max_seq_len=1024,
                  max_prefill_tokens=32, enable_kv_offload=True)


def _engine(**over):
    kw = dict(model=llama.config("debug", dtype=jnp.float32),
              **_ENGINE_KW)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _run(eng, cap=5000):
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < cap, "engine failed to converge"


def _make_server(rid, tag):
    return LLMServerImpl({
        "model_id": "m", "model_source": "debug",
        "engine_kwargs": dict(_ENGINE_KW, metrics_model_id=tag,
                              metrics_replica_id=rid),
    })


_state = {}


@pytest.fixture(scope="module")
def transport_servers():
    """Two real engine replicas, WARMED (compiles done — the stall-
    and migration-driven tests use short watchdog timeouts that must
    never race a cold compile)."""
    if "servers" not in _state:
        tag = f"kvt{uuid.uuid4().hex[:8]}"
        servers = {rid: _make_server(rid, tag) for rid in ("r0", "r1")}

        async def warm():
            for s in servers.values():
                await s.completions({"prompt": "warmup " * 8,
                                     "max_tokens": 4})
            _cancel_pumps(servers)
        asyncio.run(warm())
        _state["servers"] = servers
    return _state["servers"]


def _cancel_pumps(servers):
    for srv in servers.values():
        if srv._pump is not None:
            srv._pump.cancel()


def _fleet_over(servers, clients=None, **over):
    kw = dict(
        router=RouterConfig(prefix_depth=64, spill_waiting=16),
        admission=AdmissionConfig(max_concurrent=8, max_queue=16,
                                  queue_wait_slo_s=30.0),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        transport=TransportConfig(enable_disagg=False,
                                  enable_prefix_store=False),
        drain_timeout_s=10.0)
    kw.update(over)
    return FleetManager(
        clients if clients is not None else
        [LocalReplicaClient(rid, srv)
         for rid, srv in servers.items()], **kw)


def _sse_transcript(chunks):
    toks, texts, reasons = [], [], []
    for c in chunks:
        if not c.startswith("data: "):
            continue
        d = c[len("data: "):].strip()
        if d == "[DONE]":
            continue
        ch = json.loads(d)["choices"][0]
        toks += ch.get("token_ids") or []
        texts.append(ch.get("text") or ch.get("delta", {})
                     .get("content") or "")
        if ch.get("finish_reason"):
            reasons.append(ch["finish_reason"])
    assert len(reasons) == 1, f"want exactly one finish: {reasons}"
    return toks, "".join(texts), reasons[0]


def _oracle_tokens(body):
    """Single-engine oracle stream (same weights seed as the fleet
    replicas), by token ids. One oracle engine serves every test —
    engine construction/compiles dominate this file's runtime, and
    greedy/seeded outputs are batch-history-independent."""
    if "oracle" not in _state:
        _state["oracle"] = _make_server("oracle",
                                        f"o{uuid.uuid4().hex[:6]}")
    srv = _state["oracle"]

    async def main():
        out = []
        async for c in srv.completions_stream_tokens(dict(body)):
            out.append(c)
        _cancel_pumps({"o": srv})
        return [t for c in out for t in c["toks"]]

    return asyncio.run(main())


def _drive_stream(fleet, servers, body, on_chunk=None):
    """Consume one fleet SSE stream; on_chunk(n, loop-context) runs
    after each chunk (the mid-stream fault injection hook)."""

    async def main():
        chunks = []
        async for c in fleet.dispatch_stream("completions_stream",
                                             dict(body)):
            chunks.append(c)
            if on_chunk is not None:
                await on_chunk(len(chunks))
        _cancel_pumps(servers)
        return chunks

    return asyncio.run(main())


# ------------------------------------------------- wire-format property

def _random_state(rng, dtype):
    L = int(rng.integers(1, 3))
    n_pages = int(rng.integers(1, 6))
    page = int(rng.choice([4, 8]))
    H = int(rng.integers(1, 3))
    D = int(rng.choice([4, 8]))
    shape = (L, n_pages, page, H, D)
    k = rng.standard_normal(shape).astype(dtype)
    v = rng.standard_normal(shape).astype(dtype)
    prompt = rng.integers(2, 250, int(rng.integers(4, 40))).tolist()
    # partial last page: position deliberately NOT page-aligned
    position = (n_pages - 1) * page + int(rng.integers(1, page + 1))
    return {
        "request_id": f"req-{rng.integers(1 << 30)}",
        "prompt_tokens": prompt,
        "output_tokens": rng.integers(2, 250,
                                      int(rng.integers(0, 8))).tolist(),
        "params": {"max_tokens": int(rng.integers(1, 64)),
                   "temperature": float(rng.random()),
                   "top_p": 0.9, "top_k": 3,
                   "repetition_penalty": 1.1,
                   "stop_token_ids": [0], "seed": 123},
        "lora": None, "priority": int(rng.integers(-2, 3)),
        "restarts": int(rng.integers(0, 3)), "trace": None,
        "deadline_epoch": None,
        "seed": int(rng.integers(1 << 31)),
        "position": position, "last_token": int(rng.integers(2, 250)),
        "n_pages": n_pages, "k": k, "v": v,
    }


def test_wire_session_roundtrip_property():
    """Seeded roundtrip over ragged page shapes, partial last pages,
    and dtype variants: decode(encode(state)) is BYTE-identical — the
    KV arrays bit-for-bit, every metadata field equal."""
    import ml_dtypes
    rng = np.random.default_rng(42)
    dtypes = [np.float32, np.float16, ml_dtypes.bfloat16]
    for trial in range(24):
        state = _random_state(rng, dtypes[trial % len(dtypes)])
        blob = kvt.encode_session(state)
        # the frame is also stable: same state -> same bytes
        assert blob == kvt.encode_session(state)
        out = kvt.decode_session(blob)
        for key in ("request_id", "prompt_tokens", "output_tokens",
                    "params", "lora", "priority", "restarts",
                    "seed", "position", "last_token", "n_pages"):
            assert out[key] == state[key], key
        for name in ("k", "v"):
            assert out[name].dtype == state[name].dtype
            assert out[name].shape == state[name].shape
            assert out[name].tobytes() == state[name].tobytes()
        # b64 transport wrapper is lossless too
        assert kvt.from_b64(kvt.to_b64(blob)) == blob


def test_wire_cold_session_roundtrip():
    rng = np.random.default_rng(7)
    state = _random_state(rng, np.float32)
    state.update(n_pages=0, position=0, last_token=0, k=None, v=None,
                 output_tokens=[])
    out = kvt.decode_session(kvt.encode_session(state))
    assert out["k"] is None and out["v"] is None
    assert out["n_pages"] == 0
    assert out["prompt_tokens"] == state["prompt_tokens"]


def test_wire_prefix_roundtrip():
    rng = np.random.default_rng(9)
    k = rng.standard_normal((2, 3, 8, 2, 4)).astype(np.float32)
    v = rng.standard_normal((2, 3, 8, 2, 4)).astype(np.float32)
    toks = list(range(2, 26))
    pfx = kvt.decode_prefix(kvt.encode_prefix(toks, k, v))
    assert pfx["tokens"] == toks
    assert pfx["k"].tobytes() == k.tobytes()
    assert pfx["v"].tobytes() == v.tobytes()
    # an f32 frame (and any decoded v1 frame) resolves to kind f32
    # with no scale arrays
    assert pfx["kv_dtype"] == "f32"
    assert pfx["k_scales"] is None and pfx["v_scales"] is None


def test_wire_rejects_corruption():
    """Every corrupted byte is caught (crc32 covers the whole frame),
    truncation/magic/version faults raise TransportError — and none
    of them raise anything BUT the transport error family (the
    fleet's fall-back-to-replay contract hangs on that)."""
    rng = np.random.default_rng(3)
    blob = kvt.encode_session(_random_state(rng, np.float32))
    # corrupt one byte at positions spread across header and payload
    for frac in (0.1, 0.3, 0.5, 0.7, 0.95):
        bad = bytearray(blob)
        bad[int(len(bad) * frac)] ^= 0xFF
        with pytest.raises(kvt.TransportError):
            kvt.decode_session(bytes(bad))
    # checksum corruption specifically is the checksum subclass
    bad = bytearray(blob)
    bad[len(bad) // 2] ^= 0xFF
    with pytest.raises(kvt.TransportChecksumError):
        kvt.decode_session(bytes(bad))
    # truncations at every boundary
    for cut in (0, 3, 8, len(blob) // 2, len(blob) - 1):
        with pytest.raises(kvt.TransportError):
            kvt.decode_session(blob[:cut])
    with pytest.raises(kvt.TransportError):
        kvt.decode_session(b"NOPE" + blob[4:])
    with pytest.raises(kvt.TransportError):
        kvt.decode_session(b"not even a frame")
    with pytest.raises(kvt.TransportError):
        kvt.from_b64("!!! not base64 !!!")
    # a prefix frame is not a session frame
    with pytest.raises(kvt.TransportError):
        kvt.decode_session(kvt.encode_prefix(
            [1, 2], np.zeros((1, 1, 2, 1, 2), np.float32),
            np.zeros((1, 1, 2, 1, 2), np.float32)))


def test_wire_rejects_crc_valid_lying_header():
    """A frame whose crc is VALID but whose header lies about its
    arrays (shape inconsistent with nbytes) must still raise
    TransportError, not a bare numpy ValueError — consumers key the
    fall-back-to-replay contract on the transport error family."""
    import struct
    import zlib

    rng = np.random.default_rng(5)
    blob = kvt.encode_session(_random_state(rng, np.float32))
    _, hlen = struct.unpack("<HI", blob[4:10])
    header = json.loads(blob[10:10 + hlen])
    header["arrays"][0]["shape"][0] += 1      # size no longer matches
    new_header = json.dumps(header, sort_keys=True).encode()
    body = (blob[:4]
            + struct.pack("<HI", kvt.WIRE_VERSION, len(new_header))
            + new_header + blob[10 + hlen:-4])
    bad = body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)
    with pytest.raises(kvt.TransportError, match="array"):
        kvt.decode_session(bad)


# ------------------------------------------- engine-level session moves

@pytest.mark.parametrize("sp", [
    {"max_tokens": 24},
    {"max_tokens": 24, "temperature": 0.8, "top_p": 0.9,
     "seed": 4242},
], ids=["greedy", "sampled"])
def test_engine_export_import_token_exact(sp):
    """Session shipped mid-decode A->B continues BYTE-identical to a
    never-moved oracle (restored pages are bit-exact copies; sampling
    keys are fold_in(seed, absolute index)); the wire roundtrip rides
    the real encode/decode path."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(2, 250, 20).tolist()
    ora = _engine()
    o = Request("q0", list(prompt), SamplingParams(**sp))
    ora.add_request(o)
    _run(ora)

    a = _engine()
    r = Request("q0", list(prompt), SamplingParams(**sp))
    a.add_request(r)
    while len(r.output_tokens) < 5:
        a.step()
    state = a.export_session("q0", reason="test")
    assert state is not None
    assert r.finished and r.finish_reason == "migrated"
    assert a.host_tier.exports_total == 1
    assert len(a.host_tier) == 0 and a.host_tier.used_bytes == 0

    b = _engine()
    req = b.import_session(kvt.decode_session(
        kvt.encode_session(state)))
    assert req.output_tokens == state["output_tokens"]
    _run(b)
    assert req.finished and req.finish_reason in ("length", "stop")
    assert o.output_tokens == req.output_tokens
    assert b.host_tier.restores_total == 1
    # A keeps serving after the export
    r2 = Request("after", rng.integers(2, 250, 8).tolist(),
                 SamplingParams(max_tokens=4))
    a.add_request(r2)
    _run(a)
    assert r2.finished


def test_engine_cold_export_from_waiting_queue():
    """A request still in the waiting queue exports COLD (no pages);
    the importer re-admits it and the generation is oracle-exact."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(2, 250, 12).tolist() for _ in range(2)]
    ora = _engine()
    o = Request("w1", list(prompts[1]), SamplingParams(max_tokens=8))
    ora.add_request(o)
    _run(ora)

    a = _engine(max_batch_size=1)
    a.add_request(Request("w0", list(prompts[0]),
                          SamplingParams(max_tokens=8)))
    a.add_request(Request("w1", list(prompts[1]),
                          SamplingParams(max_tokens=8)))
    state = a.export_session("w1")            # head-of-queue blocked
    assert state is not None and state["n_pages"] == 0
    b = _engine()
    req = b.import_session(state)
    _run(b)
    _run(a)
    assert req.output_tokens == o.output_tokens


def test_engine_import_rejects_bad_sessions():
    rng = np.random.default_rng(17)
    prompt = rng.integers(2, 250, 20).tolist()
    a = _engine()
    r = Request("dup", list(prompt), SamplingParams(max_tokens=24))
    a.add_request(r)
    while len(r.output_tokens) < 3:
        a.step()
    state = a.export_session("dup")
    b = _engine()
    b.import_session({**state})
    # same id already live here -> rejected (the relay replays)
    with pytest.raises(ValueError, match="already live"):
        b.import_session({**state})
    # incompatible geometry -> rejected before touching the pool
    c = _engine()
    bad = dict(state, k=state["k"][:, :, :4], v=state["v"][:, :, :4])
    with pytest.raises(ValueError, match="geometry"):
        c.import_session(bad)
    # inconsistent position/page accounting -> rejected
    bad = dict(state, position=1)
    with pytest.raises(ValueError, match="inconsistent"):
        c.import_session(bad)
    # a cold session that somehow carries emitted tokens must replay
    bad = dict(state, n_pages=0, k=None, v=None)
    with pytest.raises(ValueError, match="replay"):
        c.import_session(bad)
    _run(b)


def test_engine_prefix_export_import_hits_and_is_exact():
    """Prefix pages prefilled on A and imported into B make B's
    match_prefix hit AND leave the generated suffix oracle-exact
    (the imported pages are bit-exact KV for the same weights)."""
    sys_prefix = list(range(2, 2 + 32))       # 4 full pages
    a = _engine()
    ra = Request("p0", sys_prefix + [100, 101, 102],
                 SamplingParams(max_tokens=6))
    a.add_request(ra)
    _run(a)
    exp = a.export_prefix(sys_prefix)
    assert exp is not None and exp["k"].shape[1] == 4
    pfx = kvt.decode_prefix(kvt.encode_prefix(
        exp["tokens"], exp["k"], exp["v"]))
    toks, k, v = pfx["tokens"], pfx["k"], pfx["v"]

    b = _engine()
    assert b.import_prefix(toks, k, v) == 4
    assert b.import_prefix(toks, k, v) == 0   # idempotent
    suffix = [110, 111, 112, 113]
    ora = _engine()
    ro = Request("p1", sys_prefix + suffix,
                 SamplingParams(max_tokens=8))
    ora.add_request(ro)
    _run(ora)
    rb = Request("p1", sys_prefix + suffix,
                 SamplingParams(max_tokens=8))
    b.add_request(rb)
    _run(b)
    assert b.allocator.cache_hit_tokens >= 32
    assert rb.output_tokens == ro.output_tokens


def test_host_tier_byte_accounting_surfaces():
    """ISSUE 12 satellite: `kv_host_bytes_used` is visible in the
    tier stats, engine stats, fleet_stats, the Prometheus gauge, and
    the /fleet snapshot row — and returns to zero when the tier
    empties."""
    rng = np.random.default_rng(19)
    eng = _engine()
    r = Request("b0", rng.integers(2, 250, 20).tolist(),
                SamplingParams(max_tokens=40))
    eng.add_request(r)
    while len(r.output_tokens) < 3:
        eng.step()
    assert eng.preempt("b0", reason="manual")
    tier = eng.host_tier
    parked = tier.entries()[0]
    want = parked.payload_bytes()
    assert want > 0
    assert tier.used_bytes == want
    assert tier.stats()["host_bytes_used"] == want
    assert eng.stats()["host_bytes_used"] == want
    # telemetry gauge renders at scrape time
    eng.telemetry.update_gauges(eng)
    assert "ray_tpu_llm_kv_host_bytes_used" in \
        eng.prometheus_metrics()
    # fleet surface: fleet_stats row -> ReplicaSnapshot -> /fleet row
    srv = LLMServerImpl.__new__(LLMServerImpl)
    srv.engine = eng
    srv.replica_id = "rX"
    srv.model_id = "m"
    stats = srv._fleet_stats_sync()
    assert stats["kv_host_bytes_used"] == want
    snap = ReplicaSnapshot.from_stats(stats)
    assert snap.kv_host_bytes == want
    _run(eng)                                  # restore + finish
    assert tier.used_bytes == 0 and tier.stats()["host_bytes_used"] \
        == 0


# ------------------------------------------------ fleet e2e: disagg

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_e2e_disagg_prefill_on_a_decode_on_b_token_exact(
        transport_servers, sampled):
    """THE acceptance gate: a long prompt prefills on the `prefill`
    replica, the parked session ships, and the `decode` replica
    resumes it — the client transcript is token-identical to a
    single-engine oracle, greedy AND sampled, with the prefill
    replica kept out of the router ring."""
    gen = 16
    body = {"prompt": "long shared context " * 16, "max_tokens": gen}
    if sampled:
        body.update(temperature=0.8, top_p=0.9, seed=20124)
    fleet = _fleet_over(
        transport_servers,
        roles=["prefill", "decode"],
        transport=TransportConfig(disagg_prompt_chars=64,
                                  enable_prefix_store=False))
    assert fleet.router.ring.nodes() == ["r1"]
    exports0 = transport_servers["r0"].engine.host_tier.exports_total
    restores0 = transport_servers["r1"].engine.host_tier \
        .restores_total
    chunks = _drive_stream(fleet, transport_servers, body)
    toks, _, reason = _sse_transcript(chunks)
    assert reason in ("length", "stop")
    want = _oracle_tokens(body)
    assert len(want) == gen
    assert toks == want, "disaggregated transcript diverged"
    # the ship REALLY happened: prefill exported, decode restored
    assert transport_servers["r0"].engine.host_tier.exports_total \
        == exports0 + 1
    assert transport_servers["r1"].engine.host_tier.restores_total \
        == restores0 + 1
    evs = [e["event"] for e in fleet.recorder.events()]
    assert "disagg_handoff" in evs
    # transport spans land in the ingress trace buffer
    names = {e.get("name") for e in fleet.trace.events()}
    assert "disagg_prefill" in names


def test_e2e_disagg_short_prompt_skips_handoff(transport_servers):
    """Prompts under the threshold take the normal decode-replica
    path — no ship, no prefill-replica involvement."""
    fleet = _fleet_over(
        transport_servers,
        roles=["prefill", "decode"],
        transport=TransportConfig(disagg_prompt_chars=256,
                                  enable_prefix_store=False))
    exports0 = transport_servers["r0"].engine.host_tier.exports_total
    body = {"prompt": "short", "max_tokens": 4}
    chunks = _drive_stream(fleet, transport_servers, body)
    toks, _, reason = _sse_transcript(chunks)
    assert reason in ("length", "stop") and len(toks) == 4
    assert transport_servers["r0"].engine.host_tier.exports_total \
        == exports0
    assert "disagg_handoff" not in [
        e["event"] for e in fleet.recorder.events()]


def test_e2e_disagg_prefill_failure_falls_back(transport_servers):
    """A dead prefill replica degrades to mixed prefill on the
    decode replica — same tokens, one failed-handoff breadcrumb."""
    schedules = {rid: ChaosSchedule() for rid in transport_servers}
    schedules["r0"].fail_calls(method="prefill_export", count=-1)
    clients = [ChaosReplicaClient(
        LocalReplicaClient(rid, srv), schedules[rid])
        for rid, srv in transport_servers.items()]
    fleet = _fleet_over(
        transport_servers, clients=clients,
        roles=["prefill", "decode"],
        transport=TransportConfig(disagg_prompt_chars=64,
                                  enable_prefix_store=False))
    body = {"prompt": "fall back to mixed prefill " * 8,
            "max_tokens": 8}
    chunks = _drive_stream(fleet, transport_servers, body)
    toks, _, reason = _sse_transcript(chunks)
    assert toks == _oracle_tokens(body)
    assert [f["kind"] for f in schedules["r0"].fired] \
        == ["call_error"]
    assert "disagg_fallback" in [
        e["event"] for e in fleet.recorder.events()]


# ------------------------------------------ fleet e2e: live migration

def test_e2e_drain_migration_ships_session_token_exact(
        transport_servers):
    """Drain-before-downscale mid-stream: the victim's live session
    ships to the survivor (pages, not token replay), the stream
    completes token-exact with exactly-once delivery, and the victim
    parks on STANDBY."""
    gen = 400
    body = {"prompt": "drain migration scenario prompt",
            "max_tokens": gen}
    want = _oracle_tokens(body)
    assert len(want) == gen
    fleet = _fleet_over(transport_servers)

    async def main():
        chunks = []
        victim = None
        async for c in fleet.dispatch_stream("completions_stream",
                                             dict(body)):
            chunks.append(c)
            if len(chunks) == 3:
                srid, info = next(iter(fleet._live_streams.items()))
                victim = info["replica"]
                fleet._begin_drain(victim)
            await asyncio.sleep(0)
        # settle on the SAME loop the drain task runs on
        drained = False
        for _ in range(500):
            if fleet.replicas[victim].status == "STANDBY":
                drained = True
                break
            await asyncio.sleep(0.02)
        _cancel_pumps(transport_servers)
        return chunks, victim, drained

    chunks, victim, drained = asyncio.run(main())
    toks, _, reason = _sse_transcript(chunks)
    assert reason == "length"
    assert toks == want, "migrated transcript diverged"
    assert len(toks) == gen                  # exactly-once
    evs = [e["event"] for e in fleet.recorder.events()]
    assert "session_migrated" in evs
    names = {e.get("name") for e in fleet.trace.events()}
    assert "session_migration" in names
    assert drained, "victim never finished draining"


def test_e2e_migration_severed_mid_ship_replays_token_exact(
        transport_servers):
    """THE chaos acceptance gate: the victim's stream is severed
    mid-flight AND its export path is dead (the ship is severed
    mid-migration) — the fleet falls back to PR 9 token replay and
    the client transcript is STILL token-exact with exactly-once
    delivery."""
    gen = 14
    body = {"prompt": "sever the ship mid migration",
            "max_tokens": gen, "temperature": 0.8, "top_p": 0.9,
            "seed": 777}
    want = _oracle_tokens(body)
    fleet0 = _fleet_over(transport_servers)
    fp = prefix_fingerprint(body, 64)
    victim = fleet0.router.pick(fp, {}, {})
    schedules = {rid: ChaosSchedule() for rid in transport_servers}
    schedules[victim].sever_stream(after_chunks=2)
    schedules[victim].fail_calls(method="export_session", count=-1)
    clients = [ChaosReplicaClient(
        LocalReplicaClient(rid, srv), schedules[rid])
        for rid, srv in transport_servers.items()]
    fleet = _fleet_over(transport_servers, clients=clients)
    chunks = _drive_stream(fleet, transport_servers, body)
    toks, _, reason = _sse_transcript(chunks)
    assert reason in ("length", "stop")
    assert toks == want and len(toks) == gen
    kinds = [f["kind"] for f in schedules[victim].fired]
    assert "stream_sever" in kinds and "call_error" in kinds
    evs = [e["event"] for e in fleet.recorder.events()]
    assert "failover" in evs
    assert "failover_restore" not in evs     # the restore path failed


class _CorruptingClient:
    """Flips one payload byte in every export_session response — the
    ship completes but the cargo is damaged (checksum catches it on
    the importing side)."""

    def __init__(self, inner):
        self.inner = inner
        self.replica_id = inner.replica_id

    @property
    def shares_registry(self):
        return bool(getattr(self.inner, "shares_registry", False))

    async def call(self, method, *args):
        out = await self.inner.call(method, *args)
        if method == "export_session" and isinstance(out, dict) \
                and out.get("session"):
            blob = bytearray(base64.b64decode(out["session"]))
            blob[len(blob) // 2] ^= 0xFF
            out = dict(out, session=base64.b64encode(
                bytes(blob)).decode("ascii"))
        return out

    def stream(self, method, body):
        return self.inner.stream(method, body)


def test_e2e_corrupted_ship_falls_back_to_replay(transport_servers):
    """A drain migration whose payload is corrupted in flight: the
    importing replica rejects it (checksum) and the relay degrades
    to token replay — token-exact, pump alive, no crash."""
    gen = 400
    body = {"prompt": "corrupted cargo scenario", "max_tokens": gen}
    want = _oracle_tokens(body)
    clients = [_CorruptingClient(LocalReplicaClient(rid, srv))
               for rid, srv in transport_servers.items()]
    fleet = _fleet_over(transport_servers, clients=clients)
    st = {"victim": None}

    async def on_chunk(n):
        if n == 3:
            srid, info = next(iter(fleet._live_streams.items()))
            st["victim"] = info["replica"]
            fleet._begin_drain(st["victim"])
        await asyncio.sleep(0)

    chunks = _drive_stream(fleet, transport_servers, body, on_chunk)
    toks, _, reason = _sse_transcript(chunks)
    assert reason == "length"
    assert toks == want and len(toks) == gen
    evs = [e["event"] for e in fleet.recorder.events()]
    assert "session_migrated" in evs         # the ship left the dock
    assert "kv_resume_failed" in evs         # ... and was rejected
    # both replicas still serve after the storm
    fleet2 = _fleet_over(transport_servers)

    async def after():
        out = await fleet2.dispatch(
            "completions", {"prompt": "after the storm",
                            "max_tokens": 2})
        _cancel_pumps(transport_servers)
        return out
    assert asyncio.run(after())["choices"][0]["finish_reason"]


def test_e2e_failover_by_restore_wedged_replica(transport_servers):
    """Failover-by-restore (ISSUE 12b): the serving replica WEDGES
    (pump dead) with the session already parked in its host tier.
    The stall watchdog fires and the fleet exports the parked pages
    off the wedged replica instead of replaying the whole transcript
    — resumed on the survivor, token-exact."""
    gen = 400
    body = {"prompt": "wedged replica restore scenario",
            "max_tokens": gen}
    want = _oracle_tokens(body)
    fleet = _fleet_over(
        transport_servers,
        health=HealthConfig(stream_stall_timeout_s=1.5))
    st = {"victim": None, "parked": None}

    async def on_chunk(n):
        if n == 1:
            srid, info = next(iter(fleet._live_streams.items()))
            st["victim"] = info["replica"]
            transport_servers[st["victim"]]._pump.cancel()
            st["parked"] = await asyncio.get_running_loop() \
                .run_in_executor(
                    None, transport_servers[st["victim"]]
                    .engine.preempt, srid)

    chunks = _drive_stream(fleet, transport_servers, body, on_chunk)
    toks, _, reason = _sse_transcript(chunks)
    assert st["parked"], "victim failed to park the session"
    assert reason == "length"
    assert toks == want and len(toks) == gen
    evs = [e["event"] for e in fleet.recorder.events()]
    assert "failover_restore" in evs
    victim_eng = transport_servers[st["victim"]].engine
    assert victim_eng.host_tier.exports_total >= 1
    names = {e.get("name") for e in fleet.trace.events()}
    assert "failover_restore" in names


# -------------------------------------------- fleet e2e: prefix store

def test_e2e_prefix_store_seeds_second_replica(transport_servers):
    """ISSUE 12c: a system prompt prefilled on r0 is published into
    the fleet store and seeded into r1 BEFORE r1's first request of
    that prefix — r1's local prefix cache hits as if it had
    prefilled the prompt itself, and the output stays oracle-exact."""
    sys_prompt = (f"shared system prompt {uuid.uuid4().hex[:8]} "
                  + "s" * 64)[:64]
    fleet = _fleet_over(
        transport_servers,
        router=RouterConfig(policy="round_robin", prefix_depth=64),
        transport=TransportConfig(enable_disagg=False,
                                  prefix_min_chars=64))
    hit0 = {rid: srv.engine.allocator.cache_hit_tokens
            for rid, srv in transport_servers.items()}

    bodies = [{"prompt": sys_prompt + f" user turn {i}",
               "max_tokens": 6} for i in range(2)]
    oracles = [_oracle_tokens(b) for b in bodies]

    async def main():
        outs = []
        for b in bodies:                      # SEQUENTIAL: publish
            outs.append(await fleet.dispatch("completions", dict(b)))
        _cancel_pumps(transport_servers)
        return outs

    outs = asyncio.run(main())
    # round-robin put one request on each replica; the second
    # replica imported the store entry and HIT
    store = fleet.prefix_store
    assert store is not None
    assert store.stats()["publishes"] == 1
    assert store.stats()["hits"] == 1
    hits = sum(v for _, v in
               fleet.kvt_metrics["prefix_store_hits"]._samples())
    assert hits >= 1
    deltas = {rid: srv.engine.allocator.cache_hit_tokens - hit0[rid]
              for rid, srv in transport_servers.items()}
    # both replicas hit the shared prefix: the publisher via its own
    # cache is irrelevant (first request is cold), the OTHER replica
    # via the imported store entry — 64 shared chars = 64 byte
    # tokens = 8 full pages
    assert sum(1 for d in deltas.values() if d >= 64) >= 1, deltas
    evs = [e["event"] for e in fleet.recorder.events()]
    assert "prefix_published" in evs and "prefix_seeded" in evs
    # correctness: store-seeded pages produce oracle-exact output
    tok = transport_servers["r0"].tokenizer
    for out, want, b in zip(outs, oracles, bodies):
        got = out["choices"][0]["text"]
        assert got == tok.decode(want), b["prompt"][-12:]


def test_e2e_transport_status_surface(transport_servers):
    """GET /fleet carries the transport block: roles, prefix-store
    stats, live-stream/migration counts, per-replica role rows."""
    fleet = _fleet_over(
        transport_servers,
        roles=["prefill", "decode"],
        transport=TransportConfig())

    async def main():
        await fleet.refresh()
        return await fleet.status()

    doc = asyncio.run(main())
    assert doc["transport"]["enabled"]
    assert doc["transport"]["roles"] == {"r0": "prefill",
                                         "r1": "decode"}
    assert doc["transport"]["prefix_store"] is not None
    assert doc["replicas"]["r0"]["role"] == "prefill"
    assert "kv_host_bytes_used" in doc["replicas"]["r0"]
    # a transport-less fleet advertises it off
    plain = _fleet_over(transport_servers, transport=None)
    doc2 = asyncio.run(plain.status())
    assert doc2["transport"] == {"enabled": False}


def test_prefix_store_hot_small_outlives_cold_large():
    """ISSUE 13 satellite (ROADMAP item 2 "REMAINS"): eviction is
    hit-frequency-weighted, not LRU-by-bytes — a HOT small prefix
    (the shared system prompt the store exists for) must survive byte
    pressure that evicts a COLD large one, even when the large one
    arrived later (pure LRU would evict the hot entry here)."""
    from ray_tpu.serve.llm.kv_transport import FleetPrefixStore

    store = FleetPrefixStore(capacity_bytes=1000)
    assert store.put("hot", "h" * 100, tokens=8, publisher="r0")
    for _ in range(5):
        assert store.get("hot") is not None      # it earns residency
    # a cold large entry lands AFTER the hot one (more recent under
    # LRU) and fills most of the store
    assert store.put("cold", "c" * 800, tokens=64, publisher="r0")
    # byte pressure: the next put must evict — the victim is the
    # cold large entry (0 hits), NOT the older-but-hot small one
    assert store.put("new", "n" * 500, tokens=32, publisher="r1")
    assert "hot" in store
    assert "cold" not in store
    assert store.evictions == 1
    assert store.stats()["policy"] == "hit-frequency-weighted"
    # repeated pressure: the fresh entry (0 hits) goes before hot
    assert store.put("new2", "m" * 500, tokens=32, publisher="r1")
    assert "hot" in store and "new" not in store


def test_prefix_store_frequency_ties_break_lru():
    """Among equally-cold entries the LEAST recently used evicts
    first (recency is the score's tie-break)."""
    from ray_tpu.serve.llm.kv_transport import FleetPrefixStore

    store = FleetPrefixStore(capacity_bytes=300)
    store.put("a", "a" * 100, tokens=8, publisher="r0")
    store.put("b", "b" * 100, tokens=8, publisher="r0")
    store.put("c", "c" * 100, tokens=8, publisher="r0")
    store.get("a")                    # a is now most recent AND hot
    store.get("b")
    store.get("b")                    # b hotter than a; c coldest
    store.put("d", "d" * 100, tokens=8, publisher="r0")
    assert "c" not in store           # 0 hits: out first
    assert {"a", "b", "d"} <= {k for k in ("a", "b", "d")
                               if k in store}
    store.put("e", "e" * 100, tokens=8, publisher="r0")
    assert "d" not in store           # 0 hits, least recent of those
    assert "a" in store and "b" in store


def test_fleet_config_wire_carries_transport_and_roles():
    """FleetConfig -> to_wire -> ingress-side reconstruction keeps
    the transport policy and the role map (the deployment path's
    JSON hop must not drop ISSUE 12 config)."""
    import types

    from ray_tpu.serve.llm.deployment import FleetConfig

    cfg = FleetConfig(
        llm_config=types.SimpleNamespace(model_id="m"),
        min_replicas=2, max_replicas=2,
        transport=TransportConfig(disagg_prompt_chars=99,
                                  prefix_min_chars=17),
        replica_roles=["prefill", "decode"])
    wire = json.loads(json.dumps(cfg.to_wire()))
    assert wire["replica_roles"] == ["prefill", "decode"]
    back = TransportConfig(**wire["transport"])
    assert back.disagg_prompt_chars == 99
    assert back.prefix_min_chars == 17
    # transport=None stays None on the wire (fleet behaves pre-12)
    off = FleetConfig(llm_config=types.SimpleNamespace(model_id="m"))
    assert off.to_wire()["transport"] is None
    assert off.to_wire()["replica_roles"] is None


def test_fleet_rejects_bad_role_configs(transport_servers):
    with pytest.raises(ValueError, match="decode-capable"):
        _fleet_over(transport_servers,
                    roles=["prefill", "prefill"])
    with pytest.raises(ValueError, match="align"):
        _fleet_over(transport_servers, roles=["mixed"])
    with pytest.raises(ValueError, match="unknown replica roles"):
        _fleet_over(transport_servers, roles=["mixed", "verifier"])


class _FakeRoleClient:
    """Bare client for role-policy unit tests (no engine)."""

    shares_registry = True

    def __init__(self, rid):
        self.replica_id = rid

    async def call(self, method, *args):
        return {}

    def stream(self, method, body):
        raise NotImplementedError


def _role_fleet(roles, min_replicas):
    clients = [_FakeRoleClient(f"r{i}") for i in range(len(roles))]
    return FleetManager(
        clients, roles=roles,
        autoscale=AutoscaleConfig(min_replicas=min_replicas,
                                  max_replicas=len(roles)),
        transport=TransportConfig())


def test_role_aware_lifecycle_never_empties_the_ring():
    """Role-blindness regressions: (a) an initial ACTIVE head that is
    all prefill is rejected at construction; (b) evicting the last
    ring replica never installs a prefill-role standby as the
    replacement (deferred instead); (c) scale-down never drains the
    last decode-capable replica while prefill replicas stay ACTIVE."""
    # (a) first min_replicas all prefill -> loud config error
    with pytest.raises(ValueError, match="min_replicas"):
        _role_fleet(["prefill", "mixed"], min_replicas=1)
    # (b1) only a prefill standby exists: the eviction DEFERS
    fleet = _role_fleet(["mixed", "prefill", "prefill"],
                        min_replicas=2)
    fleet._evict("r0", "test")
    assert fleet.replicas["r0"].status == "ACTIVE"   # deferred
    assert fleet._ring_ids() == ["r0"]
    assert "eviction_deferred" in [
        e["event"] for e in fleet.recorder.events()]
    # (b2) a decode-capable standby exists: it takes over the ring
    fleet = _role_fleet(["mixed", "prefill", "mixed"],
                        min_replicas=2)
    fleet._evict("r0", "test")
    assert fleet.replicas["r0"].status == "UNHEALTHY"
    assert fleet.replicas["r2"].status == "ACTIVE"
    assert fleet._ring_ids() == ["r2"]
    # (c) downscale drains the prefill replica, not the sole ring one
    fleet = _role_fleet(["mixed", "prefill"], min_replicas=2)

    async def downscale():
        fleet._apply_target(1)
        st = {rid: s.status for rid, s in fleet.replicas.items()}
        for s in fleet.replicas.values():
            if s.drain_task is not None:
                s.drain_task.cancel()
        return st

    statuses = asyncio.run(downscale())
    assert statuses["r0"] == "ACTIVE"
    assert statuses["r1"] == "DRAINING"
    assert fleet._ring_ids() == ["r0"]
