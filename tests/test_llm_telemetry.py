"""Request-lifecycle telemetry (ISSUE 5): SLO metrics, Prometheus
exposition, Chrome-trace lifecycles, the engine flight recorder, and
on-demand profiling.

The exactness gates pin the host-side recording to the engine's
actual lifecycle events: TTFT observations == finished requests, ITL
observations == generated tokens minus first tokens, finish-reason
counters exact, KV occupancy gauge == allocator.stats() at scrape.
Every engine here gets a UNIQUE Prometheus model tag so samples from
other tests sharing the process registry can never leak in.
"""

import json
import os
import re
import uuid
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)

REPO = Path(__file__).resolve().parent.parent


def make_engine(**over):
    cfg = llama.config("debug", dtype=jnp.float32)
    kw = dict(model=cfg, max_batch_size=4, page_size=8, num_pages=64,
              metrics_model_id=f"t{uuid.uuid4().hex[:10]}")
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _sample(text: str, name: str, **tags):
    """Value of one exposition sample (exact tag match) or None."""
    for line in text.splitlines():
        if not line.startswith(name + "{") and line.split(" ")[0] != name:
            continue
        m = re.match(r"^([a-zA-Z0-9_]+)(?:\{(.*)\})? (.+)$", line)
        if m is None or m.group(1) != name:
            continue
        got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
        if got == {k: str(v) for k, v in tags.items()}:
            return float(m.group(3))
    return None


# ----------------------------------------------------------- exposition

def test_metrics_exposition_exact_after_generation():
    """/metrics source of truth: TTFT observations == finished
    requests, ITL observations == generated tokens - first tokens,
    finish-reason counters exact, token counters exact."""
    eng = make_engine()
    tag = eng.config.metrics_model_id
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 200, n).tolist() for n in (5, 9, 14)]
    reqs = eng.generate([list(p) for p in prompts],
                        SamplingParams(max_tokens=6))
    # one more request that stops on a token mid-stream
    stop = reqs[0].output_tokens[2]
    r = eng.generate([list(prompts[0])],
                     SamplingParams(max_tokens=30,
                                    stop_token_ids=(stop,)))[0]
    assert r.finish_reason == "stop"
    gen = sum(len(q.output_tokens) for q in reqs) + len(r.output_tokens)
    text = eng.prometheus_metrics()
    assert _sample(text, "ray_tpu_llm_ttft_seconds_count",
                   model=tag) == 4
    assert _sample(text, "ray_tpu_llm_itl_seconds_count",
                   model=tag) == gen - 4
    assert _sample(text, "ray_tpu_llm_queue_wait_seconds_count",
                   model=tag) == 4
    assert _sample(text, "ray_tpu_llm_e2e_latency_seconds_count",
                   model=tag) == 4
    assert _sample(text, "ray_tpu_llm_finished_total",
                   model=tag, reason="length") == 3.0
    assert _sample(text, "ray_tpu_llm_finished_total",
                   model=tag, reason="stop") == 1.0
    assert _sample(text, "ray_tpu_llm_generated_tokens_total",
                   model=tag) == gen
    assert _sample(text, "ray_tpu_llm_prompt_tokens_total",
                   model=tag) == sum(len(p) for p in prompts) \
        + len(prompts[0])
    # histogram sums are real latencies, not zeros
    assert _sample(text, "ray_tpu_llm_ttft_seconds_sum", model=tag) > 0
    # +Inf bucket equals the count (exposition well-formed)
    inf = None
    for line in text.splitlines():
        if line.startswith("ray_tpu_llm_ttft_seconds_bucket") \
                and f'model="{tag}"' in line and 'le="+Inf"' in line:
            inf = float(line.rsplit(" ", 1)[1])
    assert inf == 4


def test_kv_occupancy_gauge_matches_allocator_mid_flight():
    """Scrape-time gauges reflect LIVE engine state: occupancy and
    free-pages match allocator.stats() while requests hold pages,
    and running/waiting match the slot/queue state."""
    eng = make_engine(max_batch_size=2)
    tag = eng.config.metrics_model_id
    rng = np.random.default_rng(1)
    for i in range(3):           # 2 admit, 1 waits (2 slots)
        eng.add_request(Request(f"r{i}",
                                rng.integers(2, 200, 12).tolist(),
                                SamplingParams(max_tokens=16)))
    for _ in range(4):
        eng.step()
    text = eng.prometheus_metrics()
    st = eng.allocator.stats()
    assert _sample(text, "ray_tpu_llm_kv_pages_free",
                   model=tag) == st["free_pages"]
    assert _sample(text, "ray_tpu_llm_kv_pages_used",
                   model=tag) == st["used_pages"]
    assert _sample(text, "ray_tpu_llm_kv_page_occupancy",
                   model=tag) == pytest.approx(st["occupancy"])
    assert st["used_pages"] > 0          # requests really hold pages
    assert _sample(text, "ray_tpu_llm_running_requests",
                   model=tag) == 2
    assert _sample(text, "ray_tpu_llm_waiting_requests",
                   model=tag) == 1
    while eng.has_work():
        eng.step()
    text = eng.prometheus_metrics()
    assert _sample(text, "ray_tpu_llm_kv_pages_used", model=tag) == 0


def test_prefix_cache_hit_rate_gauge():
    eng = make_engine(max_batch_size=2, num_pages=96)
    tag = eng.config.metrics_model_id
    shared = np.random.default_rng(2).integers(2, 200, 24).tolist()
    eng.generate([shared + [5]], SamplingParams(max_tokens=2))
    eng.generate([shared + [9]], SamplingParams(max_tokens=2))
    text = eng.prometheus_metrics()
    rate = _sample(text, "ray_tpu_llm_prefix_cache_hit_rate",
                   model=tag)
    assert rate == pytest.approx(eng.allocator.cache_hit_rate)
    assert rate > 0              # second prompt hit the shared prefix


# ------------------------------------------------------------ chrome trace

def test_chrome_trace_well_formed_lifecycle():
    """GET /debug/trace payload: valid JSON, every request carries
    queued → prefill (with chunk marks) → first_token → decode →
    finished{reason} in causal order on its own tid."""
    eng = make_engine(max_prefill_tokens=8)   # forces chunked prefill
    rng = np.random.default_rng(3)
    reqs = eng.generate([rng.integers(2, 200, 20).tolist()],
                        SamplingParams(max_tokens=4))
    doc = json.loads(json.dumps(eng.chrome_trace()))   # JSON-able
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    for e in evs:
        assert {"ph", "name", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    by_name = {}
    rid = reqs[0].request_id
    for e in evs:
        if e.get("args", {}).get("request_id") == rid \
                or e["name"] == "prefill_chunk":
            by_name.setdefault(e["name"], []).append(e)
    assert set(by_name) >= {"queued", "prefill", "first_token",
                            "decode", "finished:length",
                            "prefill_chunk"}
    q, p = by_name["queued"][0], by_name["prefill"][0]
    d = by_name["decode"][0]
    assert q["ts"] <= p["ts"] <= d["ts"]
    assert p["args"]["prompt_tokens"] == 20
    assert d["args"]["generated_tokens"] == 4
    assert len(by_name["prefill_chunk"]) >= 2       # chunked at 8
    assert sum(e["args"]["tokens"]
               for e in by_name["prefill_chunk"]) == 20
    # every lifecycle event of one request shares one tid row
    tids = {e["tid"] for es in by_name.values() for e in es}
    assert len(tids) == 1


def test_chrome_trace_merges_tracing_ring():
    """The process tracing ring (RAY_TPU_TRACE spans) rides the same
    export — one viewer shows engine lifecycles AND live spans."""
    from ray_tpu.util import tracing

    eng = make_engine()
    tracing.clear()
    tracing.enable()
    try:
        with tracing.span("driver_side_work", "custom"):
            pass
    finally:
        tracing.disable()
    names = {e["name"] for e in eng.chrome_trace()["traceEvents"]}
    assert "driver_side_work" in names
    tracing.clear()


# --------------------------------------------------------- flight recorder

def test_flight_recorder_ring_and_structured_events():
    from ray_tpu.llm._internal.telemetry import FlightRecorder

    eng = make_engine(max_batch_size=2)
    rng = np.random.default_rng(4)
    eng.generate([rng.integers(2, 200, 8).tolist() for _ in range(2)],
                 SamplingParams(max_tokens=3))
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    assert kinds.count("admission") == 2
    assert kinds.count("retirement") == 2
    assert "device_state_rebuild" in kinds
    evs = eng.telemetry.recorder.events()
    # events are seq-ordered with timestamps and structured fields
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    adm = next(e for e in evs if e["event"] == "admission")
    assert adm["prompt_tokens"] == 8 and "ts" in adm
    ret = next(e for e in evs if e["event"] == "retirement")
    assert ret["reason"] == "length" and ret["generated_tokens"] == 3

    # the ring is bounded: overflow drops oldest and counts drops
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("x", i=i)
    evs = rec.events()
    assert len(evs) == 4 and evs[0]["i"] == 6
    assert rec.stats() == {"events": 4, "total": 10, "dropped": 6}


def test_abort_paths_record_and_count():
    """Aborts from BOTH the waiting queue and a running slot land in
    the abort counter, the finish-reason counter, and the recorder."""
    eng = make_engine(max_batch_size=1, enable_prefix_caching=False)
    tag = eng.config.metrics_model_id
    rng = np.random.default_rng(5)
    r1 = Request("run1", rng.integers(2, 200, 6).tolist(),
                 SamplingParams(max_tokens=20))
    r2 = Request("wait1", rng.integers(2, 200, 6).tolist(),
                 SamplingParams(max_tokens=20))
    eng.add_request(r1)
    eng.add_request(r2)
    eng.step()
    assert eng.abort("wait1")            # still waiting (1 slot)
    assert eng.abort("run1")             # running
    text = eng.prometheus_metrics()
    assert _sample(text, "ray_tpu_llm_aborts_total", model=tag) == 2.0
    assert _sample(text, "ray_tpu_llm_finished_total",
                   model=tag, reason="abort") == 2.0
    evs = eng.telemetry.recorder.events()
    wheres = {e["request_id"]: e["where"] for e in evs
              if e["event"] == "abort"}
    assert wheres == {"wait1": "waiting", "run1": "running"}
    assert eng.telemetry.summary()["aborted"] == 2


# ----------------------------------------------------------- stats merge

def test_stats_requests_summary_and_budget_utilization():
    eng = make_engine()
    rng = np.random.default_rng(6)
    eng.generate([rng.integers(2, 200, 10).tolist() for _ in range(2)],
                 SamplingParams(max_tokens=5))
    s = eng.stats()["requests"]
    assert s["enabled"] is True
    assert s["finished"] == {"length": 2}
    assert s["generated_tokens"] == 10
    assert s["prompt_tokens"] == 20
    assert s["ttft_ms_avg"] > 0 and s["e2e_ms_avg"] >= s["ttft_ms_avg"]
    assert 0 < s["budget_utilization"] <= 1
    assert s["flight_recorder"]["events"] > 0
    assert s["live"] == 0


def test_telemetry_disabled_is_inert():
    """enable_metrics=False: generation works, stats say disabled,
    nothing lands in recorder or timelines (the bench overhead A/B's
    baseline arm)."""
    eng = make_engine(enable_metrics=False)
    rng = np.random.default_rng(7)
    reqs = eng.generate([rng.integers(2, 200, 8).tolist()],
                        SamplingParams(max_tokens=4))
    assert len(reqs[0].output_tokens) == 4
    assert eng.stats()["requests"] == {"enabled": False}
    assert eng.telemetry.recorder.events() == []
    # no request timelines (only the process tracing ring, if any)
    names = {e["name"] for e in eng.chrome_trace()["traceEvents"]}
    assert "queued" not in names


def test_disabled_and_enabled_engines_token_exact():
    """Instrumentation must never change what the engine computes:
    greedy output is bit-identical with metrics on and off."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(2, 200, n).tolist() for n in (6, 11)]

    def run(flag):
        eng = make_engine(enable_metrics=flag,
                          enable_prefix_caching=False)
        return [r.output_tokens for r in eng.generate(
            [list(p) for p in prompts], SamplingParams(max_tokens=8))]

    assert run(True) == run(False)


# ------------------------------------------------------------- profiling

def test_profile_next_ticks_writes_trace():
    eng = make_engine()
    rng = np.random.default_rng(9)
    d = eng.profile_next_ticks(2)
    with pytest.raises(RuntimeError, match="already"):
        eng.profile_next_ticks(1)        # one capture at a time
    assert eng.wait_for_profile(60)      # the trace is started off-tick
    eng.generate([rng.integers(2, 200, 8).tolist()],
                 SamplingParams(max_tokens=4))
    assert eng.wait_for_profile(60)      # the trace is written off-tick
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    if "profile_error" in kinds:
        pytest.skip("jax.profiler unavailable on this backend")
    assert "profile_armed" in kinds and "profile_done" in kinds
    assert os.path.isdir(d) and os.listdir(d)     # trace files landed
    with pytest.raises(ValueError):
        eng.profile_next_ticks(0)
    # capture finished: re-arming is allowed again
    eng.profile_next_ticks(1, log_dir=d)
    assert eng.wait_for_profile(60)
    eng.generate([rng.integers(2, 200, 8).tolist()],
                 SamplingParams(max_tokens=2))
    # leave no export running: while one is, the next engine's armed
    # capture waits for it (`profiling.session_open`, PR 32)
    assert eng.wait_for_profile(60)


def test_tick_does_not_wait_for_the_capture_to_be_written(monkeypatch):
    """A finished capture is stopped and written by a writer thread,
    not under the step lock (ROADMAP A13; on the chip one stop_trace
    held every live stream for 14 s, PERF.md section 6, PR 30): the
    tick that ends the capture returns while the writer still works,
    later ticks run beside it, the capture stays the engine's one
    capture until it is written, and then arming works again."""
    import contextlib
    import threading
    import time
    from ray_tpu.util import profiling
    release, stopping = threading.Event(), threading.Event()

    @contextlib.contextmanager
    def slow_trace(log_dir):
        yield
        stopping.set()
        assert release.wait(60)

    monkeypatch.setattr(profiling, "trace", slow_trace)
    eng = make_engine()
    eng.profile_next_ticks(1)
    assert eng.wait_for_profile(60)      # started, by the same thread
    eng.add_request(Request("p", list(range(2, 10)),
                            SamplingParams(max_tokens=6)))
    t0 = time.monotonic()
    eng.step()                           # the captured tick
    assert stopping.wait(60)             # the writer is in stop_trace
    while eng.has_work():                # ... and ticks go on beside it
        eng.step()
    assert time.monotonic() - t0 < 30
    assert not eng.wait_for_profile(0.01)
    with pytest.raises(RuntimeError, match="already"):
        eng.profile_next_ticks(1)
    assert eng._arm_profile_locked(1) is None
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    assert "profile_done" not in kinds
    release.set()
    assert eng.wait_for_profile(60)
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    assert "profile_done" in kinds and eng._profile is None
    eng.profile_next_ticks(1)            # re-arming works again
    assert eng.wait_for_profile(60)


def test_profile_disarms_on_mid_tick_exception(monkeypatch):
    """Regression (ISSUE 5 review): a tick that raises mid-capture
    must stop the jax.profiler trace and disarm — otherwise the
    capture records forever and every later profile_next_ticks()
    raises 'already armed' with no way out short of a restart."""
    eng = make_engine()
    rng = np.random.default_rng(3)
    eng.profile_next_ticks(4)
    assert eng.wait_for_profile(60)       # the trace is running

    def boom(touched):
        raise RuntimeError("mid-tick failure")

    monkeypatch.setattr(eng, "_step_tick", boom)
    with pytest.raises(RuntimeError, match="mid-tick failure"):
        eng.step()
    monkeypatch.undo()
    assert eng._profile is None           # disarmed, not wedged
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    if "profile_error" not in kinds:      # backend supports profiling
        assert "profile_aborted" in kinds
    eng.profile_next_ticks(1)             # re-arming works again
    assert eng.wait_for_profile(60)
    eng.generate([rng.integers(2, 200, 8).tolist()],
                 SamplingParams(max_tokens=2))
    assert eng.wait_for_profile(60)


# ------------------------------------------------- instrumentation lint

def test_no_instrumentation_under_trace():
    """ISSUE 5 CI gate: no metrics/tracing/telemetry call site inside
    a traced function in the engine, the model forward, or the
    telemetry module itself — instrumentation stays on the host side
    of the dispatch boundary (jaxlint JL009)."""
    from tools.jaxlint.analyzer import analyze_paths

    findings = analyze_paths(
        [str(REPO / "ray_tpu/llm/_internal/engine.py"),
         str(REPO / "ray_tpu/llm/_internal/telemetry.py"),
         str(REPO / "ray_tpu/models/llama_infer.py")],
        root=str(REPO), select={"JL009"})
    assert findings == [], "\n".join(f.render() for f in findings)


# ----------------------------------------------------------- HTTP surface

@pytest.mark.usefixtures("ray_start")
def test_observability_http_endpoints(ray_start):
    """The router's ISSUE 5 surface over real HTTP: /metrics renders
    Prometheus text populated by a STREAMED generation, /debug/trace
    is a valid Chrome trace, /debug/events dumps the flight recorder,
    POST /debug/profile arms a capture — and an unknown GET is a
    clean 404, not the old 'invalid JSON body' 400."""
    import requests
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_openai_app

    app = build_openai_app({"llm_configs": [LLMConfig(
        model_id="m0", model_source="debug",
        engine_kwargs=dict(max_batch_size=4, page_size=8,
                           num_pages=128))]})
    try:
        serve.run(app, name="llm", route_prefix="/",
                  http_options=serve.HTTPOptions(port=8129),
                  timeout_s=180)
        base = "http://127.0.0.1:8129"
        # the satellite fix first: unknown GET path → 404 JSON
        r = requests.get(f"{base}/not/a/route", timeout=30)
        assert r.status_code == 404
        assert "invalid JSON body" not in r.text
        assert "no route" in r.json()["error"]

        # streamed generation populates the SLO series
        r = requests.post(
            f"{base}/v1/chat/completions",
            json={"model": "m0", "max_tokens": 6, "stream": True,
                  "messages": [{"role": "user", "content": "hey"}]},
            stream=True, timeout=120)
        assert r.status_code == 200
        assert b"[DONE]" in b"".join(r.iter_content())

        r = requests.get(f"{base}/metrics", timeout=60)
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.text
        assert _sample(text, "ray_tpu_llm_ttft_seconds_count",
                       model="m0") >= 1
        assert _sample(text, "ray_tpu_llm_itl_seconds_count",
                       model="m0") >= 1
        assert _sample(text, "ray_tpu_llm_finished_total",
                       model="m0", reason="length") >= 1
        assert _sample(text, "ray_tpu_llm_kv_page_occupancy",
                       model="m0") is not None
        assert "# TYPE ray_tpu_llm_ttft_seconds histogram" in text
        # merged exposition: no duplicate series, one header per
        # family (in-process replicas share the registry — naive
        # concatenation would repeat every sample)
        samples = [l for l in text.splitlines()
                   if l and not l.startswith("#")]
        assert len(samples) == len(set(samples))
        types = [l for l in text.splitlines() if l.startswith("# TYPE ")]
        assert len(types) == len(set(types))

        r = requests.get(f"{base}/debug/trace", timeout=60)
        assert r.status_code == 200
        names = {e["name"] for e in r.json()["traceEvents"]}
        assert {"queued", "prefill", "decode"} <= names
        # ISSUE 7 satellite: ring fill/drop counters ride the doc
        ring = r.json()["metadata"]["m0"]["tracing_ring"]
        assert ring["capacity"] > 0 and "dropped" in ring

        r = requests.get(f"{base}/debug/events", timeout=60)
        kinds = {e["event"] for e in r.json()["models"]["m0"]}
        assert {"admission", "retirement"} <= kinds

        r = requests.post(f"{base}/debug/profile",
                          json={"ticks": 2}, timeout=60)
        assert r.status_code == 200
        m0 = r.json()["models"]["m0"]
        assert m0.get("error") or (m0["ticks"] == 2 and m0["log_dir"])

        # /stats carries the request SLO summary alongside tick_times
        r = requests.get(f"{base}/stats", timeout=60)
        reqs_summary = r.json()["models"]["m0"]["requests"]
        assert reqs_summary["finished"].get("length", 0) >= 1
    finally:
        serve.shutdown()


# ----------------------- perf families across fleet topologies (ISSUE 11)

def _drive(eng, n_req=2, gen=8):
    rng = np.random.default_rng(5)
    for i in range(n_req):
        eng.add_request(Request(
            f"pf{uuid.uuid4().hex[:6]}",
            rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=gen)))
    while eng.has_work():
        eng.step()


def test_perf_families_shared_registry_topology():
    """In-process fleet replicas share ONE registry: engines tagged
    replica=r0/r1 drive work, a single render carries BOTH replicas'
    perf series (mfu/mbu gauges, flops and per-kind hbm_bytes
    counters, per-phase tokens_per_s), and merge_expositions over two
    sequential renders of the same registry dedups to one series per
    identity and one HELP/TYPE per family."""
    from ray_tpu.util.metrics import merge_expositions

    tag = f"pf{uuid.uuid4().hex[:10]}"
    engines = [make_engine(metrics_model_id=tag,
                           metrics_replica_id=f"r{i}")
               for i in range(2)]
    for eng in engines:
        _drive(eng)
    text = engines[0].prometheus_metrics()
    text = engines[1].prometheus_metrics()   # refreshes r1's gauges too
    for rid in ("r0", "r1"):
        assert _sample(text, "ray_tpu_llm_mfu",
                       model=tag, replica=rid) is not None
        assert _sample(text, "ray_tpu_llm_mbu",
                       model=tag, replica=rid) is not None
        v = _sample(text, "ray_tpu_llm_flops_total",
                    model=tag, replica=rid)
        assert v is not None and v > 0
        for kind in ("weights", "kv_read", "kv_write"):
            assert _sample(text, "ray_tpu_llm_hbm_bytes_total",
                           model=tag, replica=rid, kind=kind), kind
        for phase in ("decode", "prefill"):
            assert _sample(text, "ray_tpu_llm_tokens_per_s",
                           model=tag, replica=rid,
                           phase=phase) is not None
    merged = merge_expositions([text,
                                engines[0].prometheus_metrics()])
    assert merged.count("# TYPE ray_tpu_llm_mfu gauge") == 1
    assert merged.count("# TYPE ray_tpu_llm_hbm_bytes_total counter") \
        == 1
    series = [ln.rsplit(" ", 1)[0] for ln in merged.splitlines()
              if ln.startswith("ray_tpu_llm_mfu{")
              and f'model="{tag}"' in ln]
    assert len(series) == len(set(series)) == 2


def test_perf_families_cross_process_relabel_topology():
    """Separate-registry replicas render IDENTICAL series (no replica
    tag); the fleet scrape relabels each exposition with replica=<id>
    before merging — afterwards the new families must carry distinct
    per-replica series instead of colliding, with one header per
    family (the ISSUE 6 relabel contract extended to ISSUE 11)."""
    from ray_tpu.util.metrics import (merge_expositions,
                                      relabel_exposition)

    tag = f"px{uuid.uuid4().hex[:10]}"
    eng = make_engine(metrics_model_id=tag)     # replica unset -> ""
    _drive(eng)
    text = eng.prometheus_metrics()
    assert _sample(text, "ray_tpu_llm_mfu", model=tag) is not None
    merged = merge_expositions([
        relabel_exposition(text, {"replica": "rA"}),
        relabel_exposition(text, {"replica": "rB"}),
    ])
    for rid in ("rA", "rB"):
        assert _sample(merged, "ray_tpu_llm_mfu",
                       model=tag, replica=rid) is not None
        for kind in ("weights", "kv_read", "kv_write"):
            assert _sample(merged, "ray_tpu_llm_hbm_bytes_total",
                           model=tag, replica=rid, kind=kind), kind
        for phase in ("decode", "prefill"):
            assert _sample(merged, "ray_tpu_llm_tokens_per_s",
                           model=tag, replica=rid,
                           phase=phase) is not None
    # the un-relabeled series collided into per-replica identities:
    # nothing for this tag survives WITHOUT a replica label
    assert _sample(merged, "ray_tpu_llm_mfu", model=tag) is None
    assert merged.count("# TYPE ray_tpu_llm_tokens_per_s gauge") == 1


# --------- tenant + anomaly families across fleet topologies (ISSUE 13)

def _drive_tenants(eng, gen=8):
    """Two tenants: the default one ("" — label omitted) and an
    explicit one, so the tenant-labeled families carry both shapes."""
    rng = np.random.default_rng(5)
    for i in range(4):
        eng.add_request(Request(
            f"tn{uuid.uuid4().hex[:6]}",
            rng.integers(2, 250, 12).tolist(),
            SamplingParams(max_tokens=gen),
            tenant="acme" if i % 2 else ""))
    while eng.has_work():
        eng.step()


def test_tenant_anomaly_families_shared_registry_topology():
    """ISSUE 13 over the shared-registry fleet topology: both
    replicas' tenant counters and anomaly families render in one
    exposition; the default tenant's series carry NO tenant label
    (byte-identical single-tenant contract); merge_expositions over
    two renders dedups to one series per identity and one HELP/TYPE
    per family."""
    from ray_tpu.util.metrics import merge_expositions

    tag = f"tf{uuid.uuid4().hex[:10]}"
    engines = [make_engine(metrics_model_id=tag,
                           metrics_replica_id=f"r{i}")
               for i in range(2)]
    for eng in engines:
        _drive_tenants(eng)
    engines[0].prometheus_metrics()
    text = engines[1].prometheus_metrics()   # refreshes r1's gauges too
    for rid in ("r0", "r1"):
        # explicit tenant labeled; default tenant label-free
        for tenant_tags in ({"tenant": "acme"}, {}):
            v = _sample(text, "ray_tpu_llm_tenant_flops_total",
                        model=tag, replica=rid, **tenant_tags)
            assert v is not None and v > 0, (rid, tenant_tags)
            assert _sample(text, "ray_tpu_llm_tenant_hbm_bytes_total",
                           model=tag, replica=rid,
                           **tenant_tags) is not None
            for phase in ("decode", "prefill"):
                assert _sample(text, "ray_tpu_llm_tenant_tokens_total",
                               model=tag, replica=rid, phase=phase,
                               **tenant_tags) is not None
        assert _sample(text, "ray_tpu_llm_tick_anomaly_rate",
                       model=tag, replica=rid) == 0.0
    merged = merge_expositions([text,
                                engines[0].prometheus_metrics()])
    assert merged.count(
        "# TYPE ray_tpu_llm_tenant_flops_total counter") == 1
    assert merged.count(
        "# TYPE ray_tpu_llm_tick_anomaly_rate gauge") == 1
    series = [ln.rsplit(" ", 1)[0] for ln in merged.splitlines()
              if ln.startswith("ray_tpu_llm_tenant_flops_total{")
              and f'model="{tag}"' in ln]
    # 2 replicas x 2 tenants, each exactly once after the merge
    assert len(series) == len(set(series)) == 4


def test_tenant_anomaly_families_cross_process_relabel_topology():
    """ISSUE 13 over the separate-registry topology: identical
    expositions relabel with replica=<id> before merging — tenant and
    anomaly series split per replica instead of colliding, and the
    tenant label survives the relabel untouched."""
    from ray_tpu.util.metrics import (merge_expositions,
                                      relabel_exposition)

    tag = f"tx{uuid.uuid4().hex[:10]}"
    eng = make_engine(metrics_model_id=tag)     # replica unset -> ""
    _drive_tenants(eng)
    text = eng.prometheus_metrics()
    assert _sample(text, "ray_tpu_llm_tenant_flops_total",
                   model=tag, tenant="acme") is not None
    merged = merge_expositions([
        relabel_exposition(text, {"replica": "rA"}),
        relabel_exposition(text, {"replica": "rB"}),
    ])
    for rid in ("rA", "rB"):
        for tenant_tags in ({"tenant": "acme"}, {}):
            assert _sample(merged, "ray_tpu_llm_tenant_flops_total",
                           model=tag, replica=rid,
                           **tenant_tags) is not None, (rid,
                                                        tenant_tags)
        assert _sample(merged, "ray_tpu_llm_tick_anomaly_rate",
                       model=tag, replica=rid) is not None
        assert _sample(merged, "ray_tpu_llm_tick_anomalies_total",
                       model=tag, replica=rid) is None  # none fired
    # nothing for this tag survives WITHOUT a replica label
    assert _sample(merged, "ray_tpu_llm_tenant_flops_total",
                   model=tag, tenant="acme") is None
    assert merged.count(
        "# TYPE ray_tpu_llm_tenant_tokens_total counter") == 1
