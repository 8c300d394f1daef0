"""ray_tpu.llm: TPU-native LLM serving and batch inference.

Reference parity: python/ray/llm + serve.llm public API
(python/ray/serve/llm/__init__.py — LLMConfig, build_openai_app), with
the external vLLM engine replaced by the in-repo TPU engine
(paged KV cache + continuous batching, _internal/engine.py).

Observability (ISSUE 5; details: BENCH_CORE.md "Observability
anatomy"): the router serves `GET /metrics` (Prometheus text),
`GET /stats` (JSON incl. tick-pipeline + request SLO summaries),
`GET /debug/trace` (Chrome-trace request lifecycles),
`GET /debug/events` (engine flight recorder),
`POST /debug/profile` (jax.profiler capture of the next N ticks) and
`POST /debug/dump` (postmortem black-box bundle, ISSUE 7).
All series carry a `model` tag (and a `replica` tag in fleets).

Fleet endpoints (ISSUE 6/7; `ray_tpu.serve.llm` — the multi-replica
ingress from `build_llm_fleet_app`, details: BENCH_CORE.md "Serving
fleet anatomy" + "Fleet observability anatomy"):

    endpoint                    payload
    POST /v1/chat/completions   unary or SSE; 429 + Retry-After on overload
    POST /v1/completions        unary or SSE; 429 + Retry-After on overload
    GET  /v1/models             the fleet's model (+ live adapters)
    GET  /fleet                 per-replica routing inputs (status, inflight,
                                KV occupancy, queue depth, last-tick age),
                                router/admission counters, watchdog burn
                                state, autoscale events
    GET  /stats                 per-replica engine stats + fleet status
    GET  /metrics               ONE Prometheus exposition for the fleet,
                                series tagged `replica` per engine
    GET  /debug/events          per-replica flight recorders
    GET  /debug/trace           merged Chrome-trace request lifecycles
    GET  /fleet/debug/trace     time-aligned fleet trace: ingress spans +
                                every replica's lifecycles with Perfetto
                                flow arrows; ?request_id= / ?trace_id=
                                narrow to one request
    GET  /fleet/debug/events    ONE time-ordered event stream merging all
                                replicas' flight recorders + the ingress's
                                (slo_alert, brownout, dumps); ?request_id=
    GET  /fleet/debug/bundles   list every replica's black-box spool;
                                ?replica=&id= fetches one bundle
    POST /debug/dump            snapshot a postmortem bundle per replica
    POST /v1/batch              submit a batch-lane job (ISSUE 14):
                                {"requests": [<completion/chat body>...],
                                "method": "completions"|"chat"} -> job
                                brief; priority-0, admission-exempt,
                                preemptible bulk inference
    GET  /v1/batch              list batch jobs + lane stats
    GET  /v1/batch/{id}         one job's status + per-request results
    POST /v1/batch/{id}/cancel  stop a job's unlaunched requests
                                (in-flight ones finish; results kept)

ISSUE 7 fleet-scoped metric additions (ingress registry):

    name                                    type       notes
    ray_tpu_llm_slo_burn_rate               gauge      + `slo` (ttft|queue_wait|e2e)
                                                       and `window` (short|long) tags;
                                                       1.0 = spending the error budget
                                                       exactly at the allowed rate
    ray_tpu_llm_slo_alerts_total            counter    watchdog page transitions, + `slo`

ISSUE 9 failure-plane metric additions (ingress registry; details:
BENCH_CORE.md "Fault tolerance anatomy"):

    name                                    type       notes
    ray_tpu_llm_failovers_total             counter    re-dispatches after a replica
                                                       failure (token-exact mid-stream
                                                       continuations + unary retries)
    ray_tpu_llm_replica_evictions_total     counter    health-state-machine ring evictions
    ray_tpu_llm_breaker_state               gauge      per `replica`: 0 closed / 1 open /
                                                       2 half-open
    ray_tpu_llm_deadline_sheds_total        counter    + `stage` (admission|engine):
                                                       requests shed/aborted past their
                                                       client `deadline_s`

Single-replica metric catalogue:

    name                                    type       notes
    ray_tpu_llm_ttft_seconds                histogram  queued -> first host-visible token
    ray_tpu_llm_itl_seconds                 histogram  gap between consecutive decode tokens
    ray_tpu_llm_queue_wait_seconds          histogram  queued -> admitted
    ray_tpu_llm_e2e_latency_seconds         histogram  queued -> finished
    ray_tpu_llm_prompt_tokens_total         counter    admitted prompt tokens
    ray_tpu_llm_generated_tokens_total      counter    emitted output tokens
    ray_tpu_llm_finished_total              counter    + `reason` tag
                                                       (stop|length|abort|deadline)
    ray_tpu_llm_aborts_total                counter    client-gone aborts
    ray_tpu_llm_drains_total                counter    tick-pipeline barriers
    ray_tpu_llm_running_requests            gauge      slots occupied
    ray_tpu_llm_waiting_requests            gauge      admission queue depth
    ray_tpu_llm_kv_pages_used               gauge      referenced KV pages
    ray_tpu_llm_kv_pages_free               gauge      allocatable (free + evictable)
    ray_tpu_llm_kv_page_occupancy           gauge      used / usable
    ray_tpu_llm_prefix_cache_hit_rate       gauge      hit tokens / queried tokens
    ray_tpu_llm_token_budget_utilization    gauge      packed / budget, unified ticks
    ray_tpu_llm_batch_lane_tokens_total     counter    tokens emitted to batch-lane
                                                       requests (ISSUE 14) — EXCLUDED
                                                       from every SLO family above
    ray_tpu_llm_batch_lane_finished_total   counter    + `reason`: batch-lane finishes

ISSUE 10 KV-memory-hierarchy additions (host-offload tier + preemption
spill/restore; details: BENCH_CORE.md "KV memory hierarchy anatomy";
`finished_total` gains reason `error` for true page exhaustion):

    ray_tpu_llm_kv_host_pages_used          gauge      KV pages parked in the host-RAM
                                                       tier (spilled, awaiting restore)
    ray_tpu_llm_parked_sessions             gauge      preempted sequences parked in the
                                                       host tier
    ray_tpu_llm_kv_page_pressure            gauge      (device pages used + parked host
                                                       pages) / usable; > 1 means the
                                                       engine is oversubscribed
    ray_tpu_llm_kv_spills_total             counter    victim sequences spilled
                                                       device -> host
    ray_tpu_llm_kv_restores_total           counter    parked sequences restored
                                                       host -> device, token-exact
    ray_tpu_llm_preemptions_total           counter    + `reason` tag (growth|manual|...)
    ray_tpu_llm_fleet_page_pressure         gauge      fleet max page pressure (ingress
                                                       registry; watchdog hysteresis +
                                                       spillability-gated brownout)

ISSUE 11 per-dispatch perf accounting (analytic FLOP/byte cost model;
details: BENCH_CORE.md "Perf accounting anatomy"; the same numbers
ride `stats()["perf"]`, `/fleet` rows, and Perfetto counter tracks in
`/debug/trace`; regression gate: `python -m tools.perfdiff` vs the
committed PERF_BASELINE.json):

    ray_tpu_llm_flops_total                 counter    analytic model FLOPs executed
                                                       (GEMM + attention split)
    ray_tpu_llm_hbm_bytes_total             counter    + `kind` tag: weights|kv_read|
                                                       kv_write (device HBM) and
                                                       d2h|h2d (KV spill/restore)
    ray_tpu_llm_mfu                         gauge      model-FLOPs utilization vs the
                                                       hardware envelope, recent window
    ray_tpu_llm_mbu                         gauge      HBM-bandwidth utilization vs the
                                                       envelope, recent window
    ray_tpu_llm_tokens_per_s                gauge      + `phase` tag (decode|prefill):
                                                       goodput over the window span
    ray_tpu_llm_fleet_mfu                   gauge      goodput-weighted mean replica MFU
                                                       (ingress registry)
    ray_tpu_llm_fleet_mbu                   gauge      goodput-weighted mean replica MBU
                                                       (ingress registry)

ISSUE 12 fleet KV transport (disaggregated prefill/decode, live
session migration, fleet prefix store; details: BENCH_CORE.md "KV
transport anatomy"; `finished_total` gains reason `migrated` for
sessions that left the replica mid-stream):

    ray_tpu_llm_kv_host_bytes_used          gauge      host-RAM bytes pinned by parked
                                                       KV payloads (beside the page
                                                       count: migration / prefix-store
                                                       byte pressure)
    ray_tpu_llm_kv_sessions_shipped_total   counter    + `kind` tag (disagg|migration|
                                                       restore): parked sessions shipped
                                                       between replicas (ingress registry)
    ray_tpu_llm_kv_ship_bytes_total         counter    + `direction` tag (export|import):
                                                       serialized transport bytes
                                                       (ingress registry)
    ray_tpu_llm_prefix_store_hits_total     counter    fleet prefix-store entries seeded
                                                       into a replica that had not
                                                       prefilled the prefix itself
                                                       (ingress registry)

KV-transport replica endpoints (fleet-internal, reached through the
replica client interface — the public ingress strips their plumbing
keys): `export_session` / `import_session` (ship a parked session),
`prefill_export` (disaggregated prefill: run the prompt, park,
export), `resume_stream_tokens` (import + stream the remainder with
global token indices), `export_prefix` / `import_prefix` (fleet
prefix store), `list_sessions`. Migration/handoff spans land in
`GET /fleet/debug/trace` under the `kv_transport` category.

ISSUE 13 per-request cost attribution + tick-anomaly analyzer
(details: BENCH_CORE.md "Attribution & anomaly anatomy"; receipts
also ride the finish event, `stats()["attribution"]`, and the
OpenAI response's `usage.cost` block; tenant identity comes from the
OpenAI `user` field at admission, "" = default tenant whose label is
omitted so single-tenant scrapes stay byte-identical):

    ray_tpu_llm_tenant_flops_total          counter    + `tenant`: analytic FLOPs
                                                       attributed to finished requests
    ray_tpu_llm_tenant_hbm_bytes_total      counter    + `tenant`: attributed device-HBM
                                                       bytes (weights share + KV traffic)
    ray_tpu_llm_tenant_tokens_total         counter    + `tenant`, `phase`
                                                       (decode|prefill)
    ray_tpu_llm_tick_anomalies_total        counter    + `kind` (recompile|h2d_transfer|
                                                       gc_pause|host_fold_stall|
                                                       device_straggler|unknown):
                                                       classified slow-tick anomalies
    ray_tpu_llm_tick_anomaly_rate           gauge      anomalous fraction of the recent
                                                       tick window (rides /fleet rows)
    ray_tpu_llm_fleet_anomaly_rate          gauge      fleet max anomaly rate (ingress
                                                       registry; watchdog page precursor
                                                       with alert/clear hysteresis)
    ray_tpu_llm_fleet_queue_wait_seconds    histogram  + `tenant`: front-door admission
                                                       queue wait (ingress registry)
    ray_tpu_llm_fleet_admission_rejected_total
                                            counter    + `tenant`, `reason` (queue_full|
                                                       brownout|queue_wait_slo|deadline):
                                                       per-tenant 429/shed diagnosis

    endpoint                      payload
    GET /debug/attribution        per-model top-K cost receipts by
                                  FLOPs + tenant rollups +
                                  conservation totals
    GET /fleet/debug/attribution  fleet-merged receipts: one re-ranked
                                  top-K, tenant rollups summed
                                  fleet-wide (?k=&tenant=)

An anomalous tick additionally records a `tick_anomaly` flight event
(batch composition attached), auto-arms a `profile_next_ticks`
capture, and drops a rate-limited black-box bundle (cause
`tick_anomaly`, fetchable at GET /fleet/debug/bundles).

ISSUE 16 quantized serving (int8/fp8 KV pages with fused-dequant
attention, quantize-on-spill/ship, quantized tp collectives; details:
BENCH_CORE.md "Quantized serving anatomy"):

    config knob (EngineConfig)              notes
    kv_dtype="f32"|"int8"|"fp8"             KV page storage kind. Quantized
                                            pages carry per-(token, head) f32
                                            scales; append quantizes once,
                                            attention dequantizes fused in the
                                            kernel's HBM->VMEM stream. Spill/
                                            restore and every ship path move
                                            the narrow bytes + scales (wire v2)
                                            and are token-exact vs a same-kind
                                            engine; imports across kinds are
                                            rejected (TransportError -> fleet
                                            replay fallback). ~3.5x (f32) /
                                            ~1.9x (bf16) smaller KV footprint
                                            and read traffic.
    quantized_collectives=True              arms the EQuARX-style block-scaled
                                            quantized allreduce/allgather
                                            helpers (ops/quantized_collectives)
                                            for the tp mesh, tolerance-gated
                                            vs f32 in tests/test_kv_quant.py.
                                            On the explicit mesh_shape= path
                                            (ISSUE 17) it also routes the
                                            row-parallel lm_head's (B, V)
                                            partial-logits psum — the dominant
                                            per-tick collective payload —
                                            through quantized_psum; per-layer
                                            residual psums stay exact f32

ISSUE 17 pod-scale data plane (tp-sharded engine replicas on named
meshes, slice-aware fleet placement; details: BENCH_CORE.md
"Pod-scale serving anatomy"):

    config knob (EngineConfig)              notes
    mesh_shape=(1, tp)                      shard the WHOLE serving engine —
                                            not just the kernel — across a
                                            named (data, tp) 2D mesh: params
                                            land in the Megatron layout
                                            (column-parallel wq/wk/wv/wg/wi,
                                            row-parallel wo/wd + lm_head), KV
                                            and scale pools shard over kv
                                            heads along `tp`, page tables and
                                            sampling state replicate, and the
                                            unified ragged tick runs as ONE
                                            shard_map'd collective-bearing
                                            program — still one dispatch, zero
                                            h2d, zero recompiles per tick
                                            (dispatch-guard suite at tp=2).
                                            The data dim must be 1 (scale
                                            replicas via the fleet). Mutually
                                            exclusive with mesh= (the GSPMD
                                            MeshSpec path); rejects MoE and
                                            LoRA. Session export/
                                            import and spill/restore stay on
                                            the topology-free wire format, so
                                            sessions move tp=2 <-> tp=1
                                            token-exact.
    tp_axis="tp"                            the named tp mesh axis (rename if
                                            an outer program owns "tp")

    fleet field                             notes
    FleetConfig.slice_shape=(1, 2)          every replica IS one slice: the
                                            deployment builder injects
                                            mesh_shape into each replica's
                                            engine_kwargs, so a scale-up
                                            provisions a whole 2-chip slice
    stats()["chips"] / /fleet row "chips"   chips behind each replica's mesh
                                            (ReplicaSnapshot.chips); the
                                            /fleet autoscale block adds
                                            chips_per_slice + active_chips,
                                            and autoscaler decisions carry
                                            active_chips/target_chips
    stats()["perf"].mfu / fleet mfu         PER-CHIP: the perf accountant's
                                            envelope is peak x n_chips, so
                                            the 0.40 serving-MFU target reads
                                            per chip at any slice size
                                            (bench.py --mesh 1x2 reports the
                                            same per-chip framing)

    ray_tpu_llm_kv_device_bytes_used        gauge      device HBM bytes in used
                                                       KV pages, from the
                                                       CONFIGURED page dtype
                                                       (values + scale pages)

`stats()` gains `kv_dtype`, `kv_page_bytes` (per-page bytes for the
configured kind) and `kv_device_bytes_used`; the perf cost model's
kv_read/kv_write byte streams and spill/restore d2h/h2d accounting are
parametrized by the same kind (f32 fingerprints byte-identical).

ISSUE 20 traffic capture + trace replay (always-on ingress flight
recorder, deterministic capture replay, capture-diff regression
gates; details: BENCH_CORE.md "Traffic capture & replay anatomy"):

    endpoint                      payload
    GET  /fleet/debug/traffic     recorder stats + recent ring records
                                  (?n=&since= cursor polling);
                                  ?capture=1 downloads the last sealed
                                  capture (RTTC1 segments, crc32 per
                                  line, typed errors on corruption)
    POST /fleet/debug/traffic     {"action": "start"|"mark"|"stop"}:
                                  arm / annotate / seal a capture

    name                                    type       notes
    ray_tpu_llm_traffic_captured_total      counter    requests recorded by the
                                                       ingress traffic recorder
                                                       (ingress registry)
    ray_tpu_llm_traffic_capture_bytes_total counter    encoded capture bytes
                                                       appended while a capture
                                                       is armed (ingress registry)

Records are privacy-scrubbed by construction (prefix fingerprint +
numeric sampling allowlist, never prompt text). Sealed captures
replay deterministically through the fleet simulator
(`ray_tpu.serve.llm.sim.RecordedTrace`) and gate via
`python -m tools.tracereplay` (banded capture-diff, what-if
re-pricing, in-process fleet replay); `python -m tools.lint` runs
every repo static analyzer as one pre-commit gate.

Instrumentation is recorded purely from host-side engine events (zero
device syncs, zero extra dispatches — the dispatch-guard suite runs
with it enabled); disable per engine with
`engine_kwargs={"enable_metrics": False}` (the perf accounting with
`enable_perf_accounting=False`, and the ISSUE 13 planes with
`enable_attribution=False` / `enable_anomaly_detection=False`).
"""

from __future__ import annotations

from .._private.usage import record_library_usage as _rlu
_rlu("llm")
del _rlu

import dataclasses
from typing import Any, Dict, List, Optional

from ._internal.engine import (EngineConfig, InferenceEngine, Request,
                               SamplingParams)
from ._internal.tokenizer import ByteTokenizer, load_tokenizer


@dataclasses.dataclass
class LLMConfig:
    """Reference: serve/llm LLMConfig (pydantic there, dataclass here)."""
    model_id: str = "default"
    model_source: Any = "debug"          # preset name or LlamaConfig
    tokenizer_source: Optional[str] = None
    engine_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    deployment_config: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    accelerator_type: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "model_id": self.model_id,
            "model_source": self.model_source,
            "tokenizer_source": self.tokenizer_source,
            "engine_kwargs": dict(self.engine_kwargs),
        }


def build_llm_deployment(llm_config: LLMConfig):
    """One LLMServer deployment for one model."""
    from .. import serve
    from ._internal.server import LLMServerImpl

    dep_cfg = dict(llm_config.deployment_config)
    dep_cfg.setdefault("name", f"LLMServer:{llm_config.model_id}")
    dep_cfg.setdefault("max_ongoing_requests", 64)
    if llm_config.accelerator_type:
        opts = dict(dep_cfg.get("ray_actor_options") or {})
        # chips follow the engine mesh: a tp engine needs tp chips on
        # its replica (reference sizes vLLM worker placement the same
        # way, vllm_models.py:123-139). Explicit-tp slices
        # (engine_kwargs.mesh_shape, ISSUE 17) size the same way:
        # a (1, tp) slice reserves tp chips.
        ekw = llm_config.engine_kwargs or {}
        mesh = ekw.get("mesh")
        mesh_shape = ekw.get("mesh_shape")
        chips = 1
        if mesh_shape is not None:
            chips = max(1, int(mesh_shape[0]) * int(mesh_shape[1]))
        elif mesh is not None:
            tp = (mesh.get("tp", 1) if isinstance(mesh, dict)
                  else getattr(mesh, "tp", 1))
            if tp == -1:
                # -1 resolves against VISIBLE devices inside the
                # replica; here we must size the reservation itself, so
                # a wildcard would silently under-provision to 1 chip
                raise ValueError(
                    "give an explicit tp size in engine_kwargs.mesh "
                    "when accelerator_type is set (wildcard -1 cannot "
                    "size the replica's chip reservation)")
            chips = max(1, tp)
        opts.setdefault("num_tpus", chips)
        dep_cfg["ray_actor_options"] = opts
    return serve.deployment(**dep_cfg)(LLMServerImpl).bind(
        llm_config.to_dict())


def build_openai_app(config: Dict[str, Any]):
    """{"llm_configs": [LLMConfig, ...]} → Application serving the
    OpenAI API (reference: serve/llm build_openai_app)."""
    from .. import serve
    from ._internal.server import LLMRouterImpl

    llm_configs = config["llm_configs"]
    servers = [build_llm_deployment(c) for c in llm_configs]
    return serve.deployment(name="LLMRouter", max_ongoing_requests=256)(
        LLMRouterImpl).bind(*servers)


__all__ = [
    "LLMConfig", "build_openai_app", "build_llm_deployment",
    "InferenceEngine", "EngineConfig", "SamplingParams", "Request",
    "ByteTokenizer", "load_tokenizer",
]
