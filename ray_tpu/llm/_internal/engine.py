"""TPU-native LLM inference engine: continuous batching over paged KV.

Net-new component (the reference wraps external vLLM:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py; here
the engine itself is built TPU-first — SURVEY.md §7 hard part #1).

Design:
- ONE forward dispatch per tick (the key split and the position update
  beside it are small programs of their own), and two forward programs
  in all: a tick with a prefilling slot runs the ragged step
  (_ragged_step) — one jitted program consuming a flat ragged token
  batch (each decoding slot contributes 1 token, prefilling slots
  contribute chunks packed under a Sarathi-style token budget; Ragged
  Paged Attention, PAPERS.md), prompt KV scattering into the page pool
  inside the same jit. Every other tick runs the device-resident decode
  program (_decode).
- The ragged program compiles per (token bucket, context bucket).
- Sampling (greedy/temperature/top-p) fused into both programs.
- Page pools are donated through every call → XLA updates KV in place
  in HBM, no copy of the cache per token.
- Continuous batching: each step() admits waiting requests into free
  slots (admission-controlled by the page allocator), then decodes all
  active slots together.
- Pipelined readback (ISSUE 4): steady-state decode is a two-deep
  software pipeline — tick t's token readback streams home
  asynchronously while tick t+1 computes from device-resident state;
  the host fold lags one tick and any structural event drains the
  pipeline first (EngineConfig.async_readback).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import itertools
import json
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models import llama
from ...models.llama import LlamaConfig
from ...models.family import family_of, resolve_config, store_params
from ...util import thread_sanitizer
from .anomaly import own_program_ms
from .kv_cache import CacheManager
from .telemetry import EngineTelemetry


@dataclasses.dataclass
class EngineConfig:
    model: Any = "debug"                 # preset name or LlamaConfig
    max_batch_size: int = 8
    page_size: int = 16
    num_pages: int = 512
    # pages of each cache group by its name, for a model whose family
    # describes more than one (models/cache_row.CacheGroup: a window
    # group wants fewer pages than a full one); a group not named here,
    # and every group when this is None, gets `num_pages`
    num_pages_by_group: Optional[Dict[str, int]] = None
    max_seq_len: Optional[int] = None    # default: model max_seq
    seed: int = 0
    # "auto": the Pallas attention kernels on TPU, dense gather elsewhere.
    # Also accepts "gather" | "pallas" | "pallas_interpret".
    decode_impl: str = "auto"
    # Chunked prefill: a prompt advances at most this many tokens per
    # engine step, so one long prompt never stalls the running batch's
    # decode ticks (SURVEY §7 hard part 1).
    max_prefill_tokens: int = 512
    # Hash-cons full prompt pages so shared prefixes skip re-prefill.
    enable_prefix_caching: bool = True
    # Sarathi-style global token budget for one ragged tick (Ragged
    # Paged Attention, PAPERS.md): decoding slots take 1 token each,
    # the remainder goes to prefilling slots round-robin (each capped
    # at max_prefill_tokens). 0 → default max_prefill_tokens +
    # max_batch_size: a full chunk always rides on top of the decode
    # tokens, so a single prefilling prompt advances at least one whole
    # chunk per tick (leftover budget may additionally start a second
    # prompt's chunk in the same tick).
    max_num_batched_tokens: int = 0
    # Tensor-parallel serving: a parallel.MeshSpec (tp>1) — params shard
    # over heads/mlp/vocab, the KV page pool over kv_heads, and both
    # forward programs jit over the whole mesh (the reference reaches TP
    # only by placing external vLLM workers, vllm_models.py:123-159).
    # A MeshSpec with pp>1 is refused: there is no pipeline-parallel
    # serving.
    mesh: Any = None
    # Explicit-tp serving on a NAMED 2D mesh (ISSUE 17 / ROADMAP 4):
    # mesh_shape=(1, tp) builds a (data, tp_axis) Mesh via
    # ops/tp_mesh.build_serving_mesh and the whole ragged tick runs as
    # ONE shard_map'd collective-bearing program — params in the
    # Megatron layout (llama_infer.tp_param_specs), KV/scale pools
    # sharded over kv heads, page tables and sampling state replicated,
    # per-layer residual psums in _layer_body, and the row-parallel
    # lm_head's partial logits all-reduced (through
    # ops/quantized_collectives when quantized_collectives=True).
    # Mutually exclusive with mesh= (the GSPMD auto-partitioning path);
    # rejects LoRA.
    # Donation, _read_tokens, async readback, and spill/restore keep
    # the single-dispatch discipline, so the dispatch guard holds at
    # tp>1 (tested on the virtual CPU mesh).
    mesh_shape: Optional[tuple] = None
    tp_axis: str = "tp"
    # Multi-LoRA capacity: adapter stacks are padded to this many slots
    # so registering adapters never changes compiled shapes (one
    # recompile when the FIRST adapter arrives, none after).
    max_loras: int = 8
    # Pipelined engine ticks (ISSUE 4): after dispatching decode tick
    # t, start a NON-BLOCKING device->host copy of its token buffer
    # and immediately dispatch tick t+1 from the device-resident loop
    # state; tick t's tokens fold into host slot state only once t+1
    # is already in flight, so the host fold (EOS/stop/max_tokens
    # checks, streaming) hides behind device compute instead of
    # serializing with it. Host-visible results lag ONE tick: a
    # request may over-generate at most one token, which is discarded
    # at fold time (its KV write stays inside the slot's preallocated
    # pages — the pending-token invariant leaves exactly one token of
    # slack in the prompt+max_tokens reservation; asserted at the
    # fold). Any structural event — admission, retirement, prefill,
    # LoRA registration, abort — drains the in-flight tick first, so
    # those paths stay byte-identical to the synchronous engine.
    # Greedy/penalized decode is token-exact vs sync.
    async_readback: bool = True
    # Request-lifecycle telemetry (ISSUE 5): SLO histograms (TTFT /
    # inter-token latency / queue wait / e2e), token + finish-reason
    # counters, KV-occupancy gauges, per-request Chrome-trace
    # timelines and the engine flight recorder — recorded from
    # host-side admission/fold events ONLY, so instrumentation adds
    # zero device syncs and zero extra dispatches (the dispatch-guard
    # suite runs with this on and off: tests/test_dispatch_guard.py).
    enable_metrics: bool = True
    # Prometheus "model" tag on this engine's metric samples (the
    # server passes its model_id; engines sharing a tag share sample
    # rows in the process-wide registry).
    metrics_model_id: Optional[str] = None
    # Prometheus "replica" tag (ISSUE 6 fleets): distinguishes the N
    # engines of one model's replica fleet. Engines outside a fleet
    # leave it unset and the label is omitted from the exposition, so
    # single-replica scrapes keep the pre-fleet series identity.
    metrics_replica_id: Optional[str] = None
    # Per-request SLO targets in seconds (ISSUE 7): {"ttft", "queue_wait",
    # "e2e"} — observations over target count into the *_bad monotone
    # totals of telemetry.slo_totals(), which the fleet burn-rate
    # watchdog (serve/llm/watchdog.py) windows into burn rates. None
    # keeps telemetry.DEFAULT_SLO_TARGETS.
    slo_targets: Optional[Dict[str, float]] = None
    # Per-dispatch perf accounting (ISSUE 11): an analytic FLOP/byte
    # cost model (perfmodel.py) over the model config + each tick's
    # ragged batch composition records a PerfSample beside the tick
    # times — GEMM/attention FLOPs, weight/KV-page HBM bytes,
    # spill/restore d2h/h2d traffic — and stats()["perf"] reports
    # rolling decode/prefill goodput, MFU/MBU against the hardware
    # envelope, and which roof binds. Pure host arithmetic: zero
    # device syncs, zero extra dispatches (the dispatch-guard suite
    # runs with this ON).
    enable_perf_accounting: bool = True
    # Hardware envelope override (a perfmodel.ENVELOPES key, e.g.
    # "tpu-v5e" | "cpu"). None autodetects from the first jax device;
    # unknown names raise so a typo can't report MFU vs the wrong peak.
    perf_envelope: Optional[str] = None
    # Per-request cost attribution (ISSUE 13, attribution.py): split
    # every committed tick's analytic cost across the requests in its
    # ragged batch into per-request receipts — {flops, hbm_bytes,
    # kv_page_ticks, queue/wall/host/device time shares} — surfaced
    # in the finish event, stats()["attribution"], the usage.cost
    # block, per-tenant Prometheus counters, and /debug/attribution.
    # Conservation: summed receipts equal the tick totals EXACTLY.
    # Pure host arithmetic riding the perf-accounting hooks; requires
    # enable_perf_accounting (silently off without it).
    enable_attribution: bool = True
    # Tick-anomaly flight analyzer (ISSUE 13, anomaly.py): a robust
    # median+MAD residual monitor comparing each tick's measured wall
    # time against the cost model's roofline prediction; a flagged
    # tick is classified (recompile | h2d_transfer | gc_pause |
    # host_fold_stall | device_straggler | unknown) and triggers
    # evidence capture: a tick_anomaly flight-recorder event with the
    # batch composition, an auto-armed profile_next_ticks capture,
    # and a rate-limited black-box bundle. Requires
    # enable_perf_accounting.
    enable_anomaly_detection: bool = True
    # AnomalyConfig field overrides (anomaly.py), e.g.
    # {"warmup_ticks": 16, "z_threshold": 4.0}. None keeps defaults.
    anomaly: Optional[Dict[str, Any]] = None
    # Postmortem black-box bundles (ISSUE 7): on a guard violation or
    # mid-tick crash the engine snapshots its flight recorder, recent
    # tick times, metric exposition, config, and in-flight request
    # states to a bounded on-disk spool (blackbox.py; also on demand
    # via POST /debug/dump). Host-side file IO on FAILURE paths only —
    # a healthy tick never touches it.
    enable_blackbox: bool = True
    blackbox_dir: Optional[str] = None      # None -> per-engine tempdir
    blackbox_capacity: int = 16             # bundles retained
    # -- KV memory hierarchy (ISSUE 10) --------------------------------
    # Host-RAM KV tier + scheduler preemption: under page pressure the
    # engine spills a victim slot's KV pages device→host (async d2h —
    # the copy streams while decode continues), retires the slot, and
    # PARKS the request; once pages free up it is re-admitted with its
    # pages restored token-exact (same per-request sampling keys as
    # failover replay, so greedy AND sampled streams are byte-identical
    # to a never-preempted run). Off by default: "out of pages" stays
    # a hard signal unless the operator opts into the latency tier.
    enable_kv_offload: bool = False
    # Host-tier capacity in pages (None = unbounded). A full tier makes
    # preemption attempts fail, falling back to the exhaustion path.
    host_kv_pages: Optional[int] = None
    # -- Quantized KV serving (ISSUE 16) -------------------------------
    # KV page storage dtype: "f32" (default, pages in model compute
    # dtype) | "int8" | "fp8" (e4m3). Quantized pools store narrow
    # values plus per-(token row, kv head) f32 scales (ops/kv_quant.py)
    # — the write paths quantize at append, the dense gather paths
    # dequantize up front, and the Pallas kernels fuse the dequant
    # multiply into their HBM→VMEM streaming loop, so decode reads
    # ~1/4 the KV bytes. Spill/restore and session/prefix shipping
    # move the narrow pages + scales as stored.
    kv_dtype: str = "f32"
    # EQuARX-style quantized tp collectives (ops/quantized_collectives):
    # expose int8 psum/all_gather for mesh programs that opt in. On the
    # GSPMD mesh= path there are no explicit collectives to swap, so
    # there this knob only arms the ops-layer helpers; on the explicit
    # mesh_shape= path it routes the row-parallel lm_head's (B, V)
    # partial-logits all-reduce — the dominant collective payload —
    # through quantized_psum (per-layer residual psums stay exact f32
    # so pool contents never compound quantization error).
    quantized_collectives: bool = False
    # Optimistic admission (ISSUE 10): None keeps the worst-case
    # prompt+max_tokens reservation. An int W shrinks the reservation
    # to prompt + min(max_tokens, W) tokens; a decoding slot crossing
    # its reservation grows page-by-page (to its full remaining need
    # when pages are plentiful, minimally under pressure), with
    # preemption as the safety valve — the engine oversubscribes
    # device pages like vLLM. REQUIRES enable_kv_offload: without the
    # preemption/parking valve the oversubscription this creates has
    # no recourse, and requests a worst-case-reserving engine would
    # simply queue behind instead finish with finish_reason="error".
    kv_watermark_tokens: Optional[int] = None
    # Real-checkpoint path: directory holding an HF-layout safetensors
    # checkpoint (model.safetensors[.index.json] + config.json). Params
    # load through models/checkpoint_io.py — sharding-aware windowed
    # reads straight onto the serving mesh. With model=None the
    # architecture comes from the checkpoint's config.json.
    checkpoint: Optional[str] = None

    def resolve_model(self):
        """The model's configuration: a `LlamaConfig`, or another
        family's (`models/family.py`)."""
        if self.model is None:
            if not self.checkpoint:
                raise ValueError("model=None requires checkpoint=")
            from ...models import checkpoint_io
            return checkpoint_io.load_config(self.checkpoint)
        return resolve_config(self.model)


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0             # 0 → greedy
    top_p: float = 1.0
    top_k: int = 0                       # 0 → off
    repetition_penalty: float = 1.0      # 1.0 → off (CTRL-style)
    stop_token_ids: tuple = ()
    # Per-request RNG seed (ISSUE 9). None derives a stable seed from
    # the request id (derive_seed), so EVERY sampled request is
    # replayable: the sampling key for the token at absolute index i
    # is fold_in(PRNGKey(seed), i) — independent of tick count,
    # batching, and which program (ragged / decode) produces it. That
    # makes sampled mid-stream failover token-exact: a continuation
    # re-prefilled from prompt + emitted tokens resumes the exact
    # sample sequence.
    seed: Optional[int] = None


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_tokens: List[int]
    params: SamplingParams
    # registered LoRA adapter name (multi-LoRA serving: slots in one
    # decode batch may run different adapters; reference parity role:
    # serve LLM LoRA multiplexing, deployments/llm/multiplex/)
    lora: Optional[str] = None
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[str] = None
    # MONOTONIC submission stamp (telemetry queue-wait/TTFT baseline):
    # durations derived from it must be NTP-step immune; convert to
    # epoch via util.tracing.mono_to_epoch for display
    submitted_at: float = dataclasses.field(
        default_factory=time.monotonic)
    # distributed trace context minted at the fleet ingress (ISSUE 7):
    # {"trace_id", "span_id", "flow_id"} — host-side metadata only,
    # carried into the telemetry timeline so one trace id follows the
    # request across ingress, router, and replica processes
    trace: Optional[Dict[str, str]] = None
    # absolute MONOTONIC deadline (ISSUE 9): the engine aborts the
    # request at the next fold boundary once time.monotonic() passes
    # it (finish_reason="deadline"), whether it is still waiting for
    # admission or holding a decode slot. None = no deadline.
    deadline: Optional[float] = None
    # preemption priority (ISSUE 10): under page pressure the LOWEST
    # priority loses its slot first (ties break youngest-first); the
    # serving plane maps tenant tiers onto this
    priority: int = 0
    # tenant identity (ISSUE 13), sourced from admission (the fleet
    # ingress mints `_tenant` from the OpenAI `user` field): tags this
    # request's cost receipt so per-tenant attribution rollups and
    # Prometheus counters know who consumed the FLOPs. "" = the
    # default tenant (label omitted from expositions, so
    # single-tenant scrapes stay byte-identical)
    tenant: str = ""
    # times this request lost its slot and came back (preemption
    # spill/restore or prefill requeue) — restores skip the admission
    # telemetry so queue-wait/prefix-hit stats count each request once
    restarts: int = 0
    # scheduling lane (ISSUE 14): "interactive" (default) or "batch".
    # Batch-lane requests are the preemptible bulk-inference tier —
    # they ride Request.priority for victim choice, and telemetry
    # EXCLUDES them from the SLO sums/violation counts the fleet
    # autoscaler and burn-rate watchdog consume (a deliberately
    # deep queue of offline work must not read as overload), keeping
    # their tokens in separate batch-lane counters instead
    lane: str = "interactive"


class _Slot:
    def __init__(self, index: int):
        self.index = index
        self.request: Optional[Request] = None
        self.pages: List[int] = []
        self.position = 0        # tokens cached so far
        self.last_token = 0
        self.prefill_pos = 0     # prompt tokens cached (< len => prefilling)
        self.ready = False       # prompt fully prefilled, decoding
        self.seed = 0            # resolved per-request sampling seed


@dataclasses.dataclass
class _InflightTick:
    """One dispatched-but-not-yet-folded decode tick (the pipeline's
    depth-2 stage): the device token buffer whose d2h copy is already
    streaming, plus the host active mask AT DISPATCH — the fold uses
    the snapshot, not live slot state, so a slot retired while this
    tick was in flight has its over-generated token discarded."""
    tokens: Any                     # (B,) device array, copy in flight
    active: "np.ndarray"            # host active mask at dispatch
    tick: int = 0                   # engine tick that dispatched it


def derive_seed(request_id: str) -> int:
    """Default per-request sampling seed: a stable 31-bit hash of the
    request id (ISSUE 9). Stable across processes and engine restarts,
    so a failover continuation carrying the original request's id (or
    its explicitly pinned seed) replays the exact sample sequence."""
    return int.from_bytes(
        hashlib.sha1(str(request_id).encode()).digest()[:4],
        "big") & 0x7FFFFFFF


def _row_sample_keys(seeds, idx):
    """Per-row sampling keys for per-request deterministic sampling
    (ISSUE 9): fold the ABSOLUTE index of the token being sampled into
    a key derived from the request's seed. The key depends only on
    (seed, token index) — never on tick count, batch composition, or
    which program (ragged / decode) produces the
    token — so a failover continuation re-prefilled from the original
    prompt + already-emitted tokens samples the same suffix the dead
    replica would have."""
    return jax.vmap(
        lambda s, i: jax.random.fold_in(jax.random.PRNGKey(s), i)
    )(seeds, idx)


def _order_keys(x):
    """uint32 keys that order as the float32 `x` does, -0.0 and +0.0
    one key (as lax.sort's comparator has them)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _prefix(keys, weights, member, limit):
    """(B, V) bool, in vocabulary order: the tokens whose predecessors,
    in order of falling key and then rising id, weigh under `limit`.
    It is what a stable sort's prefix test (cumsum - weight < limit)
    keeps. `member`: the tokens in the running, whose weights count;
    equal keys carry equal weights.

    The cut's key is the smallest c whose heavier keys weigh under the
    limit, sum(weights[keys > c]) < limit. That sum falls as c rises, so
    c is settled from its top bit down: 32 passes, each one compare and
    one row sum, whatever the row holds; a loop's trips, so the program
    stays small. One bit a pass: 3 or 15 thresholds over one read were no
    faster on a v5e (1.83 / 1.92 / 3.26 ms at 1 / 2 / 4 bits over
    [64, 200064]: the compares bind, XLA keeps the rows in VMEM across
    the loop) and a larger program, which every tick program's first
    call pays for (PERF.md section 6, PR 37)."""
    def settle(step, c):
        bit = jnp.uint32(1) << (31 - step).astype(jnp.uint32)
        cand = c | (bit - jnp.uint32(1))    # the largest c with this bit 0
        heavier = jnp.sum(
            jnp.where(keys > cand[:, None], weights, 0.0), axis=-1)
        return jnp.where(heavier < limit, c, c | bit)
    c = jax.lax.fori_loop(
        0, 32, settle, jnp.zeros(keys.shape[:1], jnp.uint32))
    over = keys > c[:, None]
    tied = member & (keys == c[:, None])
    before = jnp.sum(jnp.where(over, weights, 0.0), axis=-1)
    each = jnp.max(jnp.where(tied, weights, 0.0), axis=-1)
    # of the tokens tied on the cut, the i-th by id is kept while
    # before + i * each is under the limit (all of them where their
    # weight underflowed to 0)
    n = jnp.where(each > 0, jnp.ceil((limit - before) / each), jnp.inf)
    rank = jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
    return over | (tied & (rank <= jnp.maximum(n, 1.0)[:, None]))


def _kept_tokens(scaled, top_ps, top_ks=None):
    """(B, V) bool, in vocabulary order: the tokens top-k and then top-p
    leave of each row of `scaled` (see _sample, which says why this
    neither sorts, gathers nor scatters).

    A selection, not a sort: of the sorted order only the cut is wanted,
    and a cut is found by bisection on row sums (`_prefix`). The logits
    become uint32 keys of the same order; the cut's key is settled bit
    by bit (the count of heavier keys against top_k, then their
    probability against top_p); the tokens that share the cut's key
    share its weight, so how many of them are kept is arithmetic, and
    which is their rank by id, one cumulative sum. The same cost for a
    flat row and a peaked one: no prefix to fall back from, no cap on
    what top-p may keep. The top-k cut is searched only when some row
    of the batch sets top_k (a trip of the loop taken or not by the
    array's values, not a static argument: the tick programs always
    pass it and stay one program a bucket)."""
    v = scaled.shape[1]
    if v >= 1 << 24:
        raise ValueError(f"vocabulary {v}: counts are float32 row sums")
    keys = _order_keys(scaled)
    # top_p 1 keeps all that top-k left, though a float32 sum of the
    # row's probabilities reaches 1.0 before its last token
    by_mass = jnp.where(top_ps < 1.0, top_ps, jnp.inf)
    if top_ks is None:
        top_ks = jnp.zeros(top_ps.shape, jnp.int32)
    on = (top_ks > 0) & (top_ks < v)        # 0 or >= V: off
    ranks = jnp.where(on, top_ks, v).astype(jnp.float32)

    def cut(by_count, live):
        """One cut of what is live: by count against top_k (every token
        weighs 1), or by probability, renormalised over what is live,
        against top_p. One body for both, so a tick program holds the
        bisection once."""
        probs = jnp.where(
            live, jax.nn.softmax(jnp.where(live, scaled, -jnp.inf),
                                 axis=-1), 0.0)
        return live & _prefix(
            keys, jnp.where(by_count, 1.0, probs), live | by_count,
            jnp.where(by_count, ranks, by_mass))

    # ranks < top_k first; that cut only where a row of the batch asks
    # for it: the loop starts at top-p otherwise
    return jax.lax.fori_loop(
        jnp.where(jnp.any(on), 0, 1), 2,
        lambda phase, live: cut(phase == 0, live), jnp.isfinite(scaled))


@jax.named_scope("sample")
def _sample(logits, key, temps, top_ps, top_ks=None, rep_pens=None,
            seen=None, all_greedy: bool = False, row_keys=None):
    """logits: (B, V) f32; temps/top_ps/top_ks/rep_pens: (B,);
    seen: (B, V) bool — tokens already in each sequence (prompt +
    generated), the repetition-penalty support. Greedy where temp<=0.

    Order mirrors the usual serving stacks (HF/vLLM): repetition
    penalty on raw logits (CTRL: positive seen logits divided, negative
    multiplied), then temperature, top-k, top-p, sample.

    all_greedy (static) skips the selection entirely: pure argmax
    decoding (the common batch-inference case) never needs it (the
    engine only sets it when every penalty is off too).

    What it costs. Elementwise passes, row reductions and one
    cumulative sum: the selection of `_kept_tokens` and the draw's
    vocabulary-wide noise. On a v5e (`_sample` whole, top_p 0.9 at
    temperature 0.7, no row with top_k; PERF.md section 6, PR 37)
    2.35 ms over [64, 200064], 0.66 over [32, 92544] and under 0.5
    over [64, 16160] and [32, 25024], flat rows or peaked; with top_k
    set 4.19, 1.16 and under 0.5. The stable sort it replaces (the row
    with its ids as payload) took 18.8, 4.0, 1.05 and 1.08 ms there and
    12 to 24 s to compile, where this takes 1 to 5. Nothing here may
    sort a row, nor gather from or scatter into a [B, V] array: a
    gather of the sorted logits was 30 ms and the scatter of the keep
    mask back to vocabulary order 19 ms of an 87 ms decode tick whose
    24 layers took 17 (PERF.md section 6, PR 28). None is needed. The
    kept set is DEFINED in the order of a stable sort by falling logit
    (equal logits by rising id, -0.0 and +0.0 equal): a token is kept while
    the tokens before it weigh under the limit, first by count against
    top_k, then, over what that left, by float32 probability against
    top_p (every probability of a larger logit summed, plus the tied
    tokens before it at the one probability they share). That is a
    prefix of the order, so its last token's logit (the cut) and the
    rank by id among the tokens tied on it describe it in vocabulary
    order. top_p 0 keeps nothing, top_p 1 all that top-k left, a -inf
    logit is never kept. tests/test_llm_sampling.py holds the
    gather-and-scatter body as the oracle, token for token.

    row_keys: optional (B,) per-row PRNG keys (_row_sample_keys) —
    the per-request deterministic path, which both engine programs
    take; `key` is one key shared by the batch, for direct callers.
    """
    if rep_pens is not None and seen is not None:
        pen = jnp.where(logits > 0,
                        logits / rep_pens[:, None],
                        logits * rep_pens[:, None])
        logits = jnp.where(seen, pen, logits)
    greedy = jnp.argmax(logits, axis=-1)
    if all_greedy:
        return greedy.astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    keep = _kept_tokens(scaled, top_ps, top_ks)
    filtered = jnp.where(keep, scaled, -jnp.inf)
    if row_keys is not None:
        sampled = jax.vmap(jax.random.categorical)(row_keys, filtered)
    else:
        sampled = jax.random.categorical(key, filtered, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


def _with_rider(tokens, rider):
    """The tick's readback: the sampled tokens and, behind them, the
    small int32 array a model family sends back with every tick (an
    expert layer's per-expert assignment counts) — one array, so no
    program or transfer of its own. None: the tokens alone."""
    if rider is None:
        return tokens
    return jnp.concatenate([tokens, rider.reshape(-1).astype(
        tokens.dtype)])


# The named phases of a tick, in the order a tick meets them. Each is a
# host span `engine.<phase>` on the profiler's clock and an entry of the
# per-tick table behind stats()["tick_phases"] / ["tick_times"].
TICK_PHASES = ("sched", "pack", "account", "dispatch", "readback_wait",
               "fold", "refresh")


class _Phase:
    """One phase of a tick: a `jax.profiler.TraceAnnotation` named
    `engine.<name>` — so the span lands on the `/host:CPU` plane of
    whatever capture is running (the benchmark's traced run, an
    operator's POST /debug/profile), on the same clock as the device's
    events — and the same interval on `perf_counter`, added to the
    engine's per-tick phase table. Phases nest (a drain inside
    scheduling waits, folds and refreshes): a parent is charged only
    what none of its children covers, so a tick's entries sum to at
    most its wall. Entering returns the annotation, for
    `set_metadata()` of what is known only at the end. Runs under the
    step lock, like everything that opens one."""

    __slots__ = ("eng", "name", "span", "t0")

    def __init__(self, eng: "InferenceEngine", name: str,
                 args: Dict[str, Any]):
        self.eng = eng
        self.name = name
        self.span = jax.profiler.TraceAnnotation("engine." + name,
                                                 **args)

    def __enter__(self):
        self.span.__enter__()
        eng = self.eng
        now = time.perf_counter()
        stack = eng._phase_stack
        if stack:
            eng._phase_s[stack[-1].name] += now - stack[-1].t0
        stack.append(self)
        self.t0 = now
        return self.span

    def __exit__(self, *exc):
        eng = self.eng
        now = time.perf_counter()
        eng._phase_s[self.name] += now - self.t0
        stack = eng._phase_stack
        stack.pop()
        if stack:
            stack[-1].t0 = now
        self.span.__exit__(*exc)


# how long an armed capture waits for another profiler session to close
_START_WAIT_S = 60.0


class _Writer:
    """The engine's one writer thread, `engine-profile-writer`: what the
    analyzer's reactions cost in milliseconds and seconds (`start_trace`,
    `stop_trace` with its export, a black-box bundle's dump) runs here,
    one job at a time in the order submitted, off the step lock. Started
    by the first job and parked between jobs (a daemon: starting a thread
    is the dearest thing left under the lock, 0.7 ms on the chip's host,
    so it is paid once an engine, not once a flag)."""

    def __init__(self):
        self._cv = threading.Condition()
        self._jobs: "collections.deque" = collections.deque()
        self._pending = 0               # queued or running
        self._thread: Optional[threading.Thread] = None

    def submit(self, fn, *args) -> None:
        with self._cv:
            self._jobs.append((fn, args))
            self._pending += 1
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="engine-profile-writer",
                    daemon=True)
                self._thread.start()
            self._cv.notify_all()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._jobs:
                    self._cv.wait()
                fn, args = self._jobs.popleft()
            try:
                fn(*args)
            except Exception:
                # a capture or a bundle lost; the engine keeps serving
                import logging
                logging.getLogger(__name__).exception(
                    "engine-profile-writer: %s failed", fn.__name__)
            finally:
                with self._cv:
                    self._pending -= 1
                    self._cv.notify_all()

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        with self._cv:
            return self._cv.wait_for(lambda: not self._pending, timeout)


class _TickRecord(NamedTuple):
    """One entry of the tick ring. The first three fields are the ring's
    old (wall, host, device) triple; `host_ms` is the `fold` phase and
    `device_ms` the `readback_wait` phase."""
    wall_ms: float
    host_ms: float
    device_ms: float
    start: float            # perf_counter (the monotonic clock) at entry
    gap_ms: float           # previous step's end to this start, with work
    kind: str               # "ragged" | "decode" | "" (no such dispatch)
    T: int
    ctx: int
    rows: int
    prefill_tokens: int
    phases_ms: Dict[str, float]     # TICK_PHASES and "other"
    compiles: int                   # programs built during the tick
    attn_items: int = 0             # ragged kernel: live work items
    attn_kv_blocks: int = 0         # and the KV blocks they visit

    def brief(self) -> Dict[str, Any]:
        return {"start": self.start, "wall_ms": round(self.wall_ms, 3),
                "gap_ms": round(self.gap_ms, 3), "kind": self.kind,
                "T": self.T, "ctx": self.ctx, "rows": self.rows,
                "prefill_tokens": self.prefill_tokens,
                "phases_ms": {k: round(v, 3)
                              for k, v in self.phases_ms.items()},
                "compiles": self.compiles,
                "attn_items": self.attn_items,
                "attn_kv_blocks": self.attn_kv_blocks}


class InferenceEngine:
    # thread-sanitizer-guarded state (no-op plain attributes unless the
    # sanitizer is armed, e.g. in the tier-1 concurrency stress test):
    # the tick-times deque is read AND written only under _step_lock
    # (dump_blackbox's sanctioned lock-free read runs inside
    # thread_sanitizer.unguarded()); `waiting` is write-guarded only —
    # bare boolean/len reads of the published list reference are part
    # of the design (has_work, blackbox).
    _tick_times = thread_sanitizer.guarded_by("_step_lock")
    waiting = thread_sanitizer.guarded_by("_step_lock", writes_only=True)
    _pending_touched = thread_sanitizer.guarded_by(
        "_step_lock", writes_only=True)

    def __init__(self, config: EngineConfig,
                 params: Optional[Dict[str, Any]] = None):
        self.config = config
        self.model_cfg = config.resolve_model()
        self.max_seq = config.max_seq_len or self.model_cfg.max_seq
        cfg, ec = self.model_cfg, config
        # what the engine asks of the model: parameters, the two
        # forwards, the cache row (models/family.py)
        self.family = family_of(cfg)
        self._refuse_for_family()
        # explicit-tp state (EngineConfig.mesh_shape): defaults cover
        # every other placement mode so the compiled-program builders
        # can branch on it unconditionally
        self._explicit_tp = False
        self._tp = 1
        self._tp_axis = "tp"
        self._tp_local_cfg = None
        self._tp_specs = None
        self._tp_logits_psum = None
        if ec.mesh_shape is not None:
            if ec.mesh is not None:
                raise ValueError(
                    "mesh_shape (explicit shard_map tp) and mesh "
                    "(GSPMD MeshSpec) are mutually exclusive")
            from ...models import llama_infer
            from ...ops import tp_mesh as _tpm
            named = _tpm.build_serving_mesh(ec.mesh_shape,
                                            tp_axis=ec.tp_axis)
            tp = int(named.shape[ec.tp_axis])
            if tp > 1:
                self._explicit_tp = True
                self._tp = tp
                self._tp_axis = ec.tp_axis
                # raises for MoE / non-divisible head, hidden, ffn dims
                self._tp_local_cfg = llama_infer.tp_local_config(cfg, tp)
                self._tp_specs = llama_infer.tp_param_specs(
                    cfg, ec.tp_axis)
                self._tp_logits_psum = _tpm.logits_psum_fn(
                    "int8" if ec.quantized_collectives else "f32")
                self.mesh = named
            else:
                # (1, 1): a single-chip slice is just the plain engine
                self.mesh = None
        else:
            self.mesh = self._build_placement(ec.mesh, cfg)
        # Weights are STORED as the tick's programs use them
        # (family.storage_dtypes: the dense family's matrices and
        # embedding in cfg.dtype, head and norms in float32), cast once
        # where the tree enters the engine, on every branch below; a
        # program that converts a weight reads and writes the stack
        # again in every tick (15 of chat-open's 37 ms, PERF.md section
        # 6, PR 30). self.params is the only copy the engine holds.
        store = functools.partial(store_params, self.family, cfg)
        if params is None and ec.checkpoint:
            from ...models import checkpoint_io
            # sharded load: each device's shard is a windowed mmap read,
            # cast on the host straight to the leaf's storage type
            params = checkpoint_io.load_llama_params(
                cfg, ec.checkpoint,
                mesh=(None if self._explicit_tp else self.mesh),
                dtype=self.family.storage_dtypes(cfg))
        shardings = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ...parallel.sharding import tree_shardings
            if self._explicit_tp:
                # the Megatron layout: these shardings are ALSO the
                # shard_map in_specs, so dispatch never reshards
                shardings = jax.tree.map(
                    lambda spec: NamedSharding(self.mesh, spec),
                    self._tp_specs,
                    is_leaf=lambda x: isinstance(x, PartitionSpec))
            else:
                shardings = tree_shardings(
                    llama.param_logical_axes(cfg), self.mesh)
            self._kv_sharding = NamedSharding(
                self.mesh,
                PartitionSpec(None, None, None, self._tp_axis, None))
            self._repl = NamedSharding(self.mesh, PartitionSpec())
        else:
            self._kv_sharding = self._repl = None
        if params is None and shardings is not None:
            # born sharded and in the storage types: each chip draws
            # and casts only its own shard, so a model larger than one
            # chip's HBM (8b is 15 GiB as stored, 29.9 in float32)
            # never passes through a single device
            self.params = jax.jit(
                lambda key: store(llama.init_params(cfg, key)),
                out_shardings=shardings)(jax.random.PRNGKey(ec.seed))
        elif params is None:
            # the seed's draw is eager and float32 (the parent's bits),
            # rounded leaf by leaf and let go as it is
            self.params = store(
                self.family.init_params(cfg, jax.random.PRNGKey(ec.seed)),
                release=True)
        else:
            self.params = store(params, shardings)
        # what the arrays are, not what the configuration says: bytes
        # in all (a tp engine's over its chips) and by type
        by_dtype: Dict[str, int] = collections.Counter()
        for leaf in jax.tree.leaves(self.params):
            by_dtype[str(leaf.dtype)] += int(leaf.nbytes)
        self._weights_held = {"bytes": sum(by_dtype.values()),
                              "by_dtype": dict(by_dtype)}
        self.max_pages_per_seq = -(-self.max_seq // ec.page_size)
        # -- KV memory hierarchy (ISSUE 10) ----------------------------
        if ec.kv_watermark_tokens is not None \
                and ec.kv_watermark_tokens < 1:
            raise ValueError("kv_watermark_tokens must be >= 1 or None")
        if ec.kv_watermark_tokens is not None \
                and not ec.enable_kv_offload:
            raise ValueError(
                "kv_watermark_tokens (optimistic admission) requires "
                "enable_kv_offload: oversubscribing device pages "
                "without the preemption/parking safety valve turns "
                "ordinary contention into finish_reason=\"error\" "
                "failures a worst-case-reserving engine would simply "
                "queue through")
        # -- Quantized KV pages (ISSUE 16) -----------------------------
        from ...ops import kv_quant
        self._kv_kind = kv_quant.validate_kind(ec.kv_dtype)
        # -- KV pool layout ---------------------------------------------
        # Pools are [layers, pages, page_size, kv_heads, row] where
        # row = ops/paged_attention.pool_head_dim: the model's head_dim
        # as published for the gather impl, zero-padded to whole
        # 128-lane vectors for the kernel impls (their page DMAs need a
        # lane-aligned minor dim; at head_dim 64 the cache, not the
        # model, pays 2x). A geometry the compiled kernels cannot take
        # is refused HERE with the compiler's reason — an engine that
        # constructs must compile, and none falls back to gather.
        from ...ops import paged_attention as _pa
        impl = self._resolve_impl()
        # THE description of what the model's layers write to the
        # cache: its groups (models/cache_row.CacheGroup), each a row,
        # the layers that write it and an optional window. The manager
        # below (an allocator and a page table a group), the pools, the
        # per-page bytes, stats() and the cost model all read them.
        # `cache_row` stays the FIRST group's row for its readers.
        groups = self.family.cache_groups(
            cfg, impl, self._kv_kind)
        crow = self.cache_row = groups[0].row
        pool_dt = crow.dtype
        by_group = ec.num_pages_by_group or {}
        unknown = set(by_group) - {g.name for g in groups}
        if unknown:
            raise ValueError(
                f"num_pages_by_group names {sorted(unknown)}; the "
                f"{self.family.name} family's cache groups are "
                f"{[g.name for g in groups]}")
        self.cache = CacheManager(
            groups, [by_group.get(g.name, ec.num_pages) for g in groups],
            ec.page_size, ec.max_batch_size, self.max_pages_per_seq,
            tick_tokens=self._tick_token_budget(),
            enable_prefix_caching=ec.enable_prefix_caching)
        # the first group's allocator: a one-group family's only one
        self.allocator = self.cache.first
        # kv heads ONE shard's kernel sees, read off the sharding
        # the pools will carry
        local_kvh = (crow.heads if self._kv_sharding is None else
                     self._kv_sharding.shard_shape(
                         (1, 1, 1, crow.heads, 1))[3])
        # what the ragged kernel's block sizes derive from, besides a
        # tick's T and context bucket (ragged_work_counts)
        self._attn_geometry = (
            local_kvh, crow.padded_width, jnp.dtype(pool_dt).itemsize)
        # (a "rows" pool merges a page's tokens and heads in one axis
        # because its head count is off the tile: the rule below is the
        # [page, heads, width] page's, and tests/test_tpu_aot_compile.py
        # holds the merged one against the compiler)
        if impl == "pallas" and crow.kind == "kv" \
                and crow.layout == "token":
            why = _pa.kernel_layout_error(self._kv_kind, local_kvh,
                                          pool_dt)
            if why is not None:
                raise ValueError(
                    f"decode_impl={ec.decode_impl!r} resolves to the "
                    f"compiled Pallas kernels, which the TPU compiler "
                    f"refuses for this cache geometry: {why}. Use a "
                    f"geometry it accepts, or decode_impl='gather' "
                    f"explicitly (ROADMAP A7).")
        # per-page device bytes at the CONFIGURED storage kind and the
        # pool's row width: the occupancy/pressure gauges report bytes
        # from this, never an assumed f32 itemsize (quantized pages
        # carry 1-byte values plus the per-(row, head) f32 scale
        # sidecar)
        self._kv_page_bytes = self.cache.page_bytes()
        # what a tick's readback carries behind the tokens (an expert
        # family's per-layer, per-held-expert assignment counts), and
        # the monotone totals stats()["moe"] folds it into
        self._rider_len = int(self.family.rider_len(cfg))
        self._moe_landed = np.zeros(self._rider_len, np.int64)
        self._moe_tokens_routed = 0
        from .kv_offload import HostKVTier
        self.host_tier: Optional[HostKVTier] = (
            HostKVTier(ec.host_kv_pages) if ec.enable_kv_offload
            else None)
        self.allocator.host_tier = self.host_tier
        # preemptions by reason (growth | admission | manual | ...)
        self.preempt_counts: Dict[str, int] = {}
        # spills whose async d2h copy is still streaming; materialized
        # to host numpy at the NEXT tick entry (one tick of overlap —
        # the lagged-readback discipline applied to page migration)
        self._pending_spills: List[Any] = []
        # page-migration programs, cached per power-of-two page-count
        # bucket (state migration, excluded from self.dispatches like
        # every other non-forward refresh program)
        self._page_gather_fns: Dict[int, Any] = {}
        self._page_scatter_fns: Dict[int, Any] = {}
        # slot index last attempting a page allocation — the engine-
        # boundary MemoryError handler's victim attribution
        self._alloc_ctx: Optional[int] = None
        # observability (ISSUE 5): SLO metrics + lifecycle timelines +
        # flight recorder, recorded purely from host-side events —
        # see telemetry.py for the zero-sync contract
        self.telemetry = EngineTelemetry(
            model=ec.metrics_model_id or "default",
            enabled=bool(ec.enable_metrics),
            replica=ec.metrics_replica_id or "",
            slo_targets=ec.slo_targets)
        # postmortem black-box spool (ISSUE 7): written only on
        # failure paths (guard violation via the recorder alert hook,
        # mid-tick crash in step()) or on explicit POST /debug/dump
        from .blackbox import BlackboxSpool, default_spool_dir
        self.blackbox = BlackboxSpool(
            ec.blackbox_dir or default_spool_dir(
                ec.metrics_model_id or "default",
                ec.metrics_replica_id or ""),
            capacity=ec.blackbox_capacity)
        if ec.enable_blackbox:
            self.telemetry.recorder.alert_hook = self._on_alert_event
        # MONOTONIC stamp of the last completed tick: the fleet
        # router's liveness input (fleet_stats last_tick_age_s) — a
        # replica whose pump wedged stops advancing this
        self.last_step_at: Optional[float] = None
        # on-demand profiling: the one capture (`_arm_profile_locked`)
        # while armed, running or being written (POST /debug/profile →
        # profile_next_ticks)
        self._profile: Optional[Dict[str, Any]] = None
        kv_shape = crow.pool_shape(len(groups[0].layers),
                                   self.cache.groups[0].num_pages,
                                   ec.page_size)
        # born sharded, like the weights: a pool zeroed on the
        # default device and then resharded passes WHOLE through
        # chip 0 (on the chip: +1.8 GB peak there at tp=4, 8b).
        # A latent cache is ONE pool: `k_pages` holds it and
        # `v_pages` is None through every program's signature.
        # A family with several cache groups gets its pools (and its
        # page tables) as TUPLES, one entry a group; a one-group
        # family's programs see the arrays they always saw
        # A STATE group's arrays (`[its layers, slots, ...]`, a
        # recurrent layer's state a slot) ride the same tuples behind
        # the page groups' pools, in `cache_groups`' order: its first
        # part in `k_pages`' entry, its second in `v_pages`'. They are
        # donated through `jit_run` and `jit_step` with the pools, so a
        # tick's state is the tick before's output, however deep the
        # pipeline; a family with no state group is handed none
        def pools(spec, num_pages=0):
            return tuple(
                jnp.zeros(shape, dt, device=self._kv_sharding)
                for shape, dt in spec.array_shapes(
                    num_pages, ec.page_size, ec.max_batch_size))
        made = ([pools(g.spec, g.num_pages) for g in self.cache.groups]
                + [pools(st.spec) for st in self.cache.states])
        if len(made) == 1:
            self.k_pages = made[0][0]
            self.v_pages = made[0][1] if crow.pools == 2 else None
        else:
            self.k_pages = tuple(m[0] for m in made)
            # (a latent group is one pool: None stands for its second)
            self.v_pages = tuple(m[1] if len(m) > 1 else None
                                 for m in made)
        self._key = self._dev(jax.random.PRNGKey(ec.seed + 1))
        # per-(token row, kv head) f32 scale pools beside the value
        # pools (None for f32 engines): [L, P, page, KVH], sharded on
        # kv heads under tp exactly like the pools they scale
        self._scale_sharding = None
        if self._kv_kind != "f32":
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                self._scale_sharding = NamedSharding(
                    self.mesh,
                    PartitionSpec(None, None, None, self._tp_axis))
            sc_shape = kv_quant.scale_shape(kv_shape)
            self.k_scales = jnp.zeros(sc_shape, jnp.float32,
                                      device=self._scale_sharding)
            self.v_scales = jnp.zeros(sc_shape, jnp.float32,
                                      device=self._scale_sharding)
        else:
            self.k_scales = self.v_scales = None

        # multi-LoRA: name -> adapter index (0 = the zero adapter);
        # stacks are {proj: {"a": (A, L, H, r), "b": (A, r, O)}} device
        # arrays rebuilt on registration (first registration recompiles
        # the decode and ragged programs once)
        self._lora_names: Dict[Optional[str], int] = {None: 0}
        self._lora_raw: Dict[str, dict] = {}
        self._lora_stacks = None
        self.slots = [_Slot(i) for i in range(ec.max_batch_size)]
        self.waiting: List[Request] = []
        # host-side mirrors of the device-side slot state: the cache
        # manager's tables, the first group's under its old name
        self._page_tables = self.cache.groups[0].tables

        # quantized engines thread the scale pools right after the
        # value pools (all donated: in-place HBM updates), shifting
        # the trailing static all_greedy arg by 2
        if self._kv_kind != "f32":
            self._decode_fn = jax.jit(
                self._build_decode(), donate_argnums=(1, 2, 3, 4, 5),
                static_argnums=(18,))
        else:
            self._decode_fn = jax.jit(
                self._build_decode(), donate_argnums=(1, 2, 3),
                static_argnums=(16,))
        self._d_tokens = None          # device-resident slot state
        self._d_seen = None
        self._d_seeds = None           # per-slot sampling seeds (B,)
        self._host_active = np.zeros(ec.max_batch_size, bool)
        self._ragged_fns: Dict[tuple, Any] = {}
        self._prefill_rr = 0           # round-robin cursor over slots
        # device-resident page tables: re-uploaded only when the host
        # mirror changes (admission / finish), not per dispatch
        self._tables_version = 0
        self._d_tables_cache = (-1, None)
        # seen (repetition-penalty support): slot turnover dirties
        # ONLY that slot's row (None = full rebuild needed, e.g. no
        # device copy yet). _refresh_seen re-uploads dirty rows
        # incrementally instead of rebuilding the whole (B, V) mask
        # per ban-list mutation.
        self._seen_dirty_slots: Optional[set] = None
        # in-place row scatter for the incremental path: the (B, V)
        # buffer is donated so XLA updates it in HBM (row count is
        # bucketed by the caller; at most log2(B)+1 programs, each
        # counted into self.compiles on first use to keep the
        # jit-cache accounting contract honest)
        self._seen_update_fn = jax.jit(
            lambda seen, idx, rows: seen.at[idx].set(rows),
            donate_argnums=(0,))
        self._seen_scatter_buckets: set = set()
        # dispatch accounting: FORWARD-program executions vs engine
        # ticks (the contract is one dispatch per tick). State-refresh
        # machinery is deliberately excluded —
        # per-tick key splits, admit/finish-time uploads, and the
        # _refresh_seen row scatter run outside the tick's forward
        # dispatch and only on turnover events.
        self.ticks = 0
        self.dispatches = 0
        # jit-cache accounting: +1 whenever a NEW bucketed program is
        # built (first call then compiles it) — a steady-state run must
        # hold this flat; growth means bucket churn / recompile storms
        self.compiles = 0
        # packed per-slot sampling params, cached across ragged ticks
        # (invalidated on slot admission/retirement only)
        self._samp_cache = None
        # -- pipelined async readback (EngineConfig.async_readback) --
        self._async = bool(ec.async_readback)
        self._inflight: Optional[_InflightTick] = None
        # tokens folded OUTSIDE a step() call (drains triggered by
        # abort/register_loras) surface through the next step's
        # touched list so streaming consumers never lose them
        self._pending_touched: List[Request] = []
        # per-dispatch perf accounting (ISSUE 11): analytic cost model
        # + rolling MFU/MBU window (perfmodel.py). Host arithmetic
        # only — each tick path folds its batch composition into a
        # pending PerfSample and step() commits it with the tick wall.
        from .perfmodel import (CostModel, PerfAccountant,
                                detect_envelope)
        # chips this replica occupies — the fleet's slice-accounting
        # unit (ReplicaSnapshot.chips, /fleet rows) AND the perf
        # accountant's per-chip MFU/MBU divisor
        self.n_chips = (int(self.mesh.devices.size)
                        if self.mesh is not None else 1)
        self.perf: Optional[PerfAccountant] = None
        if ec.enable_perf_accounting:
            self.perf = PerfAccountant(
                CostModel(cfg, ec.page_size, kv_dtype=self._kv_kind,
                          cache_groups=groups,
                          weight_bytes=self._weights_held["bytes"]),
                detect_envelope(name=ec.perf_envelope),
                n_chips=self.n_chips)
        # per-request cost attribution + tick-anomaly analyzer
        # (ISSUE 13): both ride the perf accountant's numbers, so both
        # require it; both are pure host arithmetic (the dispatch-
        # guard suite runs with them enabled)
        from .attribution import ReceiptLedger
        self.attrib: Optional[ReceiptLedger] = (
            ReceiptLedger() if (self.perf is not None
                                and ec.enable_attribution) else None)
        self.anomaly = None
        if self.perf is not None and ec.enable_anomaly_detection:
            from .anomaly import AnomalyConfig, TickAnomalyDetector
            self.anomaly = TickAnomalyDetector(
                AnomalyConfig(**(ec.anomaly or {})))
        # tick-pipeline telemetry: per-tick (wall, host-fold, blocked-
        # readback) ms over a sliding window + cumulative counters
        # (stats()["tick_times"]; BENCH_CORE.md "Tick pipelining
        # anatomy")
        # 1024 records: a 50 s window of 88 ms decode ticks fits
        self._tick_times = collections.deque(maxlen=1024)
        self._lagged_ticks = 0          # ticks folded one tick late
        self._drains = 0                # structural-event barriers
        # the per-tick phase table (_Phase adds to it; step() folds it
        # into the ring and the cumulative totals, then zeroes it) and
        # what the tick's one dispatch carried, for its ring record
        self._phase_s = dict.fromkeys(TICK_PHASES, 0.0)
        self._phase_stack: List[_Phase] = []
        self._tick_carried: Optional[Dict[str, Any]] = None
        # monotone totals behind stats()["tick_phases"]
        self._phase_total_s = dict.fromkeys(TICK_PHASES + ("other",), 0.0)
        self._phase_ticks = 0
        self._gap_total_s = 0.0
        self._step_end: Optional[float] = None   # set while work remains
        # monotone totals behind stats()["prefill"]
        self._admissions = 0            # slots claimed by _admit
        self._prompt_tokens_admitted = 0
        self._prefill_tokens_dispatched = 0
        # stats()["self_captures"]: what the engine's own tracing did,
        # by trigger / cause (its lock: dump_blackbox runs lock-free)
        self._captures_lock = threading.Lock()
        self._profiles_armed: Dict[str, int] = {}
        self._profiles_started = 0
        self._blackbox_dumps: Dict[str, int] = {}
        # seconds for which the analyzer's reactions held the step lock
        # (`_held`), and the thread that does the rest of them
        self._capture_hold_s = 0.0
        self._writer = _Writer()
        # what the detector is told of a tick, gathered until it is
        # whole (tick -> record): the end of its dispatch; at the end of
        # the dispatching call its PerfSample and that call's host time;
        # at its own readback, a call later under lagged folds, the time
        # its program kept the device to itself (`_program_read`)
        self._judged: Dict[int, Dict[str, Any]] = {}
        self._read_end = 0.0     # perf_counter at the latest readback's end
        # serializes the mutating entry points (step/abort/LoRA
        # registration): the server runs step() on an executor thread
        # while abort() fires from the event loop on client
        # disconnect, and an abort-triggered drain folding the
        # in-flight tick concurrently with the step that dispatched
        # it would double-fold (duplicate tokens / double position
        # advance). Uncontended in the single-threaded case. A plain
        # threading.Lock unless the thread sanitizer is armed (stress
        # tests), in which case acquisition order and guarded-field
        # ownership are checked at runtime.
        self._step_lock = thread_sanitizer.make_lock("engine._step_lock")
        # published fleet-counter snapshot: replaced WHOLESALE under
        # _step_lock by _publish_counters_locked, read lock-free by
        # fleet_stats at router cadence (fleet_counters())
        with self._step_lock:
            self._publish_counters_locked()

    def _refuse_for_family(self) -> None:
        """Refuse, with the family's reason, every engine option the
        model's family does not compose with: an engine that constructs
        must run, and none half-runs a pairing nobody built."""
        ec = self.config
        asked = {
            "kv_dtype": ec.kv_dtype != "f32",
            "enable_kv_offload": (ec.enable_kv_offload
                                  or ec.kv_watermark_tokens is not None),
            "mesh": ec.mesh is not None,
            "mesh_shape": ec.mesh_shape is not None,
            "checkpoint": bool(ec.checkpoint),
        }
        for option, on in asked.items():
            if on:
                self._refuse_call(option)

    def _refuse_call(self, what: str) -> None:
        """Raise the family's reason if it does not compose with
        `what`: an option above, or an entry point (LoRA registration,
        session shipping) it has no path for."""
        if what in self.family.refuses:
            raise ValueError(
                f"the {self.family.name} family does not compose with "
                f"{what}: {self.family.refuses[what]}")

    @staticmethod
    def _build_placement(spec, cfg: LlamaConfig):
        """EngineConfig.mesh (MeshSpec | dict | None) -> tp Mesh | None.

        Serving supports the tp axis alone: tp shards heads/ffn/vocab
        inside the GSPMD programs. dp/fsdp/sp/ep are rejected —
        replicated decode on dp>1 silently halves the fleet — and so is
        pp. tp=-1 keeps MeshSpec's "use remaining devices" meaning."""
        if spec is None:
            return None
        from ...parallel import MeshSpec
        if isinstance(spec, dict):
            spec = MeshSpec(**spec)
        sizes = dict(spec.axis_sizes())
        devices = jax.devices()
        if sizes.get("pp", 1) != 1:
            raise ValueError(
                f"engine mesh has pp={sizes['pp']}: pipeline-parallel "
                f"serving was removed; the engine shards over tp only "
                f"(a model too large for one slice's tp needs a larger "
                f"slice)")
        if sizes["tp"] == -1:
            sizes["tp"] = len(devices)
        sizes["fsdp"] = 1 if sizes["fsdp"] == -1 else sizes["fsdp"]
        bad = {k: v for k, v in sizes.items()
               if k not in ("tp", "pp") and (v > 1 or v == -1)}
        if bad:
            raise ValueError(
                f"engine mesh supports only the tp axis; got {bad}")
        tp = sizes["tp"]
        if tp == 1:
            return None
        for name, dim in (("n_heads", cfg.n_heads),
                          ("n_kv_heads", cfg.n_kv_heads),
                          ("vocab_size", cfg.vocab_size)):
            if dim % tp:
                raise ValueError(
                    f"{name}={dim} not divisible by tp={tp}")
        if tp > len(devices):
            raise ValueError(
                f"engine mesh needs {tp} devices, have {len(devices)}")
        return MeshSpec(**sizes).build(devices[:tp])

    def _dev(self, x, sharding=None):
        """device_put honoring the engine mesh (replicated by default)."""
        if self.mesh is None:
            return jax.device_put(x)
        return jax.device_put(x, sharding if sharding is not None
                              else self._repl)

    # -- compiled programs --------------------------------------------------
    def _build_decode(self):
        cfg = self.model_cfg
        impl = self._resolve_impl()
        mesh = self.mesh
        kind = self._kv_kind
        # explicit tp: the forward runs INSIDE a shard_map (shard-local
        # cfg, no inner mesh, collectives via psum_axis/logits_psum)
        tp = self._tp if self._explicit_tp else 1
        cfg_fwd = self._tp_local_cfg if tp > 1 else cfg
        mesh_fwd = None if tp > 1 else mesh
        tp_kw = ({"psum_axis": self._tp_axis,
                  "logits_psum": self._tp_logits_psum}
                 if tp > 1 else {})

        decode_step = self.family.decode_step
        rider_len = self._rider_len

        def core(params, k_pages, v_pages, k_scales, v_scales, seen,
                 tokens, positions, page_tables, active, key, temps,
                 top_ps, top_ks, rep_pens, seeds, lora, lora_idx,
                 all_greedy):
            # the fed tokens are the last tick's readback: its rider,
            # if the family has one, rides behind the batch's tokens
            if rider_len:
                tokens = tokens[:active.shape[0]]
            out = decode_step(
                cfg_fwd, params, tokens, positions, k_pages, v_pages,
                page_tables, active, impl=impl, mesh=mesh_fwd,
                lora=lora, lora_idx=lora_idx, kv_kind=kind,
                k_scales=k_scales, v_scales=v_scales, **tp_kw)
            rider = None
            if rider_len:
                logits, k_pages, v_pages, rider = out
            elif kind != "f32":
                logits, k_pages, v_pages, k_scales, v_scales = out
            else:
                logits, k_pages, v_pages = out
            if all_greedy:
                # static fast path: no penalties/seen bookkeeping — the
                # common greedy batch-inference case stays argmax-only
                new_tokens = _with_rider(_sample(
                    logits, key, temps, top_ps, all_greedy=True), rider)
                return (new_tokens, k_pages, v_pages, k_scales,
                        v_scales, seen)
            # the fed token sits at `positions`; the sampled one lands
            # at positions+1 — the absolute index the per-request key
            # is derived from (see _row_sample_keys)
            row_keys = _row_sample_keys(seeds, positions + 1)
            new_tokens = _sample(logits, key, temps, top_ps, top_ks,
                                 rep_pens, seen, False,
                                 row_keys=row_keys)
            b = tokens.shape[0]
            seen = seen.at[jnp.arange(b), new_tokens].max(active)
            return (_with_rider(new_tokens, rider), k_pages, v_pages,
                    k_scales, v_scales, seen)

        if tp > 1:
            # ONE shard_map'd program per decode tick: outer signatures
            # (and donate/static argnums at the jit sites) are
            # IDENTICAL to the single-device path so _decode and the
            # dispatch-guard discipline don't change at tp>1. Sampling
            # runs inside the shard_map on the psum'd full logits —
            # replicated on every shard, so out_specs P() is exact.
            from jax.sharding import PartitionSpec as P
            kvs = P(None, None, None, self._tp_axis, None)
            scs = P(None, None, None, self._tp_axis)
            rep = P()
            pspec = self._tp_specs

            if kind != "f32":
                def step_q(params, k_pages, v_pages, k_scales,
                           v_scales, seen, tokens, positions,
                           page_tables, active, key, temps, top_ps,
                           top_ks, rep_pens, seeds, lora, lora_idx,
                           all_greedy):
                    # explicit-tp engines serve no adapters (gated at
                    # register_loras): lora/lora_idx stay in the outer
                    # signature but never enter the shard_map
                    def local(params, k_pages, v_pages, k_scales,
                              v_scales, seen, tokens, positions,
                              page_tables, active, key, temps, top_ps,
                              top_ks, rep_pens, seeds):
                        return core(params, k_pages, v_pages, k_scales,
                                    v_scales, seen, tokens, positions,
                                    page_tables, active, key, temps,
                                    top_ps, top_ks, rep_pens, seeds,
                                    None, None, all_greedy)
                    sm = jax.shard_map(
                        local, mesh=mesh, check_vma=False,
                        in_specs=(pspec, kvs, kvs, scs, scs)
                        + (rep,) * 11,
                        out_specs=(rep, kvs, kvs, scs, scs, rep))
                    return sm(params, k_pages, v_pages, k_scales,
                              v_scales, seen, tokens, positions,
                              page_tables, active, key, temps, top_ps,
                              top_ks, rep_pens, seeds)
                return step_q

            def step(params, k_pages, v_pages, seen, tokens,
                     positions, page_tables, active, key, temps,
                     top_ps, top_ks, rep_pens, seeds, lora, lora_idx,
                     all_greedy):
                def local(params, k_pages, v_pages, seen, tokens,
                          positions, page_tables, active, key, temps,
                          top_ps, top_ks, rep_pens, seeds):
                    toks, k_pages, v_pages, _, _, seen = core(
                        params, k_pages, v_pages, None, None, seen,
                        tokens, positions, page_tables, active, key,
                        temps, top_ps, top_ks, rep_pens, seeds, None,
                        None, all_greedy)
                    return toks, k_pages, v_pages, seen
                sm = jax.shard_map(
                    local, mesh=mesh, check_vma=False,
                    in_specs=(pspec, kvs, kvs) + (rep,) * 11,
                    out_specs=(rep, kvs, kvs, rep))
                return sm(params, k_pages, v_pages, seen, tokens,
                          positions, page_tables, active, key, temps,
                          top_ps, top_ks, rep_pens, seeds)
            return step

        if kind != "f32":
            def step_q(params, k_pages, v_pages, k_scales, v_scales,
                       seen, tokens, positions, page_tables, active,
                       key, temps, top_ps, top_ks, rep_pens, seeds,
                       lora, lora_idx, all_greedy):
                return core(params, k_pages, v_pages, k_scales,
                            v_scales, seen, tokens, positions,
                            page_tables, active, key, temps, top_ps,
                            top_ks, rep_pens, seeds, lora, lora_idx,
                            all_greedy)
            return step_q

        def step(params, k_pages, v_pages, seen, tokens, positions,
                 page_tables, active, key, temps, top_ps, top_ks,
                 rep_pens, seeds, lora, lora_idx, all_greedy):
            toks, k_pages, v_pages, _, _, seen = core(
                params, k_pages, v_pages, None, None, seen, tokens,
                positions, page_tables, active, key, temps, top_ps,
                top_ks, rep_pens, seeds, lora, lora_idx, all_greedy)
            return toks, k_pages, v_pages, seen

        return step

    def _device_tables(self):
        """Device-resident copy of the page tables, re-uploaded only
        when the host mirror changed (allocation events)."""
        ver, arr = self._d_tables_cache
        if ver != self._tables_version:
            # jnp.array, not asarray: the host mirror is mutated in
            # place on every admit/finish, and on the CPU backend
            # asarray/device_put ALIAS a large enough numpy buffer
            # (jax 0.9) — the "device" tables then change under an
            # in-flight tick and the engine stops being deterministic
            # (a family with several cache groups gets its groups'
            # tables stacked [groups, B, pages]: ONE upload, and
            # `tables[g]` reads a group's as a tuple's entry would)
            tabs = self.cache.tables
            arr = self._dev(jnp.array(
                tabs[0] if len(tabs) == 1 else np.stack(tabs)))
            self._d_tables_cache = (self._tables_version, arr)
        return arr

    def _read_tokens(self, dev, of: int = 0) -> "np.ndarray":
        """THE engine's device->host sync point: every compiled-
        program readback funnels through here — lagged async folds
        and synchronous readbacks alike. jaxlint JL005 sanctions
        exactly this site; a bare np.asarray on a dispatch result anywhere else is
        flagged (tools/jaxlint/README.md). Time spent blocked here is
        the tick's un-hidden device time (the `readback_wait` phase;
        `device_ms` in stats()["tick_times"]). `of`: the tick whose
        program's output this waits for (a lagged fold waits for the
        tick before), so that a trace can hold each program's end
        against the end of its own wait."""
        with self._phase("readback_wait", of=of):
            host = np.asarray(dev)  # jaxlint: disable=JL005 -- the one sanctioned readback: the async pipeline folds land here, a tick behind dispatch
        if self.anomaly is not None:
            self._program_read(of)
        return host

    def _program_dispatched(self) -> None:
        """The current tick's program is on its way: open the record the
        detector will judge it on (`_judged`)."""
        if self.anomaly is not None:
            self._judged[self.ticks] = {"dispatched": time.perf_counter()}

    def _program_read(self, of: int) -> None:
        """A readback has ended: tick `of`'s program (if it was one)
        kept the device to itself since the later of its dispatch's end
        and the readback before this one. Waiting for another tick's
        program is that tick's cost and is booked to it here."""
        now = time.perf_counter()
        rec = self._judged.get(of)
        if rec is not None:
            rec["device_ms"] = own_program_ms(rec["dispatched"],
                                              self._read_end, now)
            if "sample" in rec:
                self._judge(of)
        self._read_end = now

    def _judge(self, tick: int) -> None:
        """Hand the detector tick `tick`, whole: its own PerfSample, the
        host time of the call that dispatched it plus its own program's
        time, that call's fold (the classifier's host share) and the
        compile counter as that call left it."""
        rec = self._judged.pop(tick)
        ev = self.anomaly.observe(
            rec["sample"], rec["host_ms"] + rec["device_ms"],
            rec["fold_ms"], rec["device_ms"], rec["compiles"],
            self.perf.envelope.peak_flops * self.perf.n_chips,
            self.perf.envelope.peak_bytes_per_s * self.perf.n_chips)
        if ev is not None:
            self._held(self._on_tick_anomaly, ev)

    def _held(self, fn, *args):
        """Run one of the analyzer's reactions under the step lock (the
        caller holds it) and book its time to
        stats()["self_captures"]["lock_hold_s"]."""
        t0 = time.perf_counter()
        try:
            return fn(*args)
        finally:
            self._capture_hold_s += time.perf_counter() - t0

    def _phase(self, name: str, **args) -> _Phase:
        """Open phase `name` (one of TICK_PHASES) of the current tick;
        `args` become the span's arguments in a trace."""
        return _Phase(self, name, args)

    def _ragged_fn(self, t_bucket: int, ctx_pages: int,
                   all_greedy: bool):
        """Jitted ragged tick: the ragged forward over the flat token
        batch + per-slot sampling, cached per (token bucket, context
        key, all_greedy). The context key is `_ctx_bucket`'s: on a
        kernel path the whole table or none (ONE program a token bucket,
        with a context or without), on the gather path a power-of-two
        bucket of context pages. all_greedy is a STATIC jit arg — keying
        the cache on it too keeps the compile counter honest (a
        greedy<->sampled flip builds a second program for the same
        shape bucket and must count as one). Attention impl comes from
        the SAME resolver as the decode program (auto -> Pallas ragged
        kernel on TPU, dense gather on CPU, pallas_interpret for tests).

        Host state arrives PACKED — tok_meta (5, T) int32 rows
        tokens/slot_ids/positions/valid/lora_idx, slot_meta (4, B)
        int32 rows start/last_idx/emit/seed, samp (4, B) f32 rows
        temps/top_ps/top_ks/rep_pens — so a tick uploads two small
        arrays (tok_meta, slot_meta) instead of ~10; samp is cached
        across ticks (see _sampling_cache)."""
        fn = self._ragged_fns.get((t_bucket, ctx_pages, all_greedy))
        if fn is None:
            cfg = self.model_cfg
            impl = self._resolve_impl()
            mesh = self.mesh
            ragged_forward = self.family.ragged_forward
            rider_len = self._rider_len

            kind = self._kv_kind
            # explicit tp: the forward runs INSIDE a shard_map (shard-
            # local cfg, no inner mesh, collectives via psum_axis)
            tp = self._tp if self._explicit_tp else 1
            cfg_fwd = self._tp_local_cfg if tp > 1 else cfg
            mesh_fwd = None if tp > 1 else mesh
            tp_kw = ({"psum_axis": self._tp_axis,
                      "logits_psum": self._tp_logits_psum}
                     if tp > 1 else {})

            def core(params, k_pages, v_pages, k_scales, v_scales,
                     seen, tok_meta, slot_meta, samp, page_tables,
                     key, lora, all_greedy):
                tokens, slot_ids, positions = (
                    tok_meta[0], tok_meta[1], tok_meta[2])
                valid = tok_meta[3] != 0
                lora_idx = tok_meta[4]
                start, last_idx = slot_meta[0], slot_meta[1]
                emit = slot_meta[2] != 0
                seeds = slot_meta[3]
                temps, top_ps, rep_pens = samp[0], samp[1], samp[3]
                top_ks = samp[2].astype(jnp.int32)
                out = ragged_forward(
                    cfg_fwd, params, tokens, slot_ids, positions,
                    valid, start, last_idx, k_pages, v_pages,
                    page_tables, ctx_pages=ctx_pages, lora=lora,
                    lora_idx=lora_idx, impl=impl, mesh=mesh_fwd,
                    kv_kind=kind, k_scales=k_scales,
                    v_scales=v_scales, **tp_kw)
                rider = None
                if rider_len:
                    logits, k_pages, v_pages, rider = out
                elif kind != "f32":
                    logits, k_pages, v_pages, k_scales, v_scales = out
                else:
                    logits, k_pages, v_pages = out
                if all_greedy:
                    toks = _with_rider(_sample(
                        logits, key, temps, top_ps, all_greedy=True),
                        rider)
                    return (toks, k_pages, v_pages, k_scales,
                            v_scales, seen)
                # this tick's tokens count as seen BEFORE sampling
                # (prompt tokens penalize too, HF semantics; for a
                # decoding slot the one token is already seen — no-op)
                seen = seen.at[slot_ids, tokens].max(valid)
                # each slot's sample lands one past its last packed
                # token — the same absolute index the decode program
                # keys on, so a request samples identically whichever
                # program serves its tick
                row_keys = _row_sample_keys(
                    seeds, positions[last_idx] + 1)
                toks = _sample(logits, key, temps, top_ps, top_ks,
                               rep_pens, seen, row_keys=row_keys)
                b = logits.shape[0]
                # only emitting slots keep their sample (mid-prefill
                # samples are discarded host-side, so they must not
                # leak into the penalty state either)
                seen = seen.at[jnp.arange(b), toks].max(emit)
                return (_with_rider(toks, rider), k_pages, v_pages,
                        k_scales, v_scales, seen)

            if tp > 1:
                # ONE shard_map'd collective-bearing program per tick:
                # outer signatures (and donate/static argnums below)
                # stay IDENTICAL to the single-device path so
                # _ragged_step and the dispatch-guard discipline don't
                # change at tp>1. Sampling runs inside the shard_map
                # on the psum'd full logits — replicated on every
                # shard, so out_specs P() is exact. lora never enters
                # the shard_map (gated at register_loras).
                from jax.sharding import PartitionSpec as P
                kvs = P(None, None, None, self._tp_axis, None)
                scs = P(None, None, None, self._tp_axis)
                rep = P()
                pspec = self._tp_specs

                if kind != "f32":
                    def run_q(params, k_pages, v_pages, k_scales,
                              v_scales, seen, tok_meta, slot_meta,
                              samp, page_tables, key, lora,
                              all_greedy):
                        def local(params, k_pages, v_pages, k_scales,
                                  v_scales, seen, tok_meta, slot_meta,
                                  samp, page_tables, key):
                            return core(params, k_pages, v_pages,
                                        k_scales, v_scales, seen,
                                        tok_meta, slot_meta, samp,
                                        page_tables, key, None,
                                        all_greedy)
                        sm = jax.shard_map(
                            local, mesh=mesh, check_vma=False,
                            in_specs=(pspec, kvs, kvs, scs, scs)
                            + (rep,) * 6,
                            out_specs=(rep, kvs, kvs, scs, scs, rep))
                        return sm(params, k_pages, v_pages, k_scales,
                                  v_scales, seen, tok_meta, slot_meta,
                                  samp, page_tables, key)
                    fn = jax.jit(run_q,
                                 donate_argnums=(1, 2, 3, 4, 5),
                                 static_argnums=(12,))
                else:
                    def run(params, k_pages, v_pages, seen, tok_meta,
                            slot_meta, samp, page_tables, key, lora,
                            all_greedy):
                        def local(params, k_pages, v_pages, seen,
                                  tok_meta, slot_meta, samp,
                                  page_tables, key):
                            toks, k_pages, v_pages, _, _, seen = core(
                                params, k_pages, v_pages, None, None,
                                seen, tok_meta, slot_meta, samp,
                                page_tables, key, None, all_greedy)
                            return toks, k_pages, v_pages, seen
                        sm = jax.shard_map(
                            local, mesh=mesh, check_vma=False,
                            in_specs=(pspec, kvs, kvs) + (rep,) * 6,
                            out_specs=(rep, kvs, kvs, rep))
                        return sm(params, k_pages, v_pages, seen,
                                  tok_meta, slot_meta, samp,
                                  page_tables, key)

                    fn = jax.jit(run, donate_argnums=(1, 2, 3),
                                 static_argnums=(10,))
            elif kind != "f32":
                def run_q(params, k_pages, v_pages, k_scales, v_scales,
                          seen, tok_meta, slot_meta, samp, page_tables,
                          key, lora, all_greedy):
                    return core(params, k_pages, v_pages, k_scales,
                                v_scales, seen, tok_meta, slot_meta,
                                samp, page_tables, key, lora,
                                all_greedy)
                fn = jax.jit(run_q, donate_argnums=(1, 2, 3, 4, 5),
                             static_argnums=(12,))
            else:
                def run(params, k_pages, v_pages, seen, tok_meta,
                        slot_meta, samp, page_tables, key, lora,
                        all_greedy):
                    toks, k_pages, v_pages, _, _, seen = core(
                        params, k_pages, v_pages, None, None, seen,
                        tok_meta, slot_meta, samp, page_tables, key,
                        lora, all_greedy)
                    return toks, k_pages, v_pages, seen

                fn = jax.jit(run, donate_argnums=(1, 2, 3),
                             static_argnums=(10,))
            self.compiles += 1
            self._ragged_fns[(t_bucket, ctx_pages, all_greedy)] = fn
        return fn

    @staticmethod
    def _token_bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    def _tick_token_budget(self) -> int:
        """The one tick-packing token budget — _pack_ragged spends it
        and telemetry's budget-utilization gauge divides by it, so
        both must read the SAME formula."""
        ec = self.config
        return ec.max_num_batched_tokens or (
            ec.max_prefill_tokens + ec.max_batch_size)

    def _pack_ragged(self):
        """Sarathi-style token-budget packing for one ragged tick:
        every decoding slot contributes 1 token, then prefilling slots
        claim chunks round-robin from what's left of the budget (at
        least one prefill token per tick, so a decode-saturated budget
        can never starve admission-to-first-token). Returns
        [(slot, n_tokens, is_prefill)]."""
        ec = self.config
        budget = self._tick_token_budget()
        plan = []
        n_decode = 0
        for s in self.slots:
            if s.request is not None and s.ready:
                plan.append((s, 1, False))
                n_decode += 1
        left = max(budget - n_decode, 1)
        B = len(self.slots)
        first_served = None
        for off in range(B):
            if left <= 0:
                break
            s = self.slots[(self._prefill_rr + off) % B]
            if s.request is None or s.ready:
                continue
            take = min(len(s.request.prompt_tokens) - s.prefill_pos,
                       left, ec.max_prefill_tokens)
            plan.append((s, take, True))
            left -= take
            if first_served is None:
                first_served = s.index
        if first_served is not None:
            # rotate so a budget-limited tail goes first next tick
            self._prefill_rr = (first_served + 1) % B
        return plan

    def _need_penalty(self) -> bool:
        return any(s.request is not None
                   and s.request.params.repetition_penalty != 1.0
                   for s in self.slots)

    def _seen_row(self, index: int) -> "np.ndarray":
        """Host (V,) 'seen' row for ONE slot — the one builder of the
        repetition-penalty support, shared by the full (B, V) rebuild
        and the incremental dirty-row refresh so the two can never
        diverge. Ready slots have seen prompt+output; prefilling slots
        their already-cached prefix (later chunks accumulate
        in-program); empty slots an all-False row."""
        V = self.model_cfg.vocab_size
        row = np.zeros(V, bool)
        s = self.slots[index]
        if s.request is not None:
            toks = (s.request.prompt_tokens
                    + s.request.output_tokens if s.ready
                    else s.request.prompt_tokens[:s.prefill_pos])
            if toks:
                row[np.asarray(toks, np.int64) % V] = True
        return row

    def _mark_seen_dirty(self, index: int) -> None:
        """Record a ban-list mutation (slot admission/retirement) for
        the incremental seen refresh; None means a full rebuild is
        already pending."""
        if self._seen_dirty_slots is not None:
            self._seen_dirty_slots.add(index)

    def _build_seen(self):
        """Host (B, V) 'seen' array for the FULL refresh (row builder
        shared with the incremental path, see _seen_row). Rows stay
        zero when no penalty is live."""
        B = self.config.max_batch_size
        V = self.model_cfg.vocab_size
        seen = np.zeros((B, V), bool)
        if self._need_penalty():
            for s in self.slots:
                if s.request is not None:
                    seen[s.index] = self._seen_row(s.index)
        return seen

    def _refresh_seen(self) -> None:
        """Refresh ONLY the penalty 'seen' state for a ragged tick —
        a ragged tick needs nothing else device-resident (the decode
        loop state is rebuilt lazily by the next pure-decode tick).

        A ban-list mutation (admission/retirement) dirties one slot,
        so the steady path rebuilds and re-uploads just the dirty
        rows — (n, V) padded to a power-of-two row count, scattered
        in place into the donated device buffer — instead of the old
        full (B, V) host rebuild + upload per mutation. With no live
        penalty both are skipped outright: stale device rows are
        exact no-ops at rep_pen == 1.0 (a later penalty admission
        re-dirties its slot and rebuilds that row)."""
        dirty = self._seen_dirty_slots
        if self._d_seen is None or dirty is None:
            self._d_seen = self._dev(jnp.asarray(self._build_seen()))
            self._seen_dirty_slots = set()
            return
        if not dirty:
            return
        self._seen_dirty_slots = set()
        if not self._need_penalty():
            return
        idx = sorted(dirty)
        rows = np.stack([self._seen_row(i) for i in idx])
        # bucket the row count to a power of two: the scatter program
        # compiles once per bucket (log2(B)+1 max), never per distinct
        # dirty count (the JL003 discipline). Padding duplicates the
        # last row — an identical duplicate scatter is a no-op.
        n = 1
        while n < len(idx):
            n *= 2
        if n not in self._seen_scatter_buckets:
            # first use of this row-count bucket builds a program
            self._seen_scatter_buckets.add(n)
            self.compiles += 1
        if n > len(idx):
            pad = n - len(idx)
            idx = idx + [idx[-1]] * pad
            rows = np.concatenate(
                [rows, np.repeat(rows[-1:], pad, axis=0)])
        # NOT counted in self.dispatches: like the full-rebuild upload
        # it replaces, this is turnover-event state refresh, not the
        # tick's forward dispatch (see the counter's definition)
        self._d_seen = self._seen_update_fn(
            self._d_seen,
            self._dev(jnp.asarray(np.asarray(idx, np.int32))),
            self._dev(jnp.asarray(rows)))

    def _sampling_cache(self):
        """Device-resident (4, B) sampling params [temps, top_ps,
        top_ks, rep_pens] + the all_greedy flag, built ONCE and reused
        across ticks (sampling params cannot change mid-request) —
        invalidated only on slot admission/retirement. Before the
        cache, every ragged tick re-uploaded four (B,)-arrays that had
        not changed."""
        if self._samp_cache is None:
            B = self.config.max_batch_size
            samp = np.zeros((4, B), np.float32)
            samp[1] = 1.0
            samp[3] = 1.0
            for s in self.slots:
                if s.request is None:
                    continue
                p = s.request.params
                samp[0, s.index] = p.temperature
                samp[1, s.index] = p.top_p
                samp[2, s.index] = p.top_k
                samp[3, s.index] = p.repetition_penalty
            all_greedy = bool(np.all(samp[0] <= 0.0)
                              and np.all(samp[3] == 1.0))
            self._samp_cache = (self._dev(jnp.asarray(samp)),
                                all_greedy)
        return self._samp_cache

    # -- per-dispatch perf accounting (ISSUE 11) ---------------------------
    # Each hook below runs on the host next to the dispatch it
    # describes, folding that dispatch's analytic cost (perfmodel
    # closed forms over the batch composition the engine just packed)
    # into the tick's pending PerfSample. Plain int/float arithmetic:
    # nothing here can add an upload, a sync, or a compile.
    @staticmethod
    def _merge_cost(tot: Dict[str, float], c: Dict[str, float]) -> None:
        for k, v in c.items():
            tot[k] = tot.get(k, 0.0) + v

    def _account_decode_batch(self) -> Tuple[int, int, Dict[str, int]]:
        """One whole-batch decode dispatch: every active slot advances
        one token at its current context. Returns (rows, the context
        tokens their attention reads, what a family with window layers
        adds) for the dispatch span."""
        with self._phase("account"):
            cm = self.perf.model if self.perf is not None else None
            tot: Dict[str, float] = {}
            ndec = kv = 0
            # (cached tokens, 1) per row, for a family with window layers
            counts = self.family.span_counts
            segs = []
            # the host's positions lag the device's by the tick in
            # flight, if one is
            ahead = 1 if self._inflight is not None else 0
            for s in self.slots:
                if s.request is None or not s.ready \
                        or not self._host_active[s.index]:
                    continue
                ndec += 1
                kv += s.position + 1 + ahead
                if counts is not None:
                    segs.append((s.position + ahead, 1))
                if cm is None:
                    continue
                c = cm.decode_cost(s.position + 1)
                self._merge_cost(tot, c)
                if self.attrib is not None:
                    # the SAME closed-form dict rides both sides, so
                    # the receipt sum conserves against the tick total
                    # exactly
                    self.attrib.charge(s.request, c, decode_tokens=1,
                                       pages=len(s.pages))
            if ndec and cm is not None:
                self.perf.add("decode", tot, decode_tokens=ndec)
            extra = (counts(self.model_cfg, segs, [True] * ndec)
                     if counts is not None else {})
            return ndec, kv, extra

    def _ragged_step(self, touched: List[Request]) -> None:
        """One ragged tick: pack, dispatch the single ragged program,
        fold the one readback into slot state. Host->device traffic
        per tick: ONE (5, T) token-meta upload + ONE (4, B) slot-meta
        upload (page tables and sampling params ride their caches)."""
        with self._phase("pack"):
            self._refresh_seen()      # early-outs when nothing is dirty
            plan = self._pack_ragged()
            B = self.config.max_batch_size
            total = sum(n for _, n, _ in plan)
            self.telemetry.on_tick_budget(total,
                                          self._tick_token_budget())
            T = self._token_bucket(total)
            # rows: tokens / slot_ids / positions / valid / lora_idx
            tok_meta = np.zeros((5, T), np.int32)
            # rows: start / last_idx / emit / sampling seed
            slot_meta = np.zeros((4, B), np.int32)
            max_start = 0
            cur = 0
            # pairs: (query, key) kept by the causal rule; dec_pairs:
            # the decode rows' part (their context + 1 each)
            ndec = npre = kv = pairs = dec_pairs = 0
            segs = []                # (cached tokens, tokens) per row
            for s, n, is_pref in plan:
                req = s.request
                if is_pref:
                    seg = req.prompt_tokens[
                        s.prefill_pos:s.prefill_pos + n]
                    pos0 = s.prefill_pos
                    npre += n
                else:
                    seg = [s.last_token]
                    pos0 = s.position
                    ndec += 1
                    dec_pairs += pos0 + 1
                kv += pos0 + n       # the context the row's last token reads
                pairs += n * pos0 + n * (n + 1) // 2
                segs.append((pos0, n))
                tok_meta[0, cur:cur + n] = seg
                tok_meta[1, cur:cur + n] = s.index
                tok_meta[2, cur:cur + n] = np.arange(pos0, pos0 + n)
                tok_meta[3, cur:cur + n] = 1
                tok_meta[4, cur:cur + n] = self._lora_names.get(
                    req.lora, 0)
                slot_meta[0, s.index] = pos0
                slot_meta[1, s.index] = cur + n - 1
                slot_meta[2, s.index] = ((not is_pref)
                                         or s.prefill_pos + n
                                         >= len(req.prompt_tokens))
                slot_meta[3, s.index] = s.seed
                max_start = max(max_start, pos0)
                cur += n
            samp, all_greedy = self._sampling_cache()
            ctx = self._ctx_bucket(max_start)
            built = self.compiles
            fn = self._ragged_fn(T, ctx, all_greedy)
            built = self.compiles - built
            # the attention kernel's grid this tick: live (slot, query
            # block) items of a static bound, and the KV blocks they
            # sweep (context plus in-batch); the kernel reads the whole
            # page table, whatever the context bucket
            items, kv_blocks = self.family.work_counts(
                segs, T, self.config.page_size,
                self.max_pages_per_seq if ctx else 0,
                self._attn_geometry)
            # what a family with window layers adds to the span
            extra = (self.family.span_counts(
                self.model_cfg, segs, [not p for _, _, p in plan])
                if self.family.span_counts is not None else {})
        if self.perf is not None:
            with self._phase("account"):
                cm = self.perf.model
                tot: Dict[str, float] = {}
                for ps, pn, is_pref in plan:
                    if is_pref:
                        c = cm.chunk_cost(ps.prefill_pos, pn)
                    else:
                        c = cm.decode_cost(ps.position + 1)
                    self._merge_cost(tot, c)
                    if self.attrib is not None:
                        self.attrib.charge(
                            ps.request, c,
                            decode_tokens=0 if is_pref else 1,
                            prefill_tokens=pn if is_pref else 0,
                            pages=len(ps.pages))
                self.perf.add("ragged", tot, decode_tokens=ndec,
                              prefill_tokens=npre)
        self._prefill_tokens_dispatched += npre
        # what this tick's one program carries: the dispatch span's
        # arguments (only the host can say this of a `jit_run`) and the
        # tick's ring record
        carried = self._tick_carried = {
            "tick": self.ticks, "kind": "ragged", "T": T, "ctx": ctx,
            "rows": len(plan),
            "decode_rows": ndec, "prefill_tokens": npre,
            "kv_tokens": kv, "attn_pairs": pairs,
            "decode_pairs": dec_pairs, "built": built,
            "attn_items": items, "attn_kv_blocks": kv_blocks, **extra}
        with self._phase("dispatch", **carried):
            self._key, sub = jax.random.split(self._key)
            self.dispatches += 1
            if self._kv_kind != "f32":
                (toks, self.k_pages, self.v_pages, self.k_scales,
                 self.v_scales, self._d_seen) = fn(
                    self.params, self.k_pages, self.v_pages,
                    self.k_scales, self.v_scales, self._d_seen,
                    self._dev(jnp.asarray(tok_meta)),
                    self._dev(jnp.asarray(slot_meta)),
                    samp, self._device_tables(), sub,
                    self._lora_stacks, all_greedy)
            else:
                toks, self.k_pages, self.v_pages, self._d_seen = fn(
                    self.params, self.k_pages, self.v_pages,
                    self._d_seen,
                    self._dev(jnp.asarray(tok_meta)),
                    self._dev(jnp.asarray(slot_meta)),
                    samp, self._device_tables(), sub,
                    self._lora_stacks, all_greedy)
        self._program_dispatched()
        toks_host = self._read_tokens(toks, of=self.ticks)
        # fold ALL slots from the one readback before any device-state
        # refresh
        with self._phase("fold", tokens=len(plan),
                         **self._fold_rider(toks_host, total, self.ticks)):
            for s, n, is_pref in plan:
                tok = int(toks_host[s.index])
                if is_pref:
                    self.telemetry.on_prefill_chunk(s.request, n,
                                                    s.prefill_pos)
                    s.prefill_pos += n
                    if s.prefill_pos >= len(s.request.prompt_tokens):
                        self._finish_prefill(s, tok, touched)
                else:
                    s.position += 1
                    s.last_token = tok
                    self._append_token(s, tok, touched)
        # the device-resident decode loop state (tokens/positions) is
        # stale after a ragged tick; the next pure-decode tick
        # refreshes lazily. _d_seen stays live: the program updated it
        # for every surviving slot; slot turnover dirties its row via
        # _mark_seen_dirty.
        self._d_tokens = None

    def _resolve_impl(self) -> str:
        """decode_impl with "auto" resolved: an accelerator runs the
        compiled Pallas kernels; the CPU backend, which cannot compile
        them, runs the dense gather (kernel logic is covered in
        interpret mode, kernel compilation for the TPU by
        tests/test_tpu_aot_compile.py). One resolver for the pool
        layout and both programs so they can never diverge."""
        impl = self.config.decode_impl
        if impl == "auto":
            impl = ("gather" if jax.devices()[0].platform == "cpu"
                    else "pallas")
        return impl

    def _ctx_bucket(self, start: int) -> int:
        """The context key of a ragged tick's program, in pages. The
        gather path cuts the tables to it: the least power of two that
        covers `start` tokens. A kernel reads a row's own pages off the
        whole table: context or none, ONE program a token bucket."""
        need = self.allocator.pages_needed(start)
        if need and self._resolve_impl() != "gather":
            return self.max_pages_per_seq
        b = 1
        while b < need:
            b *= 2
        return min(b, self.max_pages_per_seq) if need else 0

    # -- KV memory hierarchy (ISSUE 10) -------------------------------------
    # Host-offload tier + preemption spill/restore. Every method here
    # runs at STRUCTURAL time (after a _drain, outside the steady
    # decode path): the page gather/scatter programs are state
    # migration like _refresh_device_state's uploads — excluded from
    # self.dispatches, counted into self.compiles on first build — and
    # the restore upload is a sanctioned structural-event h2d exactly
    # like admission's prefill uploads. Steady-state decode ticks with
    # the tier active stay 0 h2d / 0 compiles / 1 dispatch (the
    # dispatch-guard suite runs offload-enabled engines).

    @property
    def parked(self) -> List[Any]:
        """Parked (spilled) sequences, FIFO restore order."""
        return self.host_tier.entries() if self.host_tier else []

    def _reserve_tokens(self, prompt_len: int, max_tokens: int) -> int:
        """Admission page reservation in tokens: worst case
        (prompt + max_tokens) by default; under optimistic admission
        (kv_watermark_tokens) only prompt + watermark, with page
        growth + preemption covering the rest."""
        wm = self.config.kv_watermark_tokens
        if wm is None:
            return prompt_len + max_tokens
        return prompt_len + min(max_tokens, wm)

    @staticmethod
    def _page_bucket(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _page_gather_fn(self, nb: int):
        """Jitted d2h spill gather: copy `nb` pages out of the pools
        into a fresh (L, nb, page, H, D) buffer whose async host copy
        can stream while the freed pool pages are reused."""
        fn = self._page_gather_fns.get(nb)
        if fn is None:
            if self._kv_kind != "f32":
                # quantized pools spill AS STORED: narrow value pages
                # plus their f32 scale pages ride the same d2h stream
                def run(k_pages, v_pages, k_scales, v_scales, ids):
                    return (jnp.take(k_pages, ids, axis=1),
                            jnp.take(v_pages, ids, axis=1),
                            jnp.take(k_scales, ids, axis=1),
                            jnp.take(v_scales, ids, axis=1))
            else:
                def run(k_pages, v_pages, ids):
                    return (jnp.take(k_pages, ids, axis=1),
                            jnp.take(v_pages, ids, axis=1))

            # donation audit (JL002): the pools are deliberately NOT
            # donated — the gather READS the live pools (which the
            # next tick keeps using) into an independent spill buffer;
            # donating would invalidate the engine's pool handles
            fn = jax.jit(run)  # jaxlint: disable=JL002 -- read-only spill gather: pools stay live for the next tick, output is the independent d2h buffer
            self.compiles += 1
            self._page_gather_fns[nb] = fn
        return fn

    def _page_scatter_fn(self, nb: int):
        """Jitted h2d restore scatter: write `nb` host pages into
        their freshly-allocated pool slots. Pools are donated — XLA
        updates them in place, no copy of the cache per restore."""
        fn = self._page_scatter_fns.get(nb)
        if fn is None:
            quant = self._kv_kind != "f32"
            kw = {}
            if self._kv_sharding is not None:
                # tp mesh: pin the restored pools to the engine's KV
                # sharding — inference could otherwise replicate the
                # output, breaking donation and retracing every
                # decode program against the new layout
                kw["out_shardings"] = (self._kv_sharding,
                                       self._kv_sharding)
                if quant:
                    kw["out_shardings"] += (self._scale_sharding,
                                            self._scale_sharding)
            if quant:
                def run_q(k_pages, v_pages, k_scales, v_scales, ids,
                          kh, vh, ksh, vsh):
                    return (k_pages.at[:, ids].set(kh),
                            v_pages.at[:, ids].set(vh),
                            k_scales.at[:, ids].set(ksh),
                            v_scales.at[:, ids].set(vsh))
                fn = jax.jit(run_q, donate_argnums=(0, 1, 2, 3), **kw)
            else:
                def run(k_pages, v_pages, ids, kh, vh):
                    return (k_pages.at[:, ids].set(kh),
                            v_pages.at[:, ids].set(vh))
                fn = jax.jit(run, donate_argnums=(0, 1), **kw)
            self.compiles += 1
            self._page_scatter_fns[nb] = fn
        return fn

    def _finalize_spills(self) -> None:
        """Materialize pending spills to host numpy, one tick after
        their gather dispatched — the copy_to_host_async started at
        spill time has had a whole tick to stream, so this readback is
        (ideally) a wait-free pickup, the lagged-readback discipline
        applied to page migration."""
        if not self._pending_spills:
            return
        for parked in self._pending_spills:
            parked.materialize(self._read_tokens)
        self._pending_spills.clear()

    def _preempt_slot(self, victim: _Slot, touched: List[Request],
                      reason: str) -> bool:
        """Preempt one slot (caller has drained). A decoding victim
        SPILLS: its cached pages gather into a fresh buffer (async d2h
        starts immediately), the request parks in the host tier, and
        the device pages free for the winner. A still-prefilling
        victim REQUEUES instead — it has emitted nothing, so going
        back to the head of the waiting queue is token-exact for free
        and its warm prompt pages survive in the prefix cache.
        Returns False when the victim cannot be preempted (no host
        tier for a decoding victim, or the tier is full)."""
        req = victim.request
        if not victim.ready:
            self.allocator.free(victim.pages)
            self._clear_slot(victim)
            req.restarts += 1
            self.waiting.insert(0, req)
            self.preempt_counts[reason] = \
                self.preempt_counts.get(reason, 0) + 1
            self.telemetry.on_preempted(req, reason, mode="requeue")
            return True
        tier = self.host_tier
        if tier is None:
            return False
        n_pages = self.allocator.pages_needed(victim.position)
        if not tier.can_store(n_pages):
            return False
        from .kv_offload import ParkedSequence
        nb = self._page_bucket(n_pages)
        ids = victim.pages[:n_pages]
        ids = ids + [ids[-1]] * (nb - n_pages)
        d_ids = self._dev(jnp.asarray(np.asarray(ids, np.int32)))
        ksh = vsh = None
        if self._kv_kind != "f32":
            kh, vh, ksh, vsh = self._page_gather_fn(nb)(
                self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, d_ids)
        else:
            kh, vh = self._page_gather_fn(nb)(
                self.k_pages, self.v_pages, d_ids)
        if self.perf is not None:
            # actual transfer is the BUCKETED page count (padding
            # duplicates move too) — real d2h traffic, not the ideal
            self.perf.note_offload(d2h=nb * self.perf.model.page_bytes)
            if self.attrib is not None:
                self.attrib.charge_offload(
                    req, d2h=nb * self.perf.model.page_bytes)
        # overlap: the d2h copies stream while decode continues; the
        # gather output is its own buffer, so the pool pages freed
        # below can be rewritten without corrupting the spill
        for arr in (kh, vh, ksh, vsh):
            start = getattr(arr, "copy_to_host_async", None)
            if start is not None:
                start()
        parked = ParkedSequence(
            request=req, seed=victim.seed, position=victim.position,
            last_token=victim.last_token, n_pages=n_pages,
            reason=reason, k_pending=kh, v_pending=vh,
            kv_kind=self._kv_kind, k_scales_pending=ksh,
            v_scales_pending=vsh)
        tier.park(parked)
        self._pending_spills.append(parked)
        self.allocator.free(victim.pages)
        self._clear_slot(victim)
        self.preempt_counts[reason] = \
            self.preempt_counts.get(reason, 0) + 1
        self.telemetry.on_preempted(req, reason, mode="spill",
                                    pages=n_pages,
                                    position=victim.position)
        return True

    def _alloc_or_preempt(self, n: int, protect, touched: List[Request],
                          reason: str) -> Optional[List[int]]:
        """allocate_pages with preemption as the safety valve: while
        pages are short, spill/requeue victims (deterministic order —
        kv_offload.pick_victim) until the allocation fits or no victim
        remains. None = genuinely exhausted (caller degrades)."""
        if n <= 0:
            return []
        from .kv_offload import pick_victim
        while n > self.allocator.free_pages:
            victim = (pick_victim(self.slots, protect,
                                  spill_ok=self.host_tier is not None)
                      if self.config.enable_kv_offload else None)
            if victim is None \
                    or not self._preempt_slot(victim, touched, reason):
                return None
        return self.allocator.allocate_pages(n)

    def _grow_slots(self, touched: List[Request]) -> None:
        """Optimistic-admission page growth: any decoding slot whose
        next ticks would write past its reservation extends it BEFORE
        the dispatch — to its full remaining need when pages are
        plentiful (so a slot grows once, not every page boundary),
        minimally (with preemption) under pressure. Growth failure is
        the ISSUE-10 exhaustion path: the slot finishes with
        finish_reason="error" instead of raising into the pump."""
        if self.config.kv_watermark_tokens is None:
            return
        page = self.allocator.page_size
        # headroom past the host position: the next dispatch writes at
        # s.position, the pipelined successor one past that (the fold
        # assert's +1 slack), PLUS one more with async_readback on — the host
        # position is one tick stale at this check (the in-flight
        # tick's write is not folded yet), so growth must trigger a
        # tick early or the drain fold below trips its own assert
        slack = 2 if self._async else 1

        def targets(s):
            """(minimum, full) token targets for one slot's growth.
            Both clamp to the request's TRUE final need — position +
            remaining + 1 == prompt + max_tokens, the worst-case
            reservation add_request validated against max_seq — so
            growth can never demand a page past max_pages_per_seq
            (an unclamped slack near the end would overflow the
            fixed page-table row) nor spill a victim for a page
            that will never be written."""
            rem = max(s.request.params.max_tokens
                      - len(s.request.output_tokens), 1)
            final = s.position + rem + 1
            return min(s.position + 1 + slack, final), final

        def short(s):
            if s.request is None or not s.ready:
                return False
            return len(s.pages) * page < targets(s)[0]

        if not any(short(s) for s in self.slots):
            return
        self._drain(touched)      # structural: tables are changing
        dirty = False
        for s in self.slots:
            if not short(s):
                continue          # may have retired in the drain fold
            min_tokens, full_tokens = targets(s)
            full_need = self.allocator.pages_needed(
                full_tokens) - len(s.pages)
            min_need = self.allocator.pages_needed(
                min_tokens) - len(s.pages)
            self._alloc_ctx = s.index
            try:
                free = self.allocator.free_pages
                if free >= min_need:
                    got = self.allocator.allocate_pages(
                        max(min(full_need, free), min_need))
                else:
                    # under real pressure the victim order must hold
                    # ACROSS growers too: if this slot is itself the
                    # fleet's designated victim (lowest priority /
                    # youngest), park IT rather than letting slot
                    # iteration order preempt a higher-priority peer
                    from .kv_offload import pick_victim
                    if self.config.enable_kv_offload and pick_victim(
                            self.slots, (),
                            spill_ok=self.host_tier is not None) is s \
                            and self._preempt_slot(s, touched,
                                                   "growth"):
                        dirty = True
                        continue
                    got = self._alloc_or_preempt(
                        min_need, (s.index,), touched, "growth")
            finally:
                self._alloc_ctx = None
            if got is None:
                self._kv_exhausted(s, touched, where="growth")
                dirty = True
                continue
            s.pages.extend(got)
            self._page_tables[s.index][:len(s.pages)] = s.pages
            self._tables_version += 1
            dirty = True
        if dirty:
            self._refresh_device_state()

    def _restore_parked(self, touched: List[Request]) -> bool:  # jaxlint: disable=JL006 -- restore-time page upload: one scatter per re-admitted sequence (structural event), never on the tick path
        """Re-admit parked sequences (FIFO), restoring their KV pages
        token-exact: full prompt pages still resident in the prefix
        cache are re-shared as-is (their content IS the original
        prefill KV), the rest upload from the host tier into freshly
        allocated pages via the donated scatter program. The restored
        slot resumes the decode invariant exactly as spilled —
        `position` cached tokens, `last_token` pending — so the next
        tick samples with the same (seed, absolute index) key a
        never-preempted engine would have used."""
        tier = self.host_tier
        if tier is None or not len(tier):
            return False
        restored = False
        for parked in tier.entries():
            slot = next((s for s in self.slots if s.request is None),
                        None)
            if slot is None:
                break
            if self.waiting and self.waiting[0].priority \
                    > parked.request.priority:
                # batch-lane inversion guard (ISSUE 14): restoring a
                # preempted priority-0 batch session while a
                # higher-priority interactive request waits would
                # hand back the slot/pages the winner is queued for
                # (and thrash the spill path when it preempts again);
                # the parked work resumes in the next trough.
                # CONTINUE, not break: a parked session deeper in the
                # FIFO that the head does NOT outrank (e.g. a parked
                # interactive behind parked batch) must still
                # restore, or a mixed-priority tier livelocks — the
                # head can't outrank ALL parked (so _admit's gate
                # blocks) while the restorable one waits forever
                # behind the batch head
                continue
            req = parked.request
            remaining = (req.params.max_tokens
                         - len(req.output_tokens))
            reserve = parked.position + 1 + (
                remaining if self.config.kv_watermark_tokens is None
                else min(remaining, self.config.kv_watermark_tokens))
            shared, matched = self.allocator.match_prefix(
                req.prompt_tokens)
            need = self.allocator.pages_needed(reserve) - len(shared)
            if need > self.allocator.free_pages:
                self.allocator.free(shared)   # undo the match refs
                break        # FIFO head waits; no preempt-to-restore
            parked.materialize(self._read_tokens)
            if parked in self._pending_spills:
                self._pending_spills.remove(parked)
            tier.pop(req.request_id)
            pages = shared + self.allocator.allocate_pages(need)
            lo, hi = len(shared), parked.n_pages
            if hi > lo:
                cnt = hi - lo
                nb = self._page_bucket(cnt)
                ids = pages[lo:hi] + [pages[hi - 1]] * (nb - cnt)

                def _bucketed(host):
                    rows = host[:, lo:hi]
                    if nb > cnt:
                        rows = np.concatenate(
                            [rows, np.repeat(rows[:, -1:],
                                             nb - cnt, axis=1)], 1)
                    return self._dev(jnp.asarray(rows))

                kh = _bucketed(parked.k_host)
                vh = _bucketed(parked.v_host)
                if self.perf is not None:
                    self.perf.note_offload(
                        h2d=nb * self.perf.model.page_bytes)
                    if self.attrib is not None:
                        self.attrib.charge_offload(
                            req, h2d=nb * self.perf.model.page_bytes)
                # the sanctioned restore upload: a structural-event
                # h2d (like admission prefill uploads), never on the
                # steady decode path
                d_ids = self._dev(
                    jnp.asarray(np.asarray(ids, np.int32)))
                if self._kv_kind != "f32":
                    (self.k_pages, self.v_pages, self.k_scales,
                     self.v_scales) = self._page_scatter_fn(nb)(
                        self.k_pages, self.v_pages, self.k_scales,
                        self.v_scales, d_ids, kh, vh,
                        _bucketed(parked.k_scales_host),
                        _bucketed(parked.v_scales_host))
                else:
                    self.k_pages, self.v_pages = \
                        self._page_scatter_fn(nb)(
                            self.k_pages, self.v_pages, d_ids, kh, vh)
            slot.request = req
            slot.pages = pages
            slot.prefill_pos = len(req.prompt_tokens)
            slot.position = parked.position
            slot.last_token = parked.last_token
            slot.ready = True
            slot.seed = parked.seed
            # re-offer the FULL prompt pages to the prefix cache:
            # locally-spilled sessions usually find them still cached
            # (no-op), but a session IMPORTED from another replica
            # (ISSUE 12) carries prompt KV this replica never
            # prefilled — registering it here is what multiplies the
            # per-replica prefix cache across the fleet
            self.allocator.register_prefix(
                req.prompt_tokens,
                pages[:len(req.prompt_tokens)
                      // self.allocator.page_size])
            table = np.zeros(self.max_pages_per_seq, np.int32)
            table[:len(pages)] = pages
            self._page_tables[slot.index] = table
            self._tables_version += 1
            self._mark_seen_dirty(slot.index)
            self._samp_cache = None
            req.restarts += 1
            self.telemetry.on_restored(req, pages=parked.n_pages,
                                       parked_s=parked.idle_s(),
                                       shared_pages=len(shared))
            restored = True
        if restored:
            # restored slots are decode-ready: rebuild the device
            # loop state lazily on the next decode/ragged tick
            self._d_tokens = None
        return restored

    def _restore_possible(self) -> bool:
        """Mirror of _restore_parked's head-of-ELIGIBLE-queue
        feasibility check (conservative toward True, like
        _admit_possible): eligible = not outranked by the waiting
        head (the ISSUE 14 yield in _restore_parked)."""
        tier = self.host_tier
        if tier is None or not len(tier):
            return False
        if not any(s.request is None for s in self.slots):
            return False
        head_pri = (self.waiting[0].priority if self.waiting
                    else None)
        parked = next(
            (p for p in tier.entries()
             if head_pri is None or p.request.priority >= head_pri),
            None)
        if parked is None:
            return False
        req = parked.request
        remaining = req.params.max_tokens - len(req.output_tokens)
        reserve = parked.position + 1 + (
            remaining if self.config.kv_watermark_tokens is None
            else min(remaining, self.config.kv_watermark_tokens))
        need = self.allocator.pages_needed(reserve)
        if self.allocator.enable_prefix_caching:
            need -= ((len(req.prompt_tokens) - 1)
                     // self.allocator.page_size)
        return need <= self.allocator.free_pages

    def _kv_exhausted(self, slot: Optional[_Slot],
                      touched: List[Request], where: str,
                      error: Optional[str] = None) -> None:
        """Graceful degradation for true page exhaustion (ISSUE 10):
        a guard_violation-style flight-recorder event (alert-hooked —
        it black-boxes a postmortem bundle), and the victim request
        finishes with finish_reason="error" instead of a MemoryError
        wedging the replica's pump."""
        req = slot.request if slot is not None else None
        self.telemetry.recorder.record(
            "kv_exhausted", where=where, error=error,
            request_id=req.request_id if req else None,
            free_pages=self.allocator.free_pages,
            parked=len(self.parked), waiting=len(self.waiting))
        if req is not None:
            self._finish(slot, "error")
            touched.append(req)

    def _handle_memory_error(self, exc: MemoryError,
                             touched: List[Request]) -> None:
        """Engine-boundary backstop (ISSUE 10 satellite): a raw
        MemoryError escaping allocate_pages mid-tick — any path the
        graceful growth/admission checks did not cover — retires the
        attributable victim (or the lowest-priority/youngest slot)
        with finish_reason="error" and leaves the pump alive."""
        victim: Optional[_Slot] = None
        if self._alloc_ctx is not None:
            s = self.slots[self._alloc_ctx]
            if s.request is not None:
                victim = s
        self._alloc_ctx = None
        if victim is None:
            from .kv_offload import pick_victim
            victim = pick_victim(self.slots, ())
        self._kv_exhausted(victim, touched, where="engine_boundary",
                           error=repr(exc))
        # the refresh folds any in-flight tick and rebuilds device
        # state over the survivors, whatever the failed path left
        self._refresh_device_state()

    def lane_counts(self) -> Dict[str, int]:
        """Batch-lane occupancy (ISSUE 14): how much of this engine's
        queue/slots/parked set is priority-0 bulk work. Snapshots
        under the step lock — the pump rebinds `waiting` mid-step, and
        an unlocked sum over it can double-count or skip entries (the
        serving plane subtracts these from its overload signals, so a
        glitch here flaps the autoscaler). Lock-averse readers (the
        fleet_stats cadence) use fleet_counters() instead."""
        with self._step_lock:
            return self._lane_counts_locked()

    def _lane_counts_locked(self) -> Dict[str, int]:
        return {
            "waiting_batch": sum(1 for r in self.waiting
                                 if r.lane == "batch"),
            "active_batch": sum(
                1 for s in self.slots
                if s.request is not None
                and s.request.lane == "batch"),
            "parked_batch": (sum(1 for p in self.host_tier.entries()
                                 if p.request.lane == "batch")
                             if self.host_tier is not None else 0),
            # device pages held by batch-lane slots: displaceable
            # occupancy the autoscaler's idle check must subtract (a
            # batch-soaked fleet must still read as scale-downable)
            "batch_kv_pages": sum(
                len(s.pages) for s in self.slots
                if s.request is not None
                and s.request.lane == "batch"),
        }

    def page_pressure(self) -> float:
        """Demand on the device pool as a fraction of usable pages:
        live pages PLUS parked pages that want back in. > 1.0 means
        oversubscribed — the autoscaler and watchdog consume this
        (fleet_stats / GET /metrics)."""
        usable = self.allocator.num_usable
        if not usable:
            return 0.0
        host = self.host_tier.used_pages if self.host_tier else 0
        return (self.allocator.used_pages + host) / usable

    def _publish_counters_locked(self) -> None:
        """Rebuild the published fleet-counter snapshot. Called (with
        _step_lock held) at the end of every mutating entry point —
        step/add_request/abort/preempt/import_session — so
        fleet_counters() always reflects the last committed state.
        The dict is REPLACED wholesale, never mutated in place: a
        concurrent reader sees either the previous or the next
        snapshot, both internally consistent."""
        self._fleet_counters = {
            "active": self.num_active(),
            "waiting": len(self.waiting),
            "parked_sessions": len(self.parked),
            "preemptions_total": sum(self.preempt_counts.values()),
            "page_pressure": round(self.page_pressure(), 4),
            "lanes": self._lane_counts_locked(),
        }

    def fleet_counters(self) -> Dict[str, Any]:
        """Immutable published snapshot of the mutable-state counters
        the fleet router scrapes at sub-second cadence (fleet_stats /
        health). Lock-free BY DESIGN: fleet_stats must never block
        behind a tick, so it reads the reference the last mutator
        published instead of taking _step_lock. Callers must not
        mutate the returned dict."""
        return self._fleet_counters

    def preempt(self, request_id: str, reason: str = "manual") -> bool:
        """Preempt one running request (operator / serving-plane hook;
        also the long-idle session-parking entry point: parking a
        session between turns frees its device pages until the next
        turn restores them token-exact). Serialized against step()
        like abort(). Returns False if the request is not in a slot
        or cannot be parked (no host tier for a decoding victim)."""
        with self._step_lock:
            hit = self._preempt_locked(request_id, reason)
            if hit:
                self._publish_counters_locked()
            return hit

    def _preempt_locked(self, request_id: str, reason: str) -> bool:
        for slot in self.slots:
            req = slot.request
            if req is None or req.request_id != request_id:
                continue
            if slot.ready and self.host_tier is None:
                return False
            self._drain(self._pending_touched)
            req = slot.request
            if req is None or req.request_id != request_id:
                return False     # finished inside the drain fold
            if self._preempt_slot(slot, self._pending_touched,
                                  reason):
                self._refresh_device_state()
                return True
            return False
        return False

    # -- fleet KV transport (ISSUE 12) ----------------------------------
    def session_ids(self) -> List[str]:
        """Request ids resident on this engine (slots + waiting +
        parked) — the migration orchestrator's inventory."""
        with self._step_lock:
            out = [s.request.request_id for s in self.slots
                   if s.request is not None]
            out += [r.request_id for r in self.waiting]
            if self.host_tier is not None:
                out += [p.request.request_id
                        for p in self.host_tier.entries()]
            return out

    def export_session(self, request_id: str,
                       reason: str = "migration"
                       ) -> Optional[Dict[str, Any]]:
        """Detach one live request for shipping to another engine
        (ISSUE 12): built on the PR 10 spill path — a decoding victim
        is preempted into the host tier, materialized, and handed out
        as a plain host-side state dict (numpy KV arrays + the decode
        invariant import_session / _restore_parked resume from). A
        still-prefilling or waiting request exports COLD (no pages —
        it has emitted nothing, so the importer just re-admits it).
        Returns None when the request is not here, already finished,
        or cannot be captured (decoding victim with no host tier, or
        a full tier) — the caller falls back to token replay. On
        success the request leaves this engine with
        finish_reason="migrated", so its local stream terminates with
        a migration marker instead of an abort."""
        with self._step_lock:
            self._refuse_call("session_shipping")
            tier = self.host_tier
            if tier is not None and request_id in tier:
                # fast path: the pages were ALREADY spilled — export
                # straight out of the host tier, no device work at
                # all (this is what makes failover-by-restore cheaper
                # than failover-by-replay)
                parked = tier.export(request_id)
                if parked in self._pending_spills:
                    self._pending_spills.remove(parked)
                parked.materialize(self._read_tokens)
                return self._session_state(parked.request, parked,
                                           reason)
            for i, req in enumerate(self.waiting):
                if req.request_id == request_id:
                    del self.waiting[i]
                    return self._session_state(req, None, reason)
            slot = next(
                (s for s in self.slots if s.request is not None
                 and s.request.request_id == request_id), None)
            if slot is None:
                return None
            if slot.ready and tier is None:
                return None       # decoding KV cannot be captured
            self._drain(self._pending_touched)
            req = slot.request
            if req is None or req.request_id != request_id \
                    or req.finished:
                return None       # finished inside the drain fold
            was_ready = slot.ready
            if not self._preempt_slot(slot, self._pending_touched,
                                      reason):
                return None       # host tier full
            self._refresh_device_state()
            if not was_ready:
                # prefilling victims requeue instead of spilling:
                # pull the requeued request back off the waiting
                # head for a cold export
                for i, r in enumerate(self.waiting):
                    if r.request_id == request_id:
                        del self.waiting[i]
                        return self._session_state(r, None, reason)
                return None
            parked = tier.export(request_id)
            if parked in self._pending_spills:
                self._pending_spills.remove(parked)
            parked.materialize(self._read_tokens)
            return self._session_state(parked.request, parked, reason)

    def _session_state(self, req: Request, parked, reason: str
                       ) -> Dict[str, Any]:
        """The exported host-side session state (serialized by
        serve/llm/kv_transport.py). Marks the request finished with
        reason "migrated" — it no longer lives on this engine."""
        req.finished = True
        req.finish_reason = "migrated"
        # the receipt closes here: the request's remaining cost
        # accrues on the importing engine under its own receipt
        self._attrib_finish(req, "migrated")
        self.telemetry.recorder.record(
            "session_exported", request_id=req.request_id,
            reason=reason,
            pages=0 if parked is None else parked.n_pages,
            generated=len(req.output_tokens))
        ddl = None
        if req.deadline is not None:
            # monotonic deadlines do not survive a process hop; the
            # importer converts the wall instant back
            ddl = time.time() + (req.deadline - time.monotonic())
        return {
            "request_id": req.request_id,
            "prompt_tokens": list(req.prompt_tokens),
            "output_tokens": list(req.output_tokens),
            "params": dataclasses.asdict(req.params),
            "lora": req.lora,
            "priority": int(req.priority),
            "tenant": req.tenant,
            "lane": req.lane,
            "restarts": int(req.restarts),
            "trace": req.trace,
            "deadline_epoch": ddl,
            "seed": (parked.seed if parked is not None
                     else self._request_seed(req)),
            "position": 0 if parked is None else parked.position,
            "last_token": 0 if parked is None else parked.last_token,
            "n_pages": 0 if parked is None else parked.n_pages,
            "k": None if parked is None else parked.k_host,
            "v": None if parked is None else parked.v_host,
            # quantized serving (ISSUE 16): the pages ship AS STORED —
            # the importer must run the same kv_dtype or reject
            "kv_dtype": self._kv_kind,
            "k_scales": (None if parked is None
                         else parked.k_scales_host),
            "v_scales": (None if parked is None
                         else parked.v_scales_host),
        }

    def import_session(self, state: Dict[str, Any]) -> Request:
        """Admit a session exported by another engine: a warm session
        (pages attached) parks in the host tier and _restore_parked
        re-admits it at the next tick exactly like a locally-spilled
        victim — the restored slot resumes the shipped decode
        invariant, and because every token's sampling key is
        fold_in(seed, absolute index) the continued stream is
        byte-identical to the exporter having kept it. A cold
        session (nothing emitted yet) just re-enters admission.
        Returns the live Request this engine now owns. Raises
        ValueError on an id collision or incompatible KV geometry,
        MemoryError when the tier cannot hold it — callers treat
        both as a failed ship and fall back to replay."""
        self._refuse_call("session_shipping")
        params = dict(state.get("params") or {})
        if params.get("stop_token_ids") is not None:
            params["stop_token_ids"] = tuple(params["stop_token_ids"])
        # pin the exporter's RESOLVED seed: the importer may run this
        # session under a different request id, and token-exactness
        # hangs on the (seed, absolute index) keys staying identical
        params["seed"] = int(state["seed"])
        req = Request(str(state["request_id"]),
                      [int(t) for t in state["prompt_tokens"]],
                      SamplingParams(**params),
                      lora=state.get("lora"),
                      trace=state.get("trace"),
                      priority=int(state.get("priority") or 0),
                      tenant=str(state.get("tenant") or ""),
                      lane=str(state.get("lane") or "interactive"))
        req.output_tokens = [int(t)
                             for t in state.get("output_tokens") or []]
        req.restarts = int(state.get("restarts") or 0)
        if state.get("deadline_epoch") is not None:
            req.deadline = time.monotonic() + (
                float(state["deadline_epoch"]) - time.time())
        n_pages = int(state.get("n_pages") or 0)
        with self._step_lock:
            rid = req.request_id
            if any(s.request is not None
                   and s.request.request_id == rid
                   for s in self.slots) \
                    or any(r.request_id == rid for r in self.waiting) \
                    or (self.host_tier is not None
                        and rid in self.host_tier):
                raise ValueError(
                    f"request {rid!r} is already live on this engine")
            if n_pages == 0:
                if req.output_tokens:
                    raise ValueError(
                        "cold session carries emitted tokens; replay "
                        "it through the continuation path instead")
                self._add_request_locked(req)
                self.telemetry.recorder.record(
                    "session_imported", request_id=rid, pages=0)
                self._publish_counters_locked()
                return req
            tier = self.host_tier
            if tier is None:
                raise ValueError(
                    "import_session requires enable_kv_offload "
                    "(no host tier to stage the pages in)")
            position = int(state["position"])
            if self.allocator.pages_needed(position) != n_pages:
                raise ValueError(
                    f"inconsistent session: position {position} "
                    f"spans {self.allocator.pages_needed(position)} "
                    f"pages, payload carries {n_pages}")
            if len(req.prompt_tokens) + req.params.max_tokens \
                    > self.max_seq:
                raise ValueError(
                    f"prompt+max_tokens exceeds max_seq_len "
                    f"{self.max_seq}")
            k, v = state["k"], state["v"]
            src_kind = str(state.get("kv_dtype") or "f32")
            if src_kind != self._kv_kind:
                # never reinterpret pages across storage kinds: an
                # int8 page scattered into an f32 pool (or vice versa)
                # would be silent garbage — callers fall back to
                # token replay, which is kind-agnostic
                raise ValueError(
                    f"incompatible KV dtype kind: session pages are "
                    f"{src_kind!r}, this engine serves "
                    f"{self._kv_kind!r}")
            want = (self.k_pages.shape[0], n_pages,
                    *self.k_pages.shape[2:])
            for name, arr in (("k", k), ("v", v)):
                if tuple(arr.shape) != want:
                    raise ValueError(
                        f"incompatible KV geometry: {name} is "
                        f"{tuple(arr.shape)}, this engine expects "
                        f"{want}")
                if np.dtype(arr.dtype) != np.dtype(
                        self.k_pages.dtype):
                    raise ValueError(
                        f"incompatible KV dtype: {name} is "
                        f"{arr.dtype}, pool is {self.k_pages.dtype}")
            ksc = vsc = None
            if self._kv_kind != "f32":
                ksc, vsc = state.get("k_scales"), state.get("v_scales")
                want_s = want[:-1]
                for name, arr in (("k_scales", ksc),
                                  ("v_scales", vsc)):
                    if arr is None or tuple(arr.shape) != want_s:
                        raise ValueError(
                            f"quantized session missing/misshaped "
                            f"{name}: expected {want_s}")
            from .kv_offload import ParkedSequence
            parked = ParkedSequence(
                request=req, seed=int(state["seed"]),
                position=position,
                last_token=int(state["last_token"]),
                n_pages=n_pages, reason="import",
                k_host=k, v_host=v, kv_kind=src_kind,
                k_scales_host=ksc, v_scales_host=vsc)
            tier.park(parked, count_spill=False)  # MemoryError if full
            self.telemetry.recorder.record(
                "session_imported", request_id=rid, pages=n_pages,
                generated=len(req.output_tokens))
            self._publish_counters_locked()
            return req

    def export_prefix(self, prompt_tokens: List[int]
                      ) -> Optional[Dict[str, Any]]:
        """Gather the cached full prompt pages for this token chain
        to host numpy (the fleet prefix store's publish path). None
        when nothing is cached. A read-only structural gather off the
        live pools (the same sanctioned dispatch as the spill path) —
        never on the tick path."""
        with self._step_lock:
            self._refuse_call("session_shipping")
            if not self.allocator.enable_prefix_caching:
                return None
            pages = self.allocator.cached_prefix_pages(prompt_tokens)
            if not pages:
                return None
            self._drain(self._pending_touched)
            n = len(pages)
            nb = self._page_bucket(n)
            ids = pages + [pages[-1]] * (nb - n)
            d_ids = self._dev(jnp.asarray(np.asarray(ids, np.int32)))
            out = {}
            if self._kv_kind != "f32":
                kh, vh, ksh, vsh = self._page_gather_fn(nb)(
                    self.k_pages, self.v_pages, self.k_scales,
                    self.v_scales, d_ids)
                out["k_scales"] = self._read_tokens(ksh)[:, :n]
                out["v_scales"] = self._read_tokens(vsh)[:, :n]
            else:
                kh, vh = self._page_gather_fn(nb)(
                    self.k_pages, self.v_pages, d_ids)
            if self.perf is not None:
                self.perf.note_offload(
                    d2h=nb * self.perf.model.page_bytes)
            out["k"] = self._read_tokens(kh)[:, :n]
            out["v"] = self._read_tokens(vh)[:, :n]
            out["tokens"] = [int(t) for t in
                             prompt_tokens[:n * self.allocator.page_size]]
            out["kv_dtype"] = self._kv_kind
            self.telemetry.recorder.record(
                "prefix_exported", pages=n, tokens=len(out["tokens"]))
            return out

    def import_prefix(self, tokens: List[int], k_host, v_host,
                      k_scales=None, v_scales=None,
                      kv_dtype: str = "f32") -> int:  # jaxlint: disable=JL006 -- prefix seeding upload: one scatter per fleet prefix-store import (structural event), never on the tick path
        """Seed this engine's prefix cache with pages prefilled on
        ANOTHER replica (the fleet prefix store's import path): the
        missing tail of the chain uploads into freshly allocated
        pages and registers under the same hash-cons keys local
        prefill would have used, so the next admission's match_prefix
        hits as if this replica had prefilled the prompt itself.
        Quantized engines require matching kv_dtype pages plus their
        scale arrays (ships as stored — never reinterpreted).
        Returns the number of pages newly seeded (0 = already cached
        / no room / nothing importable)."""
        with self._step_lock:
            self._refuse_call("session_shipping")
            if not self.allocator.enable_prefix_caching:
                return 0
            if str(kv_dtype or "f32") != self._kv_kind:
                raise ValueError(
                    f"incompatible prefix KV dtype kind: pages are "
                    f"{kv_dtype!r}, this engine serves "
                    f"{self._kv_kind!r}")
            page = self.allocator.page_size
            n = min(len(tokens) // page, int(k_host.shape[1]))
            if n == 0:
                return 0
            want = (self.k_pages.shape[0], int(k_host.shape[1]),
                    *self.k_pages.shape[2:])
            for name, arr in (("k", k_host), ("v", v_host)):
                if tuple(arr.shape) != want or np.dtype(arr.dtype) \
                        != np.dtype(self.k_pages.dtype):
                    raise ValueError(
                        f"incompatible prefix KV geometry: {name} is "
                        f"{tuple(arr.shape)}/{arr.dtype}, pool wants "
                        f"{want}/{self.k_pages.dtype}")
            if self._kv_kind != "f32":
                for name, arr in (("k_scales", k_scales),
                                  ("v_scales", v_scales)):
                    if arr is None or tuple(arr.shape) != want[:-1]:
                        raise ValueError(
                            f"quantized prefix missing/misshaped "
                            f"{name}: expected {want[:-1]}")
            toks = [int(t) for t in tokens[:n * page]]
            have = self.allocator.cached_prefix_pages(toks)
            if len(have) >= n:
                return 0              # fully cached already
            need = n - len(have)
            if need > self.allocator.free_pages:
                return 0              # never evict live work for this
            self._drain(self._pending_touched)
            fresh = self.allocator.allocate_pages(need)
            nb = self._page_bucket(need)
            ids = fresh + [fresh[-1]] * (nb - need)

            def _bucketed(host):
                rows = np.ascontiguousarray(host[:, len(have):n])
                if nb > need:
                    rows = np.concatenate(
                        [rows, np.repeat(rows[:, -1:],
                                         nb - need, axis=1)], 1)
                return self._dev(jnp.asarray(rows))

            if self.perf is not None:
                self.perf.note_offload(
                    h2d=nb * self.perf.model.page_bytes)
            d_ids = self._dev(jnp.asarray(np.asarray(ids, np.int32)))
            if self._kv_kind != "f32":
                (self.k_pages, self.v_pages, self.k_scales,
                 self.v_scales) = self._page_scatter_fn(nb)(
                    self.k_pages, self.v_pages, self.k_scales,
                    self.v_scales, d_ids,
                    _bucketed(k_host), _bucketed(v_host),
                    _bucketed(k_scales), _bucketed(v_scales))
            else:
                self.k_pages, self.v_pages = self._page_scatter_fn(nb)(
                    self.k_pages, self.v_pages, d_ids,
                    _bucketed(k_host), _bucketed(v_host))
            self.allocator.register_prefix(toks, have + fresh)
            # registration took the cache's reference on the fresh
            # pages; release the allocation's so they are cache-owned
            # (rc=1 -> evictable under pressure, like local prefill)
            self.allocator.free(fresh)
            self.telemetry.recorder.record(
                "prefix_imported", pages=need, cached=len(have),
                tokens=len(toks))
            return need

    # -- public API ---------------------------------------------------------
    def register_lora(self, name: str, adapters: Dict[str, tuple],
                      scale: float = 1.0) -> None:
        """Register a LoRA adapter for multi-LoRA serving.

        adapters: {proj: (A, B)} for proj in wq/wk/wv/wo, A shaped
        (L, in_dim, r) and B (L, r, out_dim) (numpy/jax). Requests
        select it via Request(lora=name); different slots of one decode
        batch may run different adapters (per-slot gather + two rank-r
        einsums). Stacks are padded to max_loras slots AND to all four
        projections, stored layer-major in compute dtype — compiled
        shapes change only when the FIRST adapter arrives, or when a
        later registration changes a projection's rank (documented
        retrace). Validation happens on a COPY — a bad registration
        leaves prior state untouched. Re-registration refreshes device
        slot state so in-flight requests keep their adapter."""
        self.register_loras({name: adapters}, scale=scale)

    def register_loras(self, mapping: Dict[str, Dict[str, tuple]],
                       scale: float = 1.0) -> None:
        """Bulk form: stage every adapter, build + upload the padded
        stacks ONCE (k adapters via the per-name API would rebuild and
        transfer k times). Fully under the step lock: the server runs
        registrations on executor threads, so the read-modify-write
        over the adapter maps must serialize against step() AND
        against concurrent registrations (two racing registrations
        would otherwise silently drop one's adapters)."""
        with self._step_lock:
            self._register_loras_locked(mapping, scale)

    def _register_loras_locked(self, mapping: Dict[str, Dict[str, tuple]],
                               scale: float) -> None:  # jaxlint: disable=JL006 -- registration-time stack upload (one per projection), not on the tick path
        self._refuse_call("lora")
        if self._explicit_tp:
            raise NotImplementedError(
                "multi-LoRA is not supported on explicit-tp "
                "(mesh_shape) engines: adapter stacks have no "
                "Megatron-sharded layout, so the shard_map'd tick "
                "never sees them; use the GSPMD mesh= path for LoRA")
        valid = {"wq", "wk", "wv", "wo"}
        new_raw = dict(self._lora_raw)
        for name, adapters in mapping.items():
            if not adapters or set(adapters) - valid:
                raise ValueError(
                    f"adapters must map a subset of {sorted(valid)}")
            new_raw[name] = {
                k: (np.asarray(a, np.float32) * scale,
                    np.asarray(b, np.float32))
                for k, (a, b) in adapters.items()}
        if len(new_raw) > self.config.max_loras:
            raise ValueError(
                f"at most max_loras={self.config.max_loras} adapters")
        names = {None: 0}
        for i, n in enumerate(sorted(new_raw), start=1):
            names[n] = i
        # ALL FOUR projections get stacks (zero rank-1 stubs where no
        # adapter uses one) so a later registration introducing a new
        # projection doesn't change the pytree structure. Every adapter
        # for one projection must agree on rank/shapes (they share one
        # stacked array). Stacks are stored LAYER-MAJOR (L, A, ...) in
        # compute dtype: the layer scan slices them directly — no
        # relayout or cast inside the per-token decode step.
        cfg = self.model_cfg
        out_dims = {"wq": cfg.q_dim, "wk": cfg.kv_dim,
                    "wv": cfg.kv_dim, "wo": None}
        in_dims = {"wq": cfg.hidden, "wk": cfg.hidden,
                   "wv": cfg.hidden, "wo": cfg.q_dim}
        stacks = {}
        n_slots = self.config.max_loras + 1
        dt = cfg.dtype
        for p in ("wq", "wk", "wv", "wo"):
            shapes_a = {ad[p][0].shape for ad in new_raw.values()
                        if p in ad}
            shapes_b = {ad[p][1].shape for ad in new_raw.values()
                        if p in ad}
            if len(shapes_a) > 1 or len(shapes_b) > 1:
                raise ValueError(
                    f"adapters disagree on {p} shapes: "
                    f"{sorted(shapes_a)} / {sorted(shapes_b)}")
            if shapes_a:
                sa, sb = next(iter(shapes_a)), next(iter(shapes_b))
            else:
                out = out_dims[p] or cfg.hidden
                sa = (cfg.n_layers, in_dims[p], 1)
                sb = (cfg.n_layers, 1, out)
            a_stack = np.zeros((n_slots,) + sa, np.float32)
            b_stack = np.zeros((n_slots,) + sb, np.float32)
            for nm, idx in names.items():
                if nm is None or p not in new_raw[nm]:
                    continue
                a, b = new_raw[nm][p]
                a_stack[idx] = a
                b_stack[idx] = b
            stacks[p] = {
                "a": self._dev(jnp.asarray(
                    np.swapaxes(a_stack, 0, 1), dt)),
                "b": self._dev(jnp.asarray(
                    np.swapaxes(b_stack, 0, 1), dt))}
        # commit only after everything validated/built (caller holds
        # the step lock; the refresh below folds any in-flight tick)
        self._lora_raw = new_raw
        self._lora_names = names
        self._lora_stacks = stacks
        self.telemetry.recorder.record(
            "lora_registration", adapters=sorted(new_raw))
        # indices may have shifted: refresh device slot state so
        # in-flight requests keep decoding with THEIR adapter
        self._refresh_device_state()

    def add_request(self, request: Request) -> None:
        """Queue a request for admission. Takes the step lock: the
        ingress path appends from the event loop (or a client thread)
        while the pump's step() rebinds `self.waiting` to the
        survivors list mid-tick — an unlocked append can land on the
        ABOUT-TO-BE-DISCARDED list and silently vanish. Admission
        itself still happens inside step()."""
        with self._step_lock:
            self._add_request_locked(request)
            self._publish_counters_locked()

    def _add_request_locked(self, request: Request) -> None:
        if request.lora is not None \
                and request.lora not in self._lora_names:
            raise ValueError(
                f"unknown LoRA adapter {request.lora!r} "
                f"(registered: {sorted(self._lora_raw)})")
        worst_case = len(request.prompt_tokens) + request.params.max_tokens
        if worst_case > self.max_seq:
            raise ValueError(
                f"prompt+max_tokens exceeds max_seq_len {self.max_seq}")
        why = self.cache.fits(worst_case)
        if why is not None:
            # would never be admittable — reject now instead of stalling
            # the head of the queue forever
            raise ValueError(f"prompt+max_tokens {why}")
        self.telemetry.on_queued(request)
        self.waiting.append(request)

    def has_work(self) -> bool:
        # an in-flight tick or tokens folded by an out-of-step drain
        # (abort/LoRA registration) count as work: one more step()
        # delivers them — otherwise a pump loop keyed on has_work()
        # would park with finish events stranded in _pending_touched
        return (bool(self.waiting) or bool(self._pending_touched)
                or self._inflight is not None
                or (self.host_tier is not None
                    and len(self.host_tier) > 0)
                or any(s.request is not None for s in self.slots))

    def num_active(self) -> int:
        return sum(1 for s in self.slots if s.request is not None)

    def step(self) -> List[Request]:
        """One engine tick: any tick with a prefilling slot runs ONE
        ragged dispatch that advances every decoding slot by a token
        AND packs prefill chunks under the token budget; pure-decode
        ticks keep the device-resident decode loop (also one
        dispatch). Returns requests that produced a token this step
        (check .finished / .output_tokens). With async_readback (default), steady-state
        decode results lag ONE tick: a step may return [] while its
        tokens are still in flight — they surface on the next step's
        fold (every step still dispatches exactly once, so progress
        and termination are unchanged)."""
        with self._step_lock:
            # an armed capture counts the ticks whose span opens after
            # its trace has started, so that it holds their spans whole
            # (one that waits for its start costs a tick nothing)
            ps = self._profile
            if ps is not None and ps["state"] in ("armed", "running"):
                self._held(self._profile_tick_begin)
            with jax.profiler.TraceAnnotation(
                    "engine.step", tick=self.ticks + 1) as span:
                touched = self._step_locked()
                # work=0: the device idles after this tick for want of
                # requests, not for the host; with it `gaps` booked at
                # the call's close, `gap_max_ms` and `gap_cause`
                self.telemetry.annotate_call(
                    span, work=int(self._step_end is not None))
            if ps is not None and ps["live"]:
                self._held(self._profile_tick_end)
            return touched

    def _step_locked(self) -> List[Request]:
        """step()'s body, under the step lock and the tick's span."""
        # tokens folded by an out-of-step drain (abort/LoRA
        # registration) ride the NEXT step's touched list (hoisted
        # out of the try so the MemoryError path below can still
        # deliver them)
        touched: List[Request] = self._pending_touched
        self._pending_touched = []
        try:
            t0 = time.perf_counter()
            self.ticks += 1
            self.telemetry.open_call(self.ticks)
            compiles0 = self.compiles
            # phase time already in the table was spent by an
            # out-of-step drain (abort, LoRA registration) since
            # the last tick: it rides this tick's record
            carry_s = sum(self._phase_s.values())
            self._step_tick(touched)
            # `wall` is what the cost model's detectors judge; the
            # ring's record, closed below, also counts this accounting
            # (the counters' publication walks the prefix cache)
            wall = time.perf_counter() - t0
            with self._phase("account") as span:
                if self.perf is not None:
                    self._commit_tick_costs(wall)
                if self.cache.windowed:
                    span.set_metadata(
                        pages_returned=self._advance_windows())
                self._publish_counters_locked()
            self._close_tick(t0, carry_s, compiles0)
            self.last_step_at = time.monotonic()
        except MemoryError as exc:
            # page exhaustion is handled degradation, not a crash
            # (ISSUE 10): the graceful paths (_grow_slots/_admit)
            # never raise, so a raw MemoryError here is an
            # uncovered allocator path — record the alert-hooked
            # kv_exhausted event (it black-boxes a bundle), retire
            # a victim with finish_reason="error", keep pumping
            self._held(self._profile_abort)
            self._judged.pop(self.ticks, None)
            if self.perf is not None:
                self.perf.abort_tick()
            if self.attrib is not None:
                self.attrib.abort_tick()
            self._handle_memory_error(exc, touched)
            self._publish_counters_locked()
            self._step_end = None
            self.last_step_at = time.monotonic()
        except BaseException as exc:
            # a mid-tick raise (fold reservation assert,
            # GuardViolation, allocator OOM, ...) must not leave an
            # armed jax.profiler capture running forever — stop the
            # trace and disarm so /debug/profile can be re-armed
            self._held(self._profile_abort)
            self._judged.pop(self.ticks, None)
            if self.perf is not None:
                self.perf.abort_tick()
            if self.attrib is not None:
                self.attrib.abort_tick()
            # black-box the replica's last moments (ISSUE 7):
            # best-effort, lock-free gather — the step lock is
            # HELD here, so the bundle builder must not re-enter
            # stats()/step-lock paths
            self.dump_blackbox("engine_crash", error=repr(exc))
            raise
        return touched

    def _commit_tick_costs(self, wall: float) -> None:
        """Fold the tick's pending PerfSample (cost hooks ran beside
        each dispatch) into the rolling MFU/MBU window, stamped with
        the tick wall, and split it across the per-request receipts (a
        call's wall over its requests, whichever tick's program it
        waited for). The anomaly detector gets the sample and this
        call's host time, the wall less every readback wait, and judges
        the tick when its own program has been read back too: here if
        that was in this call, else at its fold (`_program_read`)."""
        fold_ms = self._phase_s["fold"] * 1e3
        wait_ms = self._phase_s["readback_wait"] * 1e3
        sample = self.perf.commit(wall * 1e3)
        if sample is None:
            return
        if self.attrib is not None:
            # split the tick's shared costs + times across its
            # per-request charges (ISSUE 13)
            self.attrib.commit(sample, host_ms=fold_ms,
                               device_ms=wait_ms)
        if self.anomaly is not None:
            # a sample with no forward program (page migration alone)
            # is judged on the call, as it always was
            rec = self._judged.setdefault(self.ticks,
                                          {"device_ms": wait_ms})
            rec.update(sample=sample, host_ms=wall * 1e3 - wait_ms,
                       fold_ms=fold_ms, compiles=self.compiles)
            if "device_ms" in rec:
                self._judge(self.ticks)

    def _close_tick(self, t0: float, carry_s: float,
                    compiles0: int) -> None:
        """End of a tick's wall: its ring record, the cumulative phase
        totals and the gap clock; then the phase table is zeroed. The
        table is zeroed here and not at entry, so that the readback and
        fold of an out-of-step drain land in the next tick's record
        (`carry_s`) and do not vanish. `other` is the wall no phase
        covers."""
        end = time.perf_counter()
        wall = end - t0
        ph = self._phase_s
        other = max(wall + carry_s - sum(ph.values()), 0.0)
        tot = self._phase_total_s
        for k, v in ph.items():
            tot[k] += v
        tot["other"] += other
        self._phase_ticks += 1
        gap = 0.0
        if self._step_end is not None:
            # time between two ticks' walls while work remained: the
            # pump's delivery, the event loop, this method's own
            # epilogue and an armed profile capture's bookkeeping
            gap = max(t0 - self._step_end, 0.0)
            self._gap_total_s += gap
        self._step_end = end if self.has_work() else None
        c = self._tick_carried or {}
        phases_ms = {k: v * 1e3 for k, v in ph.items()}
        phases_ms["other"] = other * 1e3
        self._tick_times.append(_TickRecord(
            wall * 1e3, ph["fold"] * 1e3, ph["readback_wait"] * 1e3,
            t0, gap * 1e3, c.get("kind", ""), c.get("T", 0),
            c.get("ctx", 0), c.get("rows", 0),
            c.get("prefill_tokens", 0), phases_ms,
            self.compiles - compiles0, c.get("attn_items", 0),
            c.get("attn_kv_blocks", 0)))
        # the gap ledger: the tokens this call surfaced reach their
        # streams when it returns, and their gaps are booked here. A
        # capture counts while its trace is live or being written: one
        # that waits for its start costs a tick nothing
        ps = self._profile
        self.telemetry.close_call(
            end, gap, c.get("prefill_tokens", 0),
            ps is not None and ps["state"] in ("running", "writing"))
        self._tick_carried = None
        for k in ph:
            ph[k] = 0.0

    def _advance_windows(self) -> int:
        """The tick boundary of a family with a window group: every
        live sequence hands back the pages behind its window and claims
        those its next tick may write (`CacheManager.advance`), on the
        host; the tables go up again with the next dispatch. Returns
        the pages handed back. The earliest query still to come sits at
        a prefilling slot's next chunk, or at a decoding slot's host
        position (the tick in flight, if one is, computes that one)."""
        returned, claimed = self.cache.advance(
            (s.index, s.position if s.ready else s.prefill_pos)
            for s in self.slots if s.request is not None)
        if returned or claimed:
            self._tables_version += 1
        return returned

    def _admit_possible(self) -> bool:
        """Could _admit place the head-of-line request this tick?
        Conservative toward True: an unnecessary drain only costs
        overlap, while a skipped drain before a successful admission
        would let the ragged pack read one-tick-stale host slot
        state. Mirrors _admit's head-of-line check assuming BEST-CASE
        prefix sharing (free_pages already counts evictable cached
        pages)."""
        if self.host_tier is not None and len(self.host_tier):
            top = max(p.request.priority
                      for p in self.host_tier.entries())
            if not (self.waiting
                    and self.waiting[0].priority > top):
                # parked sequences restore before (and instead of)
                # new admissions — mirror that policy here too
                return self._restore_possible()
            # batch-lane inversion guard (ISSUE 14): the head admits
            # past the parked work — but only claim a drain is
            # warranted when it can actually MOVE (a free slot whose
            # pages fit, or a strictly-outranked victim to preempt);
            # an unconditional True here would force a drain every
            # tick of a saturated all-interactive period, degrading
            # the pipeline to synchronous exactly where it matters
            if any(s.request is None for s in self.slots) \
                    and self._head_fits():
                return True
            return self._priority_victim_exists()
        if not self.waiting:
            return False
        if not any(s.request is None for s in self.slots):
            # batch-lane inversion guard: with every slot taken, the
            # head can still claim one by preempting the designated
            # victim when it strictly outranks it (ISSUE 14)
            return self._priority_victim_exists()
        # a free slot but pages short: preemption can free pages too
        return self._head_fits() or self._priority_victim_exists()

    def _priority_victim_exists(self) -> bool:
        """Does the waiting head strictly outrank the fleet's
        designated victim (the slot _preempt_for_priority would
        take), AND can that victim actually be preempted right now
        (requeue needs nothing; a decoding victim needs host-tier
        room for its spill)? Without the capacity half, a full host
        tier would force a pipeline drain every tick of a saturated
        period for a preemption that _preempt_slot then refuses."""
        if not self.config.enable_kv_offload or not self.waiting:
            return False
        from .kv_offload import pick_victim
        victim = pick_victim(self.slots, (),
                             spill_ok=self.host_tier is not None)
        if victim is None or victim.request is None \
                or victim.request.priority \
                >= self.waiting[0].priority:
            return False
        if not victim.ready:
            return True              # prefilling: requeue path
        return (self.host_tier is not None
                and self.host_tier.can_store(
                    self.allocator.pages_needed(victim.position)))

    def _step_tick(self, touched: List[Request]) -> None:
        with self._phase("sched", waiting=len(self.waiting)) as span:
            # pick up last tick's spill copies (pure d2h, usually
            # already streamed home — the page-migration analogue of
            # lagged folds)
            self._finalize_spills()
            # deadline expiry first (ISSUE 9): an expired request must
            # not consume this tick's budget, and an expired WAITING
            # request must not claim the slot a live one could take
            self._expire_deadlines(touched)
            # admission and prefill are structural events: the
            # in-flight tick (if any) folds BEFORE slot state moves. A
            # backed-up waiting queue that CANNOT admit (no free slot,
            # or pages short even with best-case prefix sharing) does
            # not force a drain — otherwise queue pressure would
            # degrade the pipeline to synchronous exactly in the
            # saturated regime it targets; the retirement that
            # eventually frees capacity drains on its own fold.
            if self._admit_possible() \
                    or any(s.request is not None and not s.ready
                           for s in self.slots):
                self._drain(touched)
            admitted = self._admissions
            self._admit(touched)
            # optimistic admission (ISSUE 10): extend reservations
            # BEFORE the dispatch whose KV writes would cross them
            # (no-op unless kv_watermark_tokens is set)
            self._grow_slots(touched)
            span.set_metadata(admitted=self._admissions - admitted)
            ragged = any(s.request is not None and not s.ready
                         for s in self.slots)
        if ragged:
            self._ragged_step(touched)
        elif any(s.ready for s in self.slots):
            self._decode(touched)

    def generate(self, prompts: List[List[int]],
                 params: Optional[SamplingParams] = None,
                 loras: Optional[List[Optional[str]]] = None
                 ) -> List[Request]:
        """Synchronous batch completion (the ray_tpu.data.llm path).
        loras: optional per-prompt adapter names (multi-LoRA batches)."""
        params = params or SamplingParams()
        loras = loras or [None] * len(prompts)
        if len(loras) != len(prompts):
            raise ValueError("loras must match prompts in length")
        with self._step_lock:
            # snapshot the adapter registry under the lock: a
            # concurrent register_loras swaps _lora_names/_lora_raw
            # mid-validation, and reading the two attributes unlocked
            # can pair a new names-set with an old raw-set in the
            # error message (racelint RL004 on the registry containers)
            known = frozenset(self._lora_names)
            registered = sorted(self._lora_raw)
        unknown = {l for l in loras if l is not None and l not in known}
        if unknown:
            # validate BEFORE queueing anything: a bad name mid-batch
            # must not strand earlier requests in the waiting queue
            raise ValueError(
                f"unknown LoRA adapter(s) {sorted(unknown)} "
                f"(registered: {registered})")
        reqs = [Request(f"gen-{i}-{id(prompts)}", list(p), params,
                        lora=loras[i])
                for i, p in enumerate(prompts)]
        for r in reqs:
            self.add_request(r)
        while not all(r.finished for r in reqs):
            self.step()
        return reqs

    # -- internals ----------------------------------------------------------
    @staticmethod
    def _request_seed(req: Request) -> int:
        """The slot's sampling seed: an explicit SamplingParams.seed
        wins; otherwise a stable hash of the request id (ISSUE 9 —
        either way the sample sequence is replayable given the
        request's identity)."""
        if req.params.seed is not None:
            return int(req.params.seed) & 0x7FFFFFFF
        return derive_seed(req.request_id)

    def _expire_deadlines(self, touched: List[Request]) -> None:
        """Fold-boundary deadline enforcement (ISSUE 9): at each tick
        entry, requests past their deadline finish with
        finish_reason="deadline" — running slots through the same
        teardown abort() uses (drain the in-flight tick first: a
        retirement is structural), waiting requests straight out of
        the queue. Zero cost when no live request carries a deadline."""
        has_slot_ddl = any(
            s.request is not None and s.request.deadline is not None
            for s in self.slots)
        has_wait_ddl = any(r.deadline is not None for r in self.waiting)
        # allocation-free when the tier is off/empty: this runs every
        # tick, and per-tick garbage shifts GC pauses into the decode
        # loop (the parked list itself only materializes on demand)
        has_park_ddl = (self.host_tier is not None
                        and len(self.host_tier) > 0
                        and any(p.request.deadline is not None
                                for p in self.parked))
        if not has_slot_ddl and not has_wait_ddl and not has_park_ddl:
            return
        now = time.monotonic()
        if has_park_ddl:
            # an expired PARKED request must not claim the restore
            # pages a live one could take; its host KV just drops
            for parked in list(self.parked):
                req = parked.request
                if req.deadline is None or now < req.deadline:
                    continue
                self.host_tier.drop(req.request_id)
                if parked in self._pending_spills:
                    self._pending_spills.remove(parked)
                req.finished = True
                req.finish_reason = "deadline"
                self.telemetry.recorder.record(
                    "deadline_abort", request_id=req.request_id,
                    where="parked", generated=len(req.output_tokens))
                self.telemetry.on_finished(
                    req, "deadline",
                    cost=self._attrib_finish(req, "deadline"))
                touched.append(req)
        if has_slot_ddl:
            expired = [s for s in self.slots
                       if s.request is not None
                       and s.request.deadline is not None
                       and now >= s.request.deadline]
            if expired:
                self._drain(touched)
                dirty = False
                for s in expired:
                    req = s.request
                    if req is None or req.finished:
                        continue     # finished inside the drain fold
                    self.telemetry.recorder.record(
                        "deadline_abort", request_id=req.request_id,
                        where="running",
                        generated=len(req.output_tokens))
                    self._finish(s, "deadline")
                    touched.append(req)
                    dirty = True
                if dirty:
                    self._refresh_device_state()
        if has_wait_ddl:
            keep: List[Request] = []
            for req in self.waiting:
                if req.deadline is not None and now >= req.deadline:
                    req.finished = True
                    req.finish_reason = "deadline"
                    self.telemetry.recorder.record(
                        "deadline_abort", request_id=req.request_id,
                        where="waiting")
                    self.telemetry.on_finished(
                        req, "deadline",
                        cost=self._attrib_finish(req, "deadline"))
                    touched.append(req)
                else:
                    keep.append(req)
            self.waiting = keep

    def _preempt_for_priority(self, touched: List[Request]) -> None:
        """Batch-lane inversion guard (ISSUE 14): while the waiting
        head STRICTLY outranks the fleet's designated victim (lowest
        priority, then youngest — kv_offload.pick_victim, the same
        total order page pressure uses) and cannot be admitted as
        things stand (no free slot, or pages short even with
        best-case prefix sharing), preempt that victim — an
        interactive request must never queue behind the priority-0
        bulk work it exists to displace. Bounded by the slot count;
        equal priorities never preempt (the pre-ISSUE-14 behavior,
        pinned by the PR 10 suite)."""
        if not self.config.enable_kv_offload or not self.waiting:
            return
        from .kv_offload import pick_victim
        for _ in range(len(self.slots)):
            if not self.waiting:
                return
            # re-read the head each round: a REQUEUED victim (below)
            # or a drain-fold retirement can change waiting[0]
            head = self.waiting[0]
            if any(s.request is None for s in self.slots) \
                    and self._head_fits():
                return
            victim = pick_victim(
                self.slots, (),
                spill_ok=self.host_tier is not None)
            if victim is None or victim.request is None \
                    or victim.request.priority >= head.priority:
                return
            self._drain(touched)       # preemption is structural
            if victim.request is None:
                continue       # retired inside the drain fold
            if victim.request.priority >= head.priority:
                return         # the fold reshuffled the order
            vreq = victim.request
            if not self._preempt_slot(victim, touched, "priority"):
                return         # host tier full: head waits its turn
            self._refresh_device_state()
            # a still-PREFILLING victim requeues to waiting[0] (the
            # PR 10 head-requeue keeps it ahead of its equal-priority
            # peers) — but here it just got preempted BY the head, so
            # leaving it at the front would re-admit it into the slot
            # it lost (priority inversion; with prefix caching off, a
            # preempt/readmit livelock). Move it behind every waiter
            # that strictly outranks it, ahead of its own tier.
            if self.waiting and self.waiting[0] is vreq:
                self.waiting.pop(0)
                i = 0
                while i < len(self.waiting) \
                        and self.waiting[i].priority > vreq.priority:
                    i += 1
                self.waiting.insert(i, vreq)

    def _head_fits(self) -> bool:
        """Could the waiting head's reservation be claimed right now,
        assuming best-case prefix sharing? (The same arithmetic as
        _admit_possible's head-of-line check.)"""
        req = self.waiting[0]
        # best case: every full page of prompt[:-1] is cached
        # (match_prefix caps one token short of the prompt)
        shared = ((len(req.prompt_tokens) - 1) // self.allocator.page_size
                  if self.allocator.enable_prefix_caching else 0)
        return self.cache.can_admit(self._reserve_tokens(
            len(req.prompt_tokens), req.params.max_tokens), shared)

    def _admit(self, touched: Optional[List[Request]] = None) -> None:
        """Claim slots + KV pages for waiting requests (prefix-cache
        match decides where their prefill starts); the prefill itself
        advances chunk-by-chunk in _ragged_step. Parked sequences
        (ISSUE 10) restore FIRST and block new admissions while any
        remain — they already hold host memory and arrived earlier, so
        a fresh request claiming the pages a parked one needs would
        starve it (and thrash the spill path). The ONE exception
        (ISSUE 14): a waiting head that strictly outranks every
        parked session — it admits past the parked batch work (which
        it could preempt out of a slot anyway, so blocking at the
        door would invert the priority order), via
        _preempt_for_priority when slots or pages are short."""
        touched = touched if touched is not None else []
        self._restore_parked(touched)
        if self.host_tier is not None and len(self.host_tier):
            top = max(p.request.priority
                      for p in self.host_tier.entries())
            if not (self.waiting
                    and self.waiting[0].priority > top):
                return
        self._preempt_for_priority(touched)
        parked_top: Optional[int] = (
            max(p.request.priority for p in self.host_tier.entries())
            if self.host_tier is not None and len(self.host_tier)
            else None)
        for slot in self.slots:
            if not self.waiting:
                break
            if slot.request is not None:
                continue
            req = self.waiting[0]
            if parked_top is not None \
                    and req.priority <= parked_top:
                # the ISSUE 14 exception is PER HEAD, not a gate the
                # first head unlocks for the whole loop: once the
                # current head no longer outranks every parked
                # session, parked-first resumes — a new batch request
                # queued behind an interactive head must not claim
                # the pages an earlier-arrived parked session needs
                break
            reserve = self._reserve_tokens(len(req.prompt_tokens),
                                           req.params.max_tokens)
            shared, matched = self.allocator.match_prefix(
                req.prompt_tokens)
            if not self.cache.can_admit(reserve, len(shared)):
                self.allocator.free(shared)   # undo the match refs
                break            # head-of-line admission control
            self.waiting.pop(0)
            self._admissions += 1
            if req.restarts == 0:
                # a requeued preemption victim counts once: its first
                # admission already recorded queue-wait/prefix stats
                # (and its prompt: what is prefilled again after that
                # shows in stats()["prefill"] as work done twice)
                self._prompt_tokens_admitted += (
                    len(req.prompt_tokens) - matched)
                self.allocator.record_match(matched,
                                            len(req.prompt_tokens))
                self.telemetry.on_admitted(req, cached_tokens=matched)
                if self.attrib is not None:
                    # queue-time share of the receipt (ISSUE 13)
                    self.attrib.note_queue(
                        req, time.monotonic() - req.submitted_at)
            else:
                self.telemetry.recorder.record(
                    "readmission", request_id=req.request_id,
                    restarts=req.restarts, cached_tokens=matched)
            slot.request = req
            self._alloc_ctx = slot.index
            try:
                # every group's reservation; the tables are the manager's
                slot.pages = self.cache.admit(slot.index, reserve, shared,
                                              matched)
            finally:
                self._alloc_ctx = None
            slot.prefill_pos = matched
            slot.ready = False
            slot.position = 0
            slot.seed = self._request_seed(req)
            self._tables_version += 1
            self._mark_seen_dirty(slot.index)  # slot reuse: stale row
            self._samp_cache = None      # new request: stale params

    def _finish_prefill(self, slot: _Slot, first_token: int,
                        touched: List[Request]) -> None:
        """Host-side prompt-completion bookkeeping (no device-state
        refresh — the ragged step folds a whole tick first and lets the
        next decode tick refresh lazily)."""
        req = slot.request
        n = len(req.prompt_tokens)
        self.allocator.register_prefix(
            req.prompt_tokens,
            slot.pages[:n // self.allocator.page_size])
        slot.prefill_pos = n
        slot.position = n
        slot.ready = True
        slot.last_token = first_token
        self._append_token(slot, first_token, touched)

    def _refresh_device_state(self) -> None:
        with self._phase("refresh"):
            self._rebuild_device_state()

    def _rebuild_device_state(self) -> None:
        """Re-upload slot state after an admit/finish. Between such
        events the decode loop is device-resident: tokens feed back from
        the previous step's output and positions advance on device, so a
        steady-state step costs ONE dispatch + ONE small readback."""
        rec = self._inflight
        if rec is not None:
            # structural barrier: rebuilding device state with a tick
            # still in flight would roll device positions back under
            # tokens the host never folded. Fold directly (not via
            # _drain) — the rebuild below already covers any
            # retirement, so _drain's recursive refresh would rebuild
            # everything twice. Tokens folded here surface via the
            # next step's touched list.
            self._inflight = None
            self._drains += 1
            self.telemetry.on_drain("device_state_rebuild")
            self._fold_inflight(rec, self._pending_touched)
        self.telemetry.recorder.record(
            "device_state_rebuild", active=self.num_active())
        B = self.config.max_batch_size
        tokens = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        temps = np.zeros(B, np.float32)
        top_ps = np.ones(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        rep_pens = np.ones(B, np.float32)
        seeds = np.zeros(B, np.int32)
        lora_idx = np.zeros(B, np.int32)
        seen = self._build_seen()
        for s in self.slots:
            if s.request is None or not s.ready:
                continue       # empty or still prefilling: inactive
            p = s.request.params
            tokens[s.index] = s.last_token
            positions[s.index] = s.position
            active[s.index] = True
            temps[s.index] = p.temperature
            top_ps[s.index] = p.top_p
            top_ks[s.index] = p.top_k
            rep_pens[s.index] = p.repetition_penalty
            seeds[s.index] = s.seed
            lora_idx[s.index] = self._lora_names.get(s.request.lora, 0)
        # a family's rider rides behind the tokens in every
        # readback, which is also the next decode tick's input: give
        # the first one the same shape, so there is one program
        self._d_tokens = self._dev(jnp.asarray(np.concatenate(
            [tokens, np.zeros(self._rider_len, np.int32)])))
        self._d_positions = self._dev(jnp.asarray(positions))
        self._d_active = self._dev(jnp.asarray(active))
        self._d_temps = self._dev(jnp.asarray(temps))
        self._d_top_ps = self._dev(jnp.asarray(top_ps))
        self._d_top_ks = self._dev(jnp.asarray(top_ks))
        self._d_rep_pens = self._dev(jnp.asarray(rep_pens))
        self._d_seeds = self._dev(jnp.asarray(seeds))
        self._d_lora_idx = self._dev(jnp.asarray(lora_idx))
        self._d_seen = self._dev(jnp.asarray(seen))
        self._all_greedy = bool(np.all(temps <= 0.0)
                                and np.all(rep_pens == 1.0))
        self._host_active = active
        self._seen_dirty_slots = set()   # full rebuild just happened

    def _fold_rider(self, toks_host: "np.ndarray", tokens: int,
                    of: int) -> Dict[str, int]:
        """Fold what rode back behind tick `of`'s tokens into the totals
        of stats()["moe"]: the assignments that landed on each held
        expert in each expert layer, and the tokens the tick routed.
        Returns the fold span's extra arguments (none for a family
        with no rider): the tick, the (layer, expert) pairs that
        received a token and the assignments landed, which is what the
        expert layer had to read and compute for that tick."""
        if not self._rider_len:
            return {}
        landed = toks_host[-self._rider_len:]
        self._moe_landed += landed
        self._moe_tokens_routed += tokens
        return {"of": of,
                "moe_experts_hit": int(np.count_nonzero(landed)),
                "moe_assignments": int(landed.sum())}

    def _drain(self, touched: List[Request]) -> None:
        """Pipeline barrier: fold the in-flight tick (if any) into
        host slot state NOW. Called before any structural event —
        slot admission, prefill advancement, LoRA
        registration, abort — so those paths observe exactly the host
        state a synchronous engine would. Refreshes device state when
        the fold retired a slot."""
        rec = self._inflight
        if rec is None:
            return
        self._inflight = None
        self._drains += 1
        self.telemetry.on_drain("structural")
        if self._fold_inflight(rec, touched):
            self._refresh_device_state()

    def _fold_inflight(self, rec: _InflightTick,
                       touched: List[Request],
                       lagged: bool = True) -> bool:
        """Fold one in-flight tick's tokens into host slot state;
        returns whether any request finished. A slot retired since
        dispatch (rec.active but request gone) contributed the
        one-token over-generation — its sample is discarded here and
        its KV write stayed inside the slot's pages (see the assert).
        lagged=False for the retirement branch's SAME-step fold of
        the just-dispatched successor (counting it would double the
        lagged_ticks pipeline-health signal)."""
        toks_host = self._read_tokens(rec.tokens, of=rec.tick)
        if lagged:
            self._lagged_ticks += 1
        page = self.allocator.page_size
        finished = False
        n_active = int(np.count_nonzero(rec.active))
        with self._phase("fold", tokens=n_active,
                         **self._fold_rider(toks_host, n_active,
                                            rec.tick)):
            for s in self.slots:
                if not rec.active[s.index]:
                    continue
                if s.request is None or not s.ready:
                    continue     # retired in flight: token discarded
                s.position += 1
                # +1-token headroom proof: admission reserves pages
                # for prompt+max_tokens, and the pending-token
                # invariant (the newest sampled token's KV is written
                # one tick LATER) leaves exactly one reserved slot
                # unused by a sync engine — the in-flight successor's
                # write (at the new s.position) consumes it and can
                # never pass the pages.
                assert s.position + 1 <= len(s.pages) * page, (
                    "async fold write past allocated pages",
                    s.index, s.position, len(s.pages), page)
                tok = int(toks_host[s.index])
                s.last_token = tok
                self._append_token(s, tok, touched)
                if s.request is None:            # EOS/stop/length
                    finished = True
        return finished

    def _decode(self, touched: List[Request]) -> None:
        if self._d_tokens is None:
            self._refresh_device_state()
        rows, kv, extra = self._account_decode_batch()
        carried = self._tick_carried = {
            "tick": self.ticks, "kind": "decode",
            "T": self.config.max_batch_size,
            "ctx": self.max_pages_per_seq, "rows": rows,
            "decode_rows": rows, "prefill_tokens": 0,
            "kv_tokens": kv, "attn_pairs": kv, "decode_pairs": kv,
            "built": 0, **extra}
        with self._phase("dispatch", **carried):
            self._key, sub = jax.random.split(self._key)
            self.dispatches += 1
            if self._kv_kind != "f32":
                (new_tokens, self.k_pages, self.v_pages, self.k_scales,
                 self.v_scales, self._d_seen) = self._decode_fn(
                    self.params, self.k_pages, self.v_pages,
                    self.k_scales, self.v_scales, self._d_seen,
                    self._d_tokens, self._d_positions,
                    self._device_tables(),
                    self._d_active, sub, self._d_temps, self._d_top_ps,
                    self._d_top_ks, self._d_rep_pens, self._d_seeds,
                    self._lora_stacks, self._d_lora_idx,
                    self._all_greedy)
            else:
                new_tokens, self.k_pages, self.v_pages, self._d_seen = \
                    self._decode_fn(
                        self.params, self.k_pages, self.v_pages,
                        self._d_seen, self._d_tokens,
                        self._d_positions, self._device_tables(),
                        self._d_active, sub, self._d_temps,
                        self._d_top_ps, self._d_top_ks,
                        self._d_rep_pens, self._d_seeds,
                        self._lora_stacks, self._d_lora_idx,
                        self._all_greedy)
            # device-side feedback for the next step
            self._d_tokens = new_tokens
            self._d_positions = self._d_positions + self._d_active
            if self._async:
                # two-deep pipeline: start the d2h copy of THIS tick
                # without blocking; below, fold the PREVIOUS tick
                # (whose copy has had a whole device step to complete)
                # — the host fold and the device's current step
                # overlap instead of serializing
                start = getattr(new_tokens, "copy_to_host_async", None)
                if start is not None:
                    start()      # no-op cost; fold blocks if absent
        self._program_dispatched()
        if not self._async:
            host = self._read_tokens(new_tokens, of=self.ticks)
            self._fold_rider(host, rows, self.ticks)
            self._post_decode(host, touched)
            return
        prev = self._inflight
        self._inflight = _InflightTick(
            new_tokens, self._host_active.copy(), self.ticks)
        if prev is not None and self._fold_inflight(prev, touched):
            # retirement is structural: drain the successor dispatched
            # above (its token for the retired slot is the one-token
            # over-generation, discarded by the fold's active check)
            # and rebuild device state for the survivors
            rec, self._inflight = self._inflight, None
            self._drains += 1
            self.telemetry.on_drain("retirement")
            self._fold_inflight(rec, touched, lagged=False)
            self._refresh_device_state()

    def _post_decode(self, host_tokens: "np.ndarray",
                     touched: List[Request]) -> None:
        """The synchronous decode tail: fold the one readback into slot
        state."""
        dirty = False
        with self._phase("fold", tokens=int(
                np.count_nonzero(self._host_active))):
            for s in self.slots:
                if s.request is None or not self._host_active[s.index]:
                    continue
                s.position += 1      # the fed token is now cached
                tok = int(host_tokens[s.index])
                s.last_token = tok
                self._append_token(s, tok, touched)
                if s.request is None:    # finished this step
                    dirty = True
        if dirty:
            self._refresh_device_state()

    def _append_token(self, slot: _Slot, tok: int,
                      touched: List[Request]) -> None:
        req = slot.request
        req.output_tokens.append(tok)
        self.telemetry.on_token(req)
        touched.append(req)
        p = req.params
        if tok in p.stop_token_ids:
            self._finish(slot, "stop")
        elif len(req.output_tokens) >= p.max_tokens:
            self._finish(slot, "length")

    def _attrib_finish(self, req: Request,
                       reason: Optional[str] = None
                       ) -> Optional[Dict[str, Any]]:
        """Close the request's cost receipt (ISSUE 13) and return its
        usage.cost brief for the finish event (None when the request
        was never charged — e.g. shed from the waiting queue)."""
        if self.attrib is None:
            return None
        rec = self.attrib.finish(req, reason)
        return None if rec is None else rec.cost_block()

    def _finish(self, slot: _Slot, reason: str) -> None:
        slot.request.finished = True
        slot.request.finish_reason = reason
        cost = self._attrib_finish(slot.request, reason)
        self.telemetry.on_finished(slot.request, reason, cost=cost)
        self.allocator.free(slot.pages)
        self._clear_slot(slot)

    def _clear_slot(self, slot: _Slot) -> None:
        """Return a slot to the empty state (pages already released by
        the caller — _finish frees them, preemption spills then frees).
        Invalidates every host/device mirror keyed on slot identity."""
        slot.request = None
        slot.pages = []
        slot.position = 0
        slot.prefill_pos = 0
        slot.ready = False
        # the table rows, and what the slot holds beyond the first group
        self.cache.vacate(slot.index)
        self._tables_version += 1
        self._mark_seen_dirty(slot.index)
        self._samp_cache = None

    def abort(self, request_id: str) -> bool:
        """Stop a request (client disconnected / stream abandoned): free
        its decode slot + KV pages, or drop it from the waiting queue
        (reference parity: the engine-level abort every serving stack
        needs once streams make client aborts routine). Serialized
        against step(): the server fires aborts from the event loop
        while the pump steps on an executor thread, and the refresh
        below folds any in-flight tick."""
        with self._step_lock:
            hit = self._abort_locked(request_id)
            if hit:
                self._publish_counters_locked()
            return hit

    def _abort_locked(self, request_id: str) -> bool:
        for i, req in enumerate(self.waiting):
            if req.request_id == request_id:
                del self.waiting[i]
                req.finished = True
                req.finish_reason = "abort"
                self.telemetry.recorder.record(
                    "abort", request_id=request_id,
                    where="waiting")
                self.telemetry.on_finished(
                    req, "abort",
                    cost=self._attrib_finish(req, "abort"))
                return True
        for slot in self.slots:
            if slot.request is not None \
                    and slot.request.request_id == request_id:
                self.telemetry.recorder.record(
                    "abort", request_id=request_id,
                    where="running")
                self._finish(slot, "abort")
                self._refresh_device_state()
                return True
        if self.host_tier is not None \
                and request_id in self.host_tier:
            # parked mid-preemption and the client gave up: drop
            # the host KV, never restore
            parked = self.host_tier.drop(request_id)
            if parked in self._pending_spills:
                self._pending_spills.remove(parked)
            req = parked.request
            req.finished = True
            req.finish_reason = "abort"
            self.telemetry.recorder.record(
                "abort", request_id=request_id, where="parked")
            self.telemetry.on_finished(
                req, "abort",
                cost=self._attrib_finish(req, "abort"))
            return True
        return False

    # -- observability (ISSUE 5) -------------------------------------------
    def profile_next_ticks(self, ticks: int = 8,
                           log_dir: Optional[str] = None) -> str:
        """Arm on-demand profiling (POST /debug/profile): `ticks` engine
        ticks run under util/profiling.trace (jax.profiler — XLA
        timeline + HLO ops for TensorBoard / xprof). Returns the log
        dir. The trace's start is the writer thread's, asked for here;
        `ticks` counts the ticks that begin after it has started, and
        the thread stops and writes it after the last.
        Re-arming while a capture is pending raises (one capture at a
        time)."""
        if int(ticks) < 1:
            raise ValueError("ticks must be >= 1")
        with self._step_lock:
            if self._profile is not None:
                raise RuntimeError(
                    "a profile capture is already armed/active "
                    f"({self._profile['remaining']} tick(s) left, "
                    f"dir {self._profile['dir']})")
            if log_dir is None:
                import tempfile
                log_dir = tempfile.mkdtemp(prefix="ray_tpu_llm_prof_")
            self._arm_profile_locked(ticks, "manual", log_dir)
            # an operator's capture is asked for at once, not at the
            # next tick's entry: it is wanted whatever the engine does
            self._profile_tick_begin()
        return log_dir

    def _count_capture(self, table: Dict[str, int], key: str) -> None:
        """stats()["self_captures"]: one more profile armed (by
        trigger) or black-box bundle dumped (by cause)."""
        with self._captures_lock:
            table[key] = table.get(key, 0) + 1

    def _profile_tick_begin(self) -> None:
        """Tick entry with a capture armed or running, under the step
        lock: the first tick after the arming asks the writer thread for
        the start; a tick that begins after the trace has started is one
        of the capture's. Starting is the thread's (`_profile_start`):
        `start_trace` took 39-48 ms of every stream's time here
        (PERF.md section 6, PR 32), and behind another session's export
        23 and 40 s."""
        ps = self._profile
        if ps is None:
            return
        if ps["state"] == "running":
            ps["live"] = True
        elif ps["state"] == "armed":
            ps["state"] = "starting"
            self._writer.submit(self._profile_start, ps)

    def _profile_start(self, ps: Dict[str, Any]) -> None:
        """Start the armed jax.profiler trace (writer thread). While
        another session is open (an operator's, the benchmark's, or one
        still being exported) the capture stays armed and the thread
        asks again every 20 ms, a minute at most."""
        from ...util import profiling
        try:
            give_up = time.monotonic() + _START_WAIT_S
            while profiling.session_open():
                if ps["aborted"]:
                    return
                if time.monotonic() > give_up:
                    raise TimeoutError(
                        "another profiler session stayed open for "
                        f"{_START_WAIT_S:g} s")
                time.sleep(0.02)
            cm = profiling.trace(ps["dir"])
            cm.__enter__()
        except Exception as e:   # profiler unavailable on this backend
            if self._profile is ps:
                self._profile = None
            self.telemetry.recorder.record("profile_error",
                                           error=repr(e))
            return
        with self._captures_lock:
            ps["cm"] = cm
            aborted = ps["aborted"]
            if not aborted:
                ps["state"] = "running"
                self._profiles_started += 1
        if aborted:              # the tick raised while this started
            self._profile_stop(ps, "profile_aborted")

    def _profile_tick_end(self) -> None:
        """Count the captured tick; after the last, hand the capture to
        the writer thread. stop_trace collects from the runtime and
        writes the trace: seconds, and 14 s behind another export
        (PERF.md section 6, PR 30), which under the step lock every
        live stream waited out to have one slow tick explained. The
        capture stays the engine's one capture until it is written."""
        ps = self._profile
        if ps is None or not ps["live"]:
            return
        ps["live"] = False
        ps["remaining"] -= 1
        if ps["remaining"] > 0:
            return
        ps["state"] = "writing"
        self._writer.submit(self._profile_stop, ps, "profile_done")

    def _profile_stop(self, ps: Dict[str, Any], event: str) -> None:
        try:
            ps["cm"].__exit__(None, None, None)
        except Exception as e:
            self.telemetry.recorder.record("profile_error",
                                           error=repr(e))
        else:
            self.telemetry.recorder.record(event, log_dir=ps["dir"])
        finally:
            if self._profile is ps:
                self._profile = None

    def wait_for_profile(self, timeout: Optional[float] = None) -> bool:
        """Block until the writer thread has nothing left to do: an
        asked-for start has been made, a finished capture's trace is
        written, a bundle is in the spool (tests, and a caller about to
        read the log dir or the spool). True when it is idle."""
        return self._writer.wait_idle(timeout)

    def _profile_abort(self) -> None:
        """Disarm after a mid-tick exception, so the next
        profile_next_ticks() isn't wedged behind a phantom capture, and
        stop a capture in flight with whatever was recorded so far. In
        line: the tick has failed already, nothing waits on it. A start
        the writer thread is in the middle of is stopped by the thread
        as soon as it is made."""
        ps = self._profile
        if ps is None or ps["state"] == "writing":
            return
        self._profile = None
        with self._captures_lock:
            ps["aborted"] = True
            started = ps["cm"] is not None
        if started:
            self._profile_stop(ps, "profile_aborted")

    def _arm_profile_locked(self, ticks: int,
                            trigger: str = "tick_anomaly",
                            log_dir: Optional[str] = None
                            ) -> Optional[str]:
        """Arm a capture of `ticks` ticks, step lock held (the anomaly
        detector fires inside step(); profile_next_ticks takes the lock
        and comes here): a dict, a counter and a flight event, nothing
        that takes time. The next tick's entry asks the writer thread
        for the start, so that an engine with no tick left after a flag
        opens no session it could not close. No-op (None) when a
        capture is already armed instead of raising: an anomaly storm
        must never crash the tick it is trying to explain."""
        if self._profile is not None:
            return None
        if log_dir is None:
            import tempfile
            log_dir = tempfile.mkdtemp(prefix="ray_tpu_llm_prof_")
        # state: armed -> starting (the writer thread has been asked) ->
        # running (cm is the trace) -> writing (handed over to be stopped
        # and written); live: the current tick began under the trace
        self._profile = {
            "remaining": int(ticks), "dir": log_dir, "state": "armed",
            "cm": None, "live": False, "aborted": False}
        self._count_capture(self._profiles_armed, trigger)
        self.telemetry.recorder.record(
            "profile_armed", ticks=int(ticks), log_dir=log_dir,
            trigger=trigger)
        return log_dir

    def _on_tick_anomaly(self, ev: Dict[str, Any]) -> None:
        """React to a classified tick anomaly (ISSUE 13): record the
        flight event with the offending batch composition, arm a profile
        capture of the next ticks, and have a rate-limited black-box
        bundle dropped (all decisions — including the rate limits —
        were made by the detector; this just acts on them). Runs under
        the step lock, at the flagged tick's fold, and only arms: the
        trace is started and the bundle gathered and written by the
        writer thread, so that a flag costs the streams nothing
        (`lock_hold_s` is the time spent here)."""
        # "kind" would collide with the recorder's positional event
        # kind — the classification rides as "anomaly_kind"
        fields = {("anomaly_kind" if k == "kind" else k): v
                  for k, v in ev.items()
                  if k not in ("arm_profile", "dump")}
        self.telemetry.recorder.record("tick_anomaly", **fields)
        if ev.get("arm_profile") and self.anomaly is not None:
            self._arm_profile_locked(self.anomaly.config.profile_ticks)
        if ev.get("dump"):
            # lock-free by contract (the crash path uses it the same
            # way); never turns an anomaly into a failure. Keyed
            # "anomaly_event" — the bundle already carries the
            # detector's stats under "anomaly", and extra is applied
            # last (it would silently replace them)
            self._writer.submit(self.dump_blackbox, "tick_anomaly", None,
                                {"anomaly_event": ev})

    def _on_alert_event(self, kind: str, event: Dict[str, Any]) -> None:
        """FlightRecorder alert hook: a guard violation landing in the
        ring snapshots a postmortem bundle (fires outside the recorder
        lock; exceptions are swallowed by the recorder)."""
        self.dump_blackbox(kind, extra={"alert_event": event})

    def dump_blackbox(self, cause: str, error: Optional[str] = None,
                      extra: Optional[Dict[str, Any]] = None
                      ) -> Optional[str]:
        """Snapshot a postmortem bundle to the on-disk spool (ISSUE 7):
        flight recorder, last-N tick times, metric exposition, engine
        config, and in-flight request states. Returns the bundle id
        (None when black-boxing is disabled or the write failed).

        LOCK-FREE by contract: the crash path calls this while the
        step lock is HELD (mid-tick exception), so nothing here may
        take it — tick_times is snapshotted with a bounded retry
        instead (a concurrent append can raise RuntimeError mid-
        iteration on the manual-dump path), and stats() is rebuilt
        from its lock-free components."""
        if not self.config.enable_blackbox:
            return None
        try:
            ticks: List[Any] = []
            # sanctioned bare read of a _step_lock-guarded field:
            # unguarded() tells the runtime sanitizer this scope is
            # lock-free on purpose, and the inline racelint disable
            # records the same contract for the static analyzer
            with thread_sanitizer.unguarded():
                for _ in range(4):
                    try:
                        ticks = list(self._tick_times)[-64:]  # racelint: disable=RL004 -- lock-free by contract: the crash path holds _step_lock; bounded retry absorbs a concurrent append
                        break
                    except RuntimeError:
                        continue
            try:
                cfg = json.loads(json.dumps(
                    dataclasses.asdict(self.config), default=repr))
            except Exception:
                cfg = {"repr": repr(self.config)}
            try:
                self.telemetry.update_gauges(self)
                from ...util import metrics as metrics_api
                exposition = metrics_api.export_prometheus()
            except Exception as e:
                exposition = f"# exposition failed: {e!r}"
            bundle = {
                "error": error,
                "engine_config": cfg,
                "counters": {
                    "ticks": self.ticks,
                    "dispatches": self.dispatches,
                    "compiled_programs": self.compiles,
                    "active": self.num_active(),
                    "waiting": len(self.waiting),
                },
                "tick_times_ms": [list(t[:3]) for t in ticks],
                "flight_recorder": self.telemetry.recorder.events(),
                "in_flight_requests": self.telemetry.live_snapshot(),
                "waiting_requests": [r.request_id for r in self.waiting],  # racelint: disable=RL004 -- lock-free by contract: the crash path holds _step_lock; reads the published list reference
                # single read of s.request per slot: the manual-dump
                # path races the pump's retirements, and a None between
                # a check and a .request_id deref would abort the
                # whole bundle
                "slots": [
                    {"index": s.index,
                     "request_id": req.request_id,
                     "position": s.position,
                     "prefill_pos": s.prefill_pos,
                     "ready": s.ready}
                    for s in self.slots
                    for req in (s.request,) if req is not None],
                "allocator": self.allocator.stats(),
                # perf accounting at the moment of death (ISSUE 11):
                # the accountant has its own lock (never held across a
                # raise), so this read is safe from the crash path
                "perf": (self.perf.summary()
                         if self.perf is not None else None),
                # ISSUE 13 forensics: who was consuming the machine
                # when it died, and what the anomaly plane last saw
                "attribution": (self.attrib.summary(top_k=4)
                                if self.attrib is not None else None),
                "anomaly": (self.anomaly.stats()
                            if self.anomaly is not None else None),
                "parked_requests": [
                    {"request_id": p.request.request_id,
                     "position": p.position, "pages": p.n_pages,
                     "reason": p.reason,
                     "parked_s": round(p.idle_s(), 3)}
                    for p in self.parked],
                "preemptions": dict(self.preempt_counts),  # racelint: disable=RL004 -- lock-free by contract: forensics-grade copy; a torn read beats a wedged crash path
                "metrics_exposition": exposition,
                **(extra or {}),
            }
            bid = self.blackbox.dump(cause, bundle)
            if bid is not None:
                self._count_capture(self._blackbox_dumps, cause)
                self.telemetry.recorder.record(
                    "blackbox_dump", cause=cause, bundle_id=bid)
            return bid
        except Exception:
            return None      # never turn a failure into a new failure

    def prometheus_metrics(self) -> str:
        """Prometheus text exposition of this process's registry with
        this engine's gauges refreshed — gauge reads happen at SCRAPE
        time only, so steady-state ticks pay nothing for them."""
        from ...util import metrics as metrics_api
        self.telemetry.update_gauges(self)
        return metrics_api.export_prometheus()

    def attribution_summary(self, top_k: int = 8) -> Dict[str, Any]:
        """GET /debug/attribution: top-K receipts by FLOPs + tenant
        rollups + conservation totals (ledger-locked reads — never
        touches the step lock, so it can't queue behind a tick)."""
        if self.attrib is None:
            return {"enabled": False}
        return self.attrib.summary(top_k=top_k)

    def chrome_trace(self) -> Dict[str, Any]:
        """Per-request lifecycle timelines (queued → admitted →
        prefill chunks → first token → decode → finished{reason}) as
        Chrome-trace JSON, merged with the process tracing ring and
        the perf counter tracks (MFU / MBU / tokens-per-tick —
        ISSUE 11) when accounting is on (GET /debug/trace)."""
        return self.telemetry.chrome_trace(perf=self.perf)

    # -- introspection ------------------------------------------------------
    @staticmethod
    def _pctl(sorted_vals, q: float) -> float:
        """Nearest-rank percentile over an already-sorted sequence."""
        if not sorted_vals:
            return 0.0
        i = min(int(q * (len(sorted_vals) - 1) + 0.5),
                len(sorted_vals) - 1)
        return sorted_vals[i]

    def _tick_times_summary(self, ticks, now: float, lagged: int,
                            drains: int) -> Dict[str, Any]:
        """Tick-pipeline telemetry over the recent window (512 ticks).
        device_ms is time BLOCKED in the sanctioned readback — the
        un-hidden device share of a tick — so overlap_ratio
        (1 - device_ms/wall_ms) rises toward 1 as the async pipeline
        hides the wait behind host folds, and sits near the device
        share itself when running synchronously. Besides the window
        averages, p50/p95/p99 expose TAIL behavior (ISSUE 11): a
        wedging tick or periodic stall moves the p99 long before it
        moves the mean.

        `ticks` is the ring copied to a tuple, `now` the clock of its
        records' `start` and `lagged` and `drains` the counters, all
        read together under _step_lock by stats()
        (the pump's executor thread appends per tick, and iterating a
        deque being mutated raises RuntimeError mid-/stats request).
        The five sorts below run AFTER the lock's release: under it
        they were tens of milliseconds of every live stream's gaps
        each time a monitor asked (ISSUE 55)."""
        n = len(ticks)
        wall = sum(t[0] for t in ticks)
        host = sum(t[1] for t in ticks)
        dev = sum(t[2] for t in ticks)
        out = {
            "window": n,
            # the clock of every `start` below, read with the ring: a
            # reader with two snapshots knows which records fall
            # between them
            "now": now,
            "longest": self._longest_ticks(ticks),
            "wall_ms_avg": round(wall / n, 3) if n else 0.0,
            "host_ms_avg": round(host / n, 3) if n else 0.0,
            "device_ms_avg": round(dev / n, 3) if n else 0.0,
            "overlap_ratio": (round(max(0.0, 1.0 - dev / wall), 3)
                              if wall > 0 else 0.0),
            "lagged_ticks": lagged,
            "drains": drains,
            "async_readback": self._async,
        }
        for i, name in enumerate(("wall_ms", "host_ms", "device_ms")):
            vals = sorted(t[i] for t in ticks)
            for q, tag in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
                out[f"{name}_{tag}"] = round(self._pctl(vals, q), 3)
        return out

    @staticmethod
    def _longest_ticks(ticks, k: int = 8) -> Dict[str, Any]:
        """The ring's k worst ticks by stall — a tick's wall over the
        median wall of its kind (ragged, decode or neither) — and its k
        longest between-tick gaps, each with its start, what it
        carried, its phases and the programs built during it: enough
        to say of one event what the tick was doing."""
        by_kind: Dict[str, List[float]] = {}
        for t in ticks:
            by_kind.setdefault(t.kind, []).append(t.wall_ms)
        median = {kind: sorted(v)[len(v) // 2]
                  for kind, v in by_kind.items()}
        stalls = sorted(ticks, key=lambda t: median[t.kind] - t.wall_ms)
        gaps = sorted(ticks, key=lambda t: -t.gap_ms)
        return {
            "median_wall_ms": {kind: round(v, 3)
                               for kind, v in median.items()},
            "stalls": [{**t.brief(), "excess_ms": round(
                t.wall_ms - median[t.kind], 3)} for t in stalls[:k]],
            "gaps": [t.brief() for t in gaps[:k] if t.gap_ms > 0],
        }

    def _moe_summary_locked(self) -> Optional[Dict[str, Any]]:
        """Monotone totals of what rode back behind every tick's tokens
        since the engine came up, as the model's family reads them (an
        expert family: its routing; None for a family with no rider)."""
        if not self._rider_len:
            return None
        return self.family.rider_summary(
            self.model_cfg, self._moe_landed, self._moe_tokens_routed)

    def stats(self) -> Dict[str, Any]:
        # ONE _step_lock acquisition around the whole mutable-state
        # snapshot (waiting/slots/parked/preempt_counts/tick deque):
        # the pump mutates all of these mid-tick, and the pre-racelint
        # version read them bare — len(waiting) vs lane_counts() could
        # disagree within one response, and dict(preempt_counts) can
        # raise RuntimeError if a preemption lands mid-copy. Component
        # summaries with their own locks (perf/attribution/anomaly/
        # telemetry) are read AFTER release to keep the hold short.
        with self._step_lock:
            snap = {
                "active": self.num_active(),
                "waiting": len(self.waiting),
                # ticks counts step() calls,
                # dispatches counts the FORWARD programs the host
                # launched — the ragged step's contract is a 1.0 ratio
                # on work ticks. The device runs more per tick (the key
                # split, the position update, refresh uploads: ~4 on
                # the chip, PERF.md §5); only a trace counts those
                "ticks": self.ticks,
                "dispatches": self.dispatches,
                "dispatches_per_step": round(
                    self.dispatches / max(self.ticks, 1), 3),
                # slice topology (ISSUE 17): chips this replica
                # occupies (mesh size; 1 off-mesh) — the fleet's
                # slice-accounting unit, and the divisor behind the
                # per-chip perf block
                "chips": self.n_chips,
                # KV memory hierarchy (ISSUE 10): parked sessions,
                # demand over the device pool (>1 = oversubscribed),
                # preemptions by reason; the host-tier block (spills/
                # restores/host pages) rides allocator.stats() below
                # when the tier is on
                "parked_sessions": len(self.parked),
                "page_pressure": round(self.page_pressure(), 4),
                # device-pool byte occupancy at the CONFIGURED page
                # dtype (ISSUE 16 small fix: int8/fp8 pools must not
                # report f32 bytes — per-page bytes include the quant
                # scale sidecar)
                "kv_dtype": self._kv_kind,
                # the FIRST cache group's row, kept for its readers:
                # `cache_groups` below (the manager's stats) is the
                # description since PR 31
                "cache_row": self.cache_row.describe(),
                # the weights as stored, read off the arrays at load
                "weights": self._weights_held,
                "kv_page_bytes": self._kv_page_bytes,
                "kv_device_bytes_used": self.cache.bytes_used(),
                "preemptions": dict(self.preempt_counts),
                # an expert family's routing, from what rode back with
                # every tick's tokens (None for a dense model)
                "moe": self._moe_summary_locked(),
                # batch lane (ISSUE 14): preemptible bulk-work
                # occupancy
                "lanes": self._lane_counts_locked(),
                # tick-pipeline telemetry (ISSUE 4): wall vs host-fold
                # vs blocked-readback per tick + lag/drain counters.
                # The ring is copied here and summarised after release
                "tick_times": (tuple(self._tick_times),
                               time.perf_counter(),
                               self._lagged_ticks, self._drains),
                # where a tick's wall goes (ISSUE 25): monotone seconds
                # per named phase (TICK_PHASES; `other` is wall that no
                # phase covers) and between ticks while work remained
                "tick_phases": {
                    "ticks": self._phase_ticks,
                    "seconds": {k: round(v, 6) for k, v
                                in self._phase_total_s.items()},
                    "gap_s": round(self._gap_total_s, 6)},
                # prompt tokens owed (admitted, less the prefix
                # cache's share) against prefill tokens run: the
                # second over the first, less one, is work done twice
                "prefill": {
                    "prompt_tokens_admitted":
                        self._prompt_tokens_admitted,
                    "prefill_tokens_dispatched":
                        self._prefill_tokens_dispatched},
            }
            with self._captures_lock:
                # what the engine's own tracing did, by trigger / cause
                snap["self_captures"] = {
                    "profiles_armed": dict(self._profiles_armed),
                    "profiles_started": self._profiles_started,
                    "blackbox_dumps": dict(self._blackbox_dumps),
                    # seconds the step lock was held by all of that
                    "lock_hold_s": round(self._capture_hold_s, 6)}
            # free_pages, total_pages and occupancy of the FULLEST
            # cache group (the one that gates admission), and
            # `cache_groups`: each group's row, layers, window, pages
            alloc_stats = self.cache.stats()
        snap["tick_times"] = self._tick_times_summary(*snap["tick_times"])
        return {
            **snap,
            # per-dispatch perf accounting (ISSUE 11): rolling
            # decode/prefill goodput, MFU/MBU vs the hardware
            # envelope, and which roof binds (perfmodel.py)
            "perf": (self.perf.summary() if self.perf is not None
                     else {"enabled": False}),
            # per-request cost attribution (ISSUE 13): top receipts,
            # per-tenant rollups, conservation totals
            "attribution": (self.attrib.summary()
                            if self.attrib is not None
                            else {"enabled": False}),
            # tick-anomaly analyzer (ISSUE 13): recent anomaly rate,
            # counts by classified kind, last event
            "anomaly": (self.anomaly.stats()
                        if self.anomaly is not None
                        else {"enabled": False}),
            # request-lifecycle SLO telemetry (ISSUE 5): per-engine
            # TTFT/ITL/queue-wait/e2e aggregates, finish-reason
            # counts, token totals, budget utilization and the
            # flight-recorder fill level (full series live on the
            # Prometheus side: GET /metrics)
            "requests": self.telemetry.summary(),
            # jit-cache observability: live bucketed programs per
            # cache + cumulative builds — a steady-state run must hold
            # `compiled_programs` flat (bucket churn = recompile storm)
            "jit_cache": {
                "ragged_buckets": len(self._ragged_fns),
                "seen_row_buckets": len(self._seen_scatter_buckets),
                "page_migration_fns": (len(self._page_gather_fns)
                                       + len(self._page_scatter_fns)),
                "compiled_programs": self.compiles,
            },
            **alloc_stats,
        }
