"""Per-dispatch performance accounting: analytic FLOP/byte cost model.

ISSUE 11: the engine reports *when* ticks happen (tick_times, PRs 4/5/7)
but not *what they cost* — "as fast as the hardware allows" (ROADMAP
north star, item 4's >=40% serving-MFU bar) was unmeasurable. This
module is the accounting plane: an analytic cost model over LlamaConfig
plus each tick's ragged batch composition (decode tokens, prefill-chunk
tokens, context lengths — metadata the engine already packs host-side),
yielding FLOPs (GEMM vs attention split), HBM bytes (weight reads per
dispatch, KV page reads/writes, spill/restore d2h/h2d traffic), and
roofline ratios against a hardware envelope table. The vocabulary is
the Gemma-on-TPU serving study's (PAPERS.md): model-FLOPs utilization
(MFU) and HBM-bandwidth utilization (MBU), and which roof binds.

Contract (the telemetry zero-sync discipline, ISSUE 5): everything here
is host-side Python arithmetic over plain ints/floats. Recording a
PerfSample adds ZERO device syncs, ZERO uploads, and ZERO dispatches to
a tick — the dispatch-guard suite runs with accounting enabled. A
slow-marked cross-check (tests/test_perfmodel.py) compares the analytic
model against jax.jit(...).lower().cost_analysis() at the one
sanctioned compile, so the model cannot silently drift from the program
it describes.

Conventions (documented so the numbers mean one thing):
- FLOPs are USEFUL model FLOPs for the tokens actually advanced — the
  standard MFU numerator. Padding rows in a bucketed program and the
  dense-gather CPU fallback's max-context reads are implementation
  overheads the ratio is supposed to expose, not hide.
- A matmul (m, k) @ (k, n) counts 2*m*n*k FLOPs; attention counts the
  QK^T and PV pair products (4 * n_heads * head_dim per
  (query token, context token) pair per layer); elementwise work
  (norms, rope, softmax, sampling) is noise against the GEMMs and is
  not counted.
- HBM bytes: weights are read ONCE per forward dispatch (param storage
  dtype); KV context reads are page-granular (the paged kernel streams
  whole pages); KV writes are one row per valid token. Activations are
  not counted (they are VMEM/cache-resident at serving batch sizes).
- MFU/MBU are computed over ENGINE-BUSY time (the sum of tick walls):
  they measure how well the ticks that ran used the hardware. Token
  goodput is computed over the window SPAN (first to last sample), so
  it reflects real delivered throughput including idle gaps.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, Optional

from ...models.family import family_of
from ...models.llama import LlamaConfig

# Rolling window of per-tick samples (matches the engine's _tick_times
# window so /stats reads one coherent recent-history length).
_WINDOW = 512


@dataclasses.dataclass(frozen=True)
class HardwareEnvelope:
    """Per-chip peak envelope: dense-matmul FLOP/s and HBM bytes/s.

    TPU numbers are the published per-chip peaks (bf16 dense MXU,
    HBM bandwidth). The CPU envelope is NOT a hardware datasheet — it
    is calibrated once from BENCH_CORE.md's single-socket dev-box
    measurements (round-3/5 CPU tiers: the shared VM sustains a few
    GFLOP/s on the serving GEMM mix and single-digit GB/s effective
    bandwidth) and pinned at a generous single-socket ceiling, so the
    CPU tier reports meaningful roofline RATIOS today instead of
    dividing by a TPU peak it can never approach."""
    name: str
    peak_flops: float            # FLOP/s per chip
    peak_bytes_per_s: float      # HBM bytes/s per chip
    source: str = ""


# THE peaks table: peak dense bf16 FLOP/s and HBM bytes/s per chip.
# v5e from Google Cloud's "TPU v5e" page (197 TFLOP/s bf16, 819 GB/s);
# the other generations from their Cloud TPU pages likewise.
ENVELOPES: Dict[str, HardwareEnvelope] = {
    "tpu-v4": HardwareEnvelope("tpu-v4", 275e12, 1228e9,
                               "Google Cloud TPU v4 page"),
    "tpu-v5e": HardwareEnvelope("tpu-v5e", 197e12, 819e9,
                                "Google Cloud TPU v5e page"),
    "tpu-v5p": HardwareEnvelope("tpu-v5p", 459e12, 2765e9,
                                "Google Cloud TPU v5p page"),
    "tpu-v6e": HardwareEnvelope("tpu-v6e", 918e12, 1638e9,
                                "Google Cloud TPU v6e page"),
    "cpu": HardwareEnvelope("cpu", 5e10, 25e9,
                            "BENCH_CORE.md CPU-tier calibration"),
}

# `device_kind` exactly as jax reports it -> ENVELOPES key. A device
# this table does not name is an error, never a default: a ratio
# against the wrong peak is worse than no ratio.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "tpu-v4",
    "TPU v5 lite": "tpu-v5e",
    "TPU v5p": "tpu-v5p",
    "TPU v6 lite": "tpu-v6e",
}


def detect_envelope(device: Any = None,
                    name: Optional[str] = None) -> HardwareEnvelope:
    """Resolve the hardware envelope for `device` (default: the first
    jax device). `name` overrides detection (EngineConfig.perf_envelope
    — tests and benches pin "cpu" explicitly). Unknown names and
    unknown accelerators raise."""
    if name is not None:
        try:
            return ENVELOPES[name]
        except KeyError:
            raise ValueError(
                f"unknown perf envelope {name!r}; known: "
                f"{sorted(ENVELOPES)}") from None
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return ENVELOPES["cpu"]
    try:
        return ENVELOPES[DEVICE_KINDS[device.device_kind]]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); known: "
            f"{sorted(DEVICE_KINDS)}. Add it to perfmodel.DEVICE_KINDS "
            f"with its source, or pin EngineConfig.perf_envelope."
        ) from None


def _dtype_bytes(dt: Any) -> int:
    import numpy as np
    return int(np.dtype(dt).itemsize)


class CostModel:
    """Closed-form serving costs for one model configuration.

    All per-token / per-pair constants precompute at construction so
    the per-tick accounting is a handful of int multiplies. A
    configuration with a `serving_costs()` method (a family other than
    the dense decoder's) gives its own FLOP and weight constants; the
    KV bytes come from the cache-row description either way."""

    def __init__(self, cfg, page_size: int, kv_dtype: str = "f32",
                 cache_row=None, weight_bytes: Optional[float] = None,
                 cache_groups=None):
        from ...ops import kv_quant
        self.cfg = cfg
        self.page_size = int(page_size)
        self.kv_dtype = kv_quant.validate_kind(kv_dtype)
        L = cfg.n_layers
        if hasattr(cfg, "serving_costs"):
            own = cfg.serving_costs()
            self.gemm_flops_per_token = float(own["gemm_flops_per_token"])
            self.head_flops = float(own["head_flops"])
            self.attn_flops_per_pair = float(own["attn_flops_per_pair"])
            self.weight_bytes = float(own["weight_bytes"])
            # layers that only a row that samples runs (a cross-decoder
            # behind one layer's cache): a chunk pays them once
            self.row_flops = float(
                own.get("gemm_flops_per_sampled_row", 0.0))
        else:
            self.row_flops = 0.0
            self._llama_constants(cfg)
        if weight_bytes is not None:
            # an engine's: the bytes of the leaves it stores (serving
            # keeps the dense family's matrices in cfg.dtype, not in
            # cfg.param_dtype as the configuration alone would say)
            self.weight_bytes = float(weight_bytes)
        # one token's rows across the stack, at the POOL's row: the
        # width the kernels stream (a head_dim of 64 is padded to 128
        # lanes in a kernel pool; the cache, not the model, pays), the
        # storage type, and a quantized pool's per-(row, head) scales
        # The cache is priced a GROUP (models/cache_row.CacheGroup):
        # its layers' rows, read over the keys a query still sees (a
        # window layer reads min(context, window) keys a row; a group
        # that other layers READ costs its row again for each reader; a
        # STATE group costs its bytes a slot once read and once written
        # a row a tick, whatever the row's tokens)
        if cache_groups is None and cache_row is None:
            # no engine behind this model (a test's): the family's
            # groups as the model writes them
            cache_groups = family_of(cfg).cache_groups(
                cfg, "gather", self.kv_dtype)
        if cache_groups is None:
            # one row for every layer (a caller with a row in hand)
            from ...models.family import one_group
            cache_groups = one_group(cache_row, L)
        self.cache_groups = tuple(cache_groups)
        self.cache_row = self.cache_groups[0].row
        self.kv_bytes_per_token = float(
            sum(g.bytes_per_token for g in self.cache_groups))
        self.page_bytes = self.kv_bytes_per_token * self.page_size
        self._windowed = any(g.window is not None
                             for g in self.cache_groups)
        self.state_bytes_per_row = float(
            sum(g.bytes_per_slot for g in self.cache_groups))
        self._read_bytes_per_token = float(
            sum(g.read_bytes_per_token for g in self.cache_groups))

    def _llama_constants(self, cfg: LlamaConfig) -> None:
        h, L = cfg.hidden, cfg.n_layers
        # -- GEMM FLOPs per token through the layer stack (no head) --
        qkvo = 2 * h * (cfg.q_dim + 2 * cfg.kv_dim) + 2 * cfg.q_dim * h
        if cfg.n_experts:
            # router + top_k active expert FFNs (inactive experts cost
            # nothing per token — same active-param convention as
            # llama.flops_per_token)
            mlp = (2 * h * cfg.n_experts
                   + cfg.moe_top_k * 3 * 2 * h * cfg.ffn)
        else:
            mlp = 3 * 2 * h * cfg.ffn
        self.gemm_flops_per_token = float(L * (qkvo + mlp))
        # lm_head, counted once per SAMPLED logits row (every decode
        # token; one per prefill chunk — the chunk's last-token logits)
        self.head_flops = float(2 * h * cfg.vocab_size)
        # attention FLOPs per (query token, context token) pair:
        # QK^T + PV, each 2 * n_heads * head_dim, per layer
        self.attn_flops_per_pair = float(4 * L * cfg.n_heads
                                         * cfg.head_dim)
        # -- HBM bytes --
        self.weight_bytes = float(cfg.num_params()
                                  * _dtype_bytes(cfg.param_dtype))
        if cfg.n_experts:
            # active-weight read per dispatch (top_k experts' FFNs);
            # matches the FLOP convention above
            inactive = (3 * h * cfg.ffn * L
                        * max(cfg.n_experts - cfg.moe_top_k, 0))
            self.weight_bytes -= inactive * _dtype_bytes(cfg.param_dtype)

    # -- primitives ----------------------------------------------------
    def _ctx_read_tokens(self, ctx: int) -> int:
        """Context tokens READ for one query token at context length
        `ctx`: page-granular (the kernel streams whole pages; a
        partially filled last page still moves end to end)."""
        if ctx <= 0:
            return 0
        pages = -(-ctx // self.page_size)
        return pages * self.page_size

    def _kv_read_bytes(self, ctx: int, n: int = 1) -> float:
        """Bytes of cached context that `n` queries whose last sits
        `ctx` keys into its sequence read, every group: a window group
        reads at most its window's keys and the n - 1 before them; a
        state group's bytes a row once (0 without one)."""
        if not self._windowed:
            return (self.state_bytes_per_row + self._read_bytes_per_token
                    * self._ctx_read_tokens(ctx))
        return self.state_bytes_per_row + float(sum(
            g.read_bytes_per_token * self._ctx_read_tokens(
                ctx if g.window is None else min(ctx, g.window + n - 1))
            for g in self.cache_groups))

    def _windowed_pairs(self, start: int, n: int) -> float:
        """(query, key) pairs `n` queries after `start` cached tokens
        keep, summed over layers as a share of `attn_flops_per_pair`'s
        every-layer count: a window layer's query keeps at most its
        window."""
        # the layers that attend: a page group's writers and readers
        attending = [(g, len(g.layers) + len(g.readers))
                     for g in self.cache_groups if g.state is None]
        total = sum(k for _, k in attending)
        pairs = 0.0
        for g, k in attending:
            kept = n * start + n * (n + 1) // 2
            if g.window is not None:
                full = max(min(g.window - start, n), 0)
                kept = (full * start + full * (full + 1) // 2
                        + (n - full) * g.window)
            pairs += kept * k / total
        return pairs

    def decode_cost(self, ctx: int) -> Dict[str, float]:
        """One decode token whose attention context is `ctx` tokens
        (cached + itself)."""
        return {
            "flops_gemm": (self.gemm_flops_per_token + self.row_flops
                           + self.head_flops),
            "flops_attn": self.attn_flops_per_pair * (
                self._windowed_pairs(ctx - 1, 1) if self._windowed
                else ctx),
            "bytes_kv_read": self._kv_read_bytes(ctx - 1),
            "bytes_kv_write": (self.kv_bytes_per_token
                               + self.state_bytes_per_row),
        }

    def chunk_cost(self, start: int, n: int) -> Dict[str, float]:
        """A prefill chunk of `n` tokens against `start` cached context
        tokens (causal: token i attends to start + i + 1 keys). The
        chunk's own K/V stay on-chip; only the cached context is read
        from the pool."""
        pairs = (self._windowed_pairs(start, n) if self._windowed
                 else n * start + n * (n + 1) // 2)
        return {
            "flops_gemm": n * self.gemm_flops_per_token
            + self.row_flops + self.head_flops,
            "flops_attn": self.attn_flops_per_pair * pairs,
            "bytes_kv_read": self._kv_read_bytes(start, n),
            "bytes_kv_write": (n * self.kv_bytes_per_token
                               + self.state_bytes_per_row),
        }

    def forward_flops(self, batch: int, seq: int) -> float:
        """Full-causal forward FLOPs for a dense (batch, seq) prefill
        with logits for every position — the shape
        jax.jit(llama.forward).lower(...).cost_analysis() describes;
        the cross-check test compares against this."""
        per_seq = (seq * (self.gemm_flops_per_token + self.head_flops)
                   + self.attn_flops_per_pair * seq * (seq + 1) // 2)
        return float(batch * per_seq)


@dataclasses.dataclass
class PerfSample:
    """One engine tick's analytic cost, recorded beside _tick_times.
    kinds: ragged | decode, the tick's one forward program."""
    kind: str = ""
    decode_tokens: int = 0
    prefill_tokens: int = 0
    dispatches: int = 0
    flops_gemm: float = 0.0
    flops_attn: float = 0.0
    bytes_weights: float = 0.0
    bytes_kv_read: float = 0.0
    bytes_kv_write: float = 0.0
    bytes_d2h: float = 0.0          # KV spill traffic (ISSUE 10)
    bytes_h2d: float = 0.0          # KV restore traffic
    wall_ms: float = 0.0            # stamped at commit (step() wall)
    mono_ts: float = 0.0            # monotonic commit stamp

    @property
    def flops(self) -> float:
        return self.flops_gemm + self.flops_attn

    @property
    def hbm_bytes(self) -> float:
        """Device-HBM traffic the roofline divides by (spill/restore
        is PCIe/host traffic — tracked, but not HBM bandwidth)."""
        return (self.bytes_weights + self.bytes_kv_read
                + self.bytes_kv_write)


class PerfAccountant:
    """Per-engine rolling perf accounting. The engine accumulates cost
    contributions into a pending sample as each dispatch path runs
    (host arithmetic only), then commit() stamps the tick's wall time
    and folds it into the window + cumulative totals. summary() is a
    scrape-time read (GET /stats, /metrics), never on the tick path."""

    def __init__(self, model: CostModel, envelope: HardwareEnvelope,
                 n_chips: int = 1):
        self.model = model
        self.envelope = envelope
        self.n_chips = max(int(n_chips), 1)
        self._lock = threading.Lock()
        self._window: "collections.deque[PerfSample]" = \
            collections.deque(maxlen=_WINDOW)
        self._pending: Optional[PerfSample] = None
        # cumulative totals (monotone — the Prometheus counter source)
        self.flops_total = 0.0
        self.flops_gemm_total = 0.0
        self.flops_attn_total = 0.0
        self.bytes_total = {"weights": 0.0, "kv_read": 0.0,
                            "kv_write": 0.0, "d2h": 0.0, "h2d": 0.0}
        self.decode_tokens_total = 0
        self.prefill_tokens_total = 0
        self.samples_total = 0

    # -- tick-path accumulation (host-only, no locks needed: the step
    # lock already serializes every caller) --------------------------
    def _pend(self) -> PerfSample:
        if self._pending is None:
            self._pending = PerfSample()
        return self._pending

    def add(self, kind: str, cost: Dict[str, float],
            decode_tokens: int = 0, prefill_tokens: int = 0) -> None:
        """Fold the tick's forward dispatch into the pending sample:
        its cost, and one read of the model's weights."""
        p = self._pend()
        p.kind = kind
        p.dispatches += 1
        p.decode_tokens += decode_tokens
        p.prefill_tokens += prefill_tokens
        p.flops_gemm += cost.get("flops_gemm", 0.0)
        p.flops_attn += cost.get("flops_attn", 0.0)
        p.bytes_weights += self.model.weight_bytes
        p.bytes_kv_read += cost.get("bytes_kv_read", 0.0)
        p.bytes_kv_write += cost.get("bytes_kv_write", 0.0)

    def abort_tick(self) -> None:
        """Drop the pending sample (mid-tick crash path): a tick that
        never completed must not fold its projected cost into the
        window with a bogus wall time."""
        self._pending = None

    def note_offload(self, d2h: float = 0.0, h2d: float = 0.0) -> None:
        """KV spill/restore traffic (ISSUE 10 page migration) — rides
        the pending tick (structural events happen inside a step)."""
        p = self._pend()
        p.bytes_d2h += d2h
        p.bytes_h2d += h2d

    def commit(self, wall_ms: float) -> Optional[PerfSample]:
        """Stamp the tick's wall time and fold the pending sample into
        the window + cumulative totals. A tick that dispatched nothing
        (admission-only) and moved no offload bytes records nothing.
        Returns the committed sample (None for an empty tick) so the
        attribution ledger and anomaly detector (ISSUE 13) can consume
        the same record the window keeps."""
        p, self._pending = self._pending, None
        if p is None:
            return None
        p.wall_ms = float(wall_ms)
        p.mono_ts = time.monotonic()
        with self._lock:
            self._window.append(p)
            self.samples_total += 1
            self.flops_gemm_total += p.flops_gemm
            self.flops_attn_total += p.flops_attn
            self.flops_total += p.flops
            self.bytes_total["weights"] += p.bytes_weights
            self.bytes_total["kv_read"] += p.bytes_kv_read
            self.bytes_total["kv_write"] += p.bytes_kv_write
            self.bytes_total["d2h"] += p.bytes_d2h
            self.bytes_total["h2d"] += p.bytes_h2d
            self.decode_tokens_total += p.decode_tokens
            self.prefill_tokens_total += p.prefill_tokens
        return p

    # -- scrape-time reads ---------------------------------------------
    def window(self) -> tuple:
        with self._lock:
            return tuple(self._window)

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {
                "flops": self.flops_total,
                "flops_gemm": self.flops_gemm_total,
                "flops_attn": self.flops_attn_total,
                "decode_tokens": float(self.decode_tokens_total),
                "prefill_tokens": float(self.prefill_tokens_total),
                "samples": float(self.samples_total),
                **{f"bytes_{k}": v for k, v in
                   self.bytes_total.items()},
            }

    def summary(self) -> Dict[str, Any]:
        """stats()["perf"]: recent-window goodput, MFU/MBU against the
        envelope, and which roof binds. MFU/MBU divide by engine-BUSY
        time (sum of tick walls: how well the ticks that ran used the
        chip); tokens/s divides by the window SPAN (delivered
        throughput, idle included)."""
        ticks = self.window()
        peak_f = self.envelope.peak_flops * self.n_chips
        peak_b = self.envelope.peak_bytes_per_s * self.n_chips
        busy_s = sum(t.wall_ms for t in ticks) * 1e-3
        # mono_ts stamps the END of a tick, so the span runs from the
        # START of the first tick (its commit stamp minus its wall) to
        # the end of the last — busy_s can never exceed it
        span_s = ((ticks[-1].mono_ts - ticks[0].mono_ts
                   + ticks[0].wall_ms * 1e-3)
                  if len(ticks) > 1 else busy_s)
        flops = sum(t.flops for t in ticks)
        hbm = sum(t.hbm_bytes for t in ticks)
        mfu = flops / (busy_s * peak_f) if busy_s > 0 else 0.0
        mbu = hbm / (busy_s * peak_b) if busy_s > 0 else 0.0
        if not ticks:
            roof = "idle"
        else:
            roof = "compute" if mfu >= mbu else "memory"
        dec = sum(t.decode_tokens for t in ticks)
        pre = sum(t.prefill_tokens for t in ticks)
        return {
            "enabled": True,
            "envelope": self.envelope.name,
            "n_chips": self.n_chips,
            "peak_flops": peak_f,
            "peak_hbm_bytes_per_s": peak_b,
            "window": len(ticks),
            "busy_s": round(busy_s, 6),
            "span_s": round(span_s, 6),
            "decode_tokens_per_s": round(dec / span_s, 3)
            if span_s > 0 else 0.0,
            "prefill_tokens_per_s": round(pre / span_s, 3)
            if span_s > 0 else 0.0,
            "achieved_flops_per_s": round(flops / busy_s, 3)
            if busy_s > 0 else 0.0,
            "achieved_hbm_bytes_per_s": round(hbm / busy_s, 3)
            if busy_s > 0 else 0.0,
            "mfu": round(mfu, 6),
            "mbu": round(mbu, 6),
            "roof": roof,
            # arithmetic intensity of the recent mix vs the machine
            # balance point — the classic roofline coordinates
            "flops_per_byte": round(flops / hbm, 3) if hbm else 0.0,
            "ridge_flops_per_byte": round(peak_f / peak_b, 3),
            "totals": self.totals(),
        }

    def brief(self) -> Dict[str, Any]:
        """The fleet-plane subset (fleet_stats -> ReplicaSnapshot ->
        /fleet rows): small enough to ride every router refresh."""
        s = self.summary()
        return {k: s[k] for k in
                ("mfu", "mbu", "roof", "decode_tokens_per_s",
                 "prefill_tokens_per_s", "envelope")}


__all__ = ["HardwareEnvelope", "ENVELOPES", "detect_envelope",
           "CostModel", "PerfSample", "PerfAccountant"]
