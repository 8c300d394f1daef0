"""SimCalibration: measured engine timing -> synthetic-replica model.

The simulator's fidelity rests entirely on this file: a synthetic
replica is nothing but a tick-index clock whose tick DURATION comes
from here. The numbers are extracted from a REAL engine's telemetry —
`stats()["tick_times"]` (PR 4's wall/host/device window) and the
per-tick `PerfSample` window PR 11's accountant keeps (batch
composition per tick — the piece the aggregate percentiles lack) —
by `tools/simcal`, which commits the result as a JSON file beside
this module (`calibration_cpu.json` for the CPU tier-1 environment;
no chip-measured file exists yet).

Model shape:
- decode ticks: wall-ms percentiles (p50/p95/p99) per
  batch-size bucket (1, 2, 4, ... slots decoding) — the simulator
  draws from a 3-point mixture over them (seeded), so simulated
  TTFT/ITL distributions grow tails instead of being delta spikes;
- prefill: extra wall-ms per prompt token ridden on a tick, plus the
  engine's chunk budget (a prompt occupies ceil(len/chunk) ticks);
- spill/restore: the latency a preemption/restore event charges
  (PR 10's page-gather + scatter, measured from offload-flagged
  ticks).

The sim-vs-real A/B gate (tests/test_fleet_sim.py, slow-marked)
replays a small real workload through both and
pins the predicted TTFT/e2e within a tolerance band — the file
cannot silently rot.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

# tolerance band of the sim-vs-real calibration A/B (ratio of sim
# predicted to real measured mean e2e) — wide because the CPU tier's
# tick times wobble with host load; the gate catches rot (10x drift
# from a stale file), not noise
CALIBRATION_BAND = (0.25, 4.0)

_PCTS = ("p50", "p95", "p99")


def _bucket(n: int) -> int:
    b = 1
    while b < max(n, 1):
        b *= 2
    return b


def _pctl(vals: List[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(int(q * (len(s) - 1) + 0.5), len(s) - 1)]


@dataclasses.dataclass
class SimCalibration:
    """The synthetic replica's timing model (JSON-serializable)."""
    name: str = "uncalibrated"
    page_size: int = 16
    # batch-size bucket (as str key for JSON) -> {"p50","p95","p99"}
    # decode-tick wall ms
    decode_tick_ms: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    # extra wall-ms a tick pays per prefill token it carries
    prefill_ms_per_token: float = 0.05
    # the engine's per-tick prefill budget (max_prefill_tokens)
    prefill_chunk_tokens: int = 512
    # preemption spill / restore latency (ms charged to the event)
    spill_ms: float = 2.0
    restore_ms: float = 2.0
    # provenance (never consumed by the model)
    source: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- the model -----------------------------------------------------
    def tick_point(self, batch: int, pct: str) -> float:
        """Decode-tick wall ms for `batch` decoding slots at one of
        the modeled percentile points, falling back to the nearest
        measured bucket (scaled linearly past the largest)."""
        if not self.decode_tick_ms:
            return 1.0
        b = _bucket(batch)
        key = str(b)
        if key in self.decode_tick_ms:
            return self.decode_tick_ms[key].get(pct, 1.0)
        known = sorted(int(k) for k in self.decode_tick_ms)
        if b < known[0]:
            return self.decode_tick_ms[str(known[0])].get(pct, 1.0)
        top = known[-1]
        base = self.decode_tick_ms[str(top)].get(pct, 1.0)
        return base * (b / top)

    def draw_tick_ms(self, batch: int, prefill_tokens: int,
                     u: float) -> float:
        """One tick's wall ms: a 3-point mixture over the bucket's
        percentiles (u ~ Uniform[0,1) from the replica's seeded RNG —
        90% body, 8% p95 shoulder, 2% p99 tail) plus the prefill
        surcharge. Deterministic given (batch, prefill_tokens, u)."""
        pct = "p50" if u < 0.90 else ("p95" if u < 0.98 else "p99")
        return (self.tick_point(batch, pct)
                + prefill_tokens * self.prefill_ms_per_token)

    def prefill_ticks(self, prompt_tokens: int) -> int:
        """Ticks a prompt occupies before its first token (Sarathi
        chunking: ceil(prompt / chunk budget))."""
        chunk = max(self.prefill_chunk_tokens, 1)
        return max((prompt_tokens + chunk - 1) // chunk, 1)

    # -- (de)serialization --------------------------------------------
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True,
                          indent=2) + "\n"

    def checksum(self) -> str:
        """sha256 of the canonical JSON rendering — the artifact
        provenance key (ISSUE 20 satellite): a committed sweep /
        summary / capture-diff names exactly which calibration
        produced it. Computed over to_json(), so a file round-trip
        (load → checksum) matches the original."""
        import hashlib
        return hashlib.sha256(self.to_json().encode()).hexdigest()

    @classmethod
    def from_json(cls, text: str) -> "SimCalibration":
        doc = json.loads(text)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in doc.items() if k in known})

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path: str) -> "SimCalibration":
        with open(path) as f:
            return cls.from_json(f.read())

    # -- extraction from a live engine --------------------------------
    @classmethod
    def from_engine(cls, engine: Any,
                    name: str = "extracted") -> "SimCalibration":
        """Extract the model from a driven engine's telemetry:
        `stats()["tick_times"]` for the aggregate provenance and the
        perf accountant's PerfSample window (ISSUE 11) for per-tick
        batch composition. The engine must have run a mixed workload
        first (tools/simcal drives one); buckets never observed fall
        back to nearest-bucket scaling at draw time."""
        stats = engine.stats()
        perf = getattr(engine, "perf", None)
        window = list(perf.window()) if perf is not None else []
        decode: Dict[int, List[float]] = {}
        prefill_rates: List[float] = []
        spill: List[float] = []
        restore: List[float] = []
        for t in window:
            if t.wall_ms <= 0:
                continue
            if t.bytes_d2h > 0:
                spill.append(t.wall_ms)
            if t.bytes_h2d > 0:
                restore.append(t.wall_ms)
            if t.prefill_tokens > 0 and t.decode_tokens >= 0:
                base = _pctl(decode.get(_bucket(
                    max(t.decode_tokens, 1)), []), 0.5)
                extra = max(t.wall_ms - base, 0.0)
                prefill_rates.append(extra / t.prefill_tokens)
            elif t.decode_tokens > 0:
                decode.setdefault(_bucket(t.decode_tokens),
                                  []).append(t.wall_ms)
        # structural-outlier trim (the anomaly detector's philosophy,
        # ISSUE 13): a cold compile or GC pause in the measurement
        # window is 10-100x the bucket median and would become the
        # model's p99 — the simulator must model steady-state tails,
        # not the measurement harness's warmup
        decode = {b: [v for v in vals
                      if v <= 10.0 * max(_pctl(vals, 0.5), 1e-6)]
                  for b, vals in decode.items()}
        decode_tick_ms = {
            str(b): {p: round(_pctl(vals, {"p50": 0.5, "p95": 0.95,
                                           "p99": 0.99}[p]), 4)
                     for p in _PCTS}
            for b, vals in sorted(decode.items()) if vals}
        # decode-only median as the baseline for event surcharges
        all_decode = [v for vals in decode.values() for v in vals]
        base_ms = _pctl(all_decode, 0.5)
        tick = stats.get("tick_times") or {}
        return cls(
            name=name,
            page_size=int(getattr(engine.allocator, "page_size", 16)),
            decode_tick_ms=decode_tick_ms,
            prefill_ms_per_token=round(
                _pctl(prefill_rates, 0.5), 6) or 0.05,
            prefill_chunk_tokens=int(
                getattr(engine.config, "max_prefill_tokens", 512)),
            spill_ms=round(max(_pctl(spill, 0.5) - base_ms, 0.1), 4),
            restore_ms=round(
                max(_pctl(restore, 0.5) - base_ms, 0.1), 4),
            source={
                "ticks_observed": len(window),
                "tick_wall_ms_p50": tick.get("wall_ms_p50"),
                "tick_wall_ms_p95": tick.get("wall_ms_p95"),
                "tick_wall_ms_p99": tick.get("wall_ms_p99"),
                "dispatches_per_step": stats.get(
                    "dispatches_per_step"),
            })


def default_cpu_calibration() -> SimCalibration:
    """The committed CPU-tier calibration (tools/simcal output against
    the debug model in this repo's tier-1 environment)."""
    path = os.path.join(os.path.dirname(__file__),
                        "calibration_cpu.json")
    return SimCalibration.load(path)


__all__ = ["SimCalibration", "default_cpu_calibration",
           "CALIBRATION_BAND"]
