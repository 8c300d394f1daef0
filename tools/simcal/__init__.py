"""simcal: extract a SimCalibration from a REAL engine's telemetry.

The fleet simulator (ray_tpu/serve/llm/sim) is only as honest as its
timing model. This tool drives a real `InferenceEngine` through a
mixed calibration workload — decode-only phases at several batch
sizes (one per batch bucket), chunked prefills, and (when the host
tier is on) forced spill/restore cycles — then distills
`stats()["tick_times"]` plus the PR 11 per-tick PerfSample window
into the `SimCalibration` JSON the synthetic replicas consume:

    python -m tools.simcal --out ray_tpu/serve/llm/sim/calibration_cpu.json

The committed `calibration_cpu.json` was produced exactly this way
against the debug model in the tier-1 CPU environment; TPU-tier files
should be regenerated on the chip (same command, bigger model).
The sim-vs-real A/B in tests/test_fleet_sim
pins predictions from the committed file within CALIBRATION_BAND, so
a stale file fails loudly instead of quietly skewing every capacity
curve.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def build_engine(num_pages: int = 96, max_batch: int = 8,
                 offload: bool = True) -> Any:
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine)
    return InferenceEngine(EngineConfig(
        model="debug", max_batch_size=max_batch, page_size=16,
        num_pages=num_pages, max_prefill_tokens=128,
        enable_kv_offload=offload,
        kv_watermark_tokens=16 if offload else None,
        host_kv_pages=4 * num_pages if offload else None,
        enable_metrics=True, enable_blackbox=False, seed=0))


def drive_calibration_workload(engine: Any,
                               decode_ticks: int = 48) -> None:
    """The measurement workload: per batch bucket (1, 2, 4, ...,
    max_batch) admit that many requests, run the prefills off, then
    `decode_ticks` pure-decode ticks so every bucket's tick-wall
    distribution is populated; finish with an oversubscribed phase
    that forces spill/restore traffic for the preemption timings."""
    from ray_tpu.llm._internal.engine import Request, SamplingParams
    rid = iter(range(10_000))

    def submit(n: int, prompt: int, out: int, priority: int = 0):
        reqs = []
        for _ in range(n):
            r = Request(f"cal-{next(rid)}", list(range(2, 2 + prompt)),
                        SamplingParams(max_tokens=out,
                                       temperature=0.0),
                        priority=priority)
            engine.add_request(r)
            reqs.append(r)
        return reqs

    b = 1
    while b <= engine.config.max_batch_size:
        reqs = submit(b, prompt=24, out=decode_ticks + 8)
        # run the prefill phase off, then measure steady decode
        while any(len(r.output_tokens) < 2 and not r.finished
                  for r in reqs):
            engine.step()
        for _ in range(decode_ticks):
            engine.step()
        for r in reqs:
            engine.abort(r.request_id)
        b *= 2
    # chunked-prefill phase: prompts several chunk budgets long
    reqs = submit(2, prompt=3 * engine.config.max_prefill_tokens
                  // 4 * 2, out=4)
    while not all(r.finished for r in reqs):
        engine.step()
    if engine.host_tier is not None:
        # force preemption churn: low-priority residents, then a
        # higher-priority burst that spills them (ISSUE 14 priority
        # path — the same machinery the batch lane rides)
        low = submit(engine.config.max_batch_size, prompt=16, out=64)
        for _ in range(8):
            engine.step()
        high = submit(engine.config.max_batch_size, prompt=16,
                      out=8, priority=1)
        while not all(r.finished for r in high):
            engine.step()
        deadline = 4000
        while not all(r.finished for r in low) and deadline:
            engine.step()
            deadline -= 1


def extract(name: str = "cpu-debug",
            engine: Optional[Any] = None) -> Any:
    """Build (or take) an engine, drive the workload, return the
    SimCalibration."""
    from ray_tpu.serve.llm.sim.calibration import SimCalibration
    eng = engine if engine is not None else build_engine()
    drive_calibration_workload(eng)
    return SimCalibration.from_engine(eng, name=name)


def check_against(calib: Any, summary: Dict[str, Any],
                  measured_e2e_s: float) -> Dict[str, Any]:
    """The A/B helper: compare a sim run's mean e2e against a real
    measured one; returns the ratio + band verdict."""
    from ray_tpu.serve.llm.sim.calibration import CALIBRATION_BAND
    sim_e2e = summary["latency"]["e2e"]["mean_ms"] / 1e3
    ratio = sim_e2e / measured_e2e_s if measured_e2e_s > 0 else 0.0
    lo, hi = CALIBRATION_BAND
    return {"sim_e2e_s": round(sim_e2e, 4),
            "real_e2e_s": round(measured_e2e_s, 4),
            "ratio": round(ratio, 4),
            "band": [lo, hi],
            "within_band": lo <= ratio <= hi}


__all__ = ["build_engine", "drive_calibration_workload", "extract",
           "check_against"]
