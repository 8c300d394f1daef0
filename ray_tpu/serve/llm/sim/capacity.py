"""Capacity-planning curves: replicas vs tail latency, as an artifact.

The question the Gemma-on-TPU serving study (PAPERS.md) asks of every
deployment — how many replicas until the p99 is bought? — answered by
sweeping the SAME trace over fleet sizes and emitting one JSON
artifact per sweep. tests/test_fleet_sim.py runs small sweeps;
operators point `python -m tools.simcal` at bigger ones.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

from .core import FleetSimulator, SimFleetConfig
from .traffic import SimSession, TraceConfig, generate


def capacity_curve(trace_cfg: TraceConfig,
                   fleet_cfg: SimFleetConfig,
                   replica_counts: List[int],
                   batch_jobs: Optional[List[SimSession]] = None,
                   capture_id: Optional[str] = None
                   ) -> Dict[str, Any]:
    """Replay `trace_cfg` at each fleet size (fixed-size fleets: min
    = max = n, autoscaling off-axis so the curve isolates capacity)
    and collect the tail metrics. Deterministic like everything else
    here: the trace regenerates from its seed per point."""
    points: List[Dict[str, Any]] = []
    for n in replica_counts:
        cfg = dataclasses.replace(fleet_cfg, replicas=n,
                                  min_replicas=n)
        sim = FleetSimulator(generate(trace_cfg), cfg,
                             batch_jobs=list(batch_jobs or []))
        s = sim.run()
        lat = s["latency"]
        sessions = s["sessions"]
        shed = sum(s["shed"].values())
        # slice topology (ISSUE 17): price each operating point per
        # chip, not per replica — a 2-chip slice that doesn't halve
        # the tail is a capacity loss the per-replica view hides
        chips = n * max(fleet_cfg.chips_per_replica, 1)
        tokens = (s["engine"]["decode_tokens"]
                  + s["batch"]["tokens"])
        virtual_s = s["sim"]["virtual_s"]
        points.append({
            "replicas": n,
            "chips": chips,
            "p50_ttft_ms": lat["ttft"]["p50_ms"],
            "p99_ttft_ms": lat["ttft"]["p99_ms"],
            "p99_itl_ms": lat["itl"]["p99_ms"],
            "p99_e2e_ms": lat["e2e"]["p99_ms"],
            "shed": shed,
            "shed_rate": round(
                shed / max(sessions["arrived"]
                           - sessions["batch_submitted"], 1), 6),
            "completed": sessions["completed"],
            "batch_tokens": s["batch"]["tokens"],
            "tokens_per_chip_s": round(
                tokens / max(virtual_s, 1e-9) / chips, 3),
            "chip_s_per_1k_tokens": round(
                virtual_s * chips / max(tokens / 1e3, 1e-9), 3),
            "watchdog_alerts": s["watchdog"]["alerts_total"],
        })
    return {
        "object": "capacity_curve",
        "trace": dataclasses.asdict(trace_cfg),
        "fleet": {
            "slots_per_replica": fleet_cfg.slots_per_replica,
            "pages_per_replica": fleet_cfg.pages_per_replica,
            "chips_per_replica": fleet_cfg.chips_per_replica,
            "calibration": (fleet_cfg.calibration.name
                            if fleet_cfg.calibration else None),
        },
        # artifact provenance (ISSUE 20 satellite): the committed
        # artifact is attributable to exactly one input set
        "provenance": {
            "calibration": (fleet_cfg.calibration.name
                            if fleet_cfg.calibration else None),
            "calibration_sha256": (fleet_cfg.calibration.checksum()
                                   if fleet_cfg.calibration
                                   else None),
            "seed": fleet_cfg.seed,
            "capture_id": capture_id,
        },
        "points": points,
    }


def write_artifact(curve: Dict[str, Any], path: str) -> str:
    """Write the sweep as a canonical JSON artifact (sorted keys, so
    artifact diffs are meaningful across runs)."""
    with open(path, "w") as f:
        json.dump(curve, f, sort_keys=True, indent=2)
        f.write("\n")
    return path


__all__ = ["capacity_curve", "write_artifact"]
