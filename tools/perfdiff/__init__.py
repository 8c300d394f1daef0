"""perfdiff: perf-fingerprint regression checker (ISSUE 11).

Turns BENCH_CORE.md's prose perf trajectory into an ASSERTED one: a
perf fingerprint — the analytic cost model's exact per-token numbers,
the workload's dispatch mix and token totals, plus the (machine-
dependent) achieved rates — is recorded by
`run_canonical_workload()` here, and `compare()` checks a fresh run
against the committed baseline (PERF_BASELINE.json at the repo root):

- `exact` metrics are DETERMINISTIC on any machine: closed-form model
  costs (FLOPs/bytes per token — they depend only on the model
  config) and the canonical workload's scheduling outcome (ticks,
  dispatches, token counts, analytic FLOP totals: token COUNTS are
  fixed by max_tokens even where near-tie argmax values flip). Any
  drift is a real change — a cost-model edit, a scheduler regression
  (extra dispatches), or a packing change — and fails the diff.
- `noisy` metrics (tokens/s, MFU, MBU) vary with the host; they are
  checked against a wide noise band (catastrophe detection, not
  micro-benchmarking) and reported, not trusted, across machines.

CLI:
    python -m tools.perfdiff                     # run + compare
    python -m tools.perfdiff --current f.json    # compare a recorded run
    python -m tools.perfdiff --write-baseline    # regenerate baseline
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

SCHEMA = 1
BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "PERF_BASELINE.json")

# Default noise band for `noisy` metrics: current/baseline ratio must
# stay inside [lo, hi]. Deliberately wide — the committed baseline was
# measured on one shared 1-vCPU VM and the gate must not flake on a
# faster/slower host; it exists to catch order-of-magnitude collapses.
DEFAULT_BAND = (0.02, 50.0)
# Relative tolerance for `exact` float comparisons (they are computed,
# not measured; anything past rounding is real drift).
EXACT_RTOL = 1e-6


def run_canonical_workload() -> Dict[str, Any]:
    """Drive the canonical perf workload and return its fingerprint.

    Fixed seeded workload on the debug model (greedy, fixed
    max_tokens, prefix caching off, envelope pinned to "cpu"): every
    `exact` field is machine-independent. Small enough for tier-1
    (tests/test_perfdiff.py runs it)."""
    import numpy as np

    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine, Request,
                                              SamplingParams)
    from ray_tpu.models import llama

    cfg = llama.config("debug")
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=4, page_size=8, num_pages=128,
        max_prefill_tokens=16, seed=7,
        enable_prefix_caching=False, perf_envelope="cpu"))
    rng = np.random.default_rng(11)
    reqs = [Request(f"pf{i}",
                    rng.integers(2, 250, 12 + 4 * (i % 3)).tolist(),
                    SamplingParams(max_tokens=16))
            for i in range(8)]
    pending = list(reqs)
    import time
    t0 = time.perf_counter()
    step = 0
    while pending or eng.has_work():
        # two requests land every 4 ticks: prefill and decode contend,
        # so the fingerprint covers ragged AND pure-decode ticks
        if step % 4 == 0:
            for r in pending[:2]:
                eng.add_request(r)
            del pending[:2]
        eng.step()
        step += 1
        assert step < 10_000
    dt = time.perf_counter() - t0
    stats = eng.stats()
    return make_fingerprint(stats, cfg, elapsed_s=dt)


def make_fingerprint(stats: Dict[str, Any], model_cfg,
                     elapsed_s: float = 0.0) -> Dict[str, Any]:
    """Build a fingerprint from engine stats() + the model config."""
    from ray_tpu.llm._internal.perfmodel import CostModel

    perf = stats.get("perf") or {}
    tot = perf.get("totals") or {}
    cm = CostModel(model_cfg, page_size=8)
    gen = tot.get("decode_tokens", 0.0) + tot.get("prefill_tokens", 0.0)
    return {
        "schema": SCHEMA,
        "exact": {
            # closed-form model costs (workload-independent)
            "gemm_flops_per_token": cm.gemm_flops_per_token,
            "head_flops": cm.head_flops,
            "attn_flops_per_pair": cm.attn_flops_per_pair,
            "kv_bytes_per_token": cm.kv_bytes_per_token,
            "weight_bytes": cm.weight_bytes,
            # scheduling outcome of the workload
            "ticks": stats.get("ticks", 0),
            "dispatches": stats.get("dispatches", 0),
            "dispatches_per_step": stats.get("dispatches_per_step",
                                             0.0),
            "decode_tokens": tot.get("decode_tokens", 0.0),
            "prefill_tokens": tot.get("prefill_tokens", 0.0),
            "flops_total": tot.get("flops", 0.0),
            "flops_attn_total": tot.get("flops_attn", 0.0),
            "hbm_bytes_weights": tot.get("bytes_weights", 0.0),
            "hbm_bytes_kv_read": tot.get("bytes_kv_read", 0.0),
            "hbm_bytes_kv_write": tot.get("bytes_kv_write", 0.0),
        },
        "noisy": {
            "tokens_per_s": round(gen / elapsed_s, 3)
            if elapsed_s > 0 else 0.0,
            "mfu": perf.get("mfu", 0.0),
            "mbu": perf.get("mbu", 0.0),
        },
        "envelope": perf.get("envelope", ""),
    }


def compare(baseline: Dict[str, Any], current: Dict[str, Any],
            band: Optional[tuple] = None) -> List[str]:
    """Diff a current fingerprint against the committed baseline.
    Returns a list of human-readable FAILURES (empty = pass): exact
    metrics must match to EXACT_RTOL, noisy metrics must stay inside
    the ratio band (baseline may override per-metric via "bands")."""
    failures: List[str] = []
    if baseline.get("schema") != current.get("schema"):
        failures.append(
            f"schema mismatch: baseline {baseline.get('schema')} vs "
            f"current {current.get('schema')}")
        return failures
    b_exact = baseline.get("exact", {})
    c_exact = current.get("exact", {})
    for key, bval in b_exact.items():
        if key not in c_exact:
            failures.append(f"exact metric missing from current: {key}")
            continue
        cval = c_exact[key]
        bf, cf = float(bval), float(cval)
        if not math.isclose(bf, cf, rel_tol=EXACT_RTOL, abs_tol=1e-9):
            failures.append(
                f"exact metric drifted: {key} baseline={bval} "
                f"current={cval}")
    bands = baseline.get("bands", {})
    lo, hi = band or DEFAULT_BAND
    for key, bval in baseline.get("noisy", {}).items():
        if key not in current.get("noisy", {}):
            failures.append(f"noisy metric missing from current: {key}")
            continue
        cval = float(current["noisy"][key])
        bf = float(bval)
        klo, khi = bands.get(key, (lo, hi))
        if bf > 0 and not (klo <= cval / bf <= khi):
            failures.append(
                f"noisy metric outside band: {key} baseline={bval} "
                f"current={cval} ratio={cval / bf:.4f} "
                f"band=[{klo}, {khi}]")
        elif bf <= 0 < cval:
            pass        # baseline idle, current live: fine
    return failures


def load_baseline(path: Optional[str] = None) -> Dict[str, Any]:
    with open(path or BASELINE_PATH) as f:
        return json.load(f)


__all__ = ["run_canonical_workload", "make_fingerprint", "compare",
           "load_baseline", "BASELINE_PATH", "SCHEMA", "DEFAULT_BAND"]
