"""Serving cells of the GraniteHybrid family: `runners/serve.py`'s window,
warm-up, client and monitor, with this family's adapter
(`lib/program_granite_hybrid.py`) and checks
(`lib/checks_granite_hybrid.py`).

The model module is imported first thing, so that a tree without it
fails when `run.py` loads this runner: at once, before JAX starts.

    python -m benchmarks.runners.serve_granite_hybrid --workload granite-concurrent --rates 2.0,3.0 [--probe]

is this family's `benchmarks/sweep.py` (whose set-up is the dense
decoder's): each rate's cycle offered twice on one warm engine, judged
by `sweep.sustained`; `--probe` also prints the readings of the
comparisons against a reference computed in float8 and computed wrong in
each way the limits have to catch (`checks_granite_hybrid.VARIANTS`:
the state or Delta kept in bfloat16, state or conv inputs not carried
over a chunk boundary, each of the four multipliers dropped, the norm
before the gate, D or dt_bias dropped, rotary applied), and which limit
catches each. Not part of a check: the driver never runs it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

from ray_tpu.models import granite_hybrid  # noqa: F401  (fail early without it)

import asyncio
import collections
import json
import os
from typing import Any, Dict

from ..lib import checks_granite_hybrid, program_granite_hybrid
from ..lib.harness import Context, RunResult, say
from . import serve
# the lengths `serve._warm` is shown, capped so that its anchors stay
# inside `max_seq_len` (here: prompts to 1,280 tokens, buckets to 128
# pages; 2,048 + 768 tokens would round to an anchor of 4k tokens)
from .serve_nemotron_h import _warm_ctx


def run(ctx: Context) -> RunResult:
    return asyncio.run(_run(ctx))


def _build_server(ctx: Context):
    from ray_tpu.llm._internal.server import LLMServerImpl
    ekw = dict(ctx.config["engine"])
    ekw["seed"] = ctx.program_seed
    return LLMServerImpl({
        "model_id": ctx.config_name,
        "model_source": program_granite_hybrid.model_config(ctx.config),
        "engine_kwargs": ekw})


async def setup(ctx: Context):
    """Engine up, logits checks, warm-up: `serve.setup` for this
    family. Returns (server, client, detail, correct)."""
    ctx.phase("imports and the chip", ctx.t_start)
    t0 = time.monotonic()
    server = _build_server(ctx)
    eng = server.engine
    cfg = eng.model_cfg
    ctx.phase("engine up", t0)
    groups = eng.stats()["cache_groups"]
    say(f"[serve] {cfg.n_layers} layers "
        f"{dict(collections.Counter(cfg.layer_types))}, hidden {cfg.hidden}"
        f", {cfg.n_heads}q/{cfg.n_kv_heads}kv heads of {cfg.head_dim}, scan "
        f"{cfg.mamba_heads} heads x {cfg.mamba_head_dim} x {cfg.ssm_state} "
        f"in {cfg.n_groups} group(s), SwiGLU {cfg.ffn}, vocab "
        f"{cfg.vocab_size}, "
        f"{cfg.num_params() / 1e9:.3f}B parameters, "
        f"{eng.stats()['weights']['bytes'] / 1e9:.3f} GB as stored; cache "
        f"groups {[(g['name'], g['layers'], g.get('pages_total', g.get('slots_total'))) for g in groups]}"
        f"; arrays {[a.shape for a in eng.k_pages + eng.v_pages]}; "
        f"decode_impl {eng._resolve_impl()}")
    detail: Dict[str, Any] = {
        "num_pages": {g["name"]: g["pages_total"] + 1 for g in groups
                      if "pages_total" in g},
        "num_pages_stated": eng.config.num_pages}
    t0 = time.monotonic()
    detail["logits"] = checks_granite_hybrid.serve_logits(
        eng, ctx.config, ctx.program_seed, say)
    # what the checks' float32 temporaries reached beside the weights
    # and the pools (None where the backend keeps no such count)
    detail["logits"]["peak_bytes_after_checks"] = (
        eng.k_pages[0].devices().pop().memory_stats() or {}).get(
            "peak_bytes_in_use")
    say(f"  peak device memory after the checks: "
        f"{detail['logits']['peak_bytes_after_checks']}")
    # the groups once the checks have given everything back (the stats at
    # the window's end mark count the requests still live there)
    detail["cache_groups_after_checks"] = eng.stats()["cache_groups"]
    ctx.phase("logits checks", t0)
    client = serve._Client(server, ctx)
    t0 = time.monotonic()
    detail["warmup"] = await serve._warm(server, _warm_ctx(ctx, eng),
                                         client)
    ctx.phase(f"warm-up {detail['warmup']}", t0)
    return server, client, detail, detail["logits"]["ok"]


# the family's named scopes, inner ones too (`span_reduce.SCOPES` names
# the outer ones every family shares)
SCOPES = ("mamba_mixer", "ssm_conv", "ssd_scan", "attn_mixer", "mlp",
          "kv_write", "embed", "lm_head", "sample")


def _scope_shares(run: Dict[str, Any]):
    """% of chip 0's busy time under each of the family's scopes in a
    traced run's capture (a scope inside another counts in both), said
    on a line of its own beside the generic `[spans]` tables; None of an
    untraced run."""
    from ..lib import span_reduce, spans_deepseek_v3
    cap = span_reduce.capture(run)
    if cap is None:
        return None
    out = {name: span_reduce.share_of_busy(
        cap, lambda _, scope, name=name: spans_deepseek_v3.in_scope(
            scope, name)) for name in SCOPES}
    say("[spans] granite_hybrid scope_share_pct: " + json.dumps(out))
    return out


# how long the window's trace waits for a capture of the engine's own
OWN_CAPTURE_WAIT_S = 30.0


def _start_trace_behind_own_captures() -> None:
    """`serve._monitor` opens the window's trace with
    `jax.profiler.start_trace`, which RAISES while another session is
    open. In this cell one often is: a tick of 120-145 ms once or twice
    a run (20 decode rows, all of it device time; PERF.md section 7) is
    flagged by the engine's detector, which arms a capture of its own,
    and the first traced run of the cell died of that. The engine's
    captures wait for the benchmark's session
    (`engine._profile_start`); this makes the benchmark's start wait for
    the engine's, `OWN_CAPTURE_WAIT_S` at most, and then start as it
    would have. The cure is `serve._monitor`'s (a `benchmark` PR may
    edit it; PERF.md section 7): this runner wraps the call it cannot
    change."""
    import jax
    from ray_tpu.util import profiling
    start = jax.profiler.start_trace

    def patient(*args, **kw):
        give_up = time.monotonic() + OWN_CAPTURE_WAIT_S
        while profiling.session_open() and time.monotonic() < give_up:
            time.sleep(0.02)
        return start(*args, **kw)
    jax.profiler.start_trace = patient


async def _run(ctx: Context) -> RunResult:
    import jax

    server, client, detail, correct = await setup(ctx)
    if ctx.trace:
        _start_trace_behind_own_captures()
    # the checks held pages back and the warm-up's anchors ran alone:
    # what the groups' peaks say is to be of the ramp and the window
    server.engine.cache.reset_peaks()
    got = await serve.window(server, client, ctx, ctx.traffic)
    summary = got["client"]
    end = got["marks"]["end"]["stats"]
    detail["cache_groups"] = end.get("cache_groups")
    # what the engine's own tracing did, since start-up, at the window's
    # two ends: an untraced run prints no per-layer metric, and its
    # generator's lateness and longest first token ask for this
    detail["self_captures"] = {
        k: got["marks"][k]["stats"].get("self_captures")
        for k in ("start", "end")}
    detail["anomaly"] = end.get("anomaly")
    end_to_end = {"setup_s": ctx.setup_s(got["started"]),
                  "serve_tok_s": summary["serve_tok_s"]}
    for name in ("ttft_mean_ms", "ttft_p50_ms", "ttft_p95_ms",
                 "itl_p50_ms", "itl_p95_ms"):
        if summary[name] is not None:
            end_to_end[name] = summary[name]
    run = {"events": got["events"], "client": summary,
           "marks": got["marks"], "window_s": float(ctx.seconds),
           "config": ctx.config, "traffic": ctx.traffic,
           "device_kind": jax.devices()[0].device_kind,
           "chips": ctx.chips}
    detail["scope_share_pct"] = _scope_shares(run)
    return RunResult(
        correct=bool(correct and summary["failed"] == 0
                     and summary["attempted"] > 0),
        attempted=summary["attempted"], failed=summary["failed"],
        end_to_end=end_to_end, run=run, detail=detail)


# ---- the family's sweep -------------------------------------------------

async def _sweep(ctx: Context, rates, probe: bool, only=()
                 ) -> Dict[str, Any]:
    from .. import sweep
    server, client, detail, correct = await setup(ctx)
    eng = server.engine
    if probe:
        detail["probe"] = checks_granite_hybrid.precision_probe(
            eng, ctx.config, ctx.program_seed, say, only)
    rows = []
    for rate in rates:
        tr = {**ctx.traffic, "rate_rps": rate}
        cycle_s = tr["cycle"] / rate
        got = await serve.window(server, client, ctx, tr,
                                 window_s=2 * cycle_s)
        first, second = sweep.halves(got["records"], got["marks"]["live"],
                                     cycle_s, eng.model_cfg.vocab_size)
        start, end = (got["marks"][k]["stats"] for k in ("start", "end"))
        rows.append({
            "rate_rps": rate, "cycle_s": cycle_s, "first": first,
            "second": second, "waiting_at_end": end["waiting"],
            "late_max_ms": got["client"]["late_max_ms"],
            "compiles_in_window": (
                end["jit_cache"]["compiled_programs"]
                - start["jit_cache"]["compiled_programs"]),
            "peak_occupancy": max(got["marks"]["occupancy"]),
            "state_slots_peak": (end.get("cache_groups") or [{}])[-1].get(
                "slots_peak"),
            "anomalies": (end.get("anomaly") or {}).get("anomalies_total"),
            "self_captures": end.get("self_captures"),
            "sustained": sweep.sustained(first, second, end["waiting"],
                                         eng.config.max_batch_size)})
        print("SWEEP " + json.dumps(rows[-1]), flush=True)
        while eng.has_work():
            await asyncio.sleep(0.05)
    return {"correct": correct, "detail": detail, "rows": rows}


def main() -> None:
    import argparse
    import sys

    from .. import run as bench_run
    from ..lib import harness, peaks
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--only", default="",
                    help="with --probe: these readings alone")
    args = ap.parse_args()
    bench, cell, config, traffic = bench_run.resolve(harness.ROOT,
                                                     args.workload)
    from ray_tpu.util.compile_cache import CompileWatch, ensure_compile_cache
    ensure_compile_cache()
    watch = CompileWatch()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" or dev.device_kind not in peaks.PEAKS:
        sys.exit(f"needs a TPU of the peaks table, found "
                 f"{dev.platform!r} {dev.device_kind!r}")
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "benchmark",
                           args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(
        workload=args.workload, config_name=cell["config"], config=config,
        traffic=traffic, chips=cell["chips"], seed=args.seed,
        seconds=float(bench["run_seconds"]), trace=False, out_dir=out_dir,
        t_start=T_START, compile_watch=watch)
    rates = [float(r) for r in args.rates.split(",") if r]
    result = asyncio.run(_sweep(
        ctx, rates, args.probe, tuple(v for v in args.only.split(",") if v)))
    with open(os.path.join(out_dir, "sweep.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)


if __name__ == "__main__":
    main()
