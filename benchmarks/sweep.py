"""Find a serving cell's knee, once: the cell's own engine, warm-up and
open-loop traffic (`runners/serve.py`, the path `run.py` takes), at each
of a few rates in turn.

    python benchmarks/sweep.py --workload chat-open --rates 0.7,0.8,0.9,1.0 --seed 11

At each rate the traffic file's one cycle of requests is offered twice
over, back to back after the ramp, the gaps scaled to the rate: two
windows of cycle / rate seconds that hold the same requests after the
same gaps. A rate is sustained if the second cycle finds the engine as
the first did (`sustained`, below). The cell then runs at about four
fifths of the highest sustained rate, written as a number into its
traffic file; PERF.md keeps the table this prints. Not part of a check:
the driver never runs it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import asyncio
import json
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# how much fuller the second cycle may find the engine than the first:
# a tenth and one slot more, the run-to-run wobble of a mean of ~40
# one-second samples
GROWTH, SLACK_SLOTS = 1.10, 1.0


def sustained(first: Dict[str, Any], second: Dict[str, Any],
              waiting_at_end: int, slots: int) -> bool:
    """No request failed, none waits for a slot at the end, the slots
    never all filled, and the mean number of live slots over the second
    cycle is no more than a tenth and one slot above the first's: a
    backlog that grows shows as slots filling from cycle to cycle long
    before a request is refused."""
    return (first["failed"] == 0 and second["failed"] == 0
            and waiting_at_end == 0
            and max(first["live_max"], second["live_max"]) < slots
            and second["live_mean"]
            <= GROWTH * first["live_mean"] + SLACK_SLOTS)


def halves(records, live: List[int], cycle_s: float,
           vocab_size: int) -> List[Dict[str, Any]]:
    """The two cycles of one rate's window, each summarised alone."""
    from benchmarks.lib import loadgen
    out = []
    for k in (0, 1):
        c = loadgen.summarise(records, cycle_s, vocab_size,
                              start_s=k * cycle_s)
        # the monitor samples at whole seconds 1, 2, ... of the window
        mine = [n for sec, n in enumerate(live, start=1)
                if k * cycle_s < sec <= (k + 1) * cycle_s]
        out.append({
            "attempted": c["attempted"], "failed": c["failed"],
            "ttft_mean_ms": c["ttft_mean_ms"],
            "ttft_p50_ms": c["ttft_p50_ms"],
            "ttft_max_ms": c["ttft_max_ms"],
            "itl_p50_ms": c["itl_p50_ms"], "itl_p95_ms": c["itl_p95_ms"],
            "serve_tok_s": c["serve_tok_s"],
            "live_mean": sum(mine) / len(mine), "live_max": max(mine),
            "live_last": mine[-1]})
    return out


async def sweep(ctx, rates: List[float]) -> Dict[str, Any]:
    from benchmarks.runners import serve
    server, client, detail, correct = await serve.setup(ctx)
    eng = server.engine
    rows = []
    for rate in rates:
        tr = {**ctx.traffic, "rate_rps": rate}
        cycle_s = tr["cycle"] / rate
        got = await serve.window(server, client, ctx, tr,
                                 window_s=2 * cycle_s)
        first, second = halves(got["records"], got["marks"]["live"],
                               cycle_s, eng.model_cfg.vocab_size)
        start, end = (got["marks"][k]["stats"] for k in ("start", "end"))
        rows.append({
            "rate_rps": rate, "cycle_s": cycle_s, "first": first,
            "second": second, "waiting_at_end": end["waiting"],
            "late_max_ms": got["client"]["late_max_ms"],
            # either above 0 means the process was held, not the engine
            # loaded: read the row as spoiled
            "compiles_in_window": (
                end["jit_cache"]["compiled_programs"]
                - start["jit_cache"]["compiled_programs"]),
            "peak_occupancy": max(got["marks"]["occupancy"]),
            "sustained": sustained(first, second, end["waiting"],
                                   eng.config.max_batch_size)})
        print("SWEEP " + json.dumps(rows[-1]), flush=True)
        while eng.has_work():                # drain before the next rate
            await asyncio.sleep(0.05)
    return {"correct": correct, "detail": detail, "rows": rows}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()
    from benchmarks import run as bench_run
    from benchmarks.lib import harness, peaks
    bench, cell, config, traffic = bench_run.resolve(ROOT, args.workload)
    from ray_tpu.util.compile_cache import CompileWatch, ensure_compile_cache
    ensure_compile_cache()
    watch = CompileWatch()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" or dev.device_kind not in peaks.PEAKS:
        sys.exit(f"sweep.py: needs a TPU of the peaks table, found "
                 f"{dev.platform!r} {dev.device_kind!r}")
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = harness.Context(
        workload=args.workload, config_name=cell["config"],
        config=config, traffic=traffic, chips=cell["chips"],
        seed=args.seed, seconds=float(bench["run_seconds"]), trace=False,
        out_dir=out_dir, t_start=T_START, compile_watch=watch)
    result = asyncio.run(sweep(ctx, [float(r) for r in
                                     args.rates.split(",")]))
    with open(os.path.join(out_dir, "sweep.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)


if __name__ == "__main__":
    main()
