"""One process, one cell, one run.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix, runner
and per-layer readers by name (files under benchmarks/, no table in
code), places the compile cache, runs the cell on the TPU this process
holds, and prints the contract's one JSON object as the last line of
stdout. With no TPU, fewer chips than the cell asks for, or a
`device_kind` the peaks table does not name, it exits non-zero and prints
no result. `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics from a run with the profiler on for part of the
window.
"""

from __future__ import annotations

import time

T_START = time.monotonic()       # process start, near enough: set-up's zero

import argparse
import importlib
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
NO_CHIP_RC = 4


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_named(root: str, kind: str, name: str) -> dict:
    """<root>/benchmarks/<kind>/<name>.json, found by name."""
    with open(os.path.join(root, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def resolve(root: str, workload: str):
    """(BENCHMARK.json, the cell's entry, its configuration file, its
    traffic file), each found by the name the entry gives."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} among "
                         f"{sorted(cells)}")
    cell = cells[workload]
    return (bench, cell, load_named(root, "configs", cell["config"]),
            load_named(root, "traffic", cell["traffic"]))


def find_runner(root: str, name: str):
    """benchmarks/runners/<name>.py, by directory listing."""
    have = sorted(f[:-3] for f in os.listdir(
        os.path.join(root, "benchmarks", "runners")) if f.endswith(".py"))
    if name not in have:
        raise SystemExit(f"run.py: no runner {name!r} among {have}")
    return importlib.import_module(f"benchmarks.runners.{name}")


def load_layer_metric(root: str, name: str):
    """benchmarks/layer_metrics/<name>.py: NAME, UNIT, LAYER, MOVES and
    read(run) -> number or None."""
    path = os.path.join(root, "benchmarks", "layer_metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: no reader for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if mod.NAME != name:
        raise SystemExit(f"run.py: {path} declares NAME {mod.NAME!r}")
    return mod


def result_line(root: str, bench: dict, cell: str, result, device: dict,
                trace: bool) -> dict:
    """The contract's last line: `--trace 0` carries the cell's
    end-to-end metrics, `--trace 1` every per-layer metric listed for the
    cell whose reader found something to read, the device's busy and
    window seconds and the breakdown."""
    from benchmarks.lib import trace_reduce
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed}
    device = dict(device)
    if trace:
        events = result.run["events"]
        device["busy_s"], device["window_s"] = (
            trace_reduce.busy_and_window_s(events))
        line["metrics"] = {}
        for m in bench["per_layer"]:
            if not applies(m, cell):
                continue
            value = load_layer_metric(root, m["name"]).read(result.run)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        line["breakdown"] = trace_reduce.breakdown(events)
    else:
        line["metrics"] = {
            m["name"]: {"value": float(result.end_to_end[m["name"]]),
                        "unit": m["unit"]}
            for m in bench["end_to_end"]
            if applies(m, cell) and m["name"] in result.end_to_end}
    line["device"] = device
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.lib import harness, peaks
    bench, cell, config, traffic = resolve(ROOT, args.workload)
    runner = find_runner(ROOT, traffic["runner"])

    from ray_tpu.util.compile_cache import CompileWatch, ensure_compile_cache
    cache_dir = ensure_compile_cache()       # first: before any compile
    watch = CompileWatch()
    import jax
    t_imports = time.monotonic()
    devs = jax.devices()             # the runtime attaches to the chip
    dev = devs[0]
    t_chip = time.monotonic()
    if (dev.platform != "tpu" or dev.device_kind not in peaks.PEAKS
            or len(devs) < cell["chips"]):
        sys.stderr.write(
            f"run.py: {args.workload} needs {cell['chips']} TPU chip(s) "
            f"of a kind in the peaks table {sorted(peaks.PEAKS)}; jax "
            f"found {len(devs)} x platform {dev.platform!r}, device_kind "
            f"{dev.device_kind!r}\n")
        sys.exit(NO_CHIP_RC)

    harness.say(f"[setup] python and imports {t_imports - T_START:.1f}s, "
                f"jax and the chip {t_chip - t_imports:.1f}s (chip_attach_s,"
                f" not in setup_s)")
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = harness.Context(
        workload=args.workload, config_name=cell["config"], config=config,
        traffic=traffic, chips=cell["chips"], seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), out_dir=out_dir,
        t_start=T_START, chip_attach_s=t_chip - t_imports,
        compile_watch=watch)
    harness.say(f"[run] {args.workload}: config {cell['config']}, traffic "
                f"{cell['traffic']}, {cell['chips']} chip(s) of "
                f"{len(devs)} x {dev.device_kind}, seed {args.seed}, "
                f"{args.seconds:g}s, trace {args.trace}, cache {cache_dir}")
    result = runner.run(ctx)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs[:cell["chips"]])}
    line = result_line(ROOT, bench, args.workload, result, device,
                       bool(args.trace))
    compiled = watch.snapshot()
    harness.say(f"[run] compile: {compiled}; detail: "
                f"{json.dumps(result.detail, default=str)}")
    with open(os.path.join(
            out_dir, f"seed{args.seed}-trace{args.trace}.json"), "w") as f:
        json.dump({**line, "detail": result.detail, "compile": compiled,
                   "chip_attach_s": ctx.chip_attach_s,
                   "end_to_end": result.end_to_end,
                   "client": result.run.get("client"),
                   "losses": result.run.get("losses")}, f, indent=1,
                  default=str)
    if args.trace:
        # a few programs' worth of chip 0's events, for a look by hand
        events = sorted((e for e in result.run["events"]
                         if e[0].endswith(":0")), key=lambda e: e[3])
        with open(os.path.join(out_dir, "trace_events.json"), "w") as f:
            json.dump(events[len(events) // 2:][:6000], f)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
