"""BENCHMARK.json keeps to the contract; everything it names is a file
found by name; a later PR adds a cell or a metric with files only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import peaks
from benchmarks.lib.harness import ROOT, RunResult

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert bench["command"][-1] == "benchmarks/run.py"
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells fits 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    size = os.path.getsize(os.path.join(ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), group,
                          entry["name"]))
    assert len(names) == len(set(names))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200


def test_end_to_end_metrics_have_bounds_and_setup(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if bench_run.applies(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(bench_run.applies(m, w["name"])
                   for m in bench["per_layer"]), w["name"]


def test_every_moves_names_a_metric_all_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert bench_run.applies(target, cell), (m["name"], cell)


def test_cells_files_exist_and_chips(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        _, cell, config, traffic = bench_run.resolve(ROOT, w["name"])
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert configs[w["config"]]["file"] == (
            f"benchmarks/configs/{w['config']}.json")
        bench_run.find_runner(ROOT, traffic["runner"])
        assert config["source"] == configs[w["config"]]["source"]
        assert config["reduced"] == configs[w["config"]]["reduced"]
    assert used == set(configs)          # every configuration has a cell
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_no_reduced_key_is_a_width(bench):
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_experts_per_tok")
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key not in widths
            assert not key.endswith(("_dim", "_rank"))


def test_every_layer_metric_has_its_reader_file(bench):
    for m in bench["per_layer"]:
        mod = bench_run.load_layer_metric(ROOT, m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        # a reader that finds nothing to read returns nothing
        assert mod.read({}) is None


def test_files_under_paths_are_named_from_a_names_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_dropped_in_cell_and_metric_are_found_by_name(tmp_path):
    """A later PR adds two JSON files, one reader file and entries; it
    edits no file that is there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {}
    for base, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()
    base_cfg = bench_run.load_named(root, "configs", "internlm2_5-1_8b")
    with open(os.path.join(root, "benchmarks/configs/other-model.json"),
              "w") as f:
        json.dump({**base_cfg, "num_hidden_layers": 12}, f)
    with open(os.path.join(root, "benchmarks/traffic/chat-burst.json"),
              "w") as f:
        json.dump({**bench_run.load_named(root, "traffic", "chat-steady"),
                   "rate_rps": 9.5}, f)
    with open(os.path.join(
            root, "benchmarks/layer_metrics/client.requests.py"), "w") as f:
        f.write('NAME = "client.requests"\nUNIT = "count"\n'
                'LAYER = "load generator"\nMOVES = "serve_tok_s"\n\n\n'
                'def read(run):\n'
                '    return (run.get("client") or {}).get("attempted")\n')
    bench["configs"].append({"name": "other-model", "source": "x",
                             "file": "benchmarks/configs/other-model.json",
                             "reduced": ["num_hidden_layers"], "why": "y"})
    bench["workloads"].append({"name": "other-burst",
                               "config": "other-model",
                               "traffic": "chat-burst", "chips": 1,
                               "why": "z"})
    bench["per_layer"].append({
        "name": "client.requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "serve_tok_s", "workloads": ["other-burst"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tok_s":
            m["workloads"].append("other-burst")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    _, cell, config, traffic = bench_run.resolve(root, "other-burst")
    assert config["num_hidden_layers"] == 12
    assert traffic["rate_rps"] == 9.5
    assert bench_run.find_runner(root, traffic["runner"]).__name__ == (
        "benchmarks.runners.serve")
    result = RunResult(correct=True, attempted=7, failed=0,
                       end_to_end={"setup_s": 1.0, "serve_tok_s": 2.0},
                       run={"events": [], "client": {"attempted": 7}})
    line = bench_run.result_line(root, bench, "other-burst", result,
                                 {"platform": "cpu"}, trace=True)
    assert line["metrics"]["client.requests"] == {"value": 7.0,
                                                  "unit": "count"}
    # the old cell does not get the new metric
    old = bench_run.result_line(root, bench, "chat-open", result,
                                {"platform": "cpu"}, trace=True)
    assert "client.requests" not in old["metrics"]
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


def test_unknown_workload_runner_or_metric_is_an_error(tmp_path):
    with pytest.raises(SystemExit):
        bench_run.resolve(ROOT, "no-such-cell")
    with pytest.raises(SystemExit):
        bench_run.find_runner(ROOT, "no-such-runner")
    with pytest.raises(SystemExit):
        bench_run.load_layer_metric(ROOT, "no.such.metric")


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_json():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "chat-open", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_peaks_table_names_the_v5e_and_nothing_by_default():
    row = peaks.PEAKS["TPU v5 lite"]
    assert row["bf16_flops"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9
    assert list(peaks.PEAKS) == ["TPU v5 lite"]      # no cpu envelope


def test_flops_per_token_by_hand():
    cfg = bench_run.load_named(ROOT, "configs", "internlm2_5-1_8b-train-d8")
    per_layer = 2048 * (2048 + 2 * 1024) + 2048 * 2048 + 3 * 2048 * 8192
    matmul = 8 * per_layer + 2048 * 92544
    assert peaks.matmul_params(cfg) == matmul == 692846592
    assert peaks.train_flops_per_token(cfg, 2048) == (
        6.0 * matmul + 6.0 * 2048 * 2048 * 8)
    # the vocabulary head's share of the matmul FLOPs at depth 8 and 24
    head = 2048 * 92544
    assert round(head / matmul, 2) == 0.27
    assert round(head / (24 * per_layer + head), 2) == 0.11
