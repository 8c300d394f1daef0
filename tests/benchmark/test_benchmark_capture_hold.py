"""`engine.capture_hold_ms` (PR 39): the reader on a pair of marks with
the counter, on the parent's marks without it, and on nothing; its entry
in BENCHMARK.json, by name."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.harness import ROOT

NAME = "engine.capture_hold_ms"
CELLS = ["chat-open", "dsv3-longchat", "trinity-mixed",
         "phi4flash-reason"]


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_layer_metric(ROOT, NAME)


def _captures(armed, hold_s=None):
    sc = {"profiles_armed": {"tick_anomaly": armed},
          "profiles_started": armed,
          "blackbox_dumps": {"tick_anomaly": armed}}
    if hold_s is not None:
        sc["lock_hold_s"] = hold_s
    return {"stats": {"self_captures": sc}}


def _run(start, end, events=(("/device:TPU:0", "XLA Ops", "f", 0, 1),)):
    return {"events": list(events), "marks": {"start": start, "end": end}}


def test_delta_of_the_counter_in_milliseconds(reader):
    run = _run(_captures(1, 0.048211), _captures(3, 0.100811))
    assert reader.read(run) == pytest.approx(52.6)
    assert reader.read(_run(_captures(1, 0.25), _captures(1, 0.25))) == 0.0


def test_the_parents_marks_lack_the_counter_and_read_nothing(reader):
    assert reader.read(_run(_captures(0), _captures(2))) is None
    assert reader.read(_run({"stats": {}}, {"stats": {}})) is None


def test_a_run_without_events_reads_nothing(reader):
    run = _run(_captures(1, 0.0), _captures(3, 0.05), events=())
    assert reader.read(run) is None
    assert reader.read({}) is None
    assert reader.read({"events": [1], "marks": {}}) is None


def test_entry_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "itl_p95_ms", "workloads": CELLS}
