"""`engine.anomaly_flags_in_window` (PR 39): the reader on a pair of
marks, on a program whose detector is off, and on nothing; the line it
prints; its entry in BENCHMARK.json, by name."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.harness import ROOT

NAME = "engine.anomaly_flags_in_window"
CELLS = ["chat-open", "dsv3-longchat", "trinity-mixed",
         "phi4flash-reason"]
LAST = {"kind": "device_straggler", "z": 6.31, "wall_ms": 16.354,
        "composition": {"tick_kind": "decode", "decode_tokens": 6}}


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_layer_metric(ROOT, NAME)


def _anomaly(total, by_kind, ticks, last=None):
    return {"stats": {"anomaly": {
        "enabled": True, "ticks": ticks, "warmed": True,
        "anomalies_total": total, "by_kind": by_kind, "rate": 0.01,
        "last": last, "gc_collections": 3}}}


def _run(start, end, events=(("/device:TPU:0", "XLA Ops", "f", 0, 1),)):
    return {"events": list(events), "marks": {"start": start, "end": end}}


def test_delta_of_the_flags_with_the_kinds_on_an_earlier_line(reader,
                                                               capsys):
    run = _run(_anomaly(12, {"device_straggler": 10, "unknown": 2}, 900),
               _anomaly(184, {"device_straggler": 172, "unknown": 2,
                              "recompile": 10}, 4586, LAST))
    assert reader.read(run) == 172
    line = capsys.readouterr().out
    assert "anomaly flags in the window: 172" in line
    assert '{"device_straggler": 162, "recompile": 10}' in line
    assert "3686 ticks judged" in line and '"wall_ms": 16.354' in line
    quiet = _run(_anomaly(3, {"unknown": 3}, 10), _anomaly(3, {"unknown": 3},
                                                           500))
    assert reader.read(quiet) == 0


def test_a_detector_that_is_off_or_absent_reads_nothing(reader):
    off = {"stats": {"anomaly": {"enabled": False}}}
    assert reader.read(_run(off, off)) is None
    assert reader.read(_run({"stats": {}}, {"stats": {}})) is None
    assert reader.read(_run(off, _anomaly(1, {"unknown": 1}, 5))) is None


def test_a_run_without_events_reads_nothing(reader):
    run = _run(_anomaly(0, {}, 1), _anomaly(2, {"unknown": 2}, 9),
               events=())
    assert reader.read(run) is None
    assert reader.read({}) is None
    assert reader.read({"events": [1], "marks": {}}) is None


def test_entry_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "itl_p95_ms", "workloads": CELLS}
