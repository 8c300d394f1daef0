"""`step.sample_share` (PR 28): the reader on a capture worked out by
hand, on the recorded `chat-open` ticks, and on nothing; its entry in
BENCHMARK.json."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

NAME = "step.sample_share"
P0 = "/device:TPU:0"
# a decode tick of the parent's shape, times in ns: the layers, the sort
# and the gather under `sample`, and the scatter's sort and fusion, which
# XLA leaves without a scope
HAND = {
    "spans": [["engine", "engine.step", 0, 2000, {"tick": 1, "work": 1}]],
    "events": [
        [P0, tr.MODULES, "jit_step(8)", 100, 1000, "", 1],
        [P0, tr.OPS, "fusion.200", 100, 300, "jit(step)/mlp/dot_general", 0],
        [P0, tr.OPS, "sort", 400, 50, "jit(step)/sample/jit(argsort)/sort",
         0],
        [P0, tr.OPS, "fusion.1", 450, 300,
         "jit(step)/sample/jit(take_along_axis)/gather", 0],
        [P0, tr.OPS, "sort.2", 750, 50, "", 0],
        [P0, tr.OPS, "fusion.6", 800, 300, "", 0],
    ],
    "enqueues": {1: 50},
}


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_layer_metric(ROOT, NAME)


def _run(cap, monkeypatch):
    monkeypatch.setattr(sr, "capture", lambda run: cap)
    return {"events": cap["events"], "config": {},
            "device_kind": "TPU v5 lite"}


def test_share_is_the_scope_sample_over_busy_time(reader, monkeypatch):
    # 350 of 1,000 busy ns carry the scope; the scatter's 350 do not,
    # which is the parent's under-reading the reader's docstring states
    assert reader.read(_run(HAND, monkeypatch)) == pytest.approx(35.0)


def test_share_on_the_recorded_chat_open_ticks(reader, monkeypatch):
    with open(os.path.join(ROOT, "benchmarks", "fixtures",
                           "chat_open_ticks_spans.json")) as f:
        cap = json.load(f)
    got = reader.read(_run(cap, monkeypatch))
    assert got == pytest.approx(sr.scope_shares(cap)["sample"])
    assert 5.0 < got < 60.0


def test_nothing_to_read_is_nothing(reader, monkeypatch):
    assert reader.read({}) is None
    assert reader.read({"events": [], "marks": {}}) is None
    train = dict(HAND, events=[
        e[:5] + [e[5].replace("/sample/", "/loss_head/"), e[6]]
        for e in HAND["events"]])
    assert reader.read(_run(train, monkeypatch)) is None


def test_entry_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "model forwards",
        "moves": "itl_p95_ms", "workloads": ["chat-open", "dsv3-longchat"]}
