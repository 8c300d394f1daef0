"""One tiny end-to-end rehearsal per runner: `debug`-size model,
interpret-mode kernels, a second or two of traffic, through the runner's
Python entry (run.py itself never reports from a CPU). Checks that the
last line has exactly the contract's keys."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.harness import ROOT

import rehearsal

CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "memory_peak_bytes": 0}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _lines(bench, cell, result):
    plain = bench_run.result_line(ROOT, bench, cell, result, CPU, False)
    traced = bench_run.result_line(ROOT, bench, cell, result, CPU, True)
    json.dumps(plain), json.dumps(traced)
    assert set(plain) == LINE_KEYS
    assert set(traced) == LINE_KEYS | {"breakdown"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(plain["device"]) == set(CPU)
    assert set(traced["device"]) == set(CPU) | {"busy_s", "window_s"}
    for line in (plain, traced):
        for m in line["metrics"].values():
            assert set(m) == {"value", "unit"}
            assert isinstance(m["value"], float)
    return plain, traced


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve
    # the smallest engine whose warm-up still walks a matrix: two token
    # buckets by two context buckets
    config = {**rehearsal.SERVE_CONFIG, "engine": {
        "max_batch_size": 3, "page_size": 16, "num_pages": 16,
        "max_prefill_tokens": 8, "decode_impl": "pallas_interpret"}}
    traffic = {**rehearsal.CHAT, "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve.run(rehearsal.context(
        config, traffic, tmp_path_factory.mktemp("serve"), seconds=1.5))


def test_serve_rehearsal_is_correct_and_warm(served):
    assert served.correct and served.failed == 0
    assert served.attempted >= 6          # 6/s for 1.5 s
    warm = served.detail["warmup"]
    assert warm["programs_built"] == (len(warm["t_buckets"])
                                      * len(warm["ctx_buckets"]))
    assert served.detail["logits"]["ok"]
    for name in ("setup_s", "serve_tok_s", "ttft_mean_ms", "itl_p95_ms"):
        assert served.end_to_end[name] > 0
    marks = served.run["marks"]
    built = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    assert built(marks["end"]) == built(marks["start"])


def test_serve_last_line_has_exactly_the_contracts_keys(bench, served):
    plain, traced = _lines(bench, "chat-open", served)
    assert set(plain["metrics"]) == {"itl_p95_ms", "serve_tok_s",
                                     "setup_s"}
    # counters read on a CPU; trace metrics have nothing to read there
    assert {"loadgen.late_max_ms", "server.queue_wait_ms",
            "server.ttft_mean_ms", "engine.compiles_in_window", "engine.host_ms_per_tick",
            "engine.rows_per_tick", "engine.live_slots",
            "kv.peak_occupancy"} == set(traced["metrics"])
    assert traced["metrics"]["engine.compiles_in_window"]["value"] == 0.0
    assert traced["device"]["busy_s"] == 0.0


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from benchmarks.runners import train
    tc = rehearsal.TRAIN_CONFIG["train"]
    config = {**rehearsal.TRAIN_CONFIG, "train": {**tc, "model": {
        **tc["model"], "attention_impl": "pallas_interpret"}}}
    return train.run(rehearsal.context(
        config, rehearsal.JOB, tmp_path_factory.mktemp("train"),
        seconds=2.0))


def test_train_rehearsal_is_correct(trained):
    assert trained.correct and trained.attempted >= 20
    assert trained.detail["checks"] == {
        "loss_vs_reference": True, "logits_vs_reference": True,
        "finite": True,
        "first_loss_near_ln_vocab": True, "loss_falls": True}
    assert trained.detail["loss_reference_gap"] < 0.005
    assert trained.detail["logits"]["rel_rms"] < 0.02
    assert len(trained.run["losses"]) == trained.attempted + 2


def test_train_last_line_has_exactly_the_contracts_keys(bench, trained):
    plain, traced = _lines(bench, "train-packed", trained)
    assert set(plain["metrics"]) == {"train_tok_s", "setup_s"}
    assert traced["metrics"] == {}
