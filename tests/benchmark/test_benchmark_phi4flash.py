"""The cell `phi4flash-reason` and what it brings: the configuration
against the catalog row's keys, the adapter, the traffic file through the
load generator, each new reader on a small capture worked out by hand and
on runs that have nothing for it (the recorded fixtures, a dense, a
latent and a Trinity run, `{}`), the cost functions by hand, a rehearsal
of the new runner at a tiny size, and its `BENCHMARK.json` entries BY
NAME (a later cell may come behind this one)."""

import json
import os
import statistics

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs_phi4flash as costs
from benchmarks.lib import loadgen, program_phi4flash
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "phi4flash-reason"
# `config` of the row `Phi-4-mini-flash-reasoning` in the catalog beside
# the model-configs guide (source_url below), copied here: the catalog is
# not part of the repository
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
NEW = ("kernel.ssm_scan_share", "kernel.ssm_scan_roofline_share",
       "kernel.shared_kv_attn_roofline_share", "kv.shared_saved_share",
       "step.cross_tokens_share")


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == []     # nothing cut
    assert entry["file"] == (
        "benchmarks/configs/phi-4-mini-flash-reasoning.json")
    for key, value in CATALOG.items():
        assert config[key] == value, key
    assumed = config["assumed"]
    assert {k: assumed[k]["value"] for k in program_phi4flash.ASSUMED_KEYS} \
        == {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_dt_rank": 160}
    assert all(assumed[k]["from"] for k in program_phi4flash.ASSUMED_KEYS)
    assert set(assumed) >= {"layer_schedule", "memory",
                            "differential_attention", "attention_biases",
                            "head_pairing", "weights"}
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    assert config["engine"] == {
        "max_batch_size": 64, "page_size": 16, "num_pages": 24576,
        "num_pages_by_group": {"full": 24576, "window": 4608},
        "max_seq_len": 8192, "max_num_batched_tokens": 512}
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert len(entry["why"]) <= 200


def test_adapter_builds_the_published_widths(resolved):
    _, _, config, _ = resolved
    cfg = program_phi4flash.model_config(config)
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2560, 40, 20, 64)
    assert (cfg.ffn, cfg.n_layers, cfg.vocab_size, cfg.sliding_window) == (
        10240, 32, 200064, 512)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (
        5120, 16, 4, 160)
    # ISSUE 36's arithmetic: 3,852M parameters, 7.70 GB in bfloat16
    assert cfg.num_params() == 3_852_562_944
    assert program_phi4flash.published_keys(cfg)["sliding_window"] == 512
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        program_phi4flash.model_config(
            {**config, "tie_word_embeddings": False})
    with pytest.raises(ValueError, match="model_type"):
        program_phi4flash.model_config({**config, "model_type": "phi3"})
    # the page groups and the state: 2.01 + 3.02 + 0.21 GB
    pages = config["engine"]["num_pages_by_group"]
    assert pages["full"] * 16 * 5120 == 2_013_265_920
    assert pages["window"] * 16 * 8 * 5120 == 3_019_898_880
    assert 64 * 9 * (5120 * 16 * 4 + 5120 * 3 * 2) == 206_438_400
    held = (cfg.num_params() * 2 + 2_013_265_920 + 3_019_898_880
            + 206_438_400)
    assert 12e9 < held < 13.5e9


def test_traffic_file_through_the_load_generator(resolved):
    bench, cell, config, tr_file = resolved
    assert tr_file["runner"] == "serve_phi4flash"
    assert tr_file["loop"] == "open"
    assert tr_file["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 1.1, "min": 48,
        "max": 4096}
    assert tr_file["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 128,
        "max": 768}
    assert tr_file["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert tr_file["arrival"] == {"dist": "exponential"}
    assert (tr_file["ramp_s"], tr_file["grace_s"], tr_file["trace_s"]) == (
        30, 45, 4)
    # a window holds exactly one cycle
    assert tr_file["cycle"] == pytest.approx(
        tr_file["rate_rps"] * bench["run_seconds"])
    cycle = loadgen.length_cycle(tr_file)
    assert len(cycle) == tr_file["cycle"]
    prompts = [p for p, _ in cycle]
    outputs = [o for _, o in cycle]
    # the grid's first quantile sits over the floor; its last reaches
    # the cap only in a cycle of a hundred and more
    assert 48 <= min(prompts) < 100 and 2500 < max(prompts) <= 4096
    assert 380 < statistics.mean(prompts) < 520          # about 450
    assert 490 < statistics.mean(outputs) < 550          # about 520
    assert min(outputs) >= 128 and max(outputs) == 768   # the budget
    # most decode rows sit past the window: a request's context passes
    # 512 somewhere in all but the shortest
    assert sum(p + o > 512 for p, o in cycle) / len(cycle) > 0.6
    gaps = loadgen.arrival_gaps(tr_file)
    assert sum(gaps) == pytest.approx(bench["run_seconds"])
    # PR 31's lesson: the busiest 10 s of a cycle hold at most 1.5 x the
    # mean arrivals
    n, rate = len(gaps), tr_file["rate_rps"]
    at = [sum(gaps[:i + 1]) for i in range(n)]
    at += [a + sum(gaps) for a in at]
    busiest = max(sum(1 for b in at if a <= b < a + 10.0) for a in at[:n])
    assert busiest <= 1.5 * 10.0 * rate + 1
    # every seed offers the cycle's requests, from another phase
    a = loadgen.open_schedule(tr_file, 11, 50.0)
    b = loadgen.open_schedule(tr_file, 2 ** 31 + 7, 50.0)
    in_window = lambda plan: sorted(
        (p.prompt_tokens, p.output_tokens) for p in plan if p.due_s >= 0)
    assert in_window(a) == in_window(b) == sorted(cycle)
    # every context fits the engine's longest sequence
    assert max(p + o for p, o in cycle) <= 4864 < (
        config["engine"]["max_seq_len"])
    # a request due at the window's last instant finishes in the grace
    assert 768 * 0.058 < tr_file["grace_s"]


# ---- the readers -------------------------------------------------------

P0 = "/device:TPU:0"
MODEL = {"model_type": "phi4flash", "hidden_size": 2560,
         "num_attention_heads": 40, "num_key_value_heads": 20,
         "num_hidden_layers": 32,
         "assumed": {"mamba_expand": {"value": 2},
                     "mamba_d_state": {"value": 16}},
         "engine": {"page_size": 16}}
SCAN = "/jit(_scan_call)/ssm_ragged_scan/pallas_call"
ATTN = "/jit(_ragged_call)/{}/pallas_call"


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (40 decode rows at 1,000 tokens each and a 472-token
# chunk from 0) and one decode tick (41 rows at 42,000 tokens of context
# between them), ns.
RAGGED = dict(
    kind="ragged", T=512, ctx=64, rows=41, decode_rows=40,
    prefill_tokens=472, kv_tokens=40 * 1001 + 472,
    attn_pairs=40 * 1001 + 472 * 473 // 2, decode_pairs=40 * 1001,
    ssm_tokens=512, ssm_rows=41, cross_tokens=41,
    win_kv_tokens=40 * 512 + 472, win_attn_pairs=40 * 512 + 472 * 473 // 2,
    win_decode_pairs=40 * 512, built=0)
DECODE = dict(
    kind="decode", T=64, ctx=64, rows=41, kv_tokens=42000,
    ssm_tokens=41, ssm_rows=41, cross_tokens=41, win_kv_tokens=41 * 512,
    win_attn_pairs=41 * 512, win_decode_pairs=41 * 512, built=0)
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, **RAGGED),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, **DECODE),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "ssm_ragged_scan.3[custom-call]", 1300, 400,
         "jit(run)/attn/mamba/scan" + SCAN, 0],
        [P0, tr.OPS, "ragged_paged_attention.4[custom-call]", 1700, 300,
         "jit(run)/attn/full" + ATTN.format("ragged_paged_attention"), 0],
        [P0, tr.OPS, "fusion.5", 2000, 800, "jit(run)/mlp/dot_general", 0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "ssm_ragged_scan.3[custom-call]", 3300, 100,
         "jit(step)/attn/mamba/scan" + SCAN, 0],
        [P0, tr.OPS, "ragged_paged_attention.4[custom-call]", 3400, 500,
         "jit(step)/attn/cross" + ATTN.format("ragged_paged_attention"),
         0],
        [P0, tr.OPS, "fusion.9", 3900, 400, "jit(step)/mlp/dot", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}
ROW = {"bytes_per_token_layer": 5120}
GROUPS = [
    {"name": "full", "layers": [17], "readers": list(range(19, 32, 2)),
     "window": None, "row": ROW, "pages_at_peak": 4000},
    {"name": "window", "layers": list(range(1, 16, 2)), "window": 512,
     "row": ROW, "pages_at_peak": 3000},
    {"name": "state", "kind": "state", "layers": list(range(0, 17, 2)),
     "window": None, "bytes_per_slot": 3225600, "slots_at_peak": 50},
]


def _reader(name):
    return bench_run.load_layer_metric(ROOT, name)


@pytest.fixture
def run_with_capture(monkeypatch):
    monkeypatch.setattr(sr, "capture", lambda run: HAND)
    return {"events": HAND["events"], "config": MODEL,
            "device_kind": "TPU v5 lite",
            "marks": {"end": {"stats": {"cache_groups": GROUPS}}}}


def test_new_readers_on_a_capture_worked_out_by_hand(run_with_capture):
    run = run_with_capture
    busy = 400 + 300 + 800 + 100 + 500 + 400
    assert _reader("kernel.ssm_scan_share").read(run) == pytest.approx(
        100 * 500 / busy)
    # the scan: a token's x, delta, y over 5,120 channels and its B and
    # C, 51,328 B; a row's state in and out, 655,360 B; nine layers
    token, state = 5120 * (2 + 4 + 4) + 2 * 16 * 4, 2 * 16 * 5120 * 4
    ragged_b = 9 * (512 * token + 41 * state)
    decode_b = 9 * (41 * token + 41 * state)
    assert _reader("kernel.ssm_scan_roofline_share").read(
        run) == pytest.approx(
            100 * (ragged_b + decode_b) / 819e9 / 500e-9)
    # the shared cache: the writing layer on 512 tokens and 7 cross
    # layers on 41 rows; the decode tick is bound by bytes, the ragged
    # tick by bytes too (a chunk from 0 keeps few pairs)
    kv, pair = 5120, 10240
    qo = lambda tokens: tokens * 2 * 2560 * 2
    r_bytes = 8 * RAGGED["kv_tokens"] * kv + qo(512) + 7 * qo(41)
    r_flops = pair * (RAGGED["attn_pairs"] + 7 * RAGGED["kv_tokens"])
    d_bytes = 8 * 42000 * kv + 8 * qo(41)
    d_flops = pair * 8 * 42000
    assert r_bytes / 819e9 > r_flops / 197e12
    assert d_bytes / 819e9 > d_flops / 197e12
    assert _reader("kernel.shared_kv_attn_roofline_share").read(
        run) == pytest.approx(100 * (r_bytes + d_bytes) / 819e9 / 800e-9)
    # 4,000 pages of ONE layer, 3,000 of 8 and 50 slots' state, where
    # 4,000 pages in each of 16 layers
    held = (4000 * 16 * 5120 + 3000 * 16 * 8 * 5120 + 50 * 3225600)
    assert _reader("kv.shared_saved_share").read(run) == pytest.approx(
        100 * (1 - held / (4000 * 16 * 16 * 5120)))
    # 41 + 41 rows of 512 + 41 tokens
    assert _reader("step.cross_tokens_share").read(
        run) == pytest.approx(100 * 82 / 553)
    # the generic readers take this family's capture as it is
    assert _reader("kernel.ragged_attn_share").read(
        run) == pytest.approx(100 * 800 / busy)


def test_generic_tables_take_this_configuration(resolved):
    """A traced run's first capture reader writes `span_reduce.tables`,
    which prices every tick that ran a ragged attention kernel by the
    dense family's `kernel_costs` from the configuration FILE: it has to
    carry what those read (`head_dim`; the first traced run on the chip
    died of a KeyError there, PERF.md section 6), and the tables'
    price is the dense pool's, not this family's (no metric reads it)."""
    _, _, config, _ = resolved
    assert config["head_dim"] == (
        config["hidden_size"] // config["num_attention_heads"])
    out = sr.tables(HAND, {"events": HAND["events"], "config": config})
    ragged = out["kernel_traffic"]["ragged"]
    assert ragged["programs"] == 1 and ragged["kernel_ms"] == 300 / 1e6
    assert ragged["min_bytes"] == 32 * (
        2 * RAGGED["kv_tokens"] * 20 * 128 * 2 + 2 * 512 * 40 * 64 * 2)
    assert out["kernel_traffic"]["decode"] is None
    assert out["programs_per_tick"] and out["kernel_share_pct"]
    json.dumps(out)


def _fixture(name):
    with open(os.path.join(ROOT, "benchmarks", "fixtures", name)) as f:
        cap = json.load(f)
    if "enqueues" in cap:
        cap["enqueues"] = {int(k): v for k, v in cap["enqueues"].items()}
    return cap


@pytest.mark.parametrize("fixture", [
    "chat_open_ticks_spans.json", "chat_open_two_ticks.json",
    "train_packed_two_steps_spans.json"])
def test_new_readers_find_nothing_in_the_recorded_fixtures(monkeypatch,
                                                           fixture):
    """Laid over the parent, whose programs these are: nothing, and no
    error, whatever the configuration says."""
    cap = _fixture(fixture)
    monkeypatch.setattr(sr, "capture", lambda run: cap)
    for config in ({}, MODEL, {"model_type": "internlm2"}):
        run = {"events": cap.get("events", []), "config": config,
               "device_kind": "TPU v5 lite",
               "marks": {"end": {"stats": {"free_pages": 3}}}}
        for name in NEW:
            assert _reader(name).read(run) is None, (name, fixture)


def test_new_readers_find_nothing_in_other_families_runs(monkeypatch):
    """A dense, a latent and a Trinity run (their stats, a capture with
    none of this family's kernels or counts), no run at all and junk:
    None, never an exception."""
    other = json.loads(json.dumps(HAND))
    other["events"] = [e for e in other["events"] if "ssm_" not in e[2]]
    for s in other["spans"]:
        for key in ("ssm_tokens", "ssm_rows", "cross_tokens"):
            s[4].pop(key, None)
    monkeypatch.setattr(sr, "capture", lambda run: other)
    row = {"bytes_per_token_layer": 4096}
    runs = {
        "dense": {"config": {"model_type": "internlm2"}, "marks": {"end": {
            "stats": {"cache_groups": [
                {"name": "all", "layers": list(range(24)), "window": None,
                 "row": row, "pages_at_peak": 900}]}}}},
        "latent": {"config": {"model_type": "deepseek_v3"}, "marks": {
            "end": {"stats": {"free_pages": 3}}}},
        "trinity": {"config": {"model_type": "afmoe"}, "marks": {"end": {
            "stats": {"cache_groups": [
                {"name": "full", "layers": [3, 7], "window": None,
                 "row": row, "pages_at_peak": 4000},
                {"name": "window", "layers": [0, 1, 2], "window": 4096,
                 "row": row, "pages_at_peak": 2500}]}}}},
        # this family's configuration over a program without it
        "laid over the parent": {"config": MODEL, "marks": {"end": {
            "stats": {"free_pages": 3}}}},
    }
    for label, run in runs.items():
        run = {"events": other["events"], "device_kind": "TPU v5 lite",
               **run}
        for name in NEW:
            assert _reader(name).read(run) is None, (name, label)
    for junk in ({}, {"config": None}, {"marks": 3, "config": MODEL},
                 None, []):
        for name in NEW:
            assert _reader(name).read(junk) is None, (name, junk)


def test_benchmark_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        mod = _reader(name)
        entry = by_name[name]
        assert entry["workloads"] == [CELL], name
        assert (entry["unit"], entry["layer"], entry["moves"]) == (
            mod.UNIT, mod.LAYER, mod.MOVES), name
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    assert by_name["kv.shared_saved_share"]["source"] == "program_counter"
    assert by_name["step.cross_tokens_share"]["source"] == "program_span"
    # the generic serving readers list the cell; those that price
    # another model's cache, experts or head do not
    listed = {n for n, m in by_name.items() if CELL in m["workloads"]}
    assert listed - set(NEW) == {
        "loadgen.late_max_ms", "server.queue_wait_ms",
        "server.ttft_mean_ms", "server.idle_between_ticks_ms",
        "engine.compiles_in_window", "engine.host_ms_per_tick",
        "engine.rows_per_tick", "engine.live_slots",
        "engine.tick_host_ms", "engine.longest_stall_ms",
        "engine.prefill_recompute_ratio",
        "engine.self_captures_in_window", "engine.programs_per_tick",
        "engine.idle_in_tick_ms", "kv.peak_occupancy", "step.decode_ms",
        "step.ragged_ms", "step.ragged_us_per_token",
        "device.idle_share.serve", "device.idle_attributed_share.serve",
        "kernel.ragged_attn_share", "kernel.swa_attn_share"}
    for name in ("kv.window_saved_share", "step.sample_share",
                 "kernel.ragged_attn_hbm_share",
                 "kernel.paged_decode_hbm_share", "moe.experts_share",
                 "moe.experts_hbm_share", "kernel.mla_attn_share",
                 "kernel.swa_attn_roofline_share",
                 "kernel.gqa_attn_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert CELL not in e2e["train_tok_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning", "reason-steady", 1)
    # no cell before this one lost a metric or a place
    names = [w["name"] for w in bench["workloads"]]
    assert names[:4] == ["chat-open", "train-packed", "dsv3-longchat",
                         "trinity-mixed"]


def test_cost_functions_by_hand():
    assert costs.schedule(MODEL) == {"mamba": 9, "swa": 8, "full": 1,
                                     "gmu": 7, "cross": 7}
    assert costs.scan_sizes(MODEL) == (5120, 16)
    assert costs.kv_row_bytes(MODEL) == 5120
    assert costs.pair_flops(MODEL) == 10240
    span = {"kind": "ragged", "rows": 3, "decode_rows": 2,
            "prefill_tokens": 100, "kv_tokens": 601 + 1201 + 400,
            "attn_pairs": 601 + 1201 + 100 * 300 + 5050,
            "ssm_tokens": 102, "ssm_rows": 3, "cross_tokens": 3}
    assert costs.scan_min_bytes(MODEL, span) == 9 * (
        102 * 51328 + 3 * 655360)
    qo = lambda tokens: tokens * 2 * 2560 * 2
    assert costs.shared_attention_min_bytes(MODEL, span) == (
        8 * 2202 * 5120 + qo(102) + 7 * qo(3))
    assert costs.shared_attention_min_flops(MODEL, span) == 10240 * (
        36852 + 7 * 2202)
    decode = {"kind": "decode", "rows": 5, "kv_tokens": 9000,
              "ssm_tokens": 5, "ssm_rows": 5, "cross_tokens": 5}
    assert costs.shared_attention_min_bytes(MODEL, decode) == (
        8 * 9000 * 5120 + 8 * qo(5))
    assert costs.shared_attention_min_flops(MODEL, decode) == (
        10240 * 8 * 9000)
    bare = {k: v for k, v in span.items()
            if k not in ("ssm_tokens", "ssm_rows", "cross_tokens")}
    assert costs.scan_min_bytes(MODEL, bare) is None
    assert costs.shared_attention_min_bytes(MODEL, bare) is None
    assert costs.shared_attention_min_flops(MODEL, bare) is None


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "embd_pdrop", "hidden_act", "layer_norm_eps", "mb_per_layer",
        "model_type", "resid_pdrop", "tie_word_embeddings", "mlp_bias",
        "lm_head_bias")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "sliding_window": 8,
    "max_position_embeddings": 256,
    "assumed": {"mamba_d_state": {"value": 16},
                "mamba_d_conv": {"value": 4},
                "mamba_expand": {"value": 2},
                "mamba_dt_rank": {"value": 4}},
    # page 16: `serve._warm`'s anchors want room in a context bucket. The
    # gather path: tests/test_phi4flash.py holds the kernel path to the
    # reference
    "engine": {"max_batch_size": 10, "page_size": 16, "num_pages": 64,
               "num_pages_by_group": {"full": 64, "window": 40},
               "max_prefill_tokens": 16, "max_num_batched_tokens": 16,
               "max_seq_len": 128, "decode_impl": "gather"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_phi4flash
    traffic = {**rehearsal.CHAT, "runner": "serve_phi4flash", "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_phi4flash.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("phi4flash"), seconds=1.5))


def test_runner_rehearsal_serves_checks_and_warms(served):
    assert served.failed == 0 and served.attempted >= 6
    logits = served.detail["logits"]
    for name in ("kernel_vs_gather.mixed", "kernel_vs_gather.decode",
                 "gather_vs_reference.mixed",
                 "gather_vs_reference.decode"):
        # 8 decode rows, a chunk and a prompt; then all 10 slots
        assert logits[name]["finite"] and len(logits[name]["rows"]) == 10
        assert logits[name]["median_row"] < 0.04, name      # toy size
    # at the engine's own sizes: past twice the window and a tick
    assert (logits["longest_context"], logits["T"]) == (38, 16)
    moved = logits["window_group"]
    assert moved["pages_handed_back"] > 0
    assert moved["handed_back_and_held_by_another"] > 0
    # the fresh prompt's slot was left with another sequence's state
    assert moved["reused_slot"] == 9
    assert moved["state_left_in_reused_slot"] > 0.01
    assert moved["state_slots_held"] == [10]
    for name in ("engine_program.mixed", "engine_program.decode"):
        assert logits[name]["ok"]
        assert logits[name]["argmax_agree"] >= 9, name
    assert served.correct == logits["ok"] is True
    # the checks gave everything back: what is held at the window's end
    # is the last requests' (a page or two and a slot each, if any)
    groups = served.detail["cache_groups"]
    assert [g["name"] for g in groups] == ["full", "window", "state"]
    assert all(g["pages_used"] <= 2 * groups[2]["slots_held"]
               for g in groups[:2])
    assert groups[2]["slots_held"] <= 3
    # the peaks are the ramp's and the window's, not the checks'
    assert 0 < groups[2]["slots_peak"] < 10
    warm = served.detail["warmup"]
    assert warm["programs_built"] == (len(warm["t_buckets"])
                                      * len(warm["ctx_buckets"]))
    marks = served.run["marks"]
    built = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    assert built(marks["end"]) == built(marks["start"])
    for name in ("setup_s", "serve_tok_s", "itl_p95_ms"):
        assert served.end_to_end[name] > 0


def test_precision_probe_gives_each_limit_its_second_reading(tmp_path):
    """The readings the limits are set against (`--probe`), at a toy
    size: the reference with float8 operands, and wrong in each of the
    eight ways, against itself."""
    from benchmarks.lib import checks_phi4flash
    from benchmarks.runners import serve_phi4flash
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_phi4flash._build_server(ctx).engine
    said = []
    got = checks_phi4flash.precision_probe(eng, DEBUG, 3, said.append)
    assert set(got) == {"fp8", *checks_phi4flash.VARIANTS}
    assert len(said) == 9
    for name, g in got.items():
        assert len(g["rows"]) == 20 and g["finite"], name
        assert len(g["past_window_median_row"]) == 2
        assert not g["would_pass"], name
    # a chunk boundary (16 tokens here) moves the rows behind it
    assert got["state_reset"]["median_row"] > 0.01
    assert got["conv_reset"]["worst_row"] > got["conv_reset"]["median_row"]


def test_runner_last_line_has_the_cells_metrics(served):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1,
           "memory_peak_bytes": 0}
    plain = bench_run.result_line(ROOT, bench, CELL, served, cpu, False)
    traced = bench_run.result_line(ROOT, bench, CELL, served, cpu, True)
    assert set(plain["metrics"]) == {"itl_p95_ms", "serve_tok_s",
                                     "setup_s"}
    # counters read on a CPU; trace metrics have nothing to read there
    assert {"loadgen.late_max_ms", "server.queue_wait_ms",
            "server.ttft_mean_ms", "engine.compiles_in_window",
            "engine.host_ms_per_tick", "engine.rows_per_tick",
            "engine.live_slots", "kv.peak_occupancy"} <= set(
                traced["metrics"])
    assert not {"kernel.ssm_scan_share", "kernel.ssm_scan_roofline_share",
                "kernel.shared_kv_attn_roofline_share",
                "step.cross_tokens_share"} & set(traced["metrics"])
    json.dumps(traced)
