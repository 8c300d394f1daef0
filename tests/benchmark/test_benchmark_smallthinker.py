"""The cell `smallthinker-assist` and what it brings: the configuration
against the catalog row's keys, the adapter, the traffic file through the
load generator, each new reader on a small capture worked out by hand and
on runs that have nothing for it (the recorded fixtures, other families'
runs, `{}`), the cost functions by hand, a rehearsal of the new runner at
a tiny size, and its `BENCHMARK.json` entries BY NAME and as subsets (a
later cell may come behind this one)."""

import json
import os
import statistics

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs_smallthinker as costs
from benchmarks.lib import loadgen, program_smallthinker
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "smallthinker-assist"
# `config` of the row `SmallThinker-21BA3B-Instruct` in the catalog beside
# the model-configs guide (source_url below), copied here: the catalog is
# not part of the repository
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "sliding_window_layout", "rope_layout"]
SOURCE = ("https://huggingface.co/PowerInfer/"
          "SmallThinker-21BA3B-Instruct/blob/main/config.json")
NEW = ("moe.reglu_experts_roofline_share", "moe.experts_hit_share",
       "moe.route_ahead_share")


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/smallthinker-21b-a3b-d12.json"
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert config[key] == value, key
    # no width differs, nothing but depth is cut: every expert, the whole
    # router, the whole vocabulary
    assert config["num_hidden_layers"] == 12
    assert config["sliding_window_layout"] == [0, 1, 1, 1] * 3 == \
        CATALOG["sliding_window_layout"][:12]
    assert config["rope_layout"] == CATALOG["rope_layout"][:12]
    assert config["published"]["num_hidden_layers"] == 52
    for key in REDUCED:
        assert key in config["published"], key
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] == 1
    assert dep["experts_held"] == [0, 64]
    for word in ("router_input", "routing", "experts", "attention", "norms",
                 "weights", "torch_dtype"):
        assert word in config["assumed"], word
    assert "ahead of the input norm" in config["assumed"]["router_input"]
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert cell["chips"] == 1


def test_adapter_builds_the_published_widths(resolved):
    _, _, config, _ = resolved
    cfg = program_smallthinker.model_config(config)
    assert cfg.num_params() == 5_561_448_960
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2560, 28, 4, 128)
    assert (cfg.moe_ffn, cfg.n_routed_experts, cfg.held, cfg.moe_top_k) == (
        768, 64, (0, 64), 6)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.norm_eps, cfg.max_seq
            ) == (4096, 1500000, 1e-6, 16384)
    assert cfg.vocab_size == 151936 and cfg.n_layers == 12
    assert cfg.windowed == cfg.roped == (0, 1, 1, 1) * 3
    back = program_smallthinker.published_keys(cfg)
    assert all(config[k] == v for k, v in back.items())
    with pytest.raises(ValueError, match="norm_topk_prob"):
        program_smallthinker.model_config({**config,
                                           "norm_topk_prob": False})
    with pytest.raises(ValueError, match="rope_scaling"):
        program_smallthinker.model_config({**config,
                                           "rope_scaling": {"type": "yarn"}})
    with pytest.raises(ValueError, match="disagree"):
        program_smallthinker.model_config({**config,
                                           "num_hidden_layers": 8})
    # the engine the file states: weights and both page groups
    from ray_tpu.models.family import family_of
    eng = config["engine"]
    full, win = family_of(cfg).cache_groups(cfg, "pallas")
    by = eng["num_pages_by_group"]
    pools = (by["full"] * eng["page_size"] * full.bytes_per_token,
             by["window"] * eng["page_size"] * win.bytes_per_token)
    assert pools == (10240 * 16 * 3 * 2048, 8192 * 16 * 9 * 2048)
    assert pools[0] == pytest.approx(1.01e9, rel=0.01)
    assert pools[1] == pytest.approx(2.42e9, rel=0.01)
    held = 2 * cfg.num_params() + sum(pools)
    assert held == pytest.approx(14.55e9, rel=0.003)
    assert held > 0.25 * 17.18e9
    assert eng["max_seq_len"] == config["max_position_embeddings"]
    assert (eng["max_batch_size"], eng["max_num_batched_tokens"]) == (
        48, 512)


def test_traffic_file_through_the_load_generator(resolved):
    bench, cell, config, tr_file = resolved
    assert tr_file["runner"] == "serve_smallthinker"
    assert tr_file["loop"] == "open"
    assert tr_file["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.9, "min": 128,
        "max": 12288}
    assert tr_file["output_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 64,
        "max": 1024}
    assert tr_file["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert tr_file["arrival"] == {"dist": "exponential"}
    assert (tr_file["ramp_s"], tr_file["grace_s"], tr_file["trace_s"]) == (
        30, 45, 4)
    # a window holds exactly one cycle
    assert tr_file["cycle"] == pytest.approx(
        tr_file["rate_rps"] * bench["run_seconds"])
    n = tr_file["cycle"]
    # no stride is 1 or -1 mod the cycle (the grid would go by in order)
    for key in ("pair_stride", "order_stride", "gap_stride"):
        assert tr_file[key] % n not in (1, n - 1), key
    cycle = loadgen.length_cycle(tr_file)
    assert len(cycle) == n
    prompts = [p for p, _ in cycle]
    outputs = [o for _, o in cycle]
    assert 128 <= min(prompts) < 400 and 9000 < max(prompts) <= 12288
    assert 1800 < statistics.median(prompts) < 2300
    assert 2500 < statistics.mean(prompts) < 3200
    assert 340 < statistics.median(outputs) < 430
    assert 400 < statistics.mean(outputs) < 480
    assert min(outputs) >= 64 and max(outputs) <= 1024
    # about a fifth of the prompts lies past the 4,096-token window
    assert 0.15 < sum(p > 4096 for p in prompts) / n < 0.3
    # decode-heavy: most of a request's ticks are decode ticks
    assert statistics.mean(outputs) > 50 * statistics.mean(prompts) / 512 / 2
    gaps = loadgen.arrival_gaps(tr_file)
    assert sum(gaps) == pytest.approx(bench["run_seconds"])
    # PR 31's lesson: the busiest 10 s of a cycle hold at most 1.5 x the
    # mean arrivals
    rate = tr_file["rate_rps"]
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
    # every context fits the engine's (and the model's) longest sequence
    assert max(p + o for p, o in cycle) <= 12288 + 1024 < (
        config["engine"]["max_seq_len"])


# ---- the readers -------------------------------------------------------

P0 = "/device:TPU:0"
MODEL = {"model_name": "smallthinker_21b_instruct", "hidden_size": 2560,
         "num_hidden_layers": 12, "moe_ffn_hidden_size": 768,
         "moe_num_primary_experts": 64,
         "deployment": {"experts_held": [0, 64]},
         "engine": {"page_size": 16}}
UP = "/jit(grouped_reglu)/moe_grouped_up_reglu/pallas_call"
DOWN = "/jit(grouped_reglu)/moe_grouped_down_reglu/pallas_call"


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (30 decode rows and a 482-token chunk) and one decode
# tick (31 rows), ns.
RAGGED = dict(kind="ragged", T=512, ctx=1024, rows=31, decode_rows=30,
              prefill_tokens=482, kv_tokens=30 * 4001 + 482,
              attn_pairs=30 * 4001 + 482 * 483 // 2, decode_pairs=30 * 4001,
              built=0)
DECODE = dict(kind="decode", T=48, ctx=1024, rows=31, decode_rows=31,
              prefill_tokens=0, kv_tokens=124000, built=0)
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, **RAGGED),
        _span("fold", 2900, 2950, of=1, moe_experts_hit=768,
              moe_assignments=512 * 6 * 12),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, **DECODE),
        _span("fold", 4900, 4950, of=2, moe_experts_hit=720,
              moe_assignments=31 * 6 * 12),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "fusion.2", 1300, 100,
         "jit(run)/moe_router/dot_general", 0],
        [P0, tr.OPS, "moe_grouped_up_reglu.4[custom-call]", 1400, 600,
         "jit(run)/mlp/moe_experts" + UP, 0],
        [P0, tr.OPS, "moe_grouped_down_reglu.5[custom-call]", 2000, 300,
         "jit(run)/mlp/moe_experts" + DOWN, 0],
        [P0, tr.OPS, "fusion.5", 2300, 500, "jit(run)/attn/swa/dot", 0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "fusion.7", 3300, 50,
         "jit(step)/moe_router/top_k", 0],
        [P0, tr.OPS, "moe_grouped_up_reglu.4[custom-call]", 3350, 400,
         "jit(step)/mlp/moe_experts" + UP, 0],
        [P0, tr.OPS, "moe_grouped_down_reglu.5[custom-call]", 3750, 250,
         "jit(step)/mlp/moe_experts" + DOWN, 0],
        [P0, tr.OPS, "fusion.9", 4000, 300, "jit(step)/attn/full/dot", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}


def _reader(name):
    return bench_run.load_layer_metric(ROOT, name)


@pytest.fixture
def run_with_capture(monkeypatch):
    monkeypatch.setattr(sr, "capture", lambda run: HAND)
    return {"events": HAND["events"], "config": MODEL,
            "device_kind": "TPU v5 lite",
            "marks": {"end": {"stats": {}}}}


def test_new_readers_on_a_capture_worked_out_by_hand(run_with_capture):
    run = run_with_capture
    busy = 100 + 600 + 300 + 500 + 50 + 400 + 250 + 300
    # the router's scope: 150 ns of 2,500 busy
    assert _reader("moe.route_ahead_share").read(run) == pytest.approx(
        100 * 150 / busy)
    # the experts hit: 768 and 720 of 768, the mean of the two ticks
    assert _reader("moe.experts_hit_share").read(run) == pytest.approx(
        100 * (768 + 720) / (2 * 768))
    # the experts: 11,796,480 B a pair hit (three matrices of 2560 x 768
    # bf16), 15,360 B a token a layer in and out; 6 x 2560 x 768
    # operations an assignment. The chunk's tick is bound by the
    # operations, the decode tick by the bytes
    pair, row, ops = 3 * 2560 * 768 * 2, 2560 * 6, 6 * 2560 * 768
    assert pair == 11_796_480
    r_bytes = (768 * pair + 12 * 512 * row) / 819e9
    r_ops = 512 * 6 * 12 * ops / 197e12
    d_bytes = (720 * pair + 12 * 31 * row) / 819e9
    d_ops = 31 * 6 * 12 * ops / 197e12
    assert r_ops < r_bytes and d_ops < d_bytes
    assert _reader("moe.reglu_experts_roofline_share").read(
        run) == pytest.approx(
            100 * (r_bytes + d_bytes) / ((600 + 300 + 400 + 250) * 1e-9))
    # the generic readers take this family's capture as it is
    assert _reader("moe.experts_share").read(run) == pytest.approx(
        100 * 1550 / busy)


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
    """Other families' runs (a capture with none of this family's
    kernels, scopes or counts, whatever their configuration), no run at
    all and junk: None, never an exception."""
    other = json.loads(json.dumps(HAND))
    other["events"] = [e for e in other["events"]
                       if "reglu" not in e[2] and "moe_router" not in e[5]]
    other["spans"] = [s for s in other["spans"]
                      if not s[1].endswith("fold")]
    monkeypatch.setattr(sr, "capture", lambda run: other)
    runs = {
        "dense": {"config": {"model_type": "internlm2"}},
        "latent": {"config": {"model_type": "deepseek_v3"}},
        "trinity": {"config": {"model_type": "afmoe"}},
        "nemotron_h": {"config": {"model_type": "nemotron_h"}},
        # this family's configuration over a program without it
        "laid over the parent": {"config": MODEL},
    }
    for label, run in runs.items():
        run = {"events": other["events"], "device_kind": "TPU v5 lite",
               "marks": {"end": {"stats": {"free_pages": 3}}}, **run}
        for name in NEW:
            assert _reader(name).read(run) is None, (name, label)
    for junk in ({}, {"config": None}, {"marks": 3, "config": MODEL},
                 None, []):
        for name in NEW:
            assert _reader(name).read(junk) is None, (name, junk)
    # another family's run WITH expert counts and a router's scope (the
    # nemotron_h family's): still nothing, the guard is the model's name
    monkeypatch.setattr(sr, "capture", lambda run: HAND)
    run = {"events": HAND["events"], "device_kind": "TPU v5 lite",
           "config": {"model_type": "nemotron_h"}}
    for name in NEW:
        assert _reader(name).read(run) is None, name


def test_benchmark_entries_by_name():
    """This PR's entries BY NAME and as subsets: its cell is in a list,
    its readers exist; never by position, never as the whole set of
    metrics that list the cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    want = {
        "moe.reglu_experts_roofline_share": (
            "%", "higher", "device_trace", "model forwards", "itl_p95_ms"),
        "moe.experts_hit_share": ("%", "higher", "program_counter",
                                  "model forwards", "itl_p95_ms"),
        "moe.route_ahead_share": ("%", "lower", "device_trace",
                                  "model forwards", "itl_p95_ms"),
    }
    assert set(want) == set(NEW)
    for name, (unit, better, source, layer, moves) in want.items():
        m, mod = by_name[name], _reader(name)
        assert CELL in m["workloads"], name
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer, moves), name
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            name, unit, layer, moves)
        assert mod.read({}) is None
    # the cell joins the two end-to-end metrics and the serving readers
    ends = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("itl_p95_ms", "serve_tok_s"):
        assert CELL in ends[name]["workloads"]
    assert "workloads" not in ends["setup_s"]
    for name in ("step.decode_ms", "step.ragged_ms",
                 "device.idle_share.serve", "engine.rows_per_tick",
                 "engine.live_slots", "kv.peak_occupancy",
                 "moe.experts_share", "kernel.ragged_attn_share",
                 "kernel.swa_attn_share", "kv.window_saved_share"):
        assert CELL in by_name[name]["workloads"], name
    # ... and none that prices another family's bytes, reads another
    # family's stats or pins its list
    for name in ("step.sample_share", "engine.capture_hold_ms",
                 "engine.anomaly_flags_in_window", "moe.experts_hbm_share",
                 "kernel.ragged_attn_hbm_share",
                 "kernel.swa_attn_roofline_share",
                 "kernel.gqa_attn_roofline_share",
                 "moe.relu2_experts_roofline_share",
                 "moe.rows_per_hit_expert", "kv.state_slots_peak_share"):
        assert CELL not in by_name[name]["workloads"], name
    # every metric that lists the cell has a reader that says nothing on {}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert _reader(m["name"]).NAME == m["name"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == "assist-longanswer-steady"
    assert cells[CELL]["config"] == "smallthinker-21b-a3b-d12"
    assert cells[CELL]["chips"] == 1
    # nothing that was there went away
    for name in ("chat-open", "train-packed", "dsv3-longchat",
                 "trinity-mixed", "phi4flash-reason", "nemotron-agent"):
        assert name in cells


def test_cost_functions_by_hand():
    assert costs.expert_bytes(MODEL) == 11_796_480
    assert costs.held_experts(MODEL) == 768
    ragged = {"kind": "ragged", "rows": 3, "decode_rows": 2,
              "prefill_tokens": 100}
    decode = {"kind": "decode", "rows": 31, "decode_rows": 31,
              "prefill_tokens": 0}
    assert costs.tokens(ragged) == 102 and costs.tokens(decode) == 31
    assert costs.experts_min_bytes(MODEL, 10, 50) == (
        10 * 11_796_480 + 12 * 50 * 15_360)
    assert costs.experts_min_flops(MODEL, 50) == 50 * 6 * 2560 * 768
    # a decode tick of 32 rows that reads 96% of the experts: 8.7 GB,
    # 10.6 ms at 819 GB/s, and 0.14 ms of operations
    hit = round(0.96 * 768)
    assert costs.experts_min_bytes(MODEL, hit, 32) / 819e9 == pytest.approx(
        10.6e-3, rel=0.02)
    assert costs.experts_min_flops(MODEL, 32 * 6 * 12) / 197e12 < 2e-4


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "model_name", "moe_primary_router_apply_softmax", "norm_topk_prob",
        "tie_word_embeddings", "rope_scaling", "rope_theta",
        "rms_norm_eps")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "sliding_window_size": 8,
    "max_position_embeddings": 256,
    "deployment": {"experts_held": [0, 8]},
    # page 16: `serve._warm`'s anchors want room in a context bucket. The
    # gather path: tests/test_smallthinker.py holds the kernel path to
    # the reference
    "engine": {"max_batch_size": 10, "page_size": 16, "num_pages": 64,
               "num_pages_by_group": {"full": 64, "window": 40},
               "max_prefill_tokens": 16, "max_num_batched_tokens": 16,
               "max_seq_len": 128, "decode_impl": "gather"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_smallthinker
    traffic = {**rehearsal.CHAT, "runner": "serve_smallthinker", "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_smallthinker.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("smallthinker"),
        seconds=1.5))


def test_runner_rehearsal_serves_checks_and_warms(served):
    assert served.failed == 0 and served.attempted >= 6
    logits = served.detail["logits"]
    for name in ("kernel_vs_gather.mixed", "kernel_vs_gather.decode",
                 "gather_vs_reference.mixed",
                 "gather_vs_reference.decode"):
        # 8 decode rows, a chunk and a prompt; then all 10 slots
        assert logits[name]["finite"] and len(logits[name]["rows"]) == 10
        assert logits[name]["median_row"] < 0.04, name      # toy size
    # at the engine's own sizes: past twice the window of 8
    assert (logits["longest_context"], logits["T"]) == (38, 16)
    window = logits["window_group"]
    assert window["pages_handed_back"] > 0
    assert window["handed_back_and_held_by_another"] > 0
    assert logits["one_pass"]["ok"] and logits["router"]["ok"]
    assert logits["one_pass"]["median_row"] < 0.03
    assert logits["router"]["picks_alike"] == 1.0
    assert logits["expert_layer"]["ok"]
    blocks = logits["attention_blocks"]
    assert blocks["ok"] and {"full.gather", "swa.gather"} <= set(blocks)
    for name in ("engine_program.mixed", "engine_program.decode"):
        e = logits[name]
        assert e["ok"] and e["rider_len_ok"]
        assert e["argmax_agree"] >= 9, name
        # every expert held: valid rows x 3 picks x 4 layers, exactly
        assert e["rider_total_got"] == e["rider_total_wanted"] > 0
    assert logits["engine_program.mixed"]["rider_total_wanted"] \
        == 16 * 3 * 4
    assert logits["engine_program.decode"]["rider_total_wanted"] \
        == 10 * 3 * 4
    assert served.correct == logits["ok"] is True
    # the checks gave everything back
    groups = served.detail["cache_groups"]
    assert [g["name"] for g in groups] == ["full", "window"]
    # the peaks are the ramp's and the window's, not the checks'
    assert 0 < groups[0]["pages_peak"] < 20
    moe = served.detail["moe"]
    assert moe["assignments_landed"] == moe["tokens_routed"] * 3 * 4 > 0
    warm = served.detail["warmup"]
    assert warm["programs_built"] >= len(warm["t_buckets"])
    marks = served.run["marks"]
    built = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    assert built(marks["end"]) == built(marks["start"])
    for name in ("setup_s", "serve_tok_s", "itl_p95_ms"):
        assert served.end_to_end[name] > 0


def test_precision_probe_gives_each_limit_its_second_reading(tmp_path):
    """The readings the limits are set against (`--probe`), at a toy
    size: the reference with float8 operands, and wrong in each of the
    ten ways, against itself: each caught by at least one limit."""
    from benchmarks.lib import checks_smallthinker
    from benchmarks.runners import serve_smallthinker
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_smallthinker._build_server(ctx).engine
    said = []
    got = checks_smallthinker.precision_probe(eng, DEBUG, 3, said.append)
    assert set(got) == {"fp8", *checks_smallthinker.VARIANTS}
    assert len(said) == 11
    for name, g in got.items():
        assert len(g["ticks"]["rows"]) == 20 and g["ticks"]["finite"], name
        assert not g["would_pass"], name
    # the precision below fails the expert layer's limit and the
    # attention blocks' on their own
    assert got["fp8"]["expert_layer"] > checks_smallthinker.EXPERTS_REL_RMS
    assert min(got["fp8"]["attention"].values()) \
        > checks_smallthinker.ATTENTION_REL_RMS
    # the router's place is seen by the routers' logits of the one pass
    assert got["router_after_norm"]["one_pass_router"] > 1.0
    assert got["router_after_attn"]["one_pass_router"] > 1.0
    # ... its weights by the router's own check, and by no other tight one
    assert got["no_pick_norm"]["router_weights"] > 0.01
    assert got["no_pick_norm"]["one_pass"]["median_row"] == 0.0
    # the experts' form by the expert layer's, not by attention's
    assert got["silu_gate"]["expert_layer"] > 0.1
    assert got["no_gate"]["expert_layer"] > 0.3
    assert got["silu_gate"]["attention"] == {"full": 0.0, "swa": 0.0}
    # a window's edge and the rope by the attention block of that kind
    assert got["all_full"]["attention"]["swa"] > 0.1
    assert got["all_window"]["attention"]["full"] > 0.1
    assert got["rope_everywhere"]["attention"]["full"] > 0.1
    assert got["no_rope"]["attention"]["swa"] > 0.1
    assert got["rope_interleaved"]["attention"]["swa"] > 0.1
    # the cheap probe: named readings alone, no ticks
    few = checks_smallthinker.precision_probe(eng, DEBUG, 3, said.append,
                                              only=("fp8",))
    assert set(few) == {"fp8"} and "ticks" not in few["fp8"]


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
            "engine.live_slots", "kv.peak_occupancy",
            "kv.window_saved_share"} <= set(traced["metrics"])
    assert not set(NEW) & set(traced["metrics"])
    json.dumps(traced)
