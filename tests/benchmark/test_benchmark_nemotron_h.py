"""The cell `nemotron-agent` and what it brings: the configuration
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
from benchmarks.lib import kernel_costs_nemotron_h as costs
from benchmarks.lib import loadgen, program_nemotron_h
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "nemotron-agent"
# `config` of the row `NVIDIA-Nemotron-3-Nano-30B-A3B-BF16` in the catalog
# beside the model-configs guide (source_url below), copied here: the
# catalog is not part of the repository
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
SOURCE = ("https://huggingface.co/nvidia/"
          "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
NEW = ("kernel.ssd_scan_share", "kernel.ssd_scan_roofline_share",
       "moe.relu2_experts_roofline_share", "moe.rows_per_hit_expert",
       "kv.state_slots_peak_share")


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/nemotron-3-nano-ep2-d16.json"
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (16, 64, 65536)
    # the first 16 layers of the published pattern
    assert config["hybrid_override_pattern"] == \
        CATALOG["hybrid_override_pattern"][:16] == "MEMEM*EMEMEM*EME"
    pub = config["published"]
    assert {k: pub[k] for k in REDUCED} == {k: CATALOG[k] for k in REDUCED}
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] == 2
    assert (dep["experts_held"], dep["router_width"]) == ([0, 64], 128)
    for word in ("d_inner", "groups", "gated_norm", "positions",
                 "time_step_limit", "weights", "torch_dtype"):
        assert word in config["assumed"], word
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert cell["chips"] == 1


def test_adapter_builds_the_published_widths(resolved):
    _, _, config, _ = resolved
    cfg = program_nemotron_h.model_config(config)
    assert cfg.num_params() == 5_282_534_208
    assert (cfg.hidden, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
            cfg.n_groups, cfg.d_conv) == (2688, 64, 64, 128, 8, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.moe_ffn, cfg.shared_ffn, cfg.n_routed_experts, cfg.held,
            cfg.moe_top_k, cfg.route_scale) == (1856, 3712, 128, (0, 64),
                                                6, 2.5)
    assert cfg.vocab_size == 65536 and cfg.pattern == "MEMEM*EMEMEM*EME"
    back = program_nemotron_h.published_keys(cfg)
    assert all(config[k] == v for k, v in back.items())
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        program_nemotron_h.model_config({**config, "mlp_hidden_act": "silu"})
    with pytest.raises(ValueError, match="disagree"):
        program_nemotron_h.model_config({**config, "n_routed_experts": 32})
    # the engine the file states: 12.6 GB of weights, pages and state
    from ray_tpu.models.family import family_of
    eng = config["engine"]
    full, state = family_of(cfg).cache_groups(cfg, "pallas")
    held = (2 * cfg.num_params()
            + eng["num_pages"] * eng["page_size"] * full.bytes_per_token
            + eng["max_batch_size"] * state.bytes_per_slot)
    assert held == pytest.approx(12.60e9, rel=0.003)
    assert held > 0.25 * 17.18e9


def test_traffic_file_through_the_load_generator(resolved):
    bench, cell, config, tr_file = resolved
    assert tr_file["runner"] == "serve_nemotron_h"
    assert tr_file["loop"] == "open"
    assert tr_file["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.8, "min": 512,
        "max": 16384}
    assert tr_file["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.7, "min": 32,
        "max": 768}
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
    assert 512 <= min(prompts) < 900 and 12000 < max(prompts) <= 16384
    assert 3800 < statistics.median(prompts) < 4400
    assert 170 < statistics.median(outputs) < 215
    assert min(outputs) >= 32 and max(outputs) <= 768
    # nearly every request is several 512-token chunks
    assert sum(p > 1024 for p in prompts) / n > 0.9
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
    # every context fits the engine's longest sequence
    assert max(p + o for p, o in cycle) <= 16384 + 768 < (
        config["engine"]["max_seq_len"])


# ---- the readers -------------------------------------------------------

P0 = "/device:TPU:0"
MODEL = {"model_type": "nemotron_h", "hidden_size": 2688,
         "hybrid_override_pattern": "MEMEM*EMEMEM*EME",
         "mamba_num_heads": 64, "mamba_head_dim": 64,
         "ssm_state_size": 128, "n_groups": 8,
         "moe_intermediate_size": 1856, "engine": {"page_size": 16}}
SCAN = "/jit(_ssd_call)/ssd_ragged_scan/pallas_call"
UP = "/jit(grouped_relu2)/moe_grouped_up_relu2/pallas_call"


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (30 decode rows and a 482-token chunk) and one decode
# tick (31 rows), ns.
RAGGED = dict(kind="ragged", T=512, ctx=1088, rows=31, decode_rows=30,
              prefill_tokens=482, kv_tokens=30 * 4001 + 482,
              attn_pairs=30 * 4001 + 482 * 483 // 2, decode_pairs=30 * 4001,
              ssm_tokens=512, ssm_rows=31, built=0)
DECODE = dict(kind="decode", T=64, ctx=1088, rows=31, kv_tokens=124000,
              ssm_tokens=31, ssm_rows=31, built=0)
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, **RAGGED),
        _span("fold", 2900, 2950, of=1, moe_experts_hit=448,
              moe_assignments=7 * 1500),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, **DECODE),
        _span("fold", 4900, 4950, of=2, moe_experts_hit=300,
              moe_assignments=7 * 93),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "ssd_ragged_scan.3[custom-call]", 1300, 400,
         "jit(run)/while/body/mamba2/ssd_scan" + SCAN, 0],
        [P0, tr.OPS, "moe_grouped_up_relu2.4[custom-call]", 1700, 600,
         "jit(run)/while/body/mlp/moe_experts" + UP, 0],
        [P0, tr.OPS, "fusion.5", 2300, 500, "jit(run)/mlp/dot_general", 0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "ssd_ragged_scan.3[custom-call]", 3300, 200,
         "jit(step)/while/body/mamba2/ssd_scan" + SCAN, 0],
        [P0, tr.OPS, "moe_grouped_up_relu2.4[custom-call]", 3500, 500,
         "jit(step)/while/body/mlp/moe_experts" + UP, 0],
        [P0, tr.OPS, "fusion.9", 4000, 300, "jit(step)/mlp/dot", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}
GROUPS = [
    {"name": "full", "layers": [5, 12], "window": None,
     "row": {"bytes_per_token_layer": 1024}, "pages_at_peak": 9000},
    {"name": "state", "kind": "state",
     "layers": [0, 2, 4, 7, 9, 11, 14], "window": None,
     "bytes_per_slot": 14938112, "slots_total": 64, "slots_held": 20,
     "slots_peak": 48, "slots_at_peak": 48},
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
    busy = 400 + 600 + 500 + 200 + 500 + 300
    assert _reader("kernel.ssd_scan_share").read(run) == pytest.approx(
        100 * 600 / busy)
    # the scan: a token's x (bf16) and y (f32) over 4,096 channels, its
    # 64 Deltas, its B and C of 1,024 each (bf16): 28,928 B; a row's
    # state in and out, 2 x 2,097,152 B; seven layers
    token, state = 4096 * 2 + 64 * 4 + 2 * 1024 * 2 + 4096 * 4, 2 * 2097152
    assert token == 28928
    ragged_b = 7 * (512 * token + 31 * state)
    decode_b = 7 * (31 * token + 31 * state)
    assert _reader("kernel.ssd_scan_roofline_share").read(
        run) == pytest.approx(
            100 * (ragged_b + decode_b) / 819e9 / 600e-9)
    # the experts: 19,955,712 B a pair hit, 16,128 B a landed row; 4 x
    # 2688 x 1856 operations a landed row; both ticks bound by bytes
    pair, row, ops = 19955712, 2688 * 6, 4 * 2688 * 1856
    r_s = (448 * pair + 10500 * row) / 819e9
    d_s = (300 * pair + 651 * row) / 819e9
    assert r_s > 10500 * ops / 197e12 and d_s > 651 * ops / 197e12
    assert _reader("moe.relu2_experts_roofline_share").read(
        run) == pytest.approx(100 * (r_s + d_s) / 1100e-9)
    assert _reader("moe.rows_per_hit_expert").read(run) == pytest.approx(
        (10500 + 651) / 748)
    assert _reader("kv.state_slots_peak_share").read(run) == 75.0
    # the generic readers take this family's capture as it is
    assert _reader("moe.experts_share").read(run) == pytest.approx(
        100 * 1100 / busy)


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
    """Other families' runs (their stats, a capture with none of this
    family's kernels or counts), no run at all and junk: None, never an
    exception."""
    other = json.loads(json.dumps(HAND))
    other["events"] = [e for e in other["events"]
                       if "ssd_" not in e[2] and "relu2" not in e[2]]
    other["spans"] = [s for s in other["spans"]
                      if not s[1].endswith("fold")]
    for s in other["spans"]:
        for key in ("ssm_tokens", "ssm_rows"):
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
        "phi4flash": {"config": {"model_type": "phi4flash"}, "marks": {
            "end": {"stats": {"cache_groups": GROUPS}}}},
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
    """This PR's entries BY NAME and as subsets: its cell is in a list,
    its readers exist; never by position, never as the whole set of
    metrics that list the cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    want = {
        "kernel.ssd_scan_share": ("%", "lower", "device_trace", "kernels",
                                  "itl_p95_ms"),
        "kernel.ssd_scan_roofline_share": ("%", "higher", "device_trace",
                                           "kernels", "itl_p95_ms"),
        "moe.relu2_experts_roofline_share": (
            "%", "higher", "device_trace", "model forwards", "itl_p95_ms"),
        "moe.rows_per_hit_expert": ("count", "higher", "program_counter",
                                    "model forwards", "itl_p95_ms"),
        "kv.state_slots_peak_share": ("%", "lower", "program_counter",
                                      "cache manager", "serve_tok_s"),
    }
    assert set(want) == set(NEW)
    for name, (unit, better, source, layer, moves) in want.items():
        m, mod = by_name[name], _reader(name)
        assert m["workloads"] == [CELL], name
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
                 "kv.peak_occupancy", "moe.experts_share",
                 "kernel.ragged_attn_share"):
        assert CELL in by_name[name]["workloads"], name
    # ... and none that prices another family's bytes or pins its list
    for name in ("step.sample_share", "engine.capture_hold_ms",
                 "engine.anomaly_flags_in_window", "moe.experts_hbm_share",
                 "kernel.ragged_attn_hbm_share",
                 "kernel.ssm_scan_roofline_share",
                 "kernel.gqa_attn_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    # every metric that lists the cell has a reader that says nothing on {}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert _reader(m["name"]).NAME == m["name"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == "agent-turns-steady"
    assert cells[CELL]["config"] == "nemotron-3-nano-ep2-d16"


def test_cost_functions_by_hand():
    assert costs.schedule(MODEL) == {"mamba": 7, "experts": 7, "attn": 2}
    assert costs.scan_sizes(MODEL) == (4096, 64, 1024, 524288)
    span = {"kind": "ragged", "rows": 3, "decode_rows": 2,
            "prefill_tokens": 100, "ssm_tokens": 102, "ssm_rows": 3}
    assert costs.scan_min_bytes(MODEL, span) == 7 * (
        102 * 28928 + 3 * 4194304)
    assert costs.scan_min_bytes(MODEL, {"kind": "ragged"}) is None
    assert costs.expert_bytes(MODEL) == 19_955_712
    assert costs.experts_min_bytes(MODEL, 10, 50) == (
        10 * 19_955_712 + 50 * 16128)
    assert costs.experts_min_flops(MODEL, 50) == 50 * 4 * 2688 * 1856


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "model_type", "mlp_hidden_act", "mamba_hidden_act",
        "tie_word_embeddings", "n_group", "topk_group", "n_shared_experts",
        "use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias",
        "use_conv_bias", "sliding_window", "conv_kernel", "chunk_size",
        "layer_norm_epsilon", "routed_scaling_factor", "norm_topk_prob",
        "time_step_min", "time_step_max", "time_step_floor")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*E", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4,
    "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "max_position_embeddings": 512,
    "deployment": {"experts_held": [0, 4], "router_width": 8},
    # page 16: `serve._warm`'s anchors want room in a context bucket. The
    # gather path: tests/test_nemotron_h.py holds the kernel path to the
    # reference
    "engine": {"max_batch_size": 10, "page_size": 16, "num_pages": 96,
               "max_prefill_tokens": 16, "max_num_batched_tokens": 16,
               "max_seq_len": 320, "decode_impl": "gather"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_nemotron_h
    traffic = {**rehearsal.CHAT, "runner": "serve_nemotron_h", "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_nemotron_h.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("nemotron_h"), seconds=1.5))


def test_runner_rehearsal_serves_checks_and_warms(served):
    assert served.failed == 0 and served.attempted >= 6
    logits = served.detail["logits"]
    for name in ("kernel_vs_gather.mixed", "kernel_vs_gather.decode",
                 "gather_vs_reference.mixed",
                 "gather_vs_reference.decode"):
        # 8 decode rows, a chunk and a prompt; then all 10 slots
        assert logits[name]["finite"] and len(logits[name]["rows"]) == 10
        assert logits[name]["median_row"] < 0.04, name      # toy size
    # at the engine's own sizes: sixteen ticks and a quarter
    assert (logits["longest_context"], logits["T"]) == (262, 16)
    state = logits["state_group"]
    # the fresh prompt's slot was left with another sequence's state
    assert state["reused_slot"] == 9
    assert state["state_left_in_reused_slot"] > 0.01
    assert state["state_slots_held"] == [10]
    assert logits["one_pass_float32"]["ok"]
    assert logits["mamba_layer"]["ok"] and logits["expert_layer"]["ok"]
    for name in ("engine_program.mixed", "engine_program.decode"):
        assert logits[name]["ok"] and logits[name]["rider_len_ok"]
        assert logits[name]["argmax_agree"] >= 9, name
    assert served.correct == logits["ok"] is True
    # the checks gave everything back
    groups = served.detail["cache_groups"]
    assert [g["name"] for g in groups] == ["full", "state"]
    assert groups[1]["slots_held"] <= 3
    assert groups[0]["pages_used"] <= 2 * max(groups[1]["slots_held"], 1)
    # the peaks are the ramp's and the window's, not the checks'
    assert 0 < groups[1]["slots_peak"] < 10
    assert served.detail["moe"]["assignments_landed"] > 0
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
    fourteen ways, against itself: each caught by at least one limit."""
    from benchmarks.lib import checks_nemotron_h
    from benchmarks.runners import serve_nemotron_h
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_nemotron_h._build_server(ctx).engine
    said = []
    got = checks_nemotron_h.precision_probe(eng, DEBUG, 3, said.append)
    assert set(got) == {"fp8", *checks_nemotron_h.VARIANTS}
    assert len(said) == 15
    for name, g in got.items():
        assert len(g["rows"]) == 20 and g["finite"], name
        assert not g["would_pass"], name
    # a chunk boundary (16 tokens here) moves the Mamba layer's output
    assert got["state_reset"]["mamba_layer"] > 0.01
    assert got["conv_reset"]["mamba_layer"] > 1e-3
    # the expert layer's variants are seen by the expert layer's check
    assert got["relu_not_squared"]["expert_layer"] > 0.1
    assert got["no_route_scale"]["expert_layer_routed"] > 0.3
    # ... and the scan's by none of the expert layer's
    assert got["no_d"]["expert_layer"] == 0.0


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
    assert not {"kernel.ssd_scan_share", "kernel.ssd_scan_roofline_share",
                "moe.relu2_experts_roofline_share",
                "moe.rows_per_hit_expert"} & set(traced["metrics"])
    json.dumps(traced)
