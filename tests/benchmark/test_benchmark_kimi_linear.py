"""The cell `kimi-longdoc` and what it brings: the configuration against
the catalog row's keys, the adapter, the traffic file through the load
generator, each new reader on a small capture worked out by hand and on
runs that have nothing for it (the recorded fixtures, the other six
families' runs, `{}`), the cost functions by hand, a rehearsal of the new
runner at a tiny size, and its `BENCHMARK.json` entries BY NAME and as
subsets (a later cell may come behind this one)."""

import json
import os
import statistics

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs_kimi_linear as costs
from benchmarks.lib import loadgen, program_kimi_linear
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "kimi-longdoc"
# `config` of the row `Kimi-Linear-48B-A3B-Instruct` in the catalog beside
# the model-configs guide (source_url below), copied here: the catalog is
# not part of the repository
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
REDUCED = ["num_experts", "vocab_size"]
SOURCE = ("https://huggingface.co/moonshotai/"
          "Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
NEW = ("kernel.kda_scan_share", "kernel.kda_scan_roofline_share",
       "step.kda_layer_share", "kv.linear_saved_share")


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/kimi-linear-48b-a3b-ep16.json"
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert config[key] == value, key
    # depth 27 of 27: only the held experts and the vocabulary's slice
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (27, 16, 20480)
    pub = config["published"]
    assert {k: pub[k] for k in REDUCED} == {k: CATALOG[k] for k in REDUCED}
    assert "49,122,681,728" in pub["parameters"]
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] == 16
    assert "no pipeline" in dep["what"]
    assert (dep["experts_held"], dep["router_width"]) == ([0, 16], 256)
    assert "4,296,057,728" in dep["parameters_held"]
    for word in ("gate_rank", "decay", "qk_norm", "output_gate", "conv",
                 "nope", "q_lora_rank", "weights", "torch_dtype"):
        assert word in config["assumed"], word
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert cell["chips"] == 1
    assert not any(w["chips"] == 4 for w in bench["workloads"])


def test_adapter_builds_the_published_widths(resolved):
    _, _, config, _ = resolved
    cfg = program_kimi_linear.model_config(config)
    assert cfg.num_params() == 4_296_057_728
    assert (cfg.hidden, cfg.kda_heads, cfg.kda_head_dim, cfg.d_conv,
            cfg.gate_rank) == (2304, 32, 128, 4, 128)
    assert (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (32, 512, 128, 64, 128)
    assert (cfg.ffn, cfg.moe_ffn, cfg.n_routed_experts, cfg.held,
            cfg.moe_top_k, cfg.route_scale, cfg.first_k_dense) == (
                9216, 1024, 256, (0, 16), 8, 2.446, 1)
    assert cfg.vocab_size == 20480 and cfg.n_layers == 27
    assert "".join(cfg.kinds) == "KKKM" * 6 + "KKM"
    back = program_kimi_linear.published_keys(cfg)
    assert all(config[k] == v for k, v in back.items())
    with pytest.raises(ValueError, match="mla_use_nope"):
        program_kimi_linear.model_config({**config, "mla_use_nope": False})
    with pytest.raises(ValueError, match="q_lora_rank"):
        program_kimi_linear.model_config({**config, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="disagree"):
        program_kimi_linear.model_config({**config, "num_experts": 32})
    # the engine the file states: weights, latent pages and state
    from ray_tpu.models.family import family_of
    eng = config["engine"]
    latent, state = family_of(cfg).cache_groups(cfg, "pallas")
    held = (2 * cfg.num_params()
            + eng["num_pages"] * eng["page_size"] * latent.bytes_per_token
            + eng["max_batch_size"] * state.bytes_per_slot)
    assert eng["max_batch_size"] * state.bytes_per_slot == 2_084_044_800
    assert held > 0.25 * 17.18e9
    assert held < 13.1e9


def test_traffic_file_through_the_load_generator(resolved):
    bench, cell, config, tr_file = resolved
    assert tr_file["runner"] == "serve_kimi_linear"
    assert tr_file["loop"] == "open"
    assert tr_file["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.7, "min": 1024,
        "max": 24576}
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
        assert tr_file[key] % n not in (0, 1, n - 1), key
    cycle = loadgen.length_cycle(tr_file)
    assert len(cycle) == n
    prompts = [p for p, _ in cycle]
    outputs = [o for _, o in cycle]
    assert 1024 <= min(prompts) < 2000 and 16000 < max(prompts) <= 24576
    assert 5600 < statistics.median(prompts) < 6700
    assert 170 < statistics.median(outputs) < 215
    assert min(outputs) >= 32 and max(outputs) <= 768
    # every request is several 512-token chunks, one in six is past 12k
    assert all(p >= 1024 for p in prompts)
    assert 0.08 < sum(p > 12288 for p in prompts) / n < 0.25
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
    assert max(p + o for p, o in cycle) <= 24576 + 768 < (
        config["engine"]["max_seq_len"])
    # the rate is 0.7 of a knee the file documents
    assert "sweep.sustained" in tr_file["stands_for"]
    assert "0.7" in tr_file["stands_for"]


# ---- the readers -------------------------------------------------------

P0 = "/device:TPU:0"
MODEL = {"model_type": "kimi_linear", "hidden_size": 2304,
         "linear_attn_config": CATALOG["linear_attn_config"],
         "engine": {"page_size": 16}}
SCAN = "/jit(_kda_call)/kda_ragged_scan/pallas_call"
KDA = "jit(run)/while/body/while/body/attn/kda"


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (3 decode rows and a 509-token chunk) and one decode
# tick (4 rows), ns.
RAGGED = dict(kind="ragged", T=512, ctx=1600, rows=4, decode_rows=3,
              prefill_tokens=509, kv_tokens=3 * 8001 + 509,
              attn_pairs=3 * 8001 + 509 * 510 // 2, decode_pairs=3 * 8001,
              ssm_tokens=512, ssm_rows=4, built=0)
DECODE = dict(kind="decode", T=48, ctx=1600, rows=4, kv_tokens=32000,
              ssm_tokens=4, ssm_rows=4, built=0)
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, **RAGGED),
        _span("fold", 2900, 2950, of=1, moe_experts_hit=416,
              moe_assignments=26 * 256),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, **DECODE),
        _span("fold", 4900, 4950, of=2, moe_experts_hit=50,
              moe_assignments=26 * 2),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "fusion.2", 1300, 200, KDA + "/conv/mul", 0],
        [P0, tr.OPS, "kda_ragged_scan.3[custom-call]", 1500, 700,
         KDA + "/kda_scan" + SCAN, 0],
        [P0, tr.OPS, "fusion.4", 2200, 100, KDA + "/out_gate/mul", 0],
        [P0, tr.OPS, "fusion.5", 2300, 500,
         "jit(run)/while/body/mlp/moe_experts/dot_general", 0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "kda_ragged_scan.3[custom-call]", 3300, 300,
         "jit(step)/while/body/while/body/attn/kda/kda_scan" + SCAN, 0],
        [P0, tr.OPS, "fusion.9", 3600, 700,
         "jit(step)/while/body/attn/mla/dot", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}
GROUPS = [
    {"name": "latent", "layers": [3, 7, 11, 15, 19, 23, 26], "window": None,
     "row": {"bytes_per_token_layer": 1280}, "pages_at_peak": 2000,
     "pages_peak": 2100},
    {"name": "state", "kind": "state",
     "layers": [0, 1, 2, 4, 5, 6, 8, 9, 10, 12, 13, 14, 16, 17, 18, 20, 21,
                22, 24, 25], "window": None,
     "bytes_per_slot": 43417600, "slots_total": 48, "slots_held": 2,
     "slots_peak": 5, "slots_at_peak": 0},
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
    busy = 200 + 700 + 100 + 500 + 300 + 700
    assert _reader("kernel.kda_scan_share").read(run) == pytest.approx(
        100 * 1000 / busy)
    # everything under scope `kda`: conv, the kernel, the gate; both ticks
    assert _reader("step.kda_layer_share").read(run) == pytest.approx(
        100 * (200 + 700 + 100 + 300) / busy)
    # the scan: a token's q, k, v, o (bf16) and g (f32) over 4,096
    # channels and 32 betas: 49,280 B; a row's state in and out,
    # 2 x 2,097,152 B; 6 x 128 x 128 x 32 operations a token; 20 layers
    token, state, ops = 4 * 4096 * 2 + 4096 * 4 + 32 * 4, 4194304, 3145728
    assert token == 49280
    ragged_s = max(20 * (512 * token + 4 * state) / 819e9,
                   20 * 512 * ops / 197e12)
    decode_s = max(20 * (4 * token + 4 * state) / 819e9,
                   20 * 4 * ops / 197e12)
    # a chunk's tick is bound by its bytes too at these peaks
    assert ragged_s == 20 * (512 * token + 4 * state) / 819e9
    assert _reader("kernel.kda_scan_roofline_share").read(
        run) == pytest.approx(100 * (ragged_s + decode_s) / 1000e-9)
    # each group at its own peak: 2,100 pages of 16 tokens at 1,280 B a
    # layer, 7 layers held, and 5 slots of 43,417,600 B, against 27
    # latent layers
    a_layer = 2100 * 16 * 1280
    assert _reader("kv.linear_saved_share").read(run) == pytest.approx(
        100 * (1 - (7 * a_layer + 5 * 43417600) / (27 * a_layer)))
    # the generic readers take this family's capture as it is
    assert _reader("moe.experts_share").read(run) == pytest.approx(
        100 * 500 / busy)


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
    """The other six families' runs (their stats, a capture with none of
    this family's kernel, scope or counts), no run at all and junk: None,
    never an exception."""
    other = json.loads(json.dumps(HAND))
    other["events"] = [
        [*e[:5], e[5].replace("/attn/kda", "/mamba2"), e[6]]
        for e in other["events"] if "kda_" not in e[2]]
    for s in other["spans"]:
        for key in ("ssm_tokens", "ssm_rows"):
            s[4].pop(key, None)
    monkeypatch.setattr(sr, "capture", lambda run: other)
    row = {"bytes_per_token_layer": 4096}
    state = {"name": "state", "kind": "state", "layers": [0, 2],
             "window": None, "bytes_per_slot": 14938112, "slots_total": 64,
             "slots_held": 2, "slots_peak": 4, "slots_at_peak": 4}
    full = {"name": "full", "layers": [5, 12], "window": None, "row": row,
            "pages_at_peak": 900}
    runs = {
        "llama": {"config": {"model_type": "internlm2"}, "marks": {"end": {
            "stats": {"cache_groups": [
                {"name": "all", "layers": list(range(24)), "window": None,
                 "row": row, "pages_at_peak": 900}]}}}},
        "deepseek_v3": {"config": {"model_type": "deepseek_v3"}, "marks": {
            "end": {"stats": {"free_pages": 3}}}},
        "trinity": {"config": {"model_type": "afmoe"}, "marks": {"end": {
            "stats": {"cache_groups": [
                full, {**full, "name": "window", "window": 4096}]}}}},
        "phi4flash": {"config": {"model_type": "phi4flash"}, "marks": {
            "end": {"stats": {"cache_groups": [full, state]}}}},
        "nemotron_h": {"config": {"model_type": "nemotron_h"}, "marks": {
            "end": {"stats": {"cache_groups": [full, state]}}}},
        "smallthinker": {"config": {"model_type": "smallthinker",
                                    "model_name": "smallthinker_21b_instruct"},
                         "marks": {"end": {"stats": {"cache_groups": [
                             full, {**full, "name": "window",
                                    "window": 4096}]}}}},
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
        "kernel.kda_scan_share": ("%", "lower", "device_trace", "kernels",
                                  "itl_p95_ms"),
        "kernel.kda_scan_roofline_share": ("%", "higher", "device_trace",
                                           "kernels", "itl_p95_ms"),
        "step.kda_layer_share": ("%", "lower", "device_trace",
                                 "model forwards", "itl_p95_ms"),
        "kv.linear_saved_share": ("%", "higher", "program_counter",
                                  "cache manager", "serve_tok_s"),
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
                 "step.ragged_us_per_token", "device.idle_share.serve",
                 "device.idle_attributed_share.serve",
                 "engine.rows_per_tick", "engine.live_slots",
                 "kv.peak_occupancy", "moe.experts_share",
                 "kernel.mla_attn_share", "loadgen.late_max_ms",
                 "server.queue_wait_ms"):
        assert CELL in by_name[name]["workloads"], name
    # ... and none that prices another family's bytes or pins its list
    for name in ("step.sample_share", "engine.capture_hold_ms",
                 "engine.anomaly_flags_in_window", "moe.experts_hbm_share",
                 "kernel.mla_attn_roofline_share",
                 "kv.state_slots_peak_share", "kernel.ssd_scan_share",
                 "kernel.ssd_scan_roofline_share",
                 "kernel.ssm_scan_share", "kernel.ssm_scan_roofline_share",
                 "moe.relu2_experts_roofline_share",
                 "moe.reglu_experts_roofline_share",
                 "moe.rows_per_hit_expert", "kernel.ragged_attn_share"):
        assert CELL not in by_name[name]["workloads"], name
    # every metric that lists the cell has a reader that says nothing on {}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert _reader(m["name"]).NAME == m["name"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == "longdoc-steady"
    assert cells[CELL]["config"] == "kimi-linear-48b-a3b-ep16"
    assert len(cells) <= 24


def test_cost_functions_by_hand():
    assert costs.scan_layers(MODEL) == 20
    assert costs.scan_sizes(MODEL) == (4096, 32, 524288)
    assert costs.token_bytes(MODEL) == 49280
    span = {"kind": "ragged", "rows": 3, "decode_rows": 2,
            "prefill_tokens": 100, "ssm_tokens": 102, "ssm_rows": 3}
    assert costs.scan_min_bytes(MODEL, span) == 20 * (
        102 * 49280 + 3 * 4194304)
    assert costs.scan_min_flops(MODEL, span) == 20 * 102 * 3145728
    assert costs.scan_min_bytes(MODEL, {"kind": "ragged"}) is None
    assert costs.scan_min_flops(MODEL, {"kind": "ragged"}) is None


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "model_type", "hidden_act", "q_lora_rank", "mla_use_nope",
        "moe_router_activation_func", "num_expert_group", "topk_group",
        "moe_layer_freq", "rope_scaling", "rope_theta",
        "tie_word_embeddings", "num_nextn_predict_layers",
        "first_k_dense_replace", "moe_renormalize", "rms_norm_eps",
        "routed_scaling_factor", "num_shared_experts")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8,
    "linear_attn_config": {"num_heads": 2, "head_dim": 16,
                           "short_conv_kernel_size": 4,
                           "kda_layers": [1, 2, 4],
                           "full_attn_layers": [3, 5]},
    "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_per_token": 3, "model_max_length": 512,
    "deployment": {"experts_held": [0, 4], "router_width": 8},
    "assumed_sizes": {"gate_rank": 8},
    # page 16: `serve._warm`'s anchors want room in a context bucket. The
    # gather path: tests/test_kimi_linear.py holds the kernel path to the
    # reference
    "engine": {"max_batch_size": 10, "page_size": 16, "num_pages": 96,
               "max_prefill_tokens": 16, "max_num_batched_tokens": 16,
               "max_seq_len": 448, "decode_impl": "gather"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_kimi_linear
    traffic = {**rehearsal.CHAT, "runner": "serve_kimi_linear", "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_kimi_linear.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("kimi_linear"),
        seconds=1.5))


def test_runner_rehearsal_serves_checks_and_warms(served):
    assert served.failed == 0 and served.attempted >= 6
    logits = served.detail["logits"]
    for name in ("kernel_vs_gather.mixed", "kernel_vs_gather.decode",
                 "gather_vs_reference.mixed",
                 "gather_vs_reference.decode"):
        # 8 decode rows, a chunk and a prompt; then all 10 slots
        assert logits[name]["finite"] and len(logits[name]["rows"]) == 10
        # toy size: half the experts held, so a flipped pick shows
        assert logits[name]["median_row"] < 0.1, name
    # at the engine's own sizes: twenty-four ticks and a quarter
    assert (logits["longest_context"], logits["T"]) == (390, 16)
    state = logits["state_group"]
    # the fresh prompt's slot was left with another sequence's state
    assert state["reused_slot"] == 9
    assert state["state_left_in_reused_slot"] > 0.01
    assert state["state_slots_held"] == [10]
    assert logits["one_pass_float32"]["ok"]
    assert logits["kda_layer"]["ok"] and logits["expert_layer"]["ok"]
    for name in ("engine_program.mixed", "engine_program.decode"):
        assert logits[name]["ok"] and logits[name]["rider_len_ok"]
        assert logits[name]["argmax_agree"] >= 9, name
    assert served.correct == logits["ok"] is True
    # the checks gave everything back
    groups = served.detail["cache_groups"]
    assert [g["name"] for g in groups] == ["latent", "state"]
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
    eleven ways, against itself: each caught by at least one limit."""
    from benchmarks.lib import checks_kimi_linear
    from benchmarks.runners import serve_kimi_linear
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_kimi_linear._build_server(ctx).engine
    said = []
    got = checks_kimi_linear.precision_probe(eng, DEBUG, 3, said.append)
    assert set(got) == {"fp8", *checks_kimi_linear.VARIANTS}
    assert len(said) == 12
    for name, g in got.items():
        assert len(g["rows"]) == 20 and g["finite"], name
        assert not g["would_pass"], name
    # a tick's boundary (16 tokens here) moves the KDA layer's output
    assert got["state_reset"]["kda_layer"] > 0.01
    assert got["conv_reset"]["kda_layer"] > 1e-3
    # a state kept in bfloat16 and a decay a head are seen by the layer's
    # own check, which no flipped pick blurs
    assert got["state_bf16"]["kda_layer"] > 2e-4
    assert got["decay_a_head"]["kda_layer"] > 1e-3
    assert got["no_beta"]["kda_layer"] > 1e-2
    # the expert layer's variants are seen by the expert layer's check
    assert got["no_route_scale"]["expert_layer_routed"] > 0.3
    # ... and the scan's by none of the expert layer's
    assert got["no_beta"]["expert_layer"] == 0.0


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
    assert not {"kernel.kda_scan_share", "kernel.kda_scan_roofline_share",
                "step.kda_layer_share"} & set(traced["metrics"])
    json.dumps(traced)
