"""The cell `trinity-mixed` and what it brings: the configuration against
the catalog row's keys, the traffic file through the load generator,
each new reader on a small capture and on nothing, the cost functions
by hand, and a rehearsal of the new runner at a tiny size."""

import json
import os
import statistics

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs_trinity as costs
from benchmarks.lib import loadgen, program_trinity
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "trinity-mixed"
S, F = "sliding_attention", "full_attention"
# `config` of the row `Trinity-Large-Preview` in the catalog beside the
# model-configs guide (source_url below), copied here: the catalog is
# not part of the repository
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": [S, S, S, F] * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "sliding_window": 4096, "tie_word_embeddings": False, "topk_group": 1,
    "use_grouped_mm": True, "vocab_size": 200192,
}
SOURCE = ("https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/"
          "main/config.json")
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key_but_the_reduced(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/trinity-large-ep16-d9.json"
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert config[key] != value, key
            if key != "layer_types":
                assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    # the cut: the first 9 layers of the published pattern, one of them
    # dense, two whole periods of 3:1 among the 8 expert layers
    assert config["layer_types"] == CATALOG["layer_types"][:9]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (
                9, 1, 16, 25024)
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] * config["num_experts"] == 256
    assert dep["router_width"] == CATALOG["num_experts"]
    assert set(config["assumed"]) >= {"qk_norm", "attention_gate", "rope",
                                      "norms", "expert_bias"}
    assert config["engine"] == {
        "max_batch_size": 32, "page_size": 16, "num_pages": 12288,
        "num_pages_by_group": {"full": 12288, "window": 6144},
        "max_seq_len": 16384, "max_num_batched_tokens": 512}
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_adapter_builds_the_published_widths(resolved):
    _, _, config, _ = resolved
    cfg = program_trinity.model_config(config)
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        3072, 48, 8, 128)
    assert (cfg.ffn, cfg.moe_ffn, cfg.n_routed_experts, cfg.moe_top_k) == (
        12288, 3072, 256, 4)
    assert (cfg.held, cfg.route_scale, cfg.sliding_window) == (
        (0, 16), 2.448, 4096)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.vocab_size) == (
        9, 1, 25024)
    assert cfg.layers_of(F) == (3, 7) and len(cfg.layers_of(S)) == 7
    # ISSUE 31's arithmetic: 9.38 GB in bfloat16
    assert cfg.num_params() == 4_689_887_232
    assert program_trinity.published_keys(cfg)["layer_types"] == (
        config["layer_types"])
    with pytest.raises(ValueError, match="score_func"):
        program_trinity.model_config({**config, "score_func": "softmax"})
    with pytest.raises(ValueError, match="disagree"):
        program_trinity.model_config({**config, "num_experts": 32})
    # two page groups: 1.61 GB and 2.82 GB
    row = 2 * 8 * 128 * 2
    pages = config["engine"]["num_pages_by_group"]
    assert pages["full"] * 16 * 2 * row == 1_610_612_736
    assert pages["window"] * 16 * 7 * row == 2_818_572_288


def test_traffic_file_through_the_load_generator(resolved):
    bench, cell, config, tr_file = resolved
    assert tr_file["runner"] == "serve_trinity"
    assert tr_file["loop"] == "open"
    assert tr_file["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 1.0, "min": 256,
        "max": 15872}
    assert tr_file["output_tokens"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.7, "min": 32,
        "max": 384}
    assert tr_file["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert tr_file["arrival"] == {"dist": "exponential"}
    assert (tr_file["ramp_s"], tr_file["grace_s"]) == (30, 45)
    # a window holds exactly one cycle
    assert tr_file["cycle"] == pytest.approx(
        tr_file["rate_rps"] * bench["run_seconds"])
    cycle = loadgen.length_cycle(tr_file)
    assert len(cycle) == tr_file["cycle"]
    prompts = [p for p, _ in cycle]
    # the grid's first quantile sits just over the floor, its last at
    # the cap (the top 5% of the lognormal)
    assert 256 <= min(prompts) < 320 and max(prompts) == 15872
    assert sum(p == 15872 for p in prompts) >= 2
    assert 4000 < statistics.mean(prompts) < 5000
    assert 140 < statistics.mean(o for _, o in cycle) < 170
    window = config["sliding_window"]
    past = sum(p > window for p in prompts) / len(prompts)
    assert 0.33 < past < 0.45                     # two in five
    assert sum(p > 2 * window for p in prompts) / len(prompts) > 0.1
    assert sum(p < 1024 for p in prompts) / len(prompts) > 0.1
    gaps = loadgen.arrival_gaps(tr_file)
    assert sum(gaps) == pytest.approx(bench["run_seconds"])
    # every seed offers the cycle's requests, from another phase
    a = loadgen.open_schedule(tr_file, 11, 50.0)
    b = loadgen.open_schedule(tr_file, 2 ** 31 + 7, 50.0)
    in_window = lambda plan: sorted(
        (p.prompt_tokens, p.output_tokens) for p in plan if p.due_s >= 0)
    assert in_window(a) == in_window(b) == sorted(cycle)
    # every context fits the engine's longest sequence, its table and
    # the cap ISSUE 31 gives; the cap stays over 2 windows and a tick
    longest = max(p + o for p, o in cycle)
    assert longest <= 16256 < config["engine"]["max_seq_len"]
    assert tr_file["prompt_tokens"]["max"] >= 2 * window + 512


# ---- the readers -------------------------------------------------------

P0 = "/device:TPU:0"
MODEL = {"num_attention_heads": 48, "num_key_value_heads": 8,
         "head_dim": 128, "layer_types": [S, S, S, F, S, S, S, F, S]}
KERNEL = "/jit(_ragged_call)/{}/pallas_call"


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (3 decode rows at contexts 9,000 / 5,000 / 1,000 and a
# 509-token chunk at 8,000) and one decode tick (20 rows at 100,000
# tokens of context between them, 60,000 inside their windows), ns.
CHUNK_PAIRS = 509 * 8000 + 509 * 510 // 2
RAGGED = dict(
    kind="ragged", T=512, ctx=1024, rows=4, decode_rows=3,
    prefill_tokens=509, kv_tokens=9001 + 5001 + 1001 + 8509,
    attn_pairs=9001 + 5001 + 1001 + CHUNK_PAIRS, decode_pairs=15003,
    win_kv_tokens=4096 + 4096 + 1001 + (509 + 4095),
    win_attn_pairs=4096 + 4096 + 1001 + 509 * 4096,
    win_decode_pairs=9193, built=0)
DECODE = dict(
    kind="decode", T=32, ctx=1024, rows=20, decode_rows=20,
    prefill_tokens=0, kv_tokens=100000, attn_pairs=100000,
    decode_pairs=100000, win_kv_tokens=60000, win_attn_pairs=60000,
    win_decode_pairs=60000, built=0)
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, **RAGGED),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, **DECODE),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "ragged_window_attention.3[custom-call]", 1300, 500,
         "jit(run)/attn/swa" + KERNEL.format("ragged_window_attention"),
         0],
        [P0, tr.OPS, "ragged_paged_attention.4[custom-call]", 1800, 300,
         "jit(run)/attn/full" + KERNEL.format("ragged_paged_attention"),
         0],
        [P0, tr.OPS, "fusion.5", 2100, 700,
         "jit(run)/mlp/moe_experts/dot_general", 0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "ragged_window_attention.3[custom-call]", 3300, 400,
         "jit(step)/attn/swa" + KERNEL.format("ragged_window_attention"),
         0],
        [P0, tr.OPS, "ragged_paged_attention.4[custom-call]", 3700, 200,
         "jit(step)/attn/full" + KERNEL.format("ragged_paged_attention"),
         0],
        [P0, tr.OPS, "fusion.9", 3900, 400, "jit(step)/mlp/dot", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}
GROUPS = [
    {"name": "full", "layers": [3, 7], "window": None,
     "row": {"bytes_per_token_layer": 4096}, "pages_at_peak": 4000},
    {"name": "window", "layers": [0, 1, 2, 4, 5, 6, 8], "window": 4096,
     "row": {"bytes_per_token_layer": 4096}, "pages_at_peak": 2500},
]
NEW = ("kernel.swa_attn_share", "kernel.swa_attn_roofline_share",
       "kernel.gqa_attn_roofline_share", "kv.window_saved_share")


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
    busy = 500 + 300 + 700 + 400 + 200 + 400
    assert _reader("kernel.swa_attn_share").read(run) == pytest.approx(
        100 * 900 / busy)
    assert _reader("kernel.ragged_attn_share").read(run) == pytest.approx(
        100 * 500 / busy)
    row, pair, qo = 4096, 4 * 48 * 128, 2 * 48 * 128 * 2
    # the window layers: the ragged tick is bound by operations (the
    # chunk's 2.1M kept pairs), the decode tick by bytes
    ragged_s = 7 * pair * RAGGED["win_attn_pairs"] / 197e12
    assert ragged_s > 7 * (RAGGED["win_kv_tokens"] * row + 512 * qo) / 819e9
    decode_s = 7 * (60000 * row + 20 * qo) / 819e9
    assert decode_s > 7 * pair * 60000 / 197e12
    assert _reader("kernel.swa_attn_roofline_share").read(
        run) == pytest.approx(100 * (ragged_s + decode_s) / 900e-9)
    # the full layers: whole contexts
    ragged_f = 2 * pair * RAGGED["attn_pairs"] / 197e12
    assert ragged_f > 2 * (RAGGED["kv_tokens"] * row + 512 * qo) / 819e9
    decode_f = 2 * (100000 * row + 20 * qo) / 819e9
    assert _reader("kernel.gqa_attn_roofline_share").read(
        run) == pytest.approx(100 * (ragged_f + decode_f) / 500e-9)
    # 4,000 pages in 2 layers and 2,500 in 7, where 4,000 in all 9
    assert _reader("kv.window_saved_share").read(run) == pytest.approx(
        100 * (1 - (4000 * 2 + 2500 * 7) / (4000 * 9)))


def test_new_readers_find_nothing_in_a_dense_programs_capture(monkeypatch):
    """Laid over the parent (no such kernel, no window counts on the
    span, no groups in the stats), and on no run at all: nothing, and
    no error."""
    dense = json.loads(json.dumps(HAND))
    dense["events"] = [e for e in dense["events"]
                       if "ragged_window" not in e[2]]
    for s in dense["spans"]:
        for key in ("win_kv_tokens", "win_attn_pairs", "win_decode_pairs"):
            s[4].pop(key, None)
    monkeypatch.setattr(sr, "capture", lambda run: dense)
    model = {k: v for k, v in MODEL.items() if k != "layer_types"}
    run = {"events": dense["events"], "config": model,
           "device_kind": "TPU v5 lite",
           "marks": {"end": {"stats": {"free_pages": 3}}}}
    for name in NEW:
        assert _reader(name).read(run) is None, name
        assert _reader(name).read({}) is None, name
    # one group, no window: nothing saved to report
    run["marks"]["end"]["stats"]["cache_groups"] = [
        {**GROUPS[0], "name": "all"}]
    assert _reader("kv.window_saved_share").read(run) is None


def test_new_metrics_come_last_and_are_the_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    assert by_name["kv.window_saved_share"]["moves"] == "serve_tok_s"
    assert by_name["kv.window_saved_share"]["layer"] == "cache manager"
    # these reckon every layer's whole context, or an 88.1 MB expert
    for name in ("kernel.ragged_attn_hbm_share",
                 "kernel.paged_decode_hbm_share", "moe.experts_hbm_share",
                 "kernel.mla_attn_share"):
        assert CELL not in by_name[name]["workloads"]
    for name in ("step.decode_ms", "step.ragged_ms", "kv.peak_occupancy",
                 "moe.experts_share", "kernel.ragged_attn_share",
                 "device.idle_share.serve"):
        assert by_name[name]["workloads"][-1] == CELL
    for m in bench["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "serve_tok_s"):
            assert m["workloads"][-1] == CELL
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [c["name"] for c in bench["configs"]][-1] == (
        "trinity-large-ep16-d9")


def test_cost_functions_by_hand():
    assert costs.kv_row_bytes(MODEL) == 4096          # K and V, 8 x 128
    assert costs.kv_row_bytes({**MODEL, "head_dim": 64}) == 4096  # padded
    assert (costs.layers_of(MODEL, S), costs.layers_of(MODEL, F)) == (7, 2)
    span = {"kind": "ragged", "rows": 2, "decode_rows": 1,
            "prefill_tokens": 100, "kv_tokens": 5001 + 6100,
            "attn_pairs": 5001 + 100 * 6000 + 5050, "decode_pairs": 5001,
            "win_kv_tokens": 4096 + 4195,
            "win_attn_pairs": 4096 + 100 * 4096}
    qo = 101 * 2 * 48 * 128 * 2
    assert costs.full_attention_min_bytes(MODEL, span) == 2 * (
        11101 * 4096 + qo)
    assert costs.window_attention_min_bytes(MODEL, span) == 7 * (
        8291 * 4096 + qo)
    assert costs.full_attention_min_flops(MODEL, span) == (
        2 * 4 * 48 * 128 * 610051)
    assert costs.window_attention_min_flops(MODEL, span) == (
        7 * 4 * 48 * 128 * 413696)
    # a window layer never needs more than a full one
    assert (costs.window_attention_min_flops(MODEL, span) / 7
            < costs.full_attention_min_flops(MODEL, span) / 2)
    bare = {k: v for k, v in span.items() if not k.startswith("win_")}
    assert costs.window_attention_min_bytes(MODEL, bare) is None
    assert costs.window_attention_min_flops(MODEL, bare) is None
    # a span of a tree before `attn_pairs`: the context alone, never more
    old = {k: v for k, v in bare.items() if k != "attn_pairs"}
    assert costs.full_attention_min_flops(MODEL, old) == (
        2 * 4 * 48 * 128 * 11101)


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "model_type", "score_func", "hidden_act", "tie_word_embeddings",
        "rope_scaling", "n_group", "topk_group", "num_expert_groups",
        "num_limited_groups", "route_norm", "route_scale", "rope_theta",
        "rms_norm_eps", "mup_enabled", "num_shared_experts",
        "global_attn_every_n_layers", "num_experts_per_tok")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "num_dense_layers": 1, "layer_types": [S, S, S, F],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "sliding_window": 8, "max_position_embeddings": 256, "num_experts": 8,
    "deployment": {"experts_held": [0, 8], "router_width": 16},
    # page 16: `serve._warm`'s anchors want room in a context bucket.
    # The gather path: a program of this family through the interpreted
    # kernel takes a minute to compile on a CPU and the rehearsal builds
    # a dozen; tests/test_trinity.py holds the kernel path to the
    # reference
    "engine": {"max_batch_size": 10, "page_size": 16, "num_pages": 64,
               "num_pages_by_group": {"full": 64, "window": 40},
               "max_prefill_tokens": 16, "max_num_batched_tokens": 16,
               "max_seq_len": 128, "decode_impl": "gather"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_trinity
    traffic = {**rehearsal.CHAT, "runner": "serve_trinity", "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_trinity.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("trinity"), seconds=1.5))


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
    for name in ("engine_program.mixed", "engine_program.decode"):
        # the engine's own jit_run / jit_step with the rider, temperature 0
        assert logits[name]["ok"] and logits[name]["rider_total"] > 0
        assert logits[name]["argmax_agree"] >= 9, name
    assert logits["expert_layer"]["ok"]
    assert served.correct == logits["ok"]
    # the checks gave everything back
    groups = served.detail["cache_groups"]
    assert [g["name"] for g in groups] == ["full", "window"]
    assert all(g["pages_used"] == 0 for g in groups)
    assert groups[1]["pages_returned"] >= moved["pages_handed_back"]
    # the peaks are the ramp's and the window's, not the checks'
    assert 0 < groups[1]["pages_at_peak"] <= groups[1]["pages_peak"] < 20
    warm = served.detail["warmup"]
    assert warm["programs_built"] == (len(warm["t_buckets"])
                                      * len(warm["ctx_buckets"]))
    marks = served.run["marks"]
    built = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    assert built(marks["end"]) == built(marks["start"])
    moe = served.detail["moe"]
    assert moe["experts_held"] == [0, 8] and moe["assignments_landed"] > 0
    for name in ("setup_s", "serve_tok_s", "itl_p95_ms"):
        assert served.end_to_end[name] > 0


def test_precision_probe_gives_each_limit_its_second_reading(tmp_path):
    """The readings the limits are set against (`--probe`), at a toy
    size: the reference with float8 operands, and wrong in each of the
    seven ways, against itself."""
    from benchmarks.lib import checks_trinity
    from benchmarks.runners import serve_trinity
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_trinity._build_server(ctx).engine
    said = []
    got = checks_trinity.precision_probe(eng, DEBUG, 3, said.append)
    assert set(got) == {"fp8", *checks_trinity.VARIANTS}
    assert len(said) == 8
    for name, g in got.items():
        assert len(g["rows"]) == 20 and g["finite"], name
        assert len(g["past_window_median_row"]) == 2
    # at a toy size most read as wrong by the median alone; a full layer
    # windowed shows only past the window, there as here
    for name in ("fp8", "no_gate", "no_qk_norm", "no_embed_scale"):
        assert got[name]["median_row"] > 0.05, name
        assert not got[name]["would_pass"], name
    assert max(got["all_window"]["past_window_median_row"]) > (
        got["all_window"]["median_row"])


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
    # a toy sequence is a page in either group: nothing saved, said so
    assert traced["metrics"]["kv.window_saved_share"]["value"] == 0.0
    assert not {"kernel.swa_attn_share", "kernel.swa_attn_roofline_share",
                "kernel.gqa_attn_roofline_share"} & set(traced["metrics"])
    json.dumps(traced)
