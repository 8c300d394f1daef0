"""The cell `dsv3-longchat` and what it brings: the configuration
against the catalog row's keys, the traffic file through the load
generator, each new reader on a small capture and on nothing, the cost
functions by hand, and a rehearsal of the new runner at a tiny size."""

import json
import os
import statistics

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs_deepseek_v3 as costs
from benchmarks.lib import loadgen, program_deepseek_v3
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import spans_deepseek_v3
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "dsv3-longchat"
# `config` of the row `DeepSeek-V3` in the catalog beside the
# model-configs guide (source_url below), copied here: the catalog is
# not part of the repository
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v3",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280,
}
SOURCE = ("https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/"
          "config.json")
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key_but_the_reduced(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == REDUCED
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # the floors: a whole period and four layers after the dense ones,
    # at least 8 routed experts, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] - config[
        "first_k_dense_replace"] >= 4 and config["first_k_dense_replace"] >= 1
    assert config["n_routed_experts"] == 16 >= 8
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] * config["n_routed_experts"] == 256
    assert dep["experts_held"] == [0, 16] and dep["router_width"] == 256
    assert "num_nextn_predict_layers" in config["not_run"]
    assert config["engine"] == {
        "max_batch_size": 64, "page_size": 16, "num_pages": 16384,
        "max_seq_len": 8192, "max_num_batched_tokens": 512}


def test_adapter_builds_the_published_widths(resolved):
    config = resolved[2]
    cfg = program_deepseek_v3.model_config(config)
    assert (cfg.hidden, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.ffn, cfg.moe_ffn) == (7168, 128, 1536, 512, 128, 64, 128,
                                      18432, 2048)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.held,
            cfg.n_routed_experts, cfg.vocab_size) == (5, 1, (0, 16), 256,
                                                      16160)
    assert (cfg.n_group, cfg.topk_group, cfg.moe_top_k,
            cfg.routed_scaling_factor) == (8, 4, 8, 2.5)
    assert (cfg.rope_factor, cfg.rope_original_max) == (40, 4096)
    # ISSUE 27's arithmetic: 4,566M parameters, 9.13 GB in bfloat16
    assert round(cfg.num_params() / 1e6) == 4566
    with pytest.raises(ValueError, match="scoring_func"):
        program_deepseek_v3.model_config({**config,
                                          "scoring_func": "softmax"})


def test_traffic_file_through_the_load_generator(resolved):
    bench, cell, _, tr_file = resolved
    assert tr_file["runner"] == "serve_deepseek_v3"
    assert tr_file["loop"] == "open"
    assert tr_file["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.6, "min": 512,
        "max": 6144}
    assert tr_file["output_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.7, "min": 32,
        "max": 384}
    assert tr_file["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (tr_file["ramp_s"], tr_file["grace_s"]) == (30, 45)
    # a window holds exactly one cycle
    assert tr_file["cycle"] == pytest.approx(
        tr_file["rate_rps"] * bench["run_seconds"])
    cycle = loadgen.length_cycle(tr_file)
    assert len(cycle) == tr_file["cycle"]
    prompts = [p for p, _ in cycle]
    assert min(prompts) == 512 and max(prompts) == 6144
    assert 2300 < statistics.mean(prompts) < 2500
    assert 170 < statistics.mean(o for _, o in cycle) < 200
    gaps = loadgen.arrival_gaps(tr_file)
    assert sum(gaps) == pytest.approx(bench["run_seconds"])
    # every seed offers the cycle's requests, from another phase
    a = loadgen.open_schedule(tr_file, 11, 50.0)
    b = loadgen.open_schedule(tr_file, 2 ** 31 + 7, 50.0)
    in_window = lambda plan: sorted(
        (p.prompt_tokens, p.output_tokens) for p in plan if p.due_s >= 0)
    assert in_window(a) == in_window(b) == sorted(cycle)
    # every context fits the engine's longest sequence and its table
    assert max(p + o for p, o in cycle) <= 8192


# ---- the readers -------------------------------------------------------

P0 = "/device:TPU:0"
RUN = "jit(run)/attn/mla/"
MODEL = {"num_hidden_layers": 5, "num_attention_heads": 128,
         "kv_lora_rank": 512, "qk_rope_head_dim": 64,
         "qk_nope_head_dim": 128, "v_head_dim": 128,
         "hidden_size": 7168, "moe_intermediate_size": 2048}


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (63 decode rows at 152,544 tokens of context between
# them + a 448-token chunk at context 2,560: 1,247,456 pairs and 3,008
# latent rows read) and one decode tick (40 rows), times in ns.
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, kind="ragged", T=512,
              ctx=512, rows=64, decode_rows=63, prefill_tokens=448,
              kv_tokens=155552, attn_pairs=1400000, decode_pairs=152544,
              built=0),
        _span("fold", 2900, 2950, tokens=64, of=1, moe_experts_hit=64,
              moe_assignments=1000),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, kind="decode", T=64,
              ctx=512, rows=40, decode_rows=40, prefill_tokens=0,
              kv_tokens=120000, attn_pairs=120000, decode_pairs=120000,
              built=0),
        _span("fold", 4900, 4950, tokens=40, of=2, moe_experts_hit=50,
              moe_assignments=80),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "mla_ragged_attention.3[custom-call]", 1300, 600,
         RUN + "jit(_mla_call)/mla_ragged_attention/pallas_call", 0],
        [P0, tr.OPS, "fusion.4", 1900, 500,
         "jit(run)/mlp/moe_experts/dot_general", 0],
        [P0, tr.OPS, "fusion.5", 2400, 400, "jit(run)/mlp/moe_shared/dot",
         0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "mla_ragged_attention.3[custom-call]", 3300, 200,
         "jit(step)/attn/mla/mla_ragged_attention/pallas_call", 0],
        [P0, tr.OPS, "fusion.9", 3500, 800,
         "jit(step)/mlp/moe_experts/dot_general", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}
NEW = ("kernel.mla_attn_share", "kernel.mla_attn_roofline_share",
       "moe.experts_share", "moe.experts_hbm_share")


def _reader(name):
    return bench_run.load_layer_metric(ROOT, name)


@pytest.fixture
def run_with_capture(monkeypatch):
    monkeypatch.setattr(sr, "capture", lambda run: HAND)
    return {"events": HAND["events"], "config": MODEL,
            "device_kind": "TPU v5 lite"}


def test_new_readers_on_a_capture_worked_out_by_hand(run_with_capture):
    run = run_with_capture
    busy = 600 + 500 + 400 + 200 + 800
    assert _reader("kernel.mla_attn_share").read(run) == pytest.approx(
        100 * 800 / busy)
    assert _reader("moe.experts_share").read(run) == pytest.approx(
        100 * 1300 / busy)
    # the ragged tick is bound by operations, the decode tick by bytes;
    # the chunk's pairs count at the decompressed form's rate plus the
    # up-projection of the 3,008 latent rows it reads, its fewer
    pair = 2 * 128 * (576 + 512)
    chunk = 2 * 128 * (192 + 128) * 1247456 + 3008 * 2 * 512 * 128 * 256
    assert chunk < pair * 1247456
    ragged_s = 5 * (pair * 152544 + chunk) / 197e12
    assert ragged_s > 5 * (155552 * 1280 + 511 * 128 * 1088 * 2) / 819e9
    decode_s = 5 * (120000 * 1280 + 40 * 128 * 1088 * 2) / 819e9
    assert decode_s > 5 * pair * 120000 / 197e12
    assert _reader("kernel.mla_attn_roofline_share").read(
        run) == pytest.approx(100 * (ragged_s + decode_s) / 800e-9)
    expert = 3 * 7168 * 2048 * 2
    need = (64 + 50) * expert + (1000 + 80) * 2 * 7168 * 2
    assert _reader("moe.experts_hbm_share").read(run) == pytest.approx(
        100 * need / 1300e-9 / 819e9)


def test_new_readers_find_nothing_in_a_dense_programs_capture(monkeypatch):
    """Laid over the parent (no such kernel, scope or fold arguments),
    and on no run at all: nothing, and no error."""
    dense = json.loads(json.dumps(HAND))
    dense["events"] = [e for e in dense["events"] if e[1] == tr.MODULES]
    dense["events"].append(
        [P0, tr.OPS, "fusion.1", 1300, 900, "jit(run)/attn/dot", 0])
    for s in dense["spans"]:
        for key in ("of", "moe_experts_hit", "moe_assignments",
                    "attn_pairs", "decode_pairs"):
            s[4].pop(key, None)
    monkeypatch.setattr(sr, "capture", lambda run: dense)
    run = {"events": dense["events"], "config": MODEL,
           "device_kind": "TPU v5 lite"}
    for name in NEW:
        assert _reader(name).read(run) is None, name
        assert _reader(name).read({}) is None, name


def test_new_metrics_are_the_cells_alone_and_old_ones_kept_theirs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "itl_p95_ms"
    # the dense kernels' byte shares reckon per-head K and V rows
    for name in ("kernel.ragged_attn_hbm_share",
                 "kernel.paged_decode_hbm_share",
                 "kernel.ragged_attn_share"):
        assert CELL not in by_name[name]["workloads"]
    for name in ("step.decode_ms", "engine.tick_host_ms",
                 "kv.peak_occupancy", "device.idle_share.serve"):
        assert by_name[name]["workloads"] == ["chat-open", CELL]


def test_cost_functions_by_hand():
    assert costs.latent_row_bytes(MODEL) == 1280
    # ISSUE 27: a decode row at context C costs 128 x (576 + 512) x 2 x C
    # operations on 1,280 x C bytes
    span = {"kind": "decode", "rows": 1, "kv_tokens": 1000,
            "attn_pairs": 1000}
    assert costs.mla_attention_min_flops(MODEL, span) == (
        5 * 128 * 1088 * 2 * 1000)
    assert costs.mla_attention_min_bytes(MODEL, span) == 5 * (
        1000 * 1280 + 128 * 1088 * 2)
    # a span without attn_pairs still gives a least count
    del span["attn_pairs"]
    assert costs.mla_attention_min_flops(MODEL, span) == (
        5 * 128 * 1088 * 2 * 1000)
    # a 512-token chunk at a 3k context: decompressed keys and values
    # and one up-projection of the 3,584 rows it reads, 0.55 of absorbed
    pairs = 512 * 3072 + 512 * 513 // 2
    chunk = {"kind": "ragged", "kv_tokens": 3584, "attn_pairs": pairs,
             "decode_pairs": 0}
    assert costs.mla_attention_min_flops(MODEL, chunk) == 5 * (
        2 * 128 * 320 * pairs + 3584 * 2 * 512 * 128 * 256)
    assert (costs.mla_attention_min_flops(MODEL, chunk)
            < 0.56 * 5 * 128 * 1088 * 2 * pairs)
    # one token of prefill at a long context is a decode row's work:
    # absorbed is its fewer
    tail = {"kind": "ragged", "kv_tokens": 4001, "attn_pairs": 4001,
            "decode_pairs": 0}
    assert costs.mla_attention_min_flops(MODEL, tail) == (
        5 * 128 * 1088 * 2 * 4001)
    # without decode_pairs: every pair at the decompressed rate
    del chunk["decode_pairs"]
    assert costs.mla_attention_min_flops(MODEL, chunk) == (
        5 * 2 * 128 * 320 * pairs)
    assert costs.expert_bytes(MODEL) == 88_080_384
    assert spans_deepseek_v3.in_scope(
        "jit(run)/mlp/moe_experts/dot_general", "moe_experts")
    assert not spans_deepseek_v3.in_scope(
        "jit(run)/mlp/moe_shared/dot_general", "moe_experts")


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "model_type", "scoring_func", "topk_method", "hidden_act",
        "tie_word_embeddings", "attention_bias", "moe_layer_freq",
        "norm_topk_prob", "rope_theta", "rms_norm_eps",
        "n_shared_experts")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
    "max_position_embeddings": 256, "n_routed_experts": 8,
    "rope_scaling": {**CATALOG["rope_scaling"],
                     "original_max_position_embeddings": 32},
    "deployment": {"experts_held": [0, 8], "router_width": 16},
    "engine": {"max_batch_size": 8, "page_size": 16, "num_pages": 64,
               "max_prefill_tokens": 8, "max_seq_len": 256,
               "decode_impl": "pallas_interpret"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_deepseek_v3
    traffic = {**rehearsal.CHAT, "runner": "serve_deepseek_v3", "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_deepseek_v3.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("dsv3"), seconds=1.5))


def test_runner_rehearsal_serves_checks_and_warms(served):
    assert served.failed == 0 and served.attempted >= 6
    logits = served.detail["logits"]
    for name in ("kernel_vs_gather.mixed", "kernel_vs_gather.decode",
                 "gather_vs_reference.mixed",
                 "gather_vs_reference.decode"):
        # 6 decode rows, a chunk and a prompt; then all 8 slots
        assert logits[name]["finite"] and len(logits[name]["rows"]) == 8
        assert logits[name]["median_row"] < 0.04, name      # toy size
    # at the engine's own sizes: contexts to three quarters of max_seq_len
    assert (logits["longest_context"], logits["T"],
            logits["ctx_bucket_pages"]) == (192, 16, 16)
    for name in ("engine_program.mixed", "engine_program.decode"):
        # the engine's own jit_run / jit_step with the rider, temperature 0
        assert logits[name]["ok"] and logits[name]["rider_total"] > 0
        assert logits[name]["argmax_agree"] >= 7, name
    # one attention block through the cache, same input both sides
    block = logits["attention_block"]
    assert block["ok"] and block["context"] == 176
    assert set(block) >= {"gather", "pallas_interpret"}
    assert logits["expert_layer"]["ok"]
    assert served.correct == logits["ok"]
    warm = served.detail["warmup"]
    # the checks built the (16, 16) program; the warm-up walks its own
    assert warm["programs_built"] == (len(warm["t_buckets"])
                                      * len(warm["ctx_buckets"]))
    assert warm["ragged_programs"] == warm["programs_built"] + 1
    marks = served.run["marks"]
    built = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    assert built(marks["end"]) == built(marks["start"])
    moe = served.detail["moe"]
    assert moe["experts_held"] == [0, 8] and moe["assignments_landed"] > 0
    for name in ("setup_s", "serve_tok_s", "itl_p95_ms"):
        assert served.end_to_end[name] > 0


def test_precision_probe_gives_each_limit_its_second_reading(tmp_path):
    """The readings the limits are set against (`--probe`), at a toy
    size: the reference with float8 operands and without m^2 against
    itself, for the logits and for the attention block; float8 is far
    from float32 at any size."""
    from benchmarks.lib import checks_deepseek_v3
    from benchmarks.runners import serve_deepseek_v3
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_deepseek_v3._build_server(ctx).engine
    said = []
    got = checks_deepseek_v3.precision_probe(eng, DEBUG, 3, said.append)
    assert len(got["rows"]) == 8 and got["finite"]
    # 3 layers of toy widths: 0.19 here, 0.52 at the cell's (PERF.md)
    assert got["median_row"] > 0.1
    assert got["without_m2"]["median_row"] > 0
    assert got["attention_block"]["fp8"] > 0.05
    assert got["attention_block"]["without_m2"] > 0
    assert len(said) == 3


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
    assert not set(NEW) & set(traced["metrics"])
    json.dumps(traced)
