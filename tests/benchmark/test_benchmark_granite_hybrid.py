"""The cell `granite-concurrent` and what it brings: the configuration
against the catalog row's keys, the adapter, the traffic file through the
load generator, each new reader on a small capture worked out by hand and
on runs that have nothing for it (the recorded fixtures, other families'
runs, `{}`), the cost function by hand, a rehearsal of the new runner at
a tiny size, and its `BENCHMARK.json` entries BY NAME and as subsets (a
later cell may come behind this one)."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs_granite_hybrid as costs
from benchmarks.lib import loadgen, program_granite_hybrid
from benchmarks.lib import span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

import rehearsal

CELL = "granite-concurrent"
MAMBA, ATTN = "mamba", "attention"
# `config` of the row `granite-4.0-h-micro` in the catalog beside the
# model-configs guide (source_url below), copied here: the catalog is not
# part of the repository
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ([MAMBA] * 5 + [ATTN] + [MAMBA] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/"
          "blob/main/config.json")
NEW = ("step.mamba_mixer_share", "kernel.hybrid_ssd_scan_share",
       "kernel.hybrid_ssd_scan_roofline_share", "kv.state_rows_per_tick",
       "kv.hybrid_state_slots_peak_share")
P0 = "/device:TPU:0"


@pytest.fixture(scope="module")
def resolved():
    return bench_run.resolve(ROOT, CELL)


def test_configuration_keeps_every_catalog_key(resolved):
    bench, cell, config, _ = resolved
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == []
    assert entry["file"] == "benchmarks/configs/granite-4.0-h-micro.json"
    for key, value in CATALOG.items():
        assert config[key] == value, key
    assert [i for i, k in enumerate(config["layer_types"]) if k == ATTN] \
        == [5, 15, 25, 35]
    assert config["deployment"]["chips"] == 1
    assert config["published"]["parameters"].startswith("3,191,396,096")
    for word in ("d_inner", "gated_norm", "time_step_limit",
                 "mamba_chunk_size", "feed_forward", "positions", "weights",
                 "torch_dtype"):
        assert word in config["assumed"], word
    assert config["engine"] == {
        "max_batch_size": 48, "page_size": 16,
        "num_pages": config["engine"]["num_pages"], "max_seq_len": 3072,
        "max_num_batched_tokens": 512}
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert cell["chips"] == 1


def test_adapter_builds_the_published_widths(resolved):
    _, _, config, _ = resolved
    cfg = program_granite_hybrid.model_config(config)
    assert cfg.num_params() == 3_191_396_096
    assert (cfg.hidden, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
            cfg.n_groups, cfg.d_conv, cfg.ffn) == (2048, 64, 64, 128, 1, 4,
                                                   8192)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                12, 0.22, 0.015625, 8)
    assert cfg.vocab_size == 100352 and len(cfg.units) == 36
    back = program_granite_hybrid.published_keys(cfg)
    assert all(config[k] == v for k, v in back.items())
    with pytest.raises(ValueError, match="position_embedding_type"):
        program_granite_hybrid.model_config(
            {**config, "position_embedding_type": "rope"})
    with pytest.raises(ValueError, match="num_local_experts"):
        program_granite_hybrid.model_config(
            {**config, "num_local_experts": 8})
    with pytest.raises(ValueError, match="disagree"):
        program_granite_hybrid.model_config(
            {**config, "num_hidden_layers": 32})
    # the engine the file states: weights, pages and state
    from ray_tpu.models.family import family_of
    eng = config["engine"]
    full, state = family_of(cfg).cache_groups(cfg, "pallas")
    assert full.bytes_per_token == 8192        # 8 heads of 64, K and V
    assert state.bytes_per_slot == 36 * (2_097_152 + 26_112)
    held = (2 * cfg.num_params()
            + eng["num_pages"] * eng["page_size"] * full.bytes_per_token
            + eng["max_batch_size"] * state.bytes_per_slot)
    assert held > 0.6 * 17.18e9
    assert 2 * cfg.num_params() == pytest.approx(6.383e9, rel=1e-3)


def test_traffic_file_through_the_load_generator(resolved):
    bench, _, _, traffic = resolved
    assert traffic["runner"] == "serve_granite_hybrid"
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.9, "min": 32,
        "max": 2048}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 320, "sigma": 0.6, "min": 64,
        "max": 768}
    assert traffic["arrival"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (traffic["ramp_s"], traffic["grace_s"], traffic["trace_s"]) == (
        30, 45, 4)
    # the cycle is the requests one window holds at the committed rate
    assert traffic["cycle"] == pytest.approx(
        traffic["rate_rps"] * bench["run_seconds"])
    cycle = loadgen.length_cycle(traffic)
    assert len(cycle) == traffic["cycle"]
    # the clips bind at the cycle's ends or nearly (the quantile grid of
    # a cycle this long reaches them or stops a step short)
    prompts, outs = [p for p, _ in cycle], [o for _, o in cycle]
    assert 32 <= min(prompts) <= 40 and 1500 <= max(prompts) <= 2048
    assert 64 <= min(outs) <= 80 and 700 <= max(outs) <= 768
    # contexts stay inside max_seq_len (192 pages of 16)
    assert max(p + o for p, o in cycle) <= 3072
    gaps = loadgen.arrival_gaps(traffic)
    assert sum(gaps) == pytest.approx(bench["run_seconds"])
    # a seed picks the phase: the same sizes after the same gaps
    a = loadgen.open_schedule(traffic, 5, 50.0)
    b = loadgen.open_schedule(traffic, 5 + traffic["cycle"], 50.0)
    assert [(r.prompt_tokens, r.output_tokens, r.due_s) for r in a] == [
        (r.prompt_tokens, r.output_tokens, r.due_s) for r in b]
    sizes = lambda plan: sorted((r.prompt_tokens, r.output_tokens)
                                for r in plan if r.due_s >= 0)
    c = loadgen.open_schedule(traffic, 2147483659, 50.0)
    assert len(sizes(c)) in (traffic["cycle"] - 1, traffic["cycle"])
    assert "sweep" in traffic["stands_for"]


SCAN = "/jit(_ssd_call)/ssd_ragged_scan/pallas_call"


def _span(name, a, b, **args):
    return ["t", "engine." + name, a, b, args]


# One ragged tick (30 decode rows and a 482-token chunk) and one decode
# tick (33 rows), ns.
RAGGED = dict(kind="ragged", T=512, ctx=192, rows=31, decode_rows=30,
              prefill_tokens=482, kv_tokens=30 * 601 + 482,
              attn_pairs=30 * 601 + 482 * 483 // 2, decode_pairs=30 * 601,
              ssm_tokens=512, ssm_rows=31, built=0)
DECODE = dict(kind="decode", T=48, ctx=192, rows=33, kv_tokens=33 * 600,
              ssm_tokens=33, ssm_rows=33, built=0)
HAND = {
    "spans": sorted([
        _span("step", 1000, 3000, tick=1, work=1),
        _span("dispatch", 1100, 1200, tick=1, **RAGGED),
        _span("step", 3000, 5000, tick=2, work=1),
        _span("dispatch", 3100, 3200, tick=2, **DECODE),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, tr.MODULES, "jit_run(7)", 1300, 1500, "", 1],
        [P0, tr.OPS, "fusion.2", 1300, 300,
         "jit(run)/while/body/mamba_mixer/dot_general", 0],
        [P0, tr.OPS, "ssd_ragged_scan.3[custom-call]", 1600, 400,
         "jit(run)/while/body/mamba_mixer/ssd_scan" + SCAN, 0],
        [P0, tr.OPS, "fusion.5", 2000, 500,
         "jit(run)/while/body/mlp/dot_general", 0],
        [P0, tr.MODULES, "jit_step(8)", 3300, 1000, "", 2],
        [P0, tr.OPS, "ssd_ragged_scan.3[custom-call]", 3300, 600,
         "jit(step)/while/body/mamba_mixer/ssd_scan" + SCAN, 0],
        [P0, tr.OPS, "fusion.9", 3900, 200,
         "jit(step)/while/body/cond/attn_mixer/dot", 0],
    ],
    "enqueues": {1: 1150, 2: 3150},
}
GROUPS = [
    {"name": "full", "layers": [5, 15, 25, 35], "window": None,
     "row": {"bytes_per_token_layer": 2048}, "pages_at_peak": 2000},
    {"name": "state", "kind": "state",
     "layers": [l for l in range(40) if l % 10 != 5], "window": None,
     "bytes_per_slot": 76437504, "slots_total": 48, "slots_held": 20,
     "slots_peak": 36, "slots_at_peak": 36},
]


def _reader(name):
    return bench_run.load_layer_metric(ROOT, name)


@pytest.fixture
def run_with_capture(monkeypatch, resolved):
    monkeypatch.setattr(sr, "capture", lambda run: HAND)
    return {"events": HAND["events"], "config": resolved[2],
            "device_kind": "TPU v5 lite",
            "marks": {"end": {"stats": {"cache_groups": GROUPS}}}}


def test_new_readers_on_a_capture_worked_out_by_hand(run_with_capture):
    run = run_with_capture
    busy = 300 + 400 + 500 + 600 + 200
    assert _reader("step.mamba_mixer_share").read(run) == pytest.approx(
        100 * (300 + 400 + 600) / busy)
    assert _reader("kernel.hybrid_ssd_scan_share").read(
        run) == pytest.approx(100 * 1000 / busy)
    # the scan: a token's x (bf16) and y (f32) over 4,096 channels, its
    # 64 Deltas, its B and C of 128 each (bf16, ONE group): 25,344 B; a
    # row's state in and out, 2 x 2,097,152 B; 36 layers
    token, state = 4096 * 2 + 64 * 4 + 2 * 128 * 2 + 4096 * 4, 2 * 2097152
    assert token == 25344
    ragged_b = 36 * (512 * token + 31 * state)
    decode_b = 36 * (33 * token + 33 * state)
    assert _reader("kernel.hybrid_ssd_scan_roofline_share").read(
        run) == pytest.approx(
            100 * (ragged_b + decode_b) / 819e9 / 1000e-9)
    assert _reader("kv.state_rows_per_tick").read(run) == 32.0
    assert _reader("kv.hybrid_state_slots_peak_share").read(run) == 75.0
    # the NemotronH family's readers of the same kernel say nothing here
    for name in ("kernel.ssd_scan_share", "kernel.ssd_scan_roofline_share",
                 "kv.state_slots_peak_share"):
        assert _reader(name).read(run) is None, name


def _fixture(name):
    with open(os.path.join(ROOT, "benchmarks", "fixtures", name)) as f:
        cap = json.load(f)
    if "enqueues" in cap:
        cap["enqueues"] = {int(k): v for k, v in cap["enqueues"].items()}
    return cap


@pytest.mark.parametrize("fixture", [
    "chat_open_ticks_spans.json", "chat_open_two_ticks.json",
    "train_packed_two_steps_spans.json"])
def test_new_readers_find_nothing_in_the_recorded_fixtures(
        monkeypatch, resolved, fixture):
    """Laid over the parent, whose programs these are: nothing, and no
    error, whatever the configuration says."""
    cap = _fixture(fixture)
    monkeypatch.setattr(sr, "capture", lambda run: cap)
    for config in ({}, resolved[2], {"model_type": "internlm2"}):
        run = {"events": cap.get("events", []), "config": config,
               "device_kind": "TPU v5 lite",
               "marks": {"end": {"stats": {"free_pages": 3}}}}
        for name in NEW:
            assert _reader(name).read(run) is None, (name, fixture)


def test_new_readers_find_nothing_in_other_families_runs(monkeypatch,
                                                         resolved):
    """Other families' runs (NemotronH's has the same kernel, the same
    counts and a state group), a capture with none of this family's
    kernel, scope or counts under this family's configuration, no run at
    all and junk: None, never an exception."""
    monkeypatch.setattr(sr, "capture", lambda run: HAND)
    stats = {"marks": {"end": {"stats": {"cache_groups": GROUPS}}}}
    for model_type in ("nemotron_h", "phi4flash", "internlm2", "kimi_linear",
                       None):
        run = {"events": HAND["events"], "device_kind": "TPU v5 lite",
               "config": {"model_type": model_type}, **stats}
        for name in NEW:
            assert _reader(name).read(run) is None, (name, model_type)
    other = json.loads(json.dumps(HAND))
    other["events"] = [e for e in other["events"]
                       if "ssd_" not in e[2] and "mamba_mixer" not in e[5]]
    for s in other["spans"]:
        for key in ("ssm_tokens", "ssm_rows"):
            s[4].pop(key, None)
    monkeypatch.setattr(sr, "capture", lambda run: other)
    run = {"events": other["events"], "device_kind": "TPU v5 lite",
           "config": resolved[2],
           "marks": {"end": {"stats": {"free_pages": 3}}}}
    for name in NEW:
        assert _reader(name).read(run) is None, name
    for junk in ({}, {"config": None}, {"marks": 3, "config": resolved[2]},
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
        "step.mamba_mixer_share": ("%", "lower", "device_trace",
                                   "model forwards", "itl_p95_ms"),
        "kernel.hybrid_ssd_scan_share": ("%", "lower", "device_trace",
                                         "kernels", "itl_p95_ms"),
        "kernel.hybrid_ssd_scan_roofline_share": (
            "%", "higher", "device_trace", "kernels", "itl_p95_ms"),
        "kv.state_rows_per_tick": ("count", "higher", "program_span",
                                   "cache manager", "serve_tok_s"),
        "kv.hybrid_state_slots_peak_share": (
            "%", "lower", "program_counter", "cache manager",
            "serve_tok_s"),
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
                 "kv.peak_occupancy", "kernel.ragged_attn_share"):
        assert CELL in by_name[name]["workloads"], name
    # ... and none that prices another family's work or pins its list
    for name in ("step.sample_share", "engine.capture_hold_ms",
                 "engine.anomaly_flags_in_window", "moe.experts_share",
                 "kernel.ssd_scan_share", "kernel.ssd_scan_roofline_share",
                 "kv.state_slots_peak_share",
                 "kernel.ragged_attn_hbm_share"):
        assert CELL not in by_name[name]["workloads"], name
    # every metric that lists the cell has a reader that says nothing on {}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert _reader(m["name"]).NAME == m["name"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == "concurrent-chat-steady"
    assert cells[CELL]["config"] == "granite-4.0-h-micro"
    assert len(cells) <= 24 and not any(w["chips"] == 4
                                        for w in cells.values())


def test_cost_function_by_hand(resolved):
    model = resolved[2]
    assert costs.mamba_layers(model) == 36
    assert costs.scan_sizes(model) == (4096, 64, 128, 524288)
    span = {"kind": "ragged", "rows": 3, "decode_rows": 2,
            "prefill_tokens": 100, "ssm_tokens": 102, "ssm_rows": 3}
    assert costs.scan_min_bytes(model, span) == 36 * (
        102 * 25344 + 3 * 4194304)
    assert costs.scan_min_bytes(model, {"kind": "ragged"}) is None


# ---- the runner --------------------------------------------------------

DEBUG = {
    **{k: CATALOG[k] for k in (
        "model_type", "hidden_act", "normalization_function",
        "position_embedding_type", "tie_word_embeddings", "attention_bias",
        "mamba_proj_bias", "mamba_conv_bias", "num_local_experts",
        "num_experts_per_tok", "mamba_d_conv", "mamba_expand",
        "mamba_n_groups", "rms_norm_eps", "embedding_multiplier",
        "residual_multiplier", "logits_scaling")},
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": [MAMBA, ATTN, MAMBA, MAMBA], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "attention_multiplier": 0.0625,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "shared_intermediate_size": 96, "max_position_embeddings": 512,
    # page 16: `serve._warm`'s anchors want room in a context bucket. The
    # gather path: tests/test_granite_hybrid.py holds the kernel path to
    # the reference. 11 slots: the decode tick has a row more than the
    # mixed tick
    "engine": {"max_batch_size": 11, "page_size": 16, "num_pages": 64,
               "max_prefill_tokens": 16, "max_num_batched_tokens": 16,
               "max_seq_len": 96, "decode_impl": "gather"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from benchmarks.runners import serve_granite_hybrid
    traffic = {**rehearsal.CHAT, "runner": "serve_granite_hybrid",
               "cycle": 5,
               "prompt_tokens": {"dist": "lognormal", "median": 10,
                                 "sigma": 0.3, "min": 8, "max": 12},
               "output_tokens": {"dist": "lognormal", "median": 3,
                                 "sigma": 0.1, "min": 3, "max": 3},
               "pair_stride": 2, "order_stride": 3, "gap_stride": 2,
               "rate_rps": 6.0}
    return serve_granite_hybrid.run(rehearsal.context(
        DEBUG, traffic, tmp_path_factory.mktemp("granite_hybrid"),
        seconds=1.5))


def test_runner_rehearsal_serves_checks_and_warms(served):
    assert served.failed == 0 and served.attempted >= 6
    logits = served.detail["logits"]
    for tick, rows in (("mixed", 10), ("decode", 11)):
        for what in ("kernel_vs_gather", "gather_vs_reference",
                     "kernel_vs_reference"):
            g = logits[f"{what}.{tick}"]
            assert g["finite"] and len(g["rows"]) == rows
            assert g["median_row"] < 0.05, (what, tick)      # toy size
        e = logits[f"engine_program.{tick}"]
        assert e["ok"] and e["argmax_agree"] >= rows - 1, tick
    # at the engine's own sizes: five ticks and three tokens
    assert (logits["longest_context"], logits["T"]) == (85, 16)
    state = logits["state_group"]
    # the fresh prompt's slot was left with another sequence's state
    assert state["reused_slot"] == 9
    assert state["state_left_in_reused_slot"] > 0.01
    assert state["state_slots_held"] == [11]
    assert logits["one_pass_float32"]["ok"] and logits["mamba_layer"]["ok"]
    assert served.correct == logits["ok"] is True
    # the checks gave everything back: read BEFORE the window (the end
    # mark's stats count the requests still live there)
    after = served.detail["cache_groups_after_checks"]
    assert [g["name"] for g in after] == ["full", "state"]
    assert after[0]["pages_used"] == 0 and after[1]["slots_held"] == 0
    # their peak was theirs (all 11 slots) ...
    assert after[1]["slots_peak"] == 11
    # ... and the end mark's is the ramp's and the window's
    groups = served.detail["cache_groups"]
    assert [g["name"] for g in groups] == ["full", "state"]
    assert 0 < groups[1]["slots_peak"] <= 11
    warm = served.detail["warmup"]
    assert warm["programs_built"] >= len(warm["t_buckets"])
    marks = served.run["marks"]
    built = lambda m: m["stats"]["jit_cache"]["compiled_programs"]
    assert built(marks["end"]) == built(marks["start"])
    for name in ("setup_s", "serve_tok_s", "itl_p95_ms"):
        assert served.end_to_end[name] > 0


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
            "kv.hybrid_state_slots_peak_share"} <= set(traced["metrics"])
    assert not {"kernel.hybrid_ssd_scan_share", "step.mamba_mixer_share",
                "kernel.hybrid_ssd_scan_roofline_share"} & set(
                    traced["metrics"])
    json.dumps(traced)


def test_precision_probe_names_the_limit_that_catches_a_fault(tmp_path):
    """The readings the limits are set against (`--probe`), at a toy
    size, for one of the planted faults of ISSUE 52 (the chip's probe
    reads them all): caught by a limit, named."""
    from benchmarks.lib import checks_granite_hybrid
    from benchmarks.runners import serve_granite_hybrid
    ctx = rehearsal.context(DEBUG, {**rehearsal.CHAT}, tmp_path)
    eng = serve_granite_hybrid._build_server(ctx).engine
    said = []
    got = checks_granite_hybrid.precision_probe(eng, DEBUG, 3, said.append,
                                                ("state_bf16",))
    assert set(got) == {"state_bf16"} and len(said) == 1
    g = got["state_bf16"]
    assert len(g["rows"]) == 21 and g["finite"]
    assert "MAMBA_LAYER_REL_RMS" in g["caught_by"] and not g["would_pass"]
