"""The reduction from a trace with the program's own spans to tables: on a
capture small enough to work out by hand, and on the fixtures recorded on
the chip."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import kernel_costs, span_reduce as sr
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import window_counters
from benchmarks.lib.harness import ROOT

P0 = "/device:TPU:0"
M, O = tr.MODULES, tr.OPS
LOOP = "jit(run)/while/body/closed_call/"


def _tick(n, a, b, work, *children):
    return [["t", "engine.step", a, b, {"tick": n, "work": work}],
            *[["t", "engine." + name, s, e, args]
              for name, s, e, args in children]]


def _dispatch(tick, kind, **kw):
    return {"tick": tick, "kind": kind, "built": 0, **kw}


# Times in ns. The device's clock runs 100 ns ahead of the host's (a
# program "starts" before the host says it enqueued it): nothing below
# may lay a device time over a host span.
#
# Tick 1 is ragged (2 decode rows + 30 prefill tokens), tick 2 decodes
# two rows and leaves no work, tick 3 comes 1,000 ns later.
HAND = {
    "spans": sorted([
        *_tick(1, 1000, 2000, 1,
               ("sched", 1010, 1100, {"waiting": 1, "admitted": 1}),
               ("pack", 1100, 1200, {}),
               ("account", 1200, 1250, {}),
               ("dispatch", 1250, 1450, _dispatch(
                   1, "ragged", T=64, ctx=4, rows=3, decode_rows=2,
                   prefill_tokens=30, kv_tokens=100)),
               ("readback_wait", 1450, 1900, {"of": 1}),
               ("fold", 1900, 1950, {"tokens": 3})),
        ["loop", "server.deliver", 2010, 2060, {"touched": 3}],
        *_tick(2, 2100, 3000, 0,
               ("sched", 2110, 2150, {"waiting": 0, "admitted": 0}),
               ("refresh", 2150, 2300, {}),
               ("account", 2300, 2320, {}),
               ("dispatch", 2320, 2400, _dispatch(
                   2, "decode", T=8, ctx=16, rows=2, decode_rows=2,
                   prefill_tokens=0, kv_tokens=80)),
               ("readback_wait", 2400, 2900, {"of": 2}),
               ("fold", 2900, 2950, {"tokens": 2})),
        *_tick(3, 4000, 4100, 0,
               ("dispatch", 4010, 4050, _dispatch(
                   3, "decode", T=8, ctx=16, rows=1, decode_rows=1,
                   prefill_tokens=0, kv_tokens=10))),
    ], key=lambda s: (s[2], -s[3])),
    "events": [
        [P0, M, "jit__threefry_split(9)", 1165, 10, "", 1],
        [P0, O, "fusion.1", 1166, 8, "jit(_threefry_split)/threefry2x32", 0],
        [P0, M, "jit_run(7)", 1350, 400, "", 2],
        [P0, O, "while", 1352, 388, "jit(run)/while", 0],
        [P0, O, "fusion.2", 1360, 100, LOOP + "attn/dot_general", 0],
        [P0, O, "ragged_paged_attention.6[custom-call]", 1470, 200,
         LOOP + "attn/ragged_paged_attention/pallas_call", 0],
        [P0, O, "fusion.3", 1680, 50, LOOP + "mlp/dot_general", 0],
        [P0, O, "sort.1", 1741, 8, "", 0],
        [P0, M, "jit_step(8)", 2300, 400, "", 3],
        [P0, O, "paged_decode_mp.5[custom-call]", 2300, 110,
         "jit(step)/while/body/closed_call/attn/paged_decode_mp/pallas_call",
         0],
        [P0, O, "fusion.9", 2420, 270, "jit(step)/lm_head/dot_general", 0],
        [P0, M, "jit_step(8)", 3950, 50, "", 4],
        [P0, O, "fusion.9", 3950, 50, "jit(step)/lm_head/dot_general", 0],
        ["/device:TPU:1", M, "jit_step(8)", 0, 9000, "", 9],
    ],
    "enqueues": {1: 1260, 2: 1440, 3: 2390, 4: 4040},
}
BUSY = 8 + 388 + 8 + 110 + 270 + 50
MODEL = {"num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 64}


def test_timeline_labels_every_instant_by_its_innermost_span():
    segs = sr.timeline(HAND)
    assert segs[0] == (1000, 1010, sr.OTHER)
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))   # no hole
    assert (1250, 1450, "engine.dispatch") in segs
    assert (1950, 2000, sr.OTHER) in segs
    # between ticks 1 and 2 work remained; the pump delivered for 50 ns
    assert segs[segs.index((1950, 2000, sr.OTHER)) + 1:][:4] == [
        (2000, 2010, sr.BETWEEN), (2010, 2060, sr.DELIVER),
        (2060, 2100, sr.BETWEEN), (2100, 2110, sr.OTHER)]
    # tick 2 left none
    assert (3000, 4000, sr.NO_WORK) in segs
    assert segs[-1] == (4050, 4100, sr.OTHER)


def test_programs_join_the_dispatch_span_that_enqueued_them():
    progs = sr.programs(HAND)
    assert [(p["kind"], p["args"]["tick"], p["start"], p["enqueued"])
            for p in progs] == [("ragged", 1, 1350, 1440),
                                ("decode", 2, 2300, 2390),
                                ("decode", 3, 3950, 4040)]
    assert [p["wait_end"] for p in progs] == [1900, 2900, None]
    # a program whose dispatch came before the capture began joins none
    early = {**HAND, "enqueues": {**HAND["enqueues"], 2: 900}}
    assert [p["args"]["tick"] for p in sr.programs(early)] == [2, 3]
    # nor does one the runtime left no record of
    bare = {**HAND, "enqueues": {}}
    assert sr.programs(bare) == [] and sr.idle_summary(bare) is None


def test_clock_check_reports_the_largest_violation_of_each_kind():
    assert sr.clock_check(HAND) == {
        "programs": 3,
        # jit_step of tick 3 "starts" 60 ns before its span does
        "program_before_dispatch_ns": 60,
        "waits": 2, "wait_ends_before_program_ns": 0,
        "enqueues": 4,
        # and the key split 95 ns before the runtime enqueued it
        "program_before_enqueue_ns": 95}
    late = json.loads(json.dumps(HAND))
    late["enqueues"] = HAND["enqueues"]
    late["spans"] = [s if s[1] != "engine.readback_wait" or s[4]["of"] != 1
                     else [s[0], s[1], s[2], 1700, s[4]]
                     for s in HAND["spans"]]
    assert sr.clock_check(late)["wait_ends_before_program_ns"] == 50


def test_idle_is_laid_on_the_hosts_clock_by_the_enqueue_records():
    # idle: 1174-1352, 1740-1741, 1749-2300, 2410-2420, 2690-3950
    assert sum(b - a for a, b in sr.idle_intervals(HAND)) == 2000
    assert sr.idle_by_label(HAND) == {
        "engine.dispatch": 178 + 70 + 30,
        "in jit_run": 1, "in jit_step": 10,
        "engine.readback_wait": 61 + 120, "engine.fold": 50 + 50,
        sr.OTHER: 50 + 10 + 50 + 10,
        sr.BETWEEN: 10 + 40, sr.DELIVER: 50,
        "engine.sched": 40, "engine.refresh": 150, "engine.account": 20,
        sr.NO_WORK: 1000}
    got = sr.idle_summary(HAND)
    assert got["ticks"] == 3
    assert got["idle_ms"] == pytest.approx(2000 / 1e6)
    assert got["between_ticks_ms_per_tick"] == pytest.approx(100 / 3e6)
    # 2000 less between (100), no work (1000) and the programs' own (11)
    assert got["in_tick_ms_per_tick"] == pytest.approx(889 / 3e6)
    assert got["attributed_share_pct"] == pytest.approx(94.0)


def test_programs_per_tick_counts_every_program_not_only_the_forward():
    got = sr.programs_per_tick(HAND)
    assert got["ticks"] == 3 and got["per_tick"] == pytest.approx(4 / 3)
    assert got["by_program"] == {
        "jit_step": pytest.approx(2 / 3), "jit_run": pytest.approx(1 / 3),
        "jit__threefry_split": pytest.approx(1 / 3)}


def test_ragged_cost_per_token_and_by_shape():
    got = sr.ragged_cost(HAND)
    assert got["programs"] == 1 and got["tokens"] == 32
    assert got["us_per_token"] == pytest.approx(0.4 / 32)
    assert got["by_T_ctx_rows"] == [
        {"T": 64, "ctx": 4, "rows": 3, "programs": 1,
         "ms_median": pytest.approx(4e-4), "tokens_mean": 32.0}]


def test_kernel_and_scope_shares_of_busy_time():
    assert sr.busy_ns(HAND) == BUSY
    # the loop is charged what its body leaves: 388 - 350
    assert ("while", "jit(run)/while", 1352, 38) in sr.op_self_ns(HAND)
    assert sr.kernel_shares(HAND) == {
        "ragged_paged_attention": pytest.approx(100 * 200 / BUSY),
        "paged_decode_mp": pytest.approx(100 * 110 / BUSY)}
    assert sr.scope_shares(HAND) == {
        "attn": pytest.approx(100 * 410 / BUSY),
        "lm_head": pytest.approx(100 * 320 / BUSY),
        "(no scope)": pytest.approx(100 * 54 / BUSY),
        "mlp": pytest.approx(100 * 50 / BUSY)}
    assert sr.share_of_busy(
        HAND, lambda name, scope: sr.is_kernel(name, *sr.RAGGED_KERNELS)
    ) == pytest.approx(100 * 200 / BUSY)
    assert sr.share_of_busy({**HAND, "events": []},
                            lambda name, scope: True) is None


@pytest.mark.parametrize("path, scope", [
    ("jit(run)/while/body/closed_call/attn/dot_general", "attn"),
    ("jit(run)/while/body/closed_call/attn/ragged_paged_attention/"
     "pallas_call", "attn"),
    ("jit(_step_impl)/jvp(loss_head)/while/body/checkpoint/dot_general",
     "loss_head"),
    ("jit(_step_impl)/transpose(jvp(loss_head))/while/body/mul",
     "loss_head"),
    ("jit(_step_impl)/optimizer/add", "optimizer"),
    ("jit(run)/sample/jit(take_along_axis)/gather", "sample"),
    ("jit(run)/scatter", ""),
    ("jit(run)/attn_fn/mul", ""),        # a whole component, or nothing
    ("", ""),
])
def test_scope_of_an_operations_path(path, scope):
    assert sr.scope_of(path) == scope


def test_kernel_names_match_whole_names_only():
    assert sr.is_kernel("ragged_paged_attention.6[custom-call]",
                        "ragged_paged_attention")
    assert sr.is_kernel("flash_fwd[custom-call]", "flash_fwd")
    assert sr.is_kernel("paged_decode_mp.5[custom-call]", "paged_decode_mp")
    assert not sr.is_kernel("paged_decode_mp.5[custom-call]", "paged_decode")
    assert sr.is_kernel("paged_decode_mp.5[custom-call]", *sr.DECODE_KERNELS)
    assert not sr.is_kernel("closed_call.9[custom-call]", "flash_fwd")


def test_least_bytes_of_both_attention_kernels():
    # one K (or V) row of a token in a layer: 2 kv heads x 128 lanes (64
    # padded) x 2 bytes
    assert kernel_costs.pool_row_bytes(MODEL) == 512
    ragged = {"decode_rows": 2, "prefill_tokens": 30, "kv_tokens": 100}
    # K and V of 100 context tokens, q and o of 32 tokens x 4 heads x 64,
    # in 2 layers
    assert kernel_costs.ragged_attention_min_bytes(MODEL, ragged) == 2 * (
        2 * 100 * 512 + 2 * 32 * 4 * 64 * 2)
    decode = {"rows": 2, "kv_tokens": 80}
    assert kernel_costs.paged_decode_min_bytes(MODEL, decode) == 2 * (
        2 * 80 * 512 + 2 * 2 * 4 * 64 * 2)


def test_kernel_traffic_sets_least_bytes_against_the_kernels_time():
    got = sr.kernel_traffic(
        HAND, "ragged", sr.RAGGED_KERNELS,
        lambda a: kernel_costs.ragged_attention_min_bytes(MODEL, a))
    assert got["programs"] == 1 and got["min_bytes"] == 270336
    assert got["bytes_per_s"] == pytest.approx(270336 / 200e-9)
    # the decode tick of tick 3 ran no kernel event inside the capture:
    # its bytes do not count either
    got = sr.kernel_traffic(
        HAND, "decode", sr.DECODE_KERNELS,
        lambda a: kernel_costs.paged_decode_min_bytes(MODEL, a))
    assert got["programs"] == 1 and got["min_bytes"] == 167936
    assert got["kernel_ms"] == pytest.approx(110 / 1e6)


# ---- the file's own encoding -------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_op_scopes_reads_the_tf_op_stat_of_event_metadata(tmp_path):
    stat_names = {1: "hlo_category", 2: "tf_op",
                  3: "jit(step)/lm_head/dot_general:"}
    stat_meta = b"".join(
        _field(5, _field(1, k) + _field(2, _field(1, k) + _field(
            2, name.encode()))) for k, name in stat_names.items())

    def event(key, name, *stats):
        meta = _field(1, key) + _field(2, name.encode()) + b"".join(
            _field(5, s) for s in stats)
        return _field(4, _field(1, key) + _field(2, meta))

    device = (_field(2, b"/device:TPU:0") + _field(3, b"\x08\x01")
              + stat_meta
              + event(1, "%fusion.2 = f32[8] fusion(...)",
                      _field(1, 1) + _field(5, b"loop fusion"),
                      _field(1, 2) + _field(5, b"jit(run)/attn/add:"))
              + event(2, "%fusion.9 = f32[8] fusion(...)",
                      _field(1, 2) + _field(7, 3))     # by reference
              + event(3, "%sort.1 = sort(...)",
                      _field(1, 1) + _field(5, b"sort")))
    host = _field(2, b"/host:CPU") + stat_meta + event(
        1, "engine.step", _field(1, 2) + _field(5, b"not an operation"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert sr.op_scopes(str(path)) == {
        "%fusion.2 = f32[8] fusion(...)": "jit(run)/attn/add",
        "%fusion.9 = f32[8] fusion(...)": "jit(step)/lm_head/dot_general"}


# ---- the counters over the window --------------------------------------

def _marks(start, end):
    return {"events": [["x"]], "marks": {"start": {"stats": start},
                                         "end": {"stats": end}}}


def test_window_counters_difference_tables_key_by_key():
    run = _marks(
        {"self_captures": {"profiles_armed": {}, "profiles_started": 0}},
        {"self_captures": {"profiles_armed": {"tick_anomaly": 2},
                           "profiles_started": 1}})
    assert window_counters.delta(run, "self_captures") == {
        "profiles_armed": {"tick_anomaly": 2}, "profiles_started": 1}
    assert window_counters.delta(run, "tick_phases") is None   # the parent
    assert window_counters.delta({**run, "events": []},
                                 "self_captures") is None      # no trace


def test_longest_stall_is_the_worst_record_inside_the_window():
    rec = lambda start, **kw: {"start": start, "kind": "decode", **kw}
    run = _marks(
        {"tick_times": {"now": 100.0}},
        {"tick_times": {"now": 150.0, "longest": {
            "stalls": [rec(90.0, excess_ms=900.0),      # in the ramp
                       rec(120.0, excess_ms=580.0), rec(130.0, excess_ms=3.0)],
            "gaps": [rec(95.0, gap_ms=7000.0), rec(140.0, gap_ms=12.5)]}}})
    what, ms, record = window_counters.longest_stall(run)
    assert (what, ms, record["start"]) == ("tick", 580.0, 120.0)
    run["marks"]["end"]["stats"]["tick_times"]["longest"]["gaps"].append(
        rec(141.0, gap_ms=7186.0))
    assert window_counters.longest_stall(run)[:2] == ("gap", 7186.0)
    assert window_counters.longest_stall(
        _marks({"tick_times": {}}, {"tick_times": {}})) is None


# ---- the readers -------------------------------------------------------

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    # the 13 metrics PR 24 brought come first; what PR 25 lists follows
    NEW = json.load(_f)["per_layer"][13:29]


def test_every_metric_this_pr_lists_is_there():
    assert len(NEW) == 16
    assert {m["name"] for m in NEW if "train-packed" in m["workloads"]} == {
        "device.idle_attributed_share.train", "kernel.flash_share",
        "train.loss_head_share", "train.optimizer_share"}


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_a_new_reader_declares_what_the_entry_says_and_reads_nothing_from_a_parent(
        metric):
    mod = bench_run.load_layer_metric(ROOT, metric["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        metric["unit"], metric["layer"], metric["moves"])
    # an untraced run, and a traced run of a program with no span and no
    # counter of this PR's: nothing to read, and no error
    assert mod.read({"events": [], "marks": {}}) is None
    parent = {"events": [[P0, M, "jit_step(1)", 0, 10]],
              "config": {}, "device_kind": "TPU v5 lite",
              "marks": {"start": {"stats": {"tick_times": {}}},
                        "end": {"stats": {"tick_times": {}}}}}
    assert mod.read(parent) is None


# ---- the fixtures recorded on the chip ---------------------------------

def _fixture(name):
    with open(os.path.join(ROOT, "benchmarks", "fixtures", name)) as f:
        cap = json.load(f)
    cap["enqueues"] = {int(k): v for k, v in cap["enqueues"].items()}
    return cap


@pytest.fixture(scope="module")
def chat():
    return _fixture("chat_open_ticks_spans.json")


@pytest.fixture(scope="module")
def train():
    return _fixture("train_packed_two_steps_spans.json")


def test_chat_fixture_joins_every_program_to_its_span(chat):
    progs = sr.programs(chat)
    assert [(p["args"]["tick"], p["kind"]) for p in progs] == [
        (370, "ragged"), (371, "decode"), (372, "decode"), (373, "ragged"),
        (374, "ragged"), (375, "decode"), (376, "decode"), (377, "decode")]
    for p in progs:
        assert p["span_start"] <= p["enqueued"]
    # tick 374: 8 decode rows and 37 prompt tokens in the 64-token
    # program, whose event on `XLA Modules` lasted 97,613,581 ns
    p = progs[4]
    assert (p["args"]["T"], p["args"]["decode_rows"],
            p["args"]["prefill_tokens"]) == (64, 8, 37)
    assert p["end"] - p["start"] == 97613581
    # the wait that names tick 374 ended after its program did
    assert p["wait_end"] == 1344502216 > p["end"]
    assert sr.clock_check(chat) == {
        "programs": 8, "program_before_dispatch_ns": 0, "waits": 7,
        "wait_ends_before_program_ns": 0, "enqueues": 32,
        "program_before_enqueue_ns": 0}
    # every tick ran its forward and the small programs beside it
    per = sr.programs_per_tick(chat)
    assert per["ticks"] == 8 and per["per_tick"] == 31 / 8
    assert per["by_program"] == {
        "jit__threefry_split": 1.0, "jit__unstack": 1.0,
        "jit_step": 0.625, "jit_add": 0.5,
        "jit_convert_element_type": 0.375, "jit_run": 0.375}


def test_chat_fixture_ragged_ticks_cost_by_their_token_bucket(chat):
    got = sr.ragged_cost(chat)
    # 216 + 512 + 45 tokens in 265,442,954 + 668,806,589 + 97,613,581 ns
    assert got["tokens"] == 773
    assert got["us_per_token"] == pytest.approx(1031863124 / 1e3 / 773)
    assert [(r["T"], r["rows"], round(r["ms_median"], 1))
            for r in got["by_T_ctx_rows"]] == [
        (64, 9, 97.6), (256, 8, 265.4), (512, 9, 668.8)]


def test_chat_fixture_idle_time_is_attributed(chat):
    got = sr.idle_summary(chat)
    by = sr.idle_by_label(chat)
    assert sum(by.values()) == 62771218 == sum(
        b - a for a, b in sr.idle_intervals(chat))
    assert by[sr.BETWEEN] == 18467730 and by["engine.sched"] == 10514260
    # recorded before the counters' publication had a phase: the 2.3-3.1
    # ms of it at each tick's end are `other` here
    assert by[sr.OTHER] == 7870907 and sr.OUTSIDE not in by
    assert got["attributed_share_pct"] == pytest.approx(
        100 * (1 - 7870907 / 62771218))
    assert got["between_ticks_ms_per_tick"] == pytest.approx(
        (18467730 + 141240) / 8e6)


def test_chat_fixture_names_its_kernels_and_scopes(chat):
    kernels = sr.kernel_shares(chat)
    assert set(kernels) == {"ragged_paged_attention", "paged_decode_mp"}
    scopes = sr.scope_shares(chat)
    assert {"attn", "mlp", "lm_head", "sample", "embed"} <= set(scopes)
    assert sum(scopes.values()) == pytest.approx(100.0, abs=0.5)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "internlm2_5-1_8b.json")) as f:
        cfg = json.load(f)
    got = sr.kernel_traffic(
        chat, "ragged", sr.RAGGED_KERNELS,
        lambda a: kernel_costs.ragged_attention_min_bytes(cfg, a))
    # 24 layers x (K and V of 4,060 / 4,588 / 4,633 context tokens at
    # 2,048 B a row, q and o of 216 / 512 / 45 tokens at 4,096 B)
    assert got["programs"] == 3 and got["min_bytes"] == 24 * (
        2 * (4060 + 4588 + 4633) * 2048 + 2 * 773 * 4096)
    got = sr.kernel_traffic(
        chat, "decode", sr.DECODE_KERNELS,
        lambda a: kernel_costs.paged_decode_min_bytes(cfg, a))
    assert got["programs"] == 5 and got["min_bytes"] == 24 * (
        2 * (4068 + 4076 + 4642 + 4651 + 4660) * 2048
        + 2 * (8 + 8 + 9 + 9 + 9) * 4096)


def test_train_fixture_names_its_kernels_and_scopes(train):
    assert [s[1] for s in train["spans"]] == ["train.step"] * 2
    kernels = sr.kernel_shares(train)
    assert set(sr.FLASH_KERNELS) <= set(kernels)
    scopes = sr.scope_shares(train)
    assert {"loss_head", "optimizer", "attn", "mlp"} <= set(scopes)
    flash = sr.share_of_busy(train, lambda name, scope: sr.is_kernel(
        name, *sr.FLASH_KERNELS))
    assert flash == pytest.approx(sum(
        kernels[k] for k in sr.FLASH_KERNELS))
    assert sr.programs(train) == [] and sr.ragged_cost(train) is None
    got = sr.idle_summary(train)
    assert got["ticks"] == 2 and got["idle_ms"] == pytest.approx(0.033244)
    assert got["by_label_ms"]["in jit__step_impl"] == pytest.approx(
        0.012612)
