"""The six readers of the engine's gap ledger (PR 55) and
`benchmarks/lib/gap_ledger.py` under them: on a pair of marks worked out
by hand (the percentile's interpolation, the tail's lower edge, shares
that add to 100); None on a parent without `gaps`, on a run without
events and on nothing, and a NUMBER from all six on every run whose
marks hold the ledger and whose events are not empty, whatever the
capture holds (PR 54 was refused for a metric that a capture could leave
out); the `[spans]` line on a small capture made by hand; the six
entries in BENCHMARK.json by name, last and in order."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import gap_ledger, span_reduce
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

CELLS = ["chat-open", "dsv3-longchat", "trinity-mixed", "phi4flash-reason",
         "nemotron-agent", "smallthinker-assist", "kimi-longdoc",
         "granite-concurrent"]
ENTRIES = [("engine.gap_p95_ms", "ms", "lower", "program_counter"),
           ("engine.tail_gap_decode_share", "%", "higher",
            "program_counter"),
           ("engine.tail_gap_ragged_share", "%", "lower",
            "program_counter"),
           ("engine.tail_gap_refill_share", "%", "lower",
            "program_counter"),
           ("engine.tail_gap_held_share", "%", "lower", "program_counter"),
           ("engine.gap_between_calls_share", "%", "lower",
            "program_counter")]
NAMES = [e[0] for e in ENTRIES]


@pytest.fixture(autouse=True)
def no_capture(monkeypatch):
    """No reader here may open a trace left under chiprun_out/."""
    monkeypatch.setattr(span_reduce, "capture", lambda run: None)


def _read(name, run):
    return bench_run.load_layer_metric(ROOT, name).read(run)


# ---- marks worked out by hand ------------------------------------------
# Buckets: 208 is [7.84, 8.19) ms, 232 [22.19, 23.17), 240 [31.38, 32.77),
# 272 [125.5, 131.1). The window's 102 gaps in order: 10 of 0 (ranks 0-9),
# 80 decode ticks (10-89), 6 in bucket 232 (90-95), 5 in 240, 1 in 272.
# The 95th percentile is the order statistic at rank 0.95 x 101 = 95.95:
# the last of bucket 232's six, 5.95 / 6 of the way through it. The tail
# is every gap from 22.19 ms up: 8 ragged, 2 refill, 1 held, 1 capture.

def _row(n, seconds, between_s, hist):
    return {"n": n, "seconds": seconds, "between_s": between_s,
            "hist": {str(b): k for b, k in hist.items()}}


START = {"same_tick": _row(5, 0.0, 0.0, {0: 5}),
         "capture": _row(0, 0.0, 0.0, {}),
         "held": _row(2, 0.5, 0.4, {290: 2}),
         "ragged": _row(50, 1.5, 0.05, {240: 50}),
         "refill": _row(0, 0.0, 0.0, {}),
         "decode": _row(1000, 7.9, 0.7, {200: 100, 208: 900})}
END = {"same_tick": _row(15, 0.0, 0.0, {0: 15}),
       "capture": _row(1, 0.032, 0.0, {240: 1}),
       "held": _row(3, 0.628, 0.5, {290: 2, 272: 1}),
       "ragged": _row(58, 1.716, 0.054, {240: 54, 232: 4}),
       "refill": _row(2, 0.045, 0.001, {232: 2}),
       "decode": _row(1080, 8.54, 0.764, {200: 100, 208: 980})}
EVENTS = (("/device:TPU:0", "XLA Ops", "f", 0, 1),)


def _marks(gaps):
    requests = {"enabled": True, "itl_ms_avg": 8.0, "finished": {}}
    if gaps is not None:
        requests["gaps"] = gaps
    return {"stats": {"requests": requests}}


def _run(start=START, end=END, events=EVENTS):
    return {"events": list(events),
            "marks": {"start": _marks(start), "end": _marks(end)}}


def test_the_windows_table_is_the_difference_of_the_marks():
    table = gap_ledger.window(_run())
    assert {c: r["n"] for c, r in table.items()} == {
        "same_tick": 10, "capture": 1, "held": 1, "ragged": 8,
        "refill": 2, "decode": 80}
    # whole-number buckets; one that did not move is not in the window
    assert table["decode"]["hist"] == {208: 80}
    assert table["ragged"]["hist"] == {240: 4, 232: 4}
    assert table["held"]["hist"] == {272: 1}
    assert table["decode"]["seconds"] == pytest.approx(0.64)


def test_the_percentile_is_interpolated_in_its_bucket(capsys):
    lo, hi = 2 ** (231 / 16), 2 ** (232 / 16)
    assert gap_ledger.edges_us(232) == pytest.approx((lo, hi))
    want_ms = (lo + (hi - lo) * 5.95 / 6) / 1e3
    assert 22.19 < want_ms < 23.17
    assert _read("engine.gap_p95_ms", _run()) == pytest.approx(want_ms)
    counters, spans = capsys.readouterr().out.splitlines()
    assert counters.startswith("[counters] gaps in the window by cause")
    assert "decode n=80 78.4%" in counters
    assert "102 booked, tail from 22.19 ms" in counters
    assert spans == "[spans] tail gaps in the capture: none in the capture"
    # one gap: the percentile is that gap's bucket's lower edge
    one = {**START, "decode": _row(1001, 7.908, 0.7,
                                   {200: 100, 208: 901})}
    assert _read("engine.gap_p95_ms", _run(end=one)) == pytest.approx(
        2 ** (207 / 16) / 1e3)


@pytest.mark.parametrize("rank_of_100,bucket", [
    (0.0, 0), (9.5, 0), (10.0, 208), (89.5, 208), (90.0, 232),
    (95.0, 232), (96.2, 240), (100.0, 240), (100.5, 240), (101.0, 272)])
def test_a_rank_falls_in_the_bucket_that_holds_it(rank_of_100, bucket):
    """The bucket of the order statistic at the rank's whole part."""
    hist = gap_ledger.merged(
        r["hist"] for r in gap_ledger.window(_run()).values())
    assert sum(hist.values()) == 102
    p = 100.0 * rank_of_100 / 101
    value, found = gap_ledger.percentile_us(hist, p)
    assert found == bucket
    lo, hi = gap_ledger.edges_us(bucket)
    assert lo <= value <= hi


@pytest.mark.parametrize("group,share", [
    ("decode", 0.0), ("ragged", 100.0 * 8 / 12),
    ("refill", 100.0 * 2 / 12), ("held", 100.0 * 2 / 12)])
def test_tail_shares_by_hand(group, share):
    edge, by_group = gap_ledger.tail(gap_ledger.window(_run()))
    assert edge == 232
    assert by_group == {"decode": 0, "ragged": 8, "refill": 2, "held": 2}
    assert _read(f"engine.tail_gap_{group}_share", _run()) \
        == pytest.approx(share)


def test_the_four_tail_shares_add_to_100():
    shares = [_read(f"engine.tail_gap_{g}_share", _run())
              for g in gap_ledger.TAIL_GROUPS]
    assert sum(shares) == pytest.approx(100.0)
    # a window whose tail is all decode ticks
    end = {**START, "decode": _row(1100, 8.7, 0.8, {200: 100, 208: 1000})}
    assert [_read(f"engine.tail_gap_{g}_share", _run(end=end))
            for g in gap_ledger.TAIL_GROUPS] == [100.0, 0.0, 0.0, 0.0]


def test_between_calls_share_over_all_causes():
    seconds = 0.64 + 0.216 + 0.045 + 0.128 + 0.032
    between = 0.064 + 0.004 + 0.001 + 0.1
    assert _read("engine.gap_between_calls_share", _run()) \
        == pytest.approx(100.0 * between / seconds)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("run", [
    _run(start=None, end=None),                    # the parent's marks
    _run(events=()),                               # no trace taken
    {"events": [1], "marks": {}},
    {}], ids=["parent", "no_events", "no_marks", "nothing"])
def test_a_reader_returns_none_without_the_ledger_or_events(name, run):
    assert _read(name, run) is None


# the rule PR 54 was refused for: marks with the ledger and events that
# are not empty give a number from all six, whatever else the run holds
EMPTY = {c: _row(0, 0.0, 0.0, {}) for c in START}
ONE_CAUSE = {**START, "refill": _row(4, 0.1, 0.0, {232: 4})}
WITH_LEDGER = {
    "an_empty_capture": (_run(), None),
    "no_gap_in_the_window": (_run(start=START, end=START),
                             dict.fromkeys(NAMES, 0.0)),
    "no_gap_ever": (_run(start=EMPTY, end=EMPTY),
                    dict.fromkeys(NAMES, 0.0)),
    # four gaps in bucket 0, [0, 1) us: rank 2.85 of its four
    "gaps_of_0_alone": (
        _run(end={**START, "same_tick": _row(9, 0.0, 0.0, {0: 9})}),
        {**dict.fromkeys(NAMES, 0.0),
         "engine.gap_p95_ms": 2.85 / 4 / 1e3}),
    "one_cause_owns_the_tail": (_run(end=ONE_CAUSE), {
        "engine.gap_p95_ms": (2 ** (231 / 16) + (2 ** (232 / 16)
                              - 2 ** (231 / 16)) * 2.85 / 4) / 1e3,
        "engine.tail_gap_decode_share": 0.0,
        "engine.tail_gap_ragged_share": 0.0,
        "engine.tail_gap_refill_share": 100.0,
        "engine.tail_gap_held_share": 0.0,
        "engine.gap_between_calls_share": 0.0}),
}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", sorted(WITH_LEDGER))
def test_a_reader_gives_a_number_wherever_the_marks_hold_the_ledger(
        name, case, monkeypatch):
    run, want = WITH_LEDGER[case]
    if case == "an_empty_capture":
        monkeypatch.setattr(span_reduce, "capture", lambda run: {
            "spans": [], "events": [], "enqueues": {}})
    got = _read(name, run)
    assert isinstance(got, float)
    if want is not None:
        assert got == pytest.approx(want[name])


def test_the_result_line_holds_all_six_on_such_a_run():
    """`run.py:result_line` leaves a metric out where its reader returns
    None: on marks with the ledger no name of the six may be missing."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] = bench["per_layer"][-len(ENTRIES):]

    class Result:
        correct, attempted, failed = True, 1, 0
        run = _run(start=START, end=START)

    for cell in CELLS:
        line = bench_run.result_line(ROOT, bench, cell, Result, {}, True)
        assert list(line["metrics"]) == NAMES


# ---- a capture made by hand --------------------------------------------
# Times in ns; the device's clock runs 0.1 ms ahead of the host's. Chip 0
# is busy 2-5 ms, 7-9 ms, 12-14 and 15-18 ms (one program: the hole in it
# is the program's own). Its idle 5-7 and 9-12 ms is laid on the host's
# clock to end where the next program was enqueued: 5.1-7.1, 9.1-12.1.

P0 = "/device:TPU:0"
MS = 1_000_000


def _step(n, a, b, **gaps):
    return ["t", "engine.step", a, b, {"tick": n, "work": 1, **gaps}]


CAPTURE = {
    "spans": [
        _step(1, 1 * MS, int(5.5 * MS), gaps=0),
        # [5.5, 10.0]: idle 5.5-7.1 and 9.1-10.0, 2.5 ms of 4.5
        _step(2, 6 * MS, 10 * MS, gaps=3, gap_max_ms=4.5,
              gap_cause="decode"),
        # [10.0, 19.0]: idle 10.0-12.1, 2.1 ms of 9
        _step(3, 11 * MS, 19 * MS, gaps=3, gap_max_ms=9.0,
              gap_cause="ragged"),
        _step(4, int(19.5 * MS), 20 * MS, gaps=2, gap_max_ms=1.0,
              gap_cause="decode"),
    ],
    "events": [
        [P0, tr.MODULES, "jit_step(1)", 2 * MS, 3 * MS, "", 1],
        [P0, tr.OPS, "fusion.1", 2 * MS, 3 * MS, "", 0],
        [P0, tr.MODULES, "jit_step(1)", 7 * MS, 2 * MS, "", 2],
        [P0, tr.OPS, "fusion.1", 7 * MS, 2 * MS, "", 0],
        [P0, tr.MODULES, "jit_run(2)", 12 * MS, 6 * MS, "", 3],
        [P0, tr.OPS, "fusion.2", 12 * MS, 2 * MS, "", 0],
        [P0, tr.OPS, "fusion.3", 15 * MS, 3 * MS, "", 0],
    ],
    "enqueues": {1: int(2.1 * MS), 2: int(7.1 * MS), 3: int(12.1 * MS)},
}


def test_idle_outside_every_program_on_the_hosts_clock():
    assert gap_ledger.host_idle(CAPTURE) == [
        (int(5.1 * MS), int(7.1 * MS)), (int(9.1 * MS), int(12.1 * MS))]


@pytest.mark.parametrize("edge_ms,n,seconds,idle", [
    (4.0, 2, 0.0135, 100.0 * (2.5 + 2.1) / (4.5 + 9.0)),
    (4.5, 2, 0.0135, 100.0 * (2.5 + 2.1) / (4.5 + 9.0)),
    (5.0, 1, 0.009, 100.0 * 2.1 / 9.0),
    (0.5, 3, 0.0145, 100.0 * (2.5 + 2.1) / (4.5 + 9.0 + 1.0)),
    (20.0, 0, 0.0, 0.0)])
def test_tail_gaps_in_a_capture_by_hand(edge_ms, n, seconds, idle):
    assert gap_ledger.tail_in_capture(CAPTURE, edge_ms) == (
        n, pytest.approx(seconds), pytest.approx(idle))


CAP_25 = {**CAPTURE, "spans": [
    _step(1, 1 * MS, 30 * MS, gaps=3, gap_max_ms=22.0, gap_cause="ragged"),
    _step(2, 31 * MS, 33 * MS, gaps=3, gap_max_ms=25.0,
          gap_cause="ragged")]}


@pytest.mark.parametrize("cap,said", [
    # the marks' tail starts at 22.19 ms: only a span whose longest gap
    # is that long counts. [8, 33]: idle 9.1-12.1
    (CAP_25, "n=1, 0.0250 s, chip idle inside 12.0%"),
    # a capture without the runtime's enqueue records lays no idle time
    ({**CAP_25, "enqueues": {}}, "n=1, 0.0250 s, chip idle inside 0.0%"),
    # the parent's spans carry no gap; a capture cut short; none at all
    ({**CAP_25, "spans": [_step(1, 1 * MS, 30 * MS)]},
     "none in the capture"),
    ({"spans": [], "events": [], "enqueues": {}}, "none in the capture"),
    (None, "none in the capture")],
    ids=["a_tail_gap", "no_enqueues", "no_tail_gap", "empty", "none"])
def test_the_spans_line_is_text_and_the_metric_stands_without_it(
        cap, said, monkeypatch, capsys):
    monkeypatch.setattr(span_reduce, "capture", lambda run: cap)
    want = _read("engine.gap_p95_ms", _run())
    assert 22.19 < want < 23.17
    assert capsys.readouterr().out.splitlines()[-1] == (
        "[spans] tail gaps in the capture: " + said)


# ---- the entries -------------------------------------------------------

def test_the_six_entries_come_last_and_in_this_order():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # what a capture may leave out is a printed line, not an entry
    assert "engine.tail_gap_idle_share" not in {m["name"]
                                                for m in per_layer}
    assert per_layer[-len(ENTRIES):] == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": "engine scheduler", "moves": "itl_p95_ms",
         "workloads": CELLS}
        for name, unit, better, source in ENTRIES]


@pytest.mark.parametrize("name,unit", [(e[0], e[1]) for e in ENTRIES])
def test_a_reader_declares_what_its_entry_says(name, unit):
    mod = bench_run.load_layer_metric(ROOT, name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, unit, "engine scheduler", "itl_p95_ms")
