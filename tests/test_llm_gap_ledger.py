"""The gap ledger (ISSUE 55): every gap between two streamed tokens of a
request booked once, when the engine closes the call that surfaced the
later token, to one cause; `stats()["requests"]["gaps"]`,
`slo_totals()`'s ITL totals fed from the same booking, and the arguments
`engine.step`'s span carries.

The rule's cases run on `EngineTelemetry` alone with stamps made by hand
(a call is `open_call`, its tokens, `close_call`); the engine's cases on
the tiny CPU engine of `test_llm_tick_spans.py`.
"""

import uuid

import jax
import pytest

from ray_tpu.llm._internal import telemetry
from ray_tpu.llm._internal.telemetry import (GAP_CAUSES, EngineTelemetry,
                                             gap_bucket)
from test_llm_tick_spans import _host_spans, _options, _req, make_engine


# ---- the rule, on stamps made by hand ----------------------------------

class _Request:
    def __init__(self, rid, lane=""):
        self.request_id, self.prompt_tokens = rid, [1, 2, 3]
        self.lora, self.lane = None, lane


def _telemetry(**kw):
    return EngineTelemetry(model=f"gl{uuid.uuid4().hex[:10]}", **kw)


def _drive(tel, calls, lane=""):
    """calls: (end, between_s, prefill_tokens, capture, tokens) a call,
    `tokens` the ids of the requests it surfaces a token of, and a sixth
    entry where the call drains the pipeline (the drain's cause).
    Returns what each call booked (`call_gaps`)."""
    reqs = {}
    booked = []
    for n, (end, between, prefill, capture, tokens, *drain) in enumerate(
            calls, 1):
        tel.open_call(n)
        if drain:
            tel.on_drain(drain[0])
        for rid in tokens:
            if rid not in reqs:
                reqs[rid] = _Request(rid, lane)
                tel.on_queued(reqs[rid])
            tel.on_token(reqs[rid])
        tel.close_call(end, between, prefill, capture)
        booked.append(dict(tel.call_gaps))
    return booked


def _ledger(tel):
    return {c: r["n"] for c, r in tel.summary()["gaps"].items() if r["n"]}


# the first call surfaces r's first token at 1.0; what follows decides
RULE = {
    "decode": ([(1.01, 0.0, 0, False, "r")], {"decode": 1}),
    "same_tick": ([(1.01, 0.0, 0, False, "rr")],
                  {"decode": 1, "same_tick": 1}),
    "ragged": ([(1.03, 0.0, 512, False, "r")], {"ragged": 1}),
    "ragged_spans_calls": ([(1.03, 0.0, 512, False, ""),
                            (1.04, 0.0, 0, False, "r")], {"ragged": 1}),
    "a_chunk_before_the_first_token_is_not_in_the_gap": (
        [(1.01, 0.0, 0, False, "r")], {"decode": 1}),
    "refill": ([(1.01, 0.0, 0, False, ""), (1.02, 0.0, 0, False, "r")],
               {"refill": 1}),
    "a_call_that_drains": ([(1.02, 0.0, 0, False, "rr", "retirement")],
                           {"refill": 1, "same_tick": 1}),
    "an_admissions_drain_is_the_chunks": (
        [(1.03, 0.0, 512, False, "rr", "structural")],
        {"ragged": 1, "same_tick": 1}),
    "a_drain_before_the_gap_is_not_in_it": (
        [(1.01, 0.0, 0, False, "r")], {"decode": 1}),
    "capture_at_the_close": ([(1.03, 0.0, 512, True, "r")],
                             {"capture": 1}),
    "capture_in_a_call_between": (
        [(1.01, 0.0, 0, True, ""), (1.02, 0.0, 0, False, "r")],
        {"capture": 1}),
    "held_outside_the_calls": ([(1.04, 0.03, 0, False, "r")],
                               {"held": 1}),
    "held_before_ragged": ([(1.04, 0.03, 512, False, "r")], {"held": 1}),
    "half_outside_is_not_held": ([(1.04, 0.02, 0, False, "r")],
                                 {"decode": 1}),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_rule_books_one_cause(case):
    calls, want = RULE[case]
    tel = _telemetry()
    prefill = 512 if case.startswith("a_chunk") else 0
    drain = ("retirement",) if case.startswith("a_drain_before") else ()
    _drive(tel, [(1.0, 0.0, prefill, False, "r", *drain)] + calls)
    assert _ledger(tel) == want


@pytest.mark.parametrize("cause,calls_a_gap", [("decode", 1),
                                               ("refill", 2)])
@pytest.mark.parametrize("held_n,gap_ms,want", [
    (64, 41.0, "held"), (64, 39.0, None), (63, 500.0, None)])
def test_a_gap_over_four_times_its_causes_mean_is_held(
        cause, calls_a_gap, held_n, gap_ms, want):
    """Once a cause holds 64 gaps (10 ms each here), a gap that would be
    its own and is over 4 x the running mean is `held`."""
    tel = _telemetry()
    end, calls = 1.0, [(1.0, 0.0, 0, False, "r")]
    for gap in [0.010] * held_n + [gap_ms / 1e3]:
        for k in range(calls_a_gap):
            end += gap / calls_a_gap
            calls.append((end, 0.0, 0, False,
                          "r" if k == calls_a_gap - 1 else ""))
    _drive(tel, calls)
    assert _ledger(tel) == ({cause: held_n, "held": 1} if want
                            else {cause: held_n + 1})


def test_a_chunks_gap_is_never_judged_by_its_mean():
    tel = _telemetry()
    ends = [1.0 + 0.010 * k for k in range(66)] + [2.0]
    _drive(tel, [(e, 0.0, 512, False, "r") for e in ends])
    assert _ledger(tel) == {"ragged": 66}


def test_seconds_between_and_the_one_accounting():
    tel = _telemetry()
    booked = _drive(tel, [
        (1.000, 0.0, 0, False, "ab"),
        (1.010, 0.002, 0, False, "ab"),         # decode x 2
        (1.050, 0.001, 256, False, "aab"),      # ragged x 2, same_tick
        (1.060, 0.001, 0, False, ""),
        (1.070, 0.001, 0, False, "ab")])        # refill x 2
    gaps = tel.summary()["gaps"]
    assert set(gaps) == set(GAP_CAUSES)
    assert {c: r["n"] for c, r in gaps.items()} == {
        "same_tick": 1, "capture": 0, "held": 0, "ragged": 2,
        "refill": 2, "decode": 2}
    assert gaps["decode"]["seconds"] == pytest.approx(0.020)
    assert gaps["decode"]["between_s"] == pytest.approx(0.004)
    assert gaps["ragged"]["seconds"] == pytest.approx(0.080)
    assert gaps["refill"]["seconds"] == pytest.approx(0.040)
    assert gaps["refill"]["between_s"] == pytest.approx(0.004)
    assert gaps["same_tick"]["seconds"] == 0.0
    assert gaps["same_tick"]["hist"] == {"0": 1}
    assert gaps["decode"]["hist"] == {str(gap_bucket(0.010)): 2}
    slo = tel.slo_totals()
    assert slo["itl_n"] == sum(r["n"] for r in gaps.values()) == 7
    assert slo["itl_s"] == pytest.approx(
        sum(r["seconds"] for r in gaps.values()))
    assert tel.summary()["itl_ms_avg"] == pytest.approx(140.0 / 7, abs=1e-3)
    # what each call's span is handed: the count and the longest
    assert booked[0] == {"gaps": 0}
    assert booked[2] == {"gaps": 3, "gap_max_ms": 40.0,
                         "gap_cause": "ragged"}
    assert booked[3] == {"gaps": 0}
    assert booked[4]["gap_cause"] == "refill"


@pytest.mark.parametrize("gap_s,bucket,lo_us,hi_us", [
    (0.0, 0, 0.0, 1.0),
    (0.9e-6, 0, 0.0, 1.0),
    (1e-6, 1, 1.0, 2 ** (1 / 16)),
    (1e-3, 160, 2 ** (159 / 16), 2 ** (160 / 16)),
    (20.0, 389, 2 ** (388 / 16), 2 ** (389 / 16)),
])
def test_a_buckets_edges(gap_s, bucket, lo_us, hi_us):
    """16 buckets a factor of two over the gap in microseconds, bucket 0
    below one; the benchmark's reader holds the same edges."""
    from benchmarks.lib import gap_ledger
    assert gap_bucket(gap_s) == bucket
    assert gap_ledger.edges_us(bucket) == pytest.approx((lo_us, hi_us))
    assert lo_us <= gap_s * 1e6 < hi_us


def test_batch_lane_tokens_are_left_out():
    tel = _telemetry()
    _drive(tel, [(1.0, 0.0, 0, False, "r"), (1.01, 0.0, 0, False, "r")],
           lane="batch")
    assert _ledger(tel) == {} and tel.slo_totals()["itl_n"] == 0
    assert tel.summary()["batch"]["generated_tokens"] == 2


def test_telemetry_off_books_nothing():
    tel = _telemetry(enabled=False)
    booked = _drive(tel, [(1.0, 0.0, 0, False, "r"),
                          (1.01, 0.0, 0, False, "r")])
    assert booked == [{}, {}]
    assert tel.summary() == {"enabled": False}
    eng = make_engine(enable_metrics=False)
    eng.add_request(_req("off", 9, max_tokens=4))
    while eng.has_work():
        eng.step()
    assert eng.stats()["requests"] == {"enabled": False}
    assert eng.telemetry.call_gaps == {}


# ---- the engine's calls ------------------------------------------------

@pytest.fixture
def no_held(monkeypatch):
    """A tiny engine's ticks on a shared CPU are a millisecond or two, so
    a thread that loses its core between two calls would book `held`:
    the engine's cases are about the other causes and switch that rule
    off (its own cases run on stamps made by hand, above)."""
    monkeypatch.setattr(telemetry, "_HELD_OUTSIDE", float("inf"))
    monkeypatch.setattr(telemetry, "_HELD_AFTER", 1 << 60)


def _gaps(eng):
    return {c: r["n"]
            for c, r in eng.stats()["requests"]["gaps"].items()}


def _since(eng, before):
    return {c: n - before[c] for c, n in _gaps(eng).items()
            if n - before[c]}


def _three_streams(**over):
    eng = make_engine(**over)
    for i in range(3):
        eng.add_request(_req(f"d{i}", 10 + i, max_tokens=40))
    for _ in range(6):
        eng.step()
    assert sum(1 for s in eng.slots if s.ready) == 3
    return eng


@pytest.mark.parametrize("async_readback", [True, False])
def test_a_decode_only_run_books_decode_alone(async_readback, no_held):
    eng = _three_streams(async_readback=async_readback)
    before = _gaps(eng)
    for _ in range(5):
        eng.step()
    assert _since(eng, before) == {"decode": 15}
    assert eng.telemetry.call_gaps["gap_cause"] == "decode"
    assert eng.telemetry.call_gaps["gaps"] == 3


def test_an_admission_beside_live_streams_books_the_rules_causes(no_held):
    """The admitting call folds the tick in flight (a token a stream),
    then its ragged tick in step (another): to the client one chunk of
    two, a long gap and a gap of 0. The next call's decode tick is read
    a call later, so the gap after a ragged tick is two calls long."""
    eng = _three_streams()
    before = _gaps(eng)
    eng.add_request(_req("p0", 10, max_tokens=8))
    eng.step()                                  # the admitting call
    assert _since(eng, before) == {"ragged": 3, "same_tick": 3}
    assert eng.telemetry.call_gaps["gaps"] == 6
    assert eng.telemetry.call_gaps["gap_cause"] == "ragged"
    eng.step()                                  # dispatches, folds nothing
    assert eng.telemetry.call_gaps == {"gaps": 0}
    eng.step()                                  # four streams' tokens
    assert _since(eng, before) == {"ragged": 3, "same_tick": 3,
                                   "refill": 4}
    eng.step()
    assert _since(eng, before) == {"ragged": 3, "same_tick": 3,
                                   "refill": 4, "decode": 4}


def test_a_retirement_beside_live_streams_books_refill_around_it(no_held):
    """The call in which a stream ends folds the tick in flight, finds
    the retirement and waits out the successor it had just dispatched
    too: two ticks in one call (a long gap and a gap of 0 for the
    survivors), and the pipeline fills again behind it."""
    eng = make_engine()
    short = _req("short", 11, max_tokens=12)
    for req in (_req("d0", 10, max_tokens=40),
                _req("d1", 12, max_tokens=40), short):
        eng.add_request(req)
    for _ in range(6):
        eng.step()
    while not short.finished:
        before = _gaps(eng)
        eng.step()
    assert _since(eng, before) == {"refill": 3, "same_tick": 2}
    assert eng.telemetry.call_gaps["gap_cause"] == "refill"
    eng.step()
    assert eng.telemetry.call_gaps == {"gaps": 0}
    eng.step()
    assert _since(eng, before) == {"refill": 5, "same_tick": 2}
    eng.step()
    assert _since(eng, before) == {"refill": 5, "same_tick": 2,
                                   "decode": 2}


def test_with_async_readback_off_no_gap_is_a_refill(no_held):
    eng = _three_streams(async_readback=False)
    before = _gaps(eng)
    eng.add_request(_req("p0", 10, max_tokens=8))
    for _ in range(4):
        eng.step()
    assert _since(eng, before) == {"ragged": 3, "decode": 3 * 4}
    while eng.has_work():
        eng.step()
    assert _gaps(eng)["refill"] == _gaps(eng)["same_tick"] == 0


@pytest.mark.parametrize("async_readback", [True, False])
def test_the_causes_sum_to_the_itl_totals(async_readback, no_held):
    eng = _three_streams(async_readback=async_readback)
    eng.add_request(_req("late", 40, max_tokens=4))     # two chunks
    while eng.has_work():
        eng.step()
    gaps = eng.stats()["requests"]["gaps"]
    slo = eng.telemetry.slo_totals()
    # every token but each request's first has its gap
    assert slo["itl_n"] == sum(r["n"] for r in gaps.values()) \
        == 3 * 40 + 4 - 4
    assert slo["itl_s"] == pytest.approx(
        sum(r["seconds"] for r in gaps.values()), abs=1e-5)
    for r in gaps.values():
        assert sum(r["hist"].values()) == r["n"]
        assert 0.0 <= r["between_s"] <= r["seconds"] + 1e-9


def test_a_live_capture_books_capture_and_a_waiting_one_nothing(
        tmp_path, no_held):
    """Behind another profiler session (the benchmark's, an operator's)
    an armed capture waits for its start and costs a tick nothing: its
    gaps are the model's. Once its trace is live, and while it is being
    written, they are `capture`."""
    eng = _three_streams()
    before = _gaps(eng)
    jax.profiler.start_trace(str(tmp_path / "other"),
                             profiler_options=_options())
    try:
        eng.profile_next_ticks(2, log_dir=str(tmp_path / "own"))
        for _ in range(3):
            eng.step()
        assert eng._profile["state"] == "starting"
        assert _since(eng, before) == {"decode": 9}
    finally:
        jax.profiler.stop_trace()
    assert eng.wait_for_profile(60.0)           # the start is made
    assert eng._profile["state"] == "running"
    eng.step()
    assert _since(eng, before) == {"decode": 9, "capture": 3}
    while eng._profile is not None:
        eng.step()
        assert eng.wait_for_profile(60.0)
    assert set(_since(eng, before)) == {"decode", "capture"}
    eng.step()                  # its gap reaches back to a captured call
    before = _gaps(eng)
    eng.step()
    assert _since(eng, before) == {"decode": 3}


def test_engine_steps_span_carries_the_calls_gaps(tmp_path, no_held):
    eng = _three_streams()
    eng.add_request(_req("warm", 10, max_tokens=2))     # compile outside
    for _ in range(6):
        eng.step()
    first = eng.ticks + 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=_options())
    try:
        eng.add_request(_req("p0", 12, max_tokens=8))
        for _ in range(4):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    steps = {s[4]["tick"]: s[4] for s in _host_spans(str(tmp_path))
             if s[1] == "engine.step"}
    admitting, quiet, refill = (steps[first + k] for k in range(3))
    assert admitting["gaps"] == 6 and admitting["gap_cause"] == "ragged"
    assert float(admitting["gap_max_ms"]) > 0.0
    assert quiet["gaps"] == 0 and "gap_cause" not in quiet
    assert refill["gaps"] == 4 and refill["gap_cause"] == "refill"
    # the longest gap reaches back over the call before it
    span = next(s for s in _host_spans(str(tmp_path))
                if s[1] == "engine.step" and s[4]["tick"] == first + 2)
    assert float(refill["gap_max_ms"]) * 1e6 > span[3] - span[2]
