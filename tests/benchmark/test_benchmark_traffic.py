"""The traffic's shape comes from its file, never from the seed; latency
in an open loop is taken from when a request was due."""

import asyncio
import collections
import json
import os

import numpy as np
import pytest

from benchmarks.lib import loadgen
from benchmarks.lib.harness import ROOT

TRAFFIC_DIR = os.path.join(ROOT, "benchmarks", "traffic")


def _traffic(name):
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


SERVE_MIXES = sorted(
    f[:-5] for f in os.listdir(TRAFFIC_DIR)
    if f.endswith(".json") and _traffic(f[:-5])["runner"] == "serve")


def test_quantile_grid_is_the_distributions_shape():
    grid = loadgen.quantile_grid(
        {"dist": "lognormal", "median": 256, "sigma": 1.0,
         "min": 64, "max": 2048}, 64)
    assert grid == sorted(grid) and grid[0] == 64 and grid[-1] == 2048
    assert 240 < (grid[31] + grid[32]) / 2 < 272        # the median
    expo = loadgen.quantile_grid({"dist": "exponential"}, 1000)
    assert abs(sum(expo) / 1000 - 1.0) < 0.01


def test_permutation_needs_a_coprime_stride():
    assert sorted(loadgen.permutation(64, 27)) == list(range(64))
    with pytest.raises(ValueError):
        loadgen.permutation(64, 6)


@pytest.mark.parametrize("stride", [1, 27, 29, 57])
def test_permutation_refuses_a_stride_that_shuffles_nothing(stride):
    """29 = 1 mod 28 left a cycle of 28 gaps sorted: one burst of 13
    arrivals in 5 s every cycle (PR 24's first submission)."""
    with pytest.raises(ValueError, match="in order"):
        loadgen.permutation(28, stride)


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_arrivals_are_spread_like_a_poisson_stream_not_a_burst(mix):
    """No 5 s of a cycle holds more than 2.5 times its share of the
    arrivals, and neighbouring gaps do not follow each other's size."""
    tr = _traffic(mix)
    gaps = loadgen.arrival_gaps(tr)
    n, period = len(gaps), sum(gaps)
    due = np.cumsum(gaps + gaps)
    most = max(int(((due >= s) & (due < s + 5.0)).sum())
               for s in np.arange(0.0, period, 0.25))
    assert most <= 2.5 * 5.0 * n / period
    ranks = np.argsort(np.argsort(gaps))
    lag1 = np.corrcoef(ranks, np.roll(ranks, 1))[0, 1]
    assert abs(lag1) < 0.5


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_length_cycle_is_fixed_and_within_bounds(mix):
    tr = _traffic(mix)
    cycle = loadgen.length_cycle(tr)
    assert cycle == loadgen.length_cycle(tr) and len(cycle) == tr["cycle"]
    for p, o in cycle:
        assert p >= 2 and o >= 1


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 31 + 11)])
def test_open_schedule_shape_is_the_same_for_two_seeds(seeds):
    tr = _traffic("chat-steady")
    n = tr["cycle"]
    window = 2 * n / tr["rate_rps"]          # a whole number of cycles
    a, b = (loadgen.open_schedule(tr, s, window) for s in seeds)

    def steps(sch):
        """(gap before it, prompt, output) of each request of the window"""
        out = []
        for prev, p in zip(sch, sch[1:]):
            if p.due_s >= 0:
                out.append((round(p.due_s - prev.due_s, 9),
                            p.prompt_tokens, p.output_tokens))
        return out

    assert len(steps(a)) == len(steps(b)) == 2 * n
    # the same sizes after the same gaps ...
    assert collections.Counter(steps(a)) == collections.Counter(steps(b))
    # ... in the same cyclic order, from another phase
    one, other = steps(a)[:n], steps(b)[:n]
    assert any(one == other[k:] + other[:k] for k in range(n))
    assert [p.due_s for p in a] != [p.due_s for p in b]


def test_arrival_gaps_last_exactly_a_cycle():
    tr = _traffic("chat-steady")
    gaps = loadgen.arrival_gaps(tr)
    assert sum(gaps) == pytest.approx(tr["cycle"] / tr["rate_rps"])
    assert {**tr, "rate_rps": 2 * tr["rate_rps"]} and sum(
        loadgen.arrival_gaps({**tr, "rate_rps": 2 * tr["rate_rps"]})
    ) == pytest.approx(tr["cycle"] / tr["rate_rps"] / 2)


def test_prompt_text_token_ids_differ_by_seed_not_length():
    a = loadgen.prompt_text(1, 5, 300)
    b = loadgen.prompt_text(2, 5, 300)
    assert len(a) == len(b) == 299 and a != b
    assert a == loadgen.prompt_text(1, 5, 300)
    assert a.isascii() and a.isprintable()
    assert loadgen.prompt_text(2 ** 31 + 11, 0, 64) != a[:63]


def test_packed_batches_same_size_every_seed_other_ids():
    job = _traffic("pretrain-packed-2k")
    a = next(loadgen.packed_batches(job, 92544, 4, 2048, 1))
    b = next(loadgen.packed_batches(job, 92544, 4, 2048, 2))
    again = next(loadgen.packed_batches(job, 92544, 4, 2048, 1))
    assert a.shape == b.shape == (4, 2048) and a.dtype == np.int32
    assert (a == again).all() and (a != b).any()
    assert a.min() >= 1 and a.max() < 92544
    # Zipf: the commonest id is far commoner than uniform
    assert (a == 3).mean() > 0.02


def _records():
    plan = lambda i, due: loadgen.Planned(i, 100, 4, due_s=due)
    mk = lambda i, due, sent, times, reason="length", err=None: (
        loadgen.Record(plan=plan(i, due), sent_s=sent, token_times=times,
                       token_ids=[5] * len(times), prompt_tokens_seen=100,
                       finish_reason=reason, done_s=times[-1] if times
                       else None, error=err))
    return [
        # ramp: due before the window, not measured, completes inside it
        mk(0, -1.0, -1.0, [0.1, 0.2, 0.3, 0.4]),
        # sent 50 ms late: latency still runs from when it was due
        mk(1, 1.0, 1.05, [1.25, 1.30, 1.35, 1.40]),
        mk(2, 2.0, 2.0, [2.1, 2.2, 2.3, 2.5]),
        # failed: counts as failed and has no latency
        mk(3, 3.0, 3.0, [], reason=None, err="boom"),
        # too many tokens: failed
        mk(4, 4.0, 4.0, [4.1, 4.2, 4.3, 4.4, 4.5]),
        # finishes after the window: measured, but its tokens are not
        # completed in the window
        mk(5, 9.5, 9.5, [9.9, 10.1, 10.2, 10.3]),
    ]


def test_open_loop_latency_is_taken_from_the_due_time():
    s = loadgen.summarise(_records(), 10.0, vocab_size=512)
    assert s["attempted"] == 5 and s["failed"] == 2
    assert s["ttft_n"] == 3
    # due 1.0 -> first token 1.25: 250 ms, not 200 from the send time
    assert s["ttft_p50_ms"] == pytest.approx(250.0)
    assert s["late_max_ms"] == pytest.approx(50.0)
    # requests 0, 1, 2 and (failed, but complete) 4 ended in the window
    assert s["completed_in_window"] == 4
    # tokens streamed inside the window: 4 + 4 + 4 + 5 + the one token
    # of request 5 at 9.9 s; prompts whose first token arrived inside it:
    # all five that got a token
    assert s["serve_tok_s"] == pytest.approx((18 + 5 * 100) / 10.0)
    assert s["itl_n"] == 9


def test_ttft_mean_is_over_every_measured_request():
    s = loadgen.summarise(_records(), 10.0, vocab_size=512)
    # 250, 100 and 400 ms; the failed requests contribute none
    assert s["ttft_mean_ms"] == pytest.approx(250.0)
    assert s["ttft_max_ms"] == pytest.approx(400.0)
    assert s["ttft_by_request"] == [(100, 250.0), (100, 100.0),
                                    (100, 100.0), (100, 400.0)]
    assert s["ttft_highest_supported_percentile"] is None


def test_summary_of_a_later_window_counts_only_what_is_due_in_it():
    """The sweep reads two cycles of one run: the second one alone."""
    s = loadgen.summarise(_records(), 5.0, vocab_size=512, start_s=5.0)
    assert s["attempted"] == 1 and s["failed"] == 0
    assert s["ttft_mean_ms"] == pytest.approx(400.0)
    # the one token of request 5 at 9.9 s and its prompt
    assert s["serve_tok_s"] == pytest.approx((1 + 100) / 5.0)


def test_token_outside_vocabulary_fails_the_request():
    recs = _records()
    recs[2].token_ids[1] = 999
    assert loadgen.summarise(recs, 10.0, 512)["failed"] == 3


def test_driver_sends_when_due_and_counts_unfinished_as_failed():
    async def go():
        t0 = asyncio.get_running_loop().time()
        clock = lambda: asyncio.get_running_loop().time() - t0 - 0.05

        async def send(rec):
            if rec.plan.index == 2:
                await asyncio.sleep(30)          # never finishes
            rec.token_times.append(clock())
            rec.token_ids.append(5)
            rec.finish_reason, rec.done_s = "length", clock()

        sched = [loadgen.Planned(i, 10, 1, due_s=0.01 * i)
                 for i in range(4)]
        return await loadgen.drive_open(send, sched, clock, 0.05, 0.05)

    recs = asyncio.run(go())
    assert len(recs) == 4
    assert [r.error is None for r in recs] == [True, True, False, True]
    assert all(r.sent_s >= r.plan.due_s for r in recs)
