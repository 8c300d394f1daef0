"""The sweep's rule for a sustained rate, its reading of two cycles of
one run, and what `setup_s` leaves out."""

import pytest

from benchmarks import sweep
from benchmarks.lib import loadgen
from benchmarks.lib.harness import Context


def _half(live_mean, live_max, failed=0):
    return {"failed": failed, "live_mean": live_mean, "live_max": live_max}


@pytest.mark.parametrize("first, second, waiting, want", [
    # the second cycle finds the engine as the first did
    (_half(9.0, 14), _half(9.4, 15), 0, True),
    # within a tenth and one slot
    (_half(10.0, 16), _half(12.0, 18), 0, True),
    # slots fill from cycle to cycle: a backlog, before anything fails
    (_half(14.0, 20), _half(21.0, 29), 0, False),
    # every slot was taken at some second
    (_half(20.0, 32), _half(20.0, 30), 0, False),
    (_half(9.0, 14), _half(9.0, 14), 2, False),       # requests waiting
    (_half(9.0, 14), _half(9.0, 14, failed=1), 0, False),
])
def test_a_rate_is_sustained_if_the_second_cycle_is_no_fuller(
        first, second, waiting, want):
    assert sweep.sustained(first, second, waiting, slots=32) is want


def test_two_cycles_of_one_run_are_read_apart():
    def rec(i, due, first_token):
        times = [first_token, first_token + 0.1]
        return loadgen.Record(
            plan=loadgen.Planned(i, 50, 2, due_s=due), sent_s=due,
            token_times=times, token_ids=[5, 5], prompt_tokens_seen=50,
            finish_reason="length", done_s=times[-1])

    records = [rec(0, -1.0, -0.5), rec(1, 1.0, 1.2), rec(2, 3.0, 3.4),
               rec(3, 5.0, 5.2), rec(4, 7.0, 8.0)]
    live = [1, 1, 2, 2, 3, 3, 4, 4]           # sampled at seconds 1 .. 8
    first, second = sweep.halves(records, live, cycle_s=4.0, vocab_size=99)
    assert first["attempted"] == second["attempted"] == 2
    assert first["ttft_mean_ms"] == pytest.approx(300.0)
    assert second["ttft_mean_ms"] == pytest.approx(600.0)
    assert (first["live_mean"], first["live_max"]) == (1.5, 2)
    assert (second["live_mean"], second["live_last"]) == (3.5, 4)


def test_setup_s_leaves_out_the_runtime_attaching_to_the_chip():
    ctx = Context(workload="w", config_name="c", config={}, traffic={},
                  chips=1, seed=1, seconds=1.0, trace=False, out_dir=".",
                  t_start=100.0, chip_attach_s=8.5)
    assert ctx.setup_s(window_start=130.0) == pytest.approx(21.5)
