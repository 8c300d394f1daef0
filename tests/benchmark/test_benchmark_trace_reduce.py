"""The reduction from a device trace to numbers, on events small enough
to work out by hand and on the fixture recorded on the chip."""

import json
import os

import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.harness import ROOT

P0, P1 = "/device:TPU:0", "/device:TPU:1"
M, O = tr.MODULES, tr.OPS

# times in ns. Two programs on chip 0: a decode tick 1000..1100 and a
# ragged tick 1200..1400; the profiler's own start-up op at 10 is outside
# the window and must not count.
HAND = [
    [P0, O, "startup", 10, 50],
    [P0, M, "jit_step(111)", 1000, 100],
    [P0, M, "jit_run(222)", 1200, 200],
    [P0, M, "jit_step(111)", 1450, 50],
    [P0, O, "while", 1000, 80],           # spans its body
    [P0, O, "fusion.1", 1010, 30],
    [P0, O, "fusion.1", 1050, 20],
    [P0, O, "convert.7", 1085, 15],
    [P0, O, "dot.3", 1200, 200],
    [P0, O, "dot.3", 1450, 50],
    ["/host:CPU", "python", "noise", 0, 5000],
    [P1, M, "jit_step(111)", 1000, 500],
    [P1, O, "while", 1000, 250],
]


def test_window_is_first_to_last_program_not_the_capture():
    assert tr.window_ns(HAND, P0) == (1000, 1500)
    assert tr.planes(HAND) == [P0, P1]


def test_busy_is_the_union_of_operation_intervals():
    # 1000..1080 (while covers its body), 1085..1100, 1200..1400,
    # 1450..1500
    assert tr.busy_intervals(HAND, P0) == [
        (1000, 1080), (1085, 1100), (1200, 1400), (1450, 1500)]
    busy, window = tr.busy_and_window_s(HAND)
    # chip 0 busy 345 ns, chip 1 busy 250 ns: the mean; window 500 ns
    assert busy == pytest.approx((345 + 250) / 2 * 1e-9)
    assert window == pytest.approx(500e-9)
    assert tr.idle_share_pct(HAND) == pytest.approx(100 * (1 - 345 / 500))
    assert tr.idle_share_pct(HAND, P1) == pytest.approx(50.0)


def test_module_medians():
    assert tr.module_durations_ms(HAND, "jit_step") == [1e-4, 5e-5]
    assert tr.module_median_ms(HAND, "jit_step") == pytest.approx(7.5e-5)
    assert tr.module_median_ms(HAND, "jit_run") == pytest.approx(2e-4)
    assert tr.module_median_ms(HAND, "jit_other") is None


def test_operation_self_time_charges_a_loop_what_its_body_leaves():
    ops = tr.op_self_seconds(HAND)
    assert ops["while"] == pytest.approx(30e-9)        # 80 - 30 - 20
    assert ops["fusion.1"] == pytest.approx(50e-9)
    assert ops["dot.3"] == pytest.approx(250e-9)
    assert "startup" in ops                            # outside, but an op


def test_idle_gaps_are_named_by_the_programs_around_them():
    gaps = tr.idle_gaps_s(HAND)
    assert gaps == {
        "jit_step": pytest.approx(5e-9),               # 1080..1085
        "jit_step->jit_run": pytest.approx(100e-9),
        "jit_run->jit_step": pytest.approx(50e-9)}
    b = tr.breakdown(HAND)
    assert b["device_ops"][0] == ["dot.3", pytest.approx(250e-9)]
    assert b["idle_gaps"][0][0] == "jit_step->jit_run"
    assert len(b["device_ops"]) <= 10


def test_no_device_plane_reads_as_nothing():
    host_only = [e for e in HAND if e[0] == "/host:CPU"]
    assert tr.busy_and_window_s(host_only) == (0.0, 0.0)
    assert tr.idle_share_pct(host_only) is None
    assert tr.breakdown([]) == {"device_ops": [], "idle_gaps": []}
    assert tr.extract(os.path.join(ROOT, "benchmarks", "lib")) == []


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(ROOT, "benchmarks", "fixtures",
                        "chat_open_two_ticks.json")
    with open(path) as f:
        data = json.load(f)
    assert os.path.getsize(path) < 1 << 20 and "TPU v5 lite" in data[
        "recorded"]
    return data["events"]


def test_fixture_two_recorded_ragged_ticks(recorded):
    """Expected values worked out outside the reduction: a sweep over
    the operations' end points with a depth counter gives 1,324,425,730
    busy ns in a window of 1,351,887,087 ns (first program's start to
    the last one's end); the two jit_run events last 667.932118 and
    656.488449 ms."""
    assert len(recorded) == 724
    assert tr.planes(recorded) == [P0]
    assert tr.window_ns(recorded, P0) == (0, 1351887087)
    busy, window = tr.busy_and_window_s(recorded)
    assert busy == pytest.approx(1.324425730, abs=1e-9)
    assert window == pytest.approx(1.351887087, abs=1e-9)
    assert tr.idle_share_pct(recorded) == pytest.approx(
        100 * (1 - 1324425730 / 1351887087))
    assert tr.module_durations_ms(recorded, "jit_run") == [
        pytest.approx(667.932118), pytest.approx(656.488449)]
    assert tr.module_median_ms(recorded, "jit_run") == pytest.approx(
        (667.932118 + 656.488449) / 2)
    assert tr.module_median_ms(recorded, "jit_step") is None


def test_fixture_breakdown_names_the_loop_and_the_gap_after_a_tick(recorded):
    b = tr.breakdown(recorded)
    # the fixture keeps the loop's nested operations for two of 24
    # iterations, so the loop itself leads; the ragged kernel is next
    assert [n for n, _ in b["device_ops"][:2]] == [
        "while.4", "closed_call.9[custom-call]"]
    # the longest idle gap: between a tick's end and the small programs
    # the host runs before the next one
    assert b["idle_gaps"][0][0] == "jit_run->jit__threefry_split"
    assert b["idle_gaps"][0][1] == pytest.approx(0.023432338, abs=1e-9)
    # gaps between operations add up to the idle time, less what lies
    # before the window's first operation and after its last (341 ns)
    assert sum(tr.idle_gaps_s(recorded).values()) == pytest.approx(
        (1351887087 - 1324425730 - 341) / 1e9, abs=1e-12)
