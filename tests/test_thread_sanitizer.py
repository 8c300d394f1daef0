"""Runtime thread sanitizer (ISSUE 18) + engine concurrency stress.

Unit half: the sanitizer's own contracts — disarmed make_lock is a
plain threading.Lock (zero production overhead), armed locks detect
order inversions and owner re-acquisition, guarded_by descriptors
check lock ownership on reads/writes with an unguarded() escape hatch.

Stress half: the tier-1 gate the static analyzer cannot give — the
REAL engine hammered from concurrent threads (stats / lane_counts /
session_ids / abort / preempt / export_session of unknown ids) while
the pump steps 200 guarded ticks, with the sanitizer armed the whole
time. Passes only if (a) the dispatch guard sees exactly one dispatch
per tick, zero h2d uploads and zero compiles — the scrape path really
is host-only; (b) the sanitizer records ZERO violations — every
guarded-field touch held the lock; and (c) the decoded streams are
token-exact against a single-threaded oracle — concurrency changed
nothing observable.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import (EngineConfig, InferenceEngine, Request,
                         SamplingParams)
from ray_tpu.models import llama
from ray_tpu.util import thread_sanitizer as ts
from ray_tpu.util.jax_guard import dispatch_guard


@pytest.fixture(autouse=True)
def _disarm():
    yield
    ts.disarm()
    ts.reset()


# ------------------------------------------------------------- unit: locks

def test_disarmed_make_lock_is_plain_lock():
    lock = ts.make_lock("x")
    assert type(lock) is type(threading.Lock())


def test_armed_make_lock_traces():
    ts.arm()
    lock = ts.make_lock("x")
    assert isinstance(lock, ts._TracedLock)
    with lock:
        assert lock.held_by_me()
    assert not lock.held_by_me()


def test_lock_order_inversion_detected():
    ts.reset()
    ts.arm()
    a, b = ts.make_lock("a"), ts.make_lock("b")
    with a:
        with b:
            pass
    assert ts.violations() == []
    with b:
        with a:
            pass
    got = ts.violations()
    assert len(got) == 1
    assert "inversion" in got[0]
    with pytest.raises(AssertionError):
        ts.assert_clean()


def test_consistent_order_clean():
    ts.reset()
    ts.arm()
    a, b = ts.make_lock("a"), ts.make_lock("b")
    for _ in range(3):
        with a:
            with b:
                pass
    assert ts.violations() == []


def test_owner_reacquisition_reported_not_deadlocked():
    ts.reset()
    ts.arm()
    lock = ts.make_lock("x")
    with lock:
        # a real threading.Lock would deadlock here forever; the
        # traced lock records the bug and declines the acquisition
        assert lock.acquire(timeout=0.1) is False
    got = ts.violations()
    assert len(got) == 1
    assert "re-acquisition" in got[0]


def test_strict_mode_raises_on_violating_thread():
    ts.reset()
    ts.arm(strict=True)
    lock = ts.make_lock("x")
    with lock:
        with pytest.raises(AssertionError):
            lock.acquire()
    ts.disarm()


# -------------------------------------------------------- unit: guarded_by

class _Box:
    items = ts.guarded_by("_lock")
    log = ts.guarded_by("_lock", writes_only=True)

    def __init__(self):
        self._lock = ts.make_lock("box._lock")
        with self._lock:
            self.items = []
            self.log = []


def test_guarded_field_checks_only_when_armed():
    box = _Box()          # disarmed: plain lock, no checks ever
    box.items = [1]
    assert box.items == [1]
    ts.arm()              # lock is still a plain Lock -> still no checks
    box.items = [2]
    assert ts.violations() == []


def test_guarded_field_armed_write_without_lock():
    ts.reset()
    ts.arm()
    box = _Box()
    box.items = [1]                   # unguarded write
    _ = box.items                     # unguarded read
    box.log = []                      # write-guarded too
    _ = box.log                       # ...but reads of log are free
    got = ts.violations()
    assert len(got) == 3
    assert any("write of _Box.items" in v for v in got)
    assert any("read of _Box.items" in v for v in got)
    assert any("write of _Box.log" in v for v in got)


def test_guarded_field_clean_under_lock_and_unguarded():
    ts.reset()
    ts.arm()
    box = _Box()
    with box._lock:
        box.items = [1]
        assert box.items == [1]
    with ts.unguarded():              # the blackbox crash-path escape
        assert box.items == [1]
        box.items = [2]
    assert ts.violations() == []


def test_guarded_field_wrong_thread_detected():
    ts.reset()
    ts.arm()
    box = _Box()
    hold = threading.Event()
    release = threading.Event()

    def holder():
        with box._lock:
            hold.set()
            release.wait(5)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    hold.wait(5)
    box.items = [9]       # lock is held -- by ANOTHER thread
    release.set()
    t.join(5)
    assert any("write of _Box.items" in v for v in ts.violations())


def test_sanitized_scope_resets_and_disarms():
    with ts.sanitized():
        assert ts.armed()
        lock = ts.make_lock("y")
        with lock:
            lock.acquire(timeout=0.01)
    assert not ts.armed()
    assert len(ts.violations()) == 1   # survives for inspection
    ts.reset()
    assert ts.violations() == []


# --------------------------------------------- engine regression: snapshots

def _engine(**over):
    kw = dict(model=llama.config("debug", dtype=jnp.float32),
              max_batch_size=4, page_size=8, num_pages=160,
              seed=7)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _requests(n=3, max_tokens=256):
    rng = np.random.default_rng(11)
    return [Request(f"g{i}", rng.integers(2, 250, 12).tolist(),
                    SamplingParams(max_tokens=max_tokens))
            for i in range(n)]


def test_fleet_counters_published_snapshot():
    """fleet_counters() is the lock-free read the fleet scrape path
    uses: every mutating entry point republishes a FRESH dict (the
    old snapshot stays internally consistent for whoever holds it)."""
    eng = _engine()
    snap0 = eng.fleet_counters()
    assert snap0["waiting"] == 0 and snap0["active"] == 0
    req = _requests(1, max_tokens=16)[0]
    eng.add_request(req)
    snap1 = eng.fleet_counters()
    assert snap1 is not snap0          # replaced, not mutated
    assert snap0["waiting"] == 0       # old snapshot untouched
    assert snap1["waiting"] == 1
    while not req.finished:
        eng.step()
    snap2 = eng.fleet_counters()
    assert snap2["active"] == 0 and snap2["waiting"] == 0
    assert set(snap2) == {"active", "waiting", "parked_sessions",
                          "preemptions_total", "page_pressure", "lanes"}


def test_concurrent_adds_never_lost():
    """The race the old unlocked add_request lost: step() rebinds
    `waiting` to the survivors list mid-tick, and an append landing on
    the discarded list vanished silently. Locked add_request makes
    every add stick, whatever the interleaving."""
    eng = _engine(num_pages=256, max_batch_size=8)
    reqs = _requests(12, max_tokens=8)
    errs = []

    def pump():
        # until every request is through, not for a count of ticks: an
        # empty tick costs microseconds, and on a loaded host 400 of
        # them were over before the adding thread got its turn
        deadline = time.monotonic() + 110
        try:
            while time.monotonic() < deadline:
                eng.step()
                if all(r.finished for r in reqs):
                    return
        except BaseException as exc:   # pragma: no cover
            errs.append(exc)

    t = threading.Thread(target=pump)
    t.start()
    for r in reqs:
        eng.add_request(r)
    t.join(120)
    assert not errs
    assert all(r.finished for r in reqs)
    assert all(len(r.output_tokens) == 8 for r in reqs)


def test_stats_consistent_under_concurrent_steps():
    """stats()/lane_counts() snapshot under ONE lock acquisition: no
    RuntimeError from iterating the tick deque / preempt dict
    mid-mutation, and the per-call view is internally consistent
    (lanes vs waiting counted in the same critical section)."""
    eng = _engine()
    reqs = _requests(3, max_tokens=64)
    for r in reqs:
        eng.add_request(r)
    errs = []
    stop = threading.Event()

    def scrape():
        try:
            while not stop.is_set():
                s = eng.stats()
                assert s["waiting"] >= 0
                assert s["tick_times"]["window"] >= 0
                eng.lane_counts()
                eng.session_ids()
        except BaseException as exc:
            errs.append(exc)

    threads = [threading.Thread(target=scrape, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        while not all(r.finished for r in reqs):
            eng.step()
    finally:
        stop.set()
        for t in threads:
            t.join(30)
    assert not errs, errs


# ----------------------------------------------------- the armed stress gate

def _oracle_tokens(n_req, max_tokens):
    eng = _engine()
    reqs = _requests(n_req, max_tokens)
    for r in reqs:
        eng.add_request(r)
    while not all(r.finished for r in reqs):
        eng.step()
    return {r.request_id: list(r.output_tokens) for r in reqs}


@pytest.mark.slow
def test_armed_stress_token_exact_and_clean():
    # 12-token prompts + 240 <= max_seq 256; 240 decode ticks per
    # stream keeps every request live across the whole guarded window
    n_req, max_tokens, guarded_ticks = 3, 240, 200
    want = _oracle_tokens(n_req, max_tokens)

    with ts.sanitized():
        eng = _engine()     # created armed: traced step lock
        assert isinstance(eng._step_lock, ts._TracedLock)
        reqs = _requests(n_req, max_tokens)
        for r in reqs:
            eng.add_request(r)
        # warmup: admit + prefill + settle into steady pipelined decode
        while eng.waiting or any(s.request is not None and not s.ready
                                 for s in eng.slots):
            eng.step()
        for _ in range(4):
            eng.step()

        stop = threading.Event()
        errs = []

        def hammer():
            # every lock-taking, host-only entry point the serving
            # plane exercises concurrently with the pump; unknown ids
            # so no structural event (drain/refresh) lands inside the
            # dispatch-guarded window
            try:
                while not stop.is_set():
                    eng.stats()
                    eng.lane_counts()
                    eng.session_ids()
                    eng.fleet_counters()
                    eng.has_work()
                    assert eng.abort("no-such-id") is False
                    assert eng.preempt("no-such-id") is False
                    assert eng.export_session("no-such-id") is None
            except BaseException as exc:
                errs.append(exc)

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        d0, c0 = eng.dispatches, eng.compiles
        try:
            with dispatch_guard() as rep:
                for _ in range(guarded_ticks):
                    eng.step()
        finally:
            stop.set()
            for t in threads:
                t.join(60)
        assert not errs, errs
        # ISSUE 18 acceptance: 1 dispatch/tick, 0 h2d, 0 compiles
        # while three threads hammered every scrape/abort entry point
        assert eng.dispatches - d0 == guarded_ticks
        assert eng.compiles == c0
        assert rep.n_compiles == 0
        # run the streams to completion (still armed)
        while not all(r.finished for r in reqs):
            eng.step()
        ts.assert_clean()

    got = {r.request_id: list(r.output_tokens) for r in reqs}
    assert got == want      # concurrency changed nothing observable
