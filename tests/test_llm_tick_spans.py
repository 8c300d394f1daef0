"""Tick spans on the profiler's clock, the per-tick phase table and the
counters behind stats()["tick_phases"] / ["prefill"] / ["self_captures"]
(ISSUE 25), on a tiny engine on the CPU.

The traced tests step the engine under `jax.profiler` with the options
the benchmark's traced run uses (Python tracer off, host tracer 1) and
read the `.xplane.pb` back with `jax.profiler.ProfileData`: the spans
are checked where a reader finds them, not where the engine puts them.
"""

import asyncio
import contextlib
import glob
import inspect
import os
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm._internal.engine import (TICK_PHASES, EngineConfig,
                                          InferenceEngine, Request,
                                          SamplingParams)
from ray_tpu.models import llama
from ray_tpu.util import profiling

SPANS = tuple("engine." + p for p in TICK_PHASES)


def make_engine(**over):
    cfg = llama.config("debug", dtype=jnp.float32)
    kw = dict(model=cfg, max_batch_size=8, page_size=8, num_pages=128,
              max_prefill_tokens=32, seed=3,
              metrics_model_id=f"sp{uuid.uuid4().hex[:10]}")
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


def _req(rid, n_prompt, max_tokens=8):
    # a prompt of its own for each id: none finds another in the
    # prefix cache
    rng = np.random.default_rng(sum(map(ord, rid)) * 1000 + n_prompt)
    return Request(rid, rng.integers(2, 250, n_prompt).tolist(),
                   SamplingParams(max_tokens=max_tokens))


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _host_spans(log_dir):
    """[(thread line, name, start_ns, end_ns, {argument: value})] of the
    spans this PR names, from the newest trace under log_dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    assert paths, f"no trace under {log_dir}"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "server.", "train.")):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _drive_mixed(eng):
    """Three requests decoding, then two prompts arrive: the tick after
    their admission is ragged with three decode rows and two prefill
    rows. Returns (that tick's ring record, its number, the context
    tokens its attention reads)."""
    for i in range(3):
        eng.add_request(_req(f"d{i}", 10 + i, max_tokens=40))
    for _ in range(6):
        eng.step()
    assert sum(1 for s in eng.slots if s.ready) == 3
    eng.add_request(_req("p0", 10))
    eng.add_request(_req("p1", 14))
    # the decode tick in flight folds before the admission, so the
    # positions the ragged tick reads are one past today's
    ahead = 1 if eng._inflight is not None else 0
    want_kv = sum(s.position + ahead + 1
                  for s in eng.slots if s.ready) + 10 + 14
    eng.step()
    return eng._tick_times[-1], eng.ticks, want_kv


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One engine stepped under the profiler: the mixed tick of
    `_drive_mixed`, then to the end of every request."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    eng = make_engine()
    eng.add_request(_req("warm", 12, max_tokens=4))   # compile outside
    while eng.has_work():
        eng.step()
    first = eng.ticks + 1
    jax.profiler.start_trace(log_dir, profiler_options=_options())
    try:
        _, mixed_tick, want_kv = _drive_mixed(eng)
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    return {"eng": eng, "spans": _host_spans(log_dir), "first": first,
            "mixed_tick": mixed_tick, "want_kv": want_kv}


def _steps(spans):
    return sorted((s for s in spans if s[1] == "engine.step"),
                  key=lambda s: s[2])


def test_every_span_present_and_nested_under_its_step(traced):
    spans = traced["spans"]
    steps = _steps(spans)
    eng = traced["eng"]
    assert [s[4]["tick"] for s in steps] == list(
        range(traced["first"], eng.ticks + 1))
    names = {s[1] for s in spans}
    for name in SPANS:
        assert name in names, f"no {name} span in the trace"
    for line, name, a, b, _ in spans:
        if not name.startswith("engine.") or name == "engine.step":
            continue
        assert any(st[0] == line and st[2] <= a and b <= st[3]
                   for st in steps), f"{name} outside every engine.step"


def test_dispatch_arguments_equal_what_the_tick_carried(traced):
    spans = traced["spans"]
    step = next(s for s in _steps(spans)
                if s[4]["tick"] == traced["mixed_tick"])
    disp = [s for s in spans if s[1] == "engine.dispatch"
            and step[2] <= s[2] and s[3] <= step[3]]
    assert len(disp) == 1
    args = disp[0][4]
    assert args["kind"] == "ragged"
    assert args["rows"] == 5 and args["decode_rows"] == 3
    assert args["prefill_tokens"] == 24
    assert args["T"] == 32            # 27 tokens, next power of two
    assert args["kv_tokens"] == traced["want_kv"]
    assert args["built"] == 1         # the first (32, ctx) program
    # the scheduling span of that tick admitted the two prompts
    sched = next(s for s in spans if s[1] == "engine.sched"
                 and step[2] <= s[2] and s[3] <= step[3])
    assert sched[4]["admitted"] == 2 and sched[4]["waiting"] == 2
    # and a decode tick says so, with its rows
    kinds = {s[4]["kind"] for s in spans if s[1] == "engine.dispatch"}
    assert kinds == {"ragged", "decode"}
    dec = next(s[4] for s in spans if s[1] == "engine.dispatch"
               and s[4]["kind"] == "decode")
    assert dec["rows"] == dec["decode_rows"] >= 1
    assert dec["prefill_tokens"] == 0 and dec["kv_tokens"] > 0


def test_dispatch_span_counts_the_attention_kernels_work(traced):
    """`attn_items` / `attn_kv_blocks`: the ragged kernel's live grid
    steps and the KV blocks they sweep, on the mixed tick (T 32, so a
    query block is 32 rows and every row of the plan is one item)."""
    spans = traced["spans"]
    eng = traced["eng"]
    args = next(s[4] for s in spans if s[1] == "engine.dispatch"
                and s[4]["tick"] == traced["mixed_tick"])
    assert args["attn_items"] == 5
    # a context block is 128 keys (16 pages of 8): each decode row
    # (12-15 cached tokens) sweeps one, and every item its own
    # in-batch block
    assert args["attn_kv_blocks"] == 3 * (1 + 1) + 2 * 1
    rec = next(r for r in eng._tick_times
               if r.kind == "ragged" and r.T == 32 and r.rows == 5)
    assert (rec.attn_items, rec.attn_kv_blocks) == (5, 8)
    assert rec.brief()["attn_items"] == 5
    # a decode tick runs another kernel: nothing to count
    dec = next(s[4] for s in spans if s[1] == "engine.dispatch"
               and s[4]["kind"] == "decode")
    assert "attn_items" not in dec


def test_attn_counts_equal_a_hand_count_on_a_three_slot_plan():
    """One row decoding at 19 cached tokens, a 200-token prompt and a
    20-token prompt in one T 256 tick: q_blk 128, so the long prompt is
    two items whose in-batch sweeps are 1 and 2 blocks (the causal
    diagonal), the others one item and one in-batch block each; only
    the decode row has a context, one block of it."""
    eng = make_engine(max_prefill_tokens=256, max_num_batched_tokens=256,
                      num_pages=256)
    eng.add_request(_req("d", 12, max_tokens=40))
    for _ in range(8):
        eng.step()
    eng.add_request(_req("long", 200))
    eng.add_request(_req("short", 20))
    eng.step()
    rec = eng._tick_times[-1]
    assert (rec.kind, rec.T, rec.rows, rec.prefill_tokens) == (
        "ragged", 256, 3, 220)
    assert rec.attn_items == 1 + 2 + 1
    assert rec.attn_kv_blocks == (1 + 1) + (1 + 2) + 1
    assert eng._tick_carried is None


def test_benchmarks_reduction_ignores_the_new_arguments():
    """The benchmark's span reduction reads the dispatch span's
    arguments by name: the chip's recorded capture reduces to the same
    tables with `attn_items` / `attn_kv_blocks` present on every
    ragged dispatch."""
    import copy
    import json
    from benchmarks.lib import span_reduce as sr
    from benchmarks.lib.harness import ROOT
    with open(os.path.join(ROOT, "benchmarks", "fixtures",
                           "chat_open_ticks_spans.json")) as f:
        cap = json.load(f)
    cap["enqueues"] = {int(k): v for k, v in cap["enqueues"].items()}
    more = copy.deepcopy(cap)
    n = 0
    for span in more["spans"]:
        if span[1] == "engine.dispatch" and span[4]["kind"] == "ragged":
            span[4].update(attn_items=9, attn_kv_blocks=40)
            n += 1
    assert n == 3
    assert sr.tables(more) == sr.tables(cap)
    assert sr.ragged_cost(more) == sr.ragged_cost(cap) is not None


def test_ring_record_matches_the_dispatch_span():
    eng = make_engine()
    rec, _, _ = _drive_mixed(eng)
    assert (rec.kind, rec.T, rec.rows, rec.prefill_tokens) == (
        "ragged", 32, 5, 24)
    assert rec.compiles == 1 and rec.start > 0
    before = eng._tick_times[-2]
    assert before.kind == "decode" and before.rows == 3


def test_phases_and_other_sum_to_the_wall():
    eng = make_engine()
    _drive_mixed(eng)
    while eng.has_work():
        eng.step()
    assert len(eng._tick_times) == eng.ticks
    for rec in eng._tick_times:
        assert set(rec.phases_ms) == set(TICK_PHASES) | {"other"}
        assert all(v >= 0 for v in rec.phases_ms.values())
        assert sum(rec.phases_ms.values()) == pytest.approx(
            rec.wall_ms, rel=1e-6, abs=1e-6)
        assert rec.host_ms == rec.phases_ms["fold"]
        assert rec.device_ms == rec.phases_ms["readback_wait"]
    tp = eng.stats()["tick_phases"]
    assert tp["ticks"] == eng.ticks
    total_wall = sum(r.wall_ms for r in eng._tick_times) / 1e3
    assert sum(tp["seconds"].values()) == pytest.approx(total_wall,
                                                        rel=1e-3)
    assert tp["gap_s"] > 0        # stepped back to back with work left


def _flat(d, prefix=""):
    for k, v in d.items():
        if isinstance(v, dict):
            yield from _flat(v, prefix + k + ".")
        else:
            yield prefix + k, v


def test_counters_are_monotone():
    eng = make_engine()
    for i in range(3):
        eng.add_request(_req(f"m{i}", 12 + 9 * i, max_tokens=12))
    prev = None
    while eng.has_work():
        eng.step()
        st = eng.stats()
        now = dict(_flat({k: st[k] for k in (
            "tick_phases", "prefill", "self_captures")}))
        if prev is not None:
            for k, v in prev.items():
                assert now[k] >= v, f"{k} went down: {v} -> {now[k]}"
        prev = now
    assert prev["tick_phases.ticks"] == eng.ticks
    assert prev["prefill.prompt_tokens_admitted"] == 12 + 21 + 30


def _recompute_ratio(eng):
    p = eng.stats()["prefill"]
    return (p["prefill_tokens_dispatched"]
            / p["prompt_tokens_admitted"] - 1.0)


def test_recompute_ratio_zero_on_a_plain_run():
    eng = make_engine()
    _drive_mixed(eng)
    while eng.has_work():
        eng.step()
    assert _recompute_ratio(eng) == 0.0


def test_recompute_ratio_positive_after_preempt_and_recompute():
    eng = make_engine(enable_prefix_caching=False)
    eng.add_request(_req("long", 100, max_tokens=4))
    eng.step()                       # first 32-token chunk
    eng.step()
    assert not eng.slots[0].ready
    assert eng.preempt("long", reason="test")      # requeue: restarts
    while eng.has_work():
        eng.step()
    p = eng.stats()["prefill"]
    assert p["prompt_tokens_admitted"] == 100
    assert p["prefill_tokens_dispatched"] == 100 + 64
    assert _recompute_ratio(eng) == pytest.approx(0.64)


OLD_TICK_TIMES_KEYS = {
    "window", "wall_ms_avg", "host_ms_avg", "device_ms_avg",
    "overlap_ratio", "lagged_ticks", "drains", "async_readback",
    *(f"{n}_{q}" for n in ("wall_ms", "host_ms", "device_ms")
      for q in ("p50", "p95", "p99"))}


def test_tick_times_keeps_every_key_and_names_the_longest():
    eng = make_engine()
    _drive_mixed(eng)
    while eng.has_work():
        eng.step()
    tt = eng.stats()["tick_times"]
    assert OLD_TICK_TIMES_KEYS <= set(tt)
    assert eng._tick_times.maxlen == 1024
    longest = tt["longest"]
    assert set(longest["median_wall_ms"]) == {"ragged", "decode"}
    worst = longest["stalls"][0]
    assert worst["excess_ms"] == max(s["excess_ms"]
                                     for s in longest["stalls"])
    for key in ("start", "wall_ms", "kind", "T", "ctx", "rows",
                "phases_ms", "compiles", "gap_ms"):
        assert key in worst
    assert worst["start"] <= tt["now"]
    gaps = longest["gaps"]
    assert gaps and gaps[0]["gap_ms"] == pytest.approx(
        max(r.gap_ms for r in eng._tick_times), abs=1e-3)
    # the old triple survives in the black box's tick list
    bid = eng.dump_blackbox("manual")
    ticks = eng.blackbox.read(bid)["tick_times_ms"]
    assert ticks and all(len(t) == 3 for t in ticks)


def test_self_captures_count_arming_starting_and_dumping(tmp_path):
    eng = make_engine()
    eng.add_request(_req("c", 12, max_tokens=6))
    eng.step()
    assert eng.stats()["self_captures"] == {
        "profiles_armed": {}, "profiles_started": 0,
        "blackbox_dumps": {}, "lock_hold_s": 0.0}
    eng.profile_next_ticks(2, str(tmp_path / "prof"))
    assert eng._arm_profile_locked(2) is None     # armed already: no count
    assert eng.wait_for_profile(60)               # started off-tick
    while eng.has_work():
        eng.step()
    assert eng.wait_for_profile(60)               # written off-tick
    assert eng._arm_profile_locked(1, trigger="tick_anomaly")
    eng.dump_blackbox("manual")
    eng.dump_blackbox("manual")
    sc = eng.stats()["self_captures"]
    assert sc["profiles_armed"] == {"manual": 1, "tick_anomaly": 1}
    assert sc["profiles_started"] == 1
    assert sc["blackbox_dumps"] == {"manual": 2}
    assert 0.0 < sc["lock_hold_s"] < 0.05         # the ticks' counting
    # the operator's capture is the light one and holds the spans
    names = {s[1] for s in _host_spans(str(tmp_path / "prof"))}
    assert {"engine.step", "engine.dispatch"} <= names


def test_an_armed_capture_waits_out_another_profiler_session(
        tmp_path, monkeypatch):
    """While someone else's jax.profiler session is open (or being
    exported: the session object stays until the export ends), an armed
    capture neither starts nor fails: `start_trace` under the step lock
    would wait out that export. It starts at the first tick after."""
    from jax._src import profiler as jax_profiler
    eng = make_engine()
    eng.add_request(_req("w", 12, max_tokens=8))
    eng.step()
    monkeypatch.setattr(jax_profiler._profile_state, "profile_session",
                        object())
    eng.profile_next_ticks(2, str(tmp_path / "prof"))
    eng.step()
    eng.step()
    assert eng._profile is not None and eng._profile["cm"] is None
    assert eng.stats()["self_captures"]["profiles_started"] == 0
    assert not eng.wait_for_profile(0.1)          # the thread asks on
    monkeypatch.setattr(jax_profiler._profile_state, "profile_session",
                        None)
    assert eng.wait_for_profile(60)               # started off-tick
    while eng.has_work():
        eng.step()
    assert eng.wait_for_profile(60)
    assert eng.stats()["self_captures"]["profiles_started"] == 1
    assert eng._profile is None


def test_session_open_is_not_known_where_jax_keeps_it_elsewhere(
        tmp_path, monkeypatch):
    """The session object is jax's own (`jax._src.profiler`). Where a
    jax has moved it `session_open` answers False, not an error, and
    the armed capture goes on to `start_trace` as it did before."""
    from jax._src import profiler as jax_profiler
    assert profiling.session_open() is False
    monkeypatch.delattr(jax_profiler, "_profile_state")
    assert profiling.session_open() is False
    eng = make_engine()
    eng.add_request(_req("w", 12, max_tokens=6))
    eng.step()
    started = []

    @contextlib.contextmanager
    def trace(log_dir):
        started.append(log_dir)
        yield

    monkeypatch.setattr(profiling, "trace", trace)
    eng.profile_next_ticks(2, str(tmp_path / "prof"))
    assert eng.wait_for_profile(60)               # started off-tick
    monkeypatch.undo()
    while eng.has_work():
        eng.step()
    assert eng.wait_for_profile(60)
    assert eng.stats()["self_captures"]["profiles_started"] == 1
    assert started == [str(tmp_path / "prof")] and eng._profile is None


def test_a_fault_in_the_question_is_a_profile_error_not_a_failed_tick(
        tmp_path, monkeypatch):
    """Whatever `session_open` raises is recorded as the capture's
    error, as `start_trace`'s own failure is: `step()` goes on."""
    eng = make_engine()
    eng.add_request(_req("w", 12, max_tokens=6))
    eng.step()

    def broken():
        raise RuntimeError("no such state")
    monkeypatch.setattr(profiling, "session_open", broken)
    eng.profile_next_ticks(2, str(tmp_path / "prof"))
    eng.step()
    assert eng._profile is None
    assert eng.stats()["self_captures"]["profiles_started"] == 0
    errors = [e for e in eng.telemetry.recorder.events()
              if e["event"] == "profile_error"]
    assert errors and "no such state" in errors[-1]["error"]


def test_request_timeline_records_engine_ticks():
    eng = make_engine()
    eng.add_request(_req("first", 12, max_tokens=3))
    eng.step()
    eng.add_request(_req("t", 70, max_tokens=3))    # three 32-token chunks
    while eng.has_work():
        eng.step()
    snap = {s["request_id"]: s for s in eng.telemetry.live_snapshot()}
    assert snap["first"]["admitted_tick"] == 1
    assert snap["first"]["first_token_tick"] == 1
    t = snap["t"]
    assert t["admitted_tick"] == 2
    assert t["prefill_ticks"] == [2, 3, 4]
    assert t["first_token_tick"] == 4
    chunk = [e for e in eng.chrome_trace()["traceEvents"]
             if e["name"] == "prefill_chunk"
             and e["args"]["request_id"] == "t"]
    assert [e["args"]["tick"] for e in chunk] == [2, 3, 4]


def test_server_deliver_span(tmp_path):
    from ray_tpu.llm._internal.server import LLMServerImpl
    server = LLMServerImpl({
        "model_id": f"sv{uuid.uuid4().hex[:8]}",
        "model_source": llama.config("debug", dtype=jnp.float32),
        "engine_kwargs": {"max_batch_size": 4, "page_size": 8,
                          "num_pages": 64}})

    async def one(n):
        toks = []
        async for chunk in server.completions_stream_tokens(
                {"prompt": "hello there", "max_tokens": n,
                 "stream": True}):
            toks.extend(chunk["toks"])
        return toks

    async def main():
        await one(3)                                  # compile outside
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=_options())
        try:
            return await one(5)
        finally:
            jax.profiler.stop_trace()

    assert len(asyncio.run(main())) == 5
    spans = _host_spans(str(tmp_path))
    deliver = [s for s in spans if s[1] == "server.deliver"]
    assert deliver and sum(s[4]["touched"] for s in deliver) == 5
    # the pump delivers between ticks, never inside one
    for _, _, a, b, _ in deliver:
        assert not any(st[2] < b and a < st[3] for st in _steps(spans))


def test_train_step_span(tmp_path):
    from ray_tpu.models.training import TrainStepBundle
    from ray_tpu.parallel import MeshSpec
    cfg = llama.config("debug", dtype=jnp.float32)
    mesh = MeshSpec(dp=1, fsdp=1, sp=1, tp=1).build(jax.devices()[:1])
    bundle = TrainStepBundle(cfg, mesh)
    state = bundle.init_state(0)
    tokens = bundle.shard_batch(jnp.ones((2, 16), jnp.int32))
    state, _ = bundle.step(state, tokens)
    jax.profiler.start_trace(str(tmp_path), profiler_options=_options())
    try:
        for _ in range(2):
            state, _ = bundle.step(state, tokens)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    steps = [s for s in _host_spans(str(tmp_path))
             if s[1] == "train.step"]
    assert [s[4]["step_num"] for s in steps] == [2, 3]


def test_profiling_module_lost_what_had_no_caller():
    assert not hasattr(profiling, "profile_step")
    assert list(inspect.signature(profiling.trace).parameters) == [
        "log_dir"]


# ---- names on the device: kernels and layer scopes ---------------------

def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _under(scope, text):
    """An operation's location names `scope` as a whole path component
    (the body of a layer scan names its own from the scan inwards; under
    differentiation the component reads `jvp(scope)` or
    `transpose(jvp(scope))`)."""
    import re
    return re.search(rf'["/(]{scope}[/)]', text) is not None


def test_serving_forward_carries_the_layer_scopes():
    from ray_tpu.models.llama_infer import decode_step
    from ray_tpu.ops.paged_attention import pool_head_dim
    cfg = llama.config("debug", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    b, pages = 2, 8
    pool = jnp.zeros((cfg.n_layers, 16, 8, cfg.n_kv_heads,
                      pool_head_dim(cfg.head_dim, "gather")), cfg.dtype)
    text = _hlo(
        lambda p, k, v: decode_step(
            cfg, p, jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
            k, v, jnp.zeros((b, pages), jnp.int32),
            jnp.ones(b, bool), impl="gather"),
        params, pool, pool)
    for scope in ("embed", "attn", "mlp", "lm_head"):
        assert _under(scope, text), f"no op under scope {scope}"


def test_sampling_carries_its_scope():
    from ray_tpu.llm._internal.engine import _sample
    text = _hlo(lambda l, k: _sample(l, k, jnp.ones(2), jnp.ones(2)),
                jnp.zeros((2, 64)), jax.random.PRNGKey(0))
    assert _under("sample", text)


def _equations(jaxpr, outer=""):
    """Every equation of a jaxpr and of the jaxprs inside it, each with
    the scope path it lowers under."""
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, path)


@pytest.mark.parametrize(
    "b,v", [(32, 92544), (64, 16160), (32, 25024), (64, 200064)],
    ids=["chat-open", "dsv3-longchat", "trinity-mixed", "phi4flash-reason"])
def test_sampling_moves_nothing_across_the_vocabulary(b, v):
    """`_sample` as the tick programs call it, at the four serving cells'
    [B, V]: no gather and no scatter has an operand or a result with a
    vocabulary-sized dimension (on the chip those two were 49 of the 53 ms
    `_sample` took over [32, 92544]; PERF.md section 6, PR 28), the row is
    not sorted either (17 ms over [64, 200064]; PR 37: the cut is selected),
    and every operation sits under `sample`."""
    from ray_tpu.llm._internal.engine import _sample
    S = jax.ShapeDtypeStruct

    def tick_sample(logits, key, temps, top_ps, top_ks, rep_pens, seen,
                    row_keys):
        return _sample(logits, key, temps, top_ps, top_ks, rep_pens, seen,
                       False, row_keys=row_keys)
    args = (S((b, v), jnp.float32), S((2,), jnp.uint32),
            S((b,), jnp.float32), S((b,), jnp.float32), S((b,), jnp.int32),
            S((b,), jnp.float32), S((b, v), jnp.bool_),
            S((b, 2), jnp.uint32))
    for eqn, path in _equations(jax.make_jaxpr(tick_sample)(*args).jaxpr):
        name = eqn.primitive.name
        if name.startswith(("gather", "scatter")):
            shapes = [getattr(x.aval, "shape", ())
                      for x in list(eqn.invars) + list(eqn.outvars)]
            assert not any(v in s for s in shapes), (name, shapes)
        assert "sample" in path.split("/"), (name, path)
    # and in what is handed to the compiler: none at all, and no sort
    text = jax.jit(tick_sample).lower(*args).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text
    assert "stablehlo.sort" not in text


def test_train_step_carries_the_layer_scopes():
    from ray_tpu.models.training import TrainStepBundle
    from ray_tpu.parallel import MeshSpec
    cfg = llama.config("debug", dtype=jnp.float32)
    mesh = MeshSpec(dp=1, fsdp=1, sp=1, tp=1).build(jax.devices()[:1])
    bundle = TrainStepBundle(cfg, mesh)
    state = jax.eval_shape(bundle._init_impl, jax.random.PRNGKey(0))
    with bundle._mesh_ctx():
        text = bundle._step.lower(
            state, jax.ShapeDtypeStruct((2, 16), jnp.int32)).as_text(
                debug_info=True)
    for scope in ("embed", "attn", "mlp", "loss_head", "optimizer"):
        assert _under(scope, text), f"no op under scope {scope}"
    # the backward pass names the scope it transposes
    assert "transpose(jvp(loss_head))" in text


KERNEL_NAMES = {
    "ragged_paged_attention": "ray_tpu/ops/ragged_paged_attention.py",
    # the same call site on a sliding-window layer (`window=`, PR 31)
    "ragged_window_attention": "ray_tpu/ops/ragged_paged_attention.py",
    "paged_decode": "ray_tpu/ops/paged_attention.py",
    "paged_decode_mp": "ray_tpu/ops/paged_attention.py",
    "flash_fwd": "ray_tpu/ops/attention.py",
    "flash_bwd_dq": "ray_tpu/ops/attention.py",
    "flash_bwd_dkv": "ray_tpu/ops/attention.py",
}


def test_every_pallas_call_site_passes_a_stable_name():
    import re
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = {}
    for path in sorted(set(KERNEL_NAMES.values())):
        src = open(os.path.join(root, path)).read()
        calls = src.count("pl.pallas_call(")
        # a literal, or a choice between literals by a static argument
        named = re.findall(r'\n\s+name=((?:.|\n)*?),\n', src)
        assert calls == len(named), f"{path}: a pallas_call without name="
        for arg in named:
            names = re.findall(r'"(\w+)"', arg)
            assert names, f"{path}: name={arg} is no literal"
            found.update({n: path for n in names})
    assert found == KERNEL_NAMES


def test_flash_kernels_show_their_names_in_the_program():
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 128, 2, 32), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 64, 64, True))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text
