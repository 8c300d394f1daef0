"""Serving cells: an `LLMServerImpl` in this process, driven through its
token-stream entry by the load generator's open loop.

Set-up (counted in `setup_s`): engine up with weights from the seed,
logits checks, then a warm-up that walks every (token bucket, context
bucket) program the cell's lengths can reach, so that nothing compiles in
the window. Then the ramp, the window, the grace.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

from ..lib import checks, loadgen, program, trace_reduce
from ..lib.harness import (Context, RunResult, say, trace_options,
                           trace_span)


def run(ctx: Context) -> RunResult:
    return asyncio.run(_run(ctx))


def _build_server(ctx: Context):
    """The engine at the configuration's pool size; if the chip refuses
    the pool, lower num_pages by steps of 256 and say so out loud."""
    import jax

    from ray_tpu.llm._internal.server import LLMServerImpl
    ekw = dict(ctx.config["engine"])
    if "mesh_shape" in ekw:
        ekw["mesh_shape"] = tuple(ekw["mesh_shape"])
    ekw["seed"] = ctx.program_seed
    model = program.llama_config(ctx.config)
    stated = ekw["num_pages"]
    while True:
        try:
            return LLMServerImpl({"model_id": ctx.config_name,
                                  "model_source": model,
                                  "engine_kwargs": ekw}), stated
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) or ekw["num_pages"] <= 256:
                raise
            say(f"[serve] CONFIG CHANGED: num_pages {ekw['num_pages']} "
                f"refused for memory ({str(e).splitlines()[0][:200]}); "
                f"trying {ekw['num_pages'] - 256}")
            ekw["num_pages"] -= 256


def _bucket(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _powers(lo: int, hi: int) -> List[int]:
    """lo, 2 lo, 4 lo, ... up to hi."""
    out = []
    while lo <= hi:
        out.append(lo)
        lo *= 2
    return out


class _Client:
    """Sends one planned request through the server's token stream and
    records what a client sees."""

    def __init__(self, server, ctx: Context):
        self.server, self.ctx = server, ctx
        self.origin = time.monotonic()      # clock's zero; window() sets it
        self.sampling = dict(ctx.traffic.get("sampling") or {})

    def clock(self) -> float:
        """Seconds from the window's start."""
        return time.monotonic() - self.origin

    async def __call__(self, rec: loadgen.Record, tag: int = 0) -> None:
        plan = rec.plan
        body = {"prompt": loadgen.prompt_text(
                    self.ctx.seed + tag, plan.index, plan.prompt_tokens),
                "max_tokens": plan.output_tokens, "stream": True,
                **self.sampling}
        if self.sampling.get("temperature"):
            body["seed"] = (self.ctx.seed * 1000003 + plan.index) % 2 ** 31
        async for chunk in self.server.completions_stream_tokens(body):
            now = self.clock()
            rec.token_times.extend([now] * len(chunk["toks"]))
            rec.token_ids.extend(chunk["toks"])
            rec.prompt_tokens_seen = chunk["prompt_tokens"]
            if chunk["finished"]:
                rec.finish_reason = chunk["reason"]
        rec.done_s = self.clock()


async def _warm(server, ctx: Context, client: _Client) -> Dict[str, Any]:
    """Walk the ragged programs this cell can reach. A tick's program is
    keyed by (tokens in the tick rounded up to a power of two, pages of
    the longest context in it rounded up to a power of two, all-greedy).
    For each context bucket an anchor request decodes inside it while
    short-lived requests of each token bucket ride beside it."""
    eng = server.engine
    ec, tr = eng.config, ctx.traffic
    page, chunk = ec.page_size, ec.max_prefill_tokens
    budget = ec.max_num_batched_tokens or (chunk + ec.max_batch_size)
    t_buckets = _powers(8, _bucket(budget, 8))
    cycle = loadgen.length_cycle(tr)
    lo_tokens = min(min(p for p, _ in cycle), chunk)
    hi_tokens = max(p + o for p, o in cycle)
    ctx_buckets = [0] + _powers(_bucket(-(-lo_tokens // page)),
                                _bucket(-(-hi_tokens // page)))
    before = eng.stats()["jit_cache"]["compiled_programs"]
    n = 0
    rider_ms: Dict[str, float] = {}   # "T/ctx pages" -> a rider's latency

    def rec(prompt: int, out: int) -> loadgen.Record:
        nonlocal n
        n += 1
        return loadgen.Record(plan=loadgen.Planned(n, prompt, out),
                              sent_s=client.clock())

    async def rider(t: int, c: int) -> None:
        """One short request that makes a tick of token bucket t: alone
        (c == 0) all of the tick, beside the anchor its row plus ours."""
        t0 = time.monotonic()
        first = rec(min(t if c == 0 else t - 1, chunk), 1)
        if t > chunk and c == 0:
            # more than a chunk at context 0 takes two prompts in a tick
            await asyncio.gather(client(first, tag=1),
                                 client(rec(budget - chunk, 1), tag=1))
        else:
            await client(first, tag=1)
        rider_ms[f"{t}/{c}"] = round((time.monotonic() - t0) * 1e3, 1)

    for c in ctx_buckets:
        if c == 0:
            for t in t_buckets:
                await rider(t, 0)
            continue
        lo, hi = (c // 2) * page + 2, c * page
        todo = list(t_buckets)
        while todo:
            anchor = rec(lo, hi - lo)
            task = asyncio.create_task(client(anchor, tag=1))
            while not anchor.token_ids and not task.done():
                await asyncio.sleep(0.002)
            # riders while the anchor has room left in its bucket; then
            # let it go and, if riders remain, start another
            while todo and not task.done() and (
                    lo + len(anchor.token_ids) + 8 < hi):
                await rider(todo.pop(0), c)
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
    # cancelled anchors retire at the engine's next ticks
    while eng.has_work():
        await asyncio.sleep(0.01)
    stats = eng.stats()["jit_cache"]
    return {"requests": n, "t_buckets": t_buckets,
            "ctx_buckets": ctx_buckets,
            "programs_built": stats["compiled_programs"] - before,
            "ragged_programs": stats["ragged_buckets"],
            "rider_ms": rider_ms}


async def _monitor(eng, clock, window_s: float,
                   trace_at: Optional[Tuple[float, float]], log_dir: str,
                   marks: Dict[str, Any]) -> None:
    """Counter snapshots at the window's two ends, pool occupancy each
    second between them, and (traced runs) the profiler over
    [trace_at[0], trace_at[1]) of the window."""
    import jax
    loop = asyncio.get_running_loop()

    async def until(t: float) -> None:
        wait = t - clock()
        if wait > 0:
            await asyncio.sleep(wait)

    def snapshot() -> Dict[str, Any]:
        return {"stats": eng.stats(), "slo": eng.telemetry.slo_totals()}

    await until(0.0)
    marks["start"] = await loop.run_in_executor(None, snapshot)
    marks["occupancy"], marks["live"] = [], []
    tracing = False
    for sec in range(1, int(window_s) + 1):
        if trace_at and not tracing and clock() >= trace_at[0]:
            await loop.run_in_executor(
                None, lambda: jax.profiler.start_trace(
                    log_dir, profiler_options=trace_options()))
            tracing = True
        if tracing and clock() >= trace_at[1]:
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            tracing, trace_at = False, None
        await until(min(float(sec), window_s))
        st = await loop.run_in_executor(None, eng.stats)
        marks["occupancy"].append(
            1.0 - st["free_pages"] / max(st["total_pages"], 1))
        marks["live"].append(st["active"])
    if tracing:
        await loop.run_in_executor(None, jax.profiler.stop_trace)
    await until(window_s)
    marks["end"] = await loop.run_in_executor(None, snapshot)


async def setup(ctx: Context):
    """Engine up, logits checks, warm-up. Returns (server, client,
    detail, correct); `client.clock` reads seconds from the origin that
    `window` sets."""
    ctx.phase("imports and the chip", ctx.t_start)
    t0 = time.monotonic()
    server, stated_pages = _build_server(ctx)
    eng = server.engine
    cfg = eng.model_cfg
    ctx.phase("engine up", t0)
    say(f"[serve] {cfg.n_layers} layers, hidden {cfg.hidden}, {cfg.n_heads}q/"
        f"{cfg.n_kv_heads}kv, head_dim {cfg.head_dim}, vocab "
        f"{cfg.vocab_size}; pool {eng.k_pages.shape} {eng.k_pages.dtype}; "
        f"decode_impl {eng._resolve_impl()}")
    detail: Dict[str, Any] = {"num_pages": eng.config.num_pages,
                              "num_pages_stated": stated_pages}
    correct = True
    if ctx.chips == 1:
        t0 = time.monotonic()
        detail["logits"] = checks.serve_logits(
            eng, ctx.config, ctx.program_seed, say)
        correct = detail["logits"]["ok"]
        ctx.phase("logits checks", t0)
    client = _Client(server, ctx)
    t0 = time.monotonic()
    detail["warmup"] = await _warm(server, ctx, client)
    ctx.phase(f"warm-up {detail['warmup']}", t0)
    return server, client, detail, correct


async def window(server, client: _Client, ctx: Context,
                 tr: Dict[str, Any],
                 window_s: Optional[float] = None) -> Dict[str, Any]:
    """The ramp, the window (ctx.seconds long unless the sweep asks for
    another length) and the grace of one traffic mix on a warm server.
    Returns the client summary, the requests' records, the counter marks,
    the trace's events and when the window started."""
    if tr["loop"] != "open":
        raise ValueError(f"no loop {tr['loop']!r}: the generator offers "
                         "open-loop traffic only")
    eng = server.engine
    window_s = float(ctx.seconds if window_s is None else window_s)
    clock = client.clock
    # the window starts ramp_s from now
    client.origin = started = (time.monotonic() + float(tr["ramp_s"])
                               + 0.05)
    log_dir = os.path.join(ctx.out_dir, "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    marks: Dict[str, Any] = {}
    monitor = asyncio.create_task(
        _monitor(eng, clock, window_s, trace_span(ctx), log_dir, marks))
    records = await loadgen.drive_open(
        client, loadgen.open_schedule(tr, ctx.seed, window_s), clock,
        float(tr["grace_s"]), window_s)
    await monitor
    summary = loadgen.summarise(records, window_s,
                                eng.model_cfg.vocab_size)
    live = marks["live"]
    say(f"[serve] window {window_s:.0f}s: " + ", ".join(
        f"{k}={v}" for k, v in summary.items())
        + f", live_slots first/mean/last="
        f"{live[0]}/{sum(live) / len(live):.1f}/{live[-1]}")
    events = trace_reduce.extract(log_dir) if ctx.trace else []
    return {"client": summary, "records": records, "marks": marks,
            "events": events, "started": started}


async def _run(ctx: Context) -> RunResult:
    import jax

    tr = ctx.traffic
    server, client, detail, correct = await setup(ctx)
    got = await window(server, client, ctx, tr)
    summary = got["client"]
    devs = jax.devices()[:ctx.chips]
    end_to_end = {"setup_s": ctx.setup_s(got["started"]),
                  "serve_tok_s": summary["serve_tok_s"]}
    for name in ("ttft_mean_ms", "ttft_p50_ms", "ttft_p95_ms",
                 "itl_p50_ms", "itl_p95_ms"):
        if summary[name] is not None:
            end_to_end[name] = summary[name]
    run = {"events": got["events"], "client": summary,
           "marks": got["marks"], "window_s": float(ctx.seconds),
           "config": ctx.config, "traffic": tr,
           "device_kind": devs[0].device_kind, "chips": ctx.chips}
    return RunResult(
        correct=bool(correct and summary["failed"] == 0
                     and summary["attempted"] > 0),
        attempted=summary["attempted"], failed=summary["failed"],
        end_to_end=end_to_end, run=run, detail=detail)
