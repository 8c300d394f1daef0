"""LLM engine decode benchmark: continuous-batching tokens/s on one chip.

Prints ONE JSON line per run: {"metric", "value", "unit", "detail"}.
Measures steady-state decode throughput of the native paged-KV engine
(ray_tpu/llm/_internal/engine.py) at a fixed running batch, plus the
per-layer paged-attention decode cost at short vs long context — the
number that shows kernel decode cost scaling with ACTUAL context rather
than max context (VERDICT r1 weak #5).

On TPU the Pallas paged kernel runs compiled; on CPU the dense-gather
path runs (kernel correctness is covered by interpret-mode tests).
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _tpu_bench_model():
    """The ~890M bench model, shared by every sub-benchmark so they
    can never silently measure different models."""
    from ray_tpu.models import llama
    return llama.config("tiny", vocab_size=32000, hidden=2048,
                        n_layers=12, n_heads=16, n_kv_heads=8,
                        head_dim=128, ffn=8192, max_seq=2048)


def bench_engine(on_tpu: bool) -> dict:
    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)

    from ray_tpu.models import llama
    if on_tpu:
        cfg = _tpu_bench_model()
        batch, prompt_len, gen = 8, 128, 128
    else:
        cfg = llama.config("debug")
        batch, prompt_len, gen = 4, 16, 16
    ec = EngineConfig(model=cfg, max_batch_size=batch,
                      num_pages=max(256, batch * 32), page_size=16)
    eng = InferenceEngine(ec)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(batch):
        reqs.append(Request(
            request_id=f"r{i}",
            prompt_tokens=rng.integers(
                1, cfg.vocab_size, prompt_len).tolist(),
            params=SamplingParams(max_tokens=gen)))
        eng.add_request(reqs[-1])
    # Warm up until the whole batch is decoding (all prefills done +
    # first decode compiled) so the timed window is pure decode.
    while any(not r.output_tokens for r in reqs):
        eng.step()
    eng.step()
    before = sum(len(r.output_tokens) for r in reqs)
    t0 = time.perf_counter()
    steps = 0
    while steps < gen - 8 and eng.has_work():
        eng.step()
        steps += 1
    dt = time.perf_counter() - t0
    toks = sum(len(r.output_tokens) for r in reqs) - before
    return {
        "decode_tokens_per_sec": round(toks / dt, 1),
        "decode_step_ms": round(dt / max(steps, 1) * 1e3, 2),
        "batch": batch, "prompt_len": prompt_len,
        "params": cfg.num_params(),
    }


def bench_mixed(on_tpu: bool, smoke: bool = False) -> dict:
    """Mixed prefill+decode throughput (ISSUE 1 headline): bursts of
    prompts land WHILE a batch decodes, so prefilling and decoding
    slots contend for the whole run — the regime where the legacy
    engine serializes prefills one chunk per tick (paying a separate
    whole-batch decode dispatch each time) and the unified ragged step
    packs everything into ONE dispatch under the token budget.
    Records the new rows: steps-per-token and dispatches-per-step.
    token_match is the fraction of requests whose greedy output is
    bit-identical across the two engines — flips are near-tie argmax
    noise (~0.02 logit margins, where the unified step tracks the
    full-forward gold at least as closely as the legacy path)."""
    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    if smoke:
        # CI contract: tiny and fast (<30 s) regardless of host
        cfg = llama.config("debug")
        batch, plen, n_req, chunk, budget = 4, 48, 10, 16, 64
        burst, every, gen0 = 3, 6, 8
    elif on_tpu:
        cfg = _tpu_bench_model()
        batch, plen, n_req, chunk, budget = 8, 256, 24, 64, 512
        burst, every, gen0 = 6, 10, 48
    else:
        # big enough that compute (not Python overhead) dominates a tick
        cfg = llama.config("tiny", vocab_size=2048, hidden=256,
                           n_layers=4, n_heads=8, n_kv_heads=4,
                           head_dim=32, ffn=1024, max_seq=512)
        batch, plen, n_req, chunk, budget = 8, 112, 24, 16, 256
        burst, every, gen0 = 6, 10, 16
    rng = np.random.default_rng(4)
    lens = [plen + 16 * (i % 3) for i in range(n_req)]
    gens = [gen0 + 8 * (i % 3) for i in range(n_req)]
    prompts = [rng.integers(1, cfg.vocab_size, lens[i]).tolist()
               for i in range(n_req)]

    def run(unified):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=16,
            num_pages=max(512, batch * 32), seed=5,
            max_prefill_tokens=chunk, enable_prefix_caching=False,
            unified_step=unified, max_num_batched_tokens=budget))

        def drive():
            eng._prefill_rr = 0          # identical packing every pass
            reqs = [Request(f"m{i}", list(p),
                            SamplingParams(max_tokens=gens[i]))
                    for i, p in enumerate(prompts)]
            pending = list(reqs)
            steps = 0
            while eng.has_work() or pending:
                if pending and steps % every == 0:
                    for r in pending[:burst]:
                        eng.add_request(r)
                    pending = pending[burst:]
                eng.step()
                steps += 1
            return reqs, steps

        drive()                          # warmup: compiles every bucket
        d0, t0s = eng.dispatches, eng.ticks
        t0 = time.perf_counter()
        reqs, steps = drive()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {
            "tokens_per_sec": round(toks / dt, 1),
            "steps_per_token": round(steps / toks, 3),
            "dispatches_per_step": round(
                (eng.dispatches - d0) / max(eng.ticks - t0s, 1), 3),
            "steps": steps,
        }, [r.output_tokens for r in reqs]

    unified, out_u = run(True)
    legacy, out_l = run(False)
    return {
        "unified": unified, "legacy": legacy,
        "unified_speedup": round(
            unified["tokens_per_sec"]
            / max(legacy["tokens_per_sec"], 1e-9), 2),
        "token_match": round(
            sum(a == b for a, b in zip(out_u, out_l)) / n_req, 3),
        "batch": batch, "prompt_len": plen, "requests": n_req,
        "chunk": chunk, "token_budget": budget,
    }


def bench_async_ab(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 4 A/B: pipelined async readback vs synchronous folds on
    the bursty mixed prefill+decode workload — the regime with both
    steady decode runs (where the pipeline overlaps host folds with
    device compute) and constant structural events (where it drains).
    Greedy, so the async engine must be TOKEN-EXACT vs sync: the
    one-tick lag only delays when tokens become host-visible, never
    what they are. Reports tokens/s each way plus the async engine's
    tick_times telemetry (overlap_ratio = share of tick wall-time NOT
    blocked on the device readback). In --smoke mode this asserts
    exactness and a never-materially-slower tripwire."""
    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    if smoke:
        cfg = llama.config("debug")
        batch, plen, n_req, chunk, budget = 4, 48, 10, 16, 64
        burst, every, gen0 = 3, 6, 8
    elif on_tpu:
        cfg = _tpu_bench_model()
        batch, plen, n_req, chunk, budget = 8, 256, 24, 64, 512
        burst, every, gen0 = 6, 10, 48
    else:
        cfg = llama.config("tiny", vocab_size=2048, hidden=256,
                           n_layers=4, n_heads=8, n_kv_heads=4,
                           head_dim=32, ffn=1024, max_seq=512)
        batch, plen, n_req, chunk, budget = 8, 112, 24, 16, 256
        burst, every, gen0 = 6, 10, 16
    rng = np.random.default_rng(8)
    lens = [plen + 16 * (i % 3) for i in range(n_req)]
    gens = [gen0 + 8 * (i % 3) for i in range(n_req)]
    prompts = [rng.integers(1, cfg.vocab_size, lens[i]).tolist()
               for i in range(n_req)]

    def run(async_readback):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=16,
            num_pages=max(512, batch * 32), seed=5,
            max_prefill_tokens=chunk, enable_prefix_caching=False,
            max_num_batched_tokens=budget,
            async_readback=async_readback))

        def drive():
            eng._prefill_rr = 0          # identical packing every pass
            reqs = [Request(f"a{i}", list(p),
                            SamplingParams(max_tokens=gens[i]))
                    for i, p in enumerate(prompts)]
            pending = list(reqs)
            steps = 0
            while eng.has_work() or pending:
                if pending and steps % every == 0:
                    for r in pending[:burst]:
                        eng.add_request(r)
                    pending = pending[burst:]
                eng.step()
                steps += 1
            return reqs, steps

        drive()                          # warmup: compiles every bucket
        # align the GC phase before timing: cyclic collection points
        # are deterministic in allocation counts, so WITHOUT this an
        # unrelated upstream code change can shift a ~100 ms gen-2
        # pass (the jax object graph is big) into exactly one arm of
        # the A/B and fake a 0.6x "regression" at smoke sizes
        import gc
        gc.collect()
        t0 = time.perf_counter()
        reqs, steps = drive()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {"tokens_per_sec": round(toks / dt, 1), "steps": steps,
                "tick_times": eng.stats()["tick_times"]}, \
            [r.output_tokens for r in reqs]

    async_row, out_a = run(True)
    sync_row, out_s = run(False)
    res = {
        "async": async_row, "sync": sync_row,
        "async_speedup": round(
            async_row["tokens_per_sec"]
            / max(sync_row["tokens_per_sec"], 1e-9), 2),
        "token_exact": out_a == out_s,
        "batch": batch, "requests": n_req, "chunk": chunk,
    }
    if smoke:
        assert res["token_exact"], \
            f"async decode diverged from sync: {out_a} vs {out_s}"
        assert async_row["tick_times"]["lagged_ticks"] > 0, \
            "async engine never pipelined a tick"
        # regression tripwire with slack for CI timer noise: the
        # pipeline must never make decode materially slower
        assert res["async_speedup"] >= 0.8, res
    return res


def bench_telemetry(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 5 gate, two halves. Correctness: after a bursty mixed
    run, /metrics must render with TTFT observations == finished
    requests and ITL observations == generated tokens minus first
    tokens (every token the engine folded is accounted exactly once).
    Overhead: the identical workload with enable_metrics=False is the
    baseline — instrumentation is host-only Python on the fold path
    (the dispatch-guard suite separately proves zero transfers /
    compiles), so the instrumented run must not be slower beyond
    timer noise. In --smoke mode both halves assert."""
    import re
    import uuid

    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    if on_tpu and not smoke:
        cfg = _tpu_bench_model()
        batch, plen, n_req, chunk, budget = 8, 256, 24, 64, 512
        burst, every, gen0 = 6, 10, 48
    else:
        cfg = llama.config("debug")
        batch, plen, n_req, chunk, budget = 4, 48, 10, 16, 64
        burst, every, gen0 = 3, 6, 8
    rng = np.random.default_rng(11)
    lens = [plen + 16 * (i % 3) for i in range(n_req)]
    gens = [gen0 + 8 * (i % 3) for i in range(n_req)]
    prompts = [rng.integers(1, cfg.vocab_size, lens[i]).tolist()
               for i in range(n_req)]

    def run(enable_metrics):
        tag = f"bench{uuid.uuid4().hex[:8]}"
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=16,
            num_pages=max(512, batch * 32), seed=5,
            max_prefill_tokens=chunk, enable_prefix_caching=False,
            max_num_batched_tokens=budget,
            enable_metrics=enable_metrics, metrics_model_id=tag))

        def drive():
            eng._prefill_rr = 0
            reqs = [Request(f"t{uuid.uuid4().hex[:6]}", list(p),
                            SamplingParams(max_tokens=gens[i]))
                    for i, p in enumerate(prompts)]
            pending = list(reqs)
            steps = 0
            while eng.has_work() or pending:
                if pending and steps % every == 0:
                    for r in pending[:burst]:
                        eng.add_request(r)
                    pending = pending[burst:]
                eng.step()
                steps += 1
            return reqs

        drive()                          # warmup: compiles every bucket
        t0 = time.perf_counter()
        reqs = drive()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {"tokens_per_sec": round(toks / dt, 1)}, eng, tag

    on_row, eng_on, tag = run(True)
    off_row, _, _ = run(False)

    def sample(text, name, **tags):
        for line in text.splitlines():
            m = re.match(r"^([a-zA-Z0-9_]+)(?:\{(.*)\})? (.+)$", line)
            if m is None or m.group(1) != name:
                continue
            got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
            if got == {k: str(v) for k, v in tags.items()}:
                return float(m.group(3))
        return None

    text = eng_on.prometheus_metrics()
    s = eng_on.stats()["requests"]
    finished = sum(s["finished"].values())
    ttft = sample(text, "ray_tpu_llm_ttft_seconds_count", model=tag)
    itl = sample(text, "ray_tpu_llm_itl_seconds_count", model=tag)
    res = {
        "metrics_on": on_row, "metrics_off": off_row,
        "overhead_ratio": round(
            on_row["tokens_per_sec"]
            / max(off_row["tokens_per_sec"], 1e-9), 3),
        "renders": bool(text) and ttft is not None,
        "finished_requests": finished,
        "generated_tokens": s["generated_tokens"],
        "ttft_count": ttft, "itl_count": itl,
        "ttft_count_ok": ttft == finished,
        "itl_count_ok": itl == s["generated_tokens"] - finished,
    }
    if smoke:
        assert res["renders"], "metrics exposition failed to render"
        assert res["ttft_count_ok"], res
        assert res["itl_count_ok"], res
        # tripwire with slack for CI timer noise: host-only recording
        # must never make decode materially slower
        assert res["overhead_ratio"] >= 0.8, res
    return res


def bench_perf_accounting(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 11 gate, three parts.

    Self-consistency: a single-request sync run's analytic totals must
    equal the closed form replayed from the known composition (one
    full-prompt prefill + G-1 decode ticks at growing context) — the
    accounting can't drift from the costs it claims to sum. And the
    rolling summary must be sane: flops > 0, 0 < MFU <= 1 against the
    envelope, a roof named.

    Overhead: the bursty mixed workload with
    enable_perf_accounting=False as baseline — accounting is a handful
    of host multiplies per tick, so the A/B must be ~1.0x (the
    dispatch-guard suite separately proves zero transfers/compiles).

    Regression gate: the canonical perfdiff workload's fingerprint
    (exact closed-form costs + deterministic dispatch mix and token
    totals) must match the committed PERF_BASELINE.json; noisy rates
    are checked against their wide bands. In --smoke mode all three
    assert."""
    import uuid

    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.llm._internal.perfmodel import CostModel
    from ray_tpu.models import llama
    from tools import perfdiff

    if on_tpu and not smoke:
        cfg = _tpu_bench_model()
        batch, plen, n_req, chunk, budget = 8, 256, 24, 64, 512
        burst, every, gen0 = 6, 10, 48
    else:
        cfg = llama.config("debug")
        batch, plen, n_req, chunk, budget = 4, 48, 10, 16, 64
        burst, every, gen0 = 3, 6, 8

    # -- part 1: closed-form self-consistency (sync, one request) ------
    P, G = 24, 12
    eng1 = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=2, page_size=16, num_pages=64,
        max_prefill_tokens=max(P, chunk), seed=3,
        enable_prefix_caching=False, async_readback=False,
        metrics_model_id=f"perf{uuid.uuid4().hex[:8]}"))
    rng = np.random.default_rng(17)
    r1 = Request("pa0", rng.integers(1, cfg.vocab_size, P).tolist(),
                 SamplingParams(max_tokens=G))
    eng1.add_request(r1)
    while eng1.has_work():
        eng1.step()
    tot = eng1.stats()["perf"]["totals"]
    cm = CostModel(cfg, page_size=16)
    expect = {"flops_gemm": 0.0, "flops_attn": 0.0,
              "bytes_kv_read": 0.0, "bytes_kv_write": 0.0}
    for k, v in cm.chunk_cost(0, P).items():
        expect[k] += v
    for i in range(G - 1):                 # decode at growing context
        for k, v in cm.decode_cost(P + 1 + i).items():
            expect[k] += v
    closed_form_ok = (
        abs(tot["flops_gemm"] - expect["flops_gemm"]) < 1e-3
        and abs(tot["flops_attn"] - expect["flops_attn"]) < 1e-3
        and abs(tot["bytes_kv_read"] - expect["bytes_kv_read"]) < 1e-3
        and abs(tot["bytes_kv_write"] - expect["bytes_kv_write"]) < 1e-3)
    perf1 = eng1.stats()["perf"]

    # -- part 2: accounting-on vs -off overhead A/B --------------------
    rng = np.random.default_rng(11)
    lens = [plen + 16 * (i % 3) for i in range(n_req)]
    gens = [gen0 + 8 * (i % 3) for i in range(n_req)]
    prompts = [rng.integers(1, cfg.vocab_size, lens[i]).tolist()
               for i in range(n_req)]

    def run(enable_perf):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=16,
            num_pages=max(512, batch * 32), seed=5,
            max_prefill_tokens=chunk, enable_prefix_caching=False,
            max_num_batched_tokens=budget,
            enable_perf_accounting=enable_perf,
            # the ISSUE 13 planes ride the accounting hooks but are
            # NOT what this gate measures — bench_attribution holds
            # their own on/off A/B (and the anomaly detector's
            # auto-capture must not tax a timed arm)
            enable_attribution=False,
            enable_anomaly_detection=False,
            metrics_model_id=f"perf{uuid.uuid4().hex[:8]}"))

        def drive():
            eng._prefill_rr = 0
            reqs = [Request(f"p{uuid.uuid4().hex[:6]}", list(p),
                            SamplingParams(max_tokens=gens[i]))
                    for i, p in enumerate(prompts)]
            pending = list(reqs)
            steps = 0
            while eng.has_work() or pending:
                if pending and steps % every == 0:
                    for r in pending[:burst]:
                        eng.add_request(r)
                    pending = pending[burst:]
                eng.step()
                steps += 1
            return reqs

        drive()                          # warmup: compiles every bucket
        import gc
        gc.collect()                     # align GC (see bench_async_ab)
        t0 = time.perf_counter()
        reqs = drive()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {"tokens_per_sec": round(toks / dt, 1)}, eng

    on_row, eng_on = run(True)
    off_row, eng_off = run(False)
    perf_on = eng_on.stats()["perf"]

    # -- part 3: fingerprint vs the committed baseline -----------------
    fingerprint = perfdiff.run_canonical_workload()
    try:
        baseline = perfdiff.load_baseline()
        diff_failures = perfdiff.compare(baseline, fingerprint)
    except FileNotFoundError:
        baseline, diff_failures = None, ["baseline file missing"]

    res = {
        "accounting_on": on_row, "accounting_off": off_row,
        "overhead_ratio": round(
            on_row["tokens_per_sec"]
            / max(off_row["tokens_per_sec"], 1e-9), 3),
        "closed_form_ok": closed_form_ok,
        "flops_total": tot["flops"],
        "mfu": perf_on["mfu"], "mbu": perf_on["mbu"],
        "roof": perf_on["roof"], "envelope": perf_on["envelope"],
        "decode_tokens_per_s": perf_on["decode_tokens_per_s"],
        "single_request_perf": {k: perf1[k] for k in
                                ("mfu", "mbu", "roof")},
        "accounting_off_disabled": (
            eng_off.stats()["perf"].get("enabled") is False),
        "fingerprint": fingerprint,
        "perfdiff_failures": diff_failures,
    }
    if smoke:
        assert res["closed_form_ok"], (tot, expect)
        assert res["flops_total"] > 0, res
        assert 0 < res["mfu"] <= 1.0, res
        assert 0 < res["mbu"] <= 1.0, res
        assert res["roof"] in ("compute", "memory"), res
        assert res["accounting_off_disabled"], res
        # tripwire with slack for CI timer noise: per-tick host
        # arithmetic must never make decode materially slower
        assert res["overhead_ratio"] >= 0.8, res
        assert not diff_failures, diff_failures
    return res


def bench_quant_ab(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 16 gate: quantized-vs-f32 serving A/B.

    Three surfaces, each with its own tolerance discipline:

    Bytes (exact): int8 pages + per-(row, head) f32 scales must cut
    the per-page device footprint and the cost model's KV read bytes
    by >= 1.9x vs a TRUE f32 baseline (the debug config's bf16
    activations are pinned to f32 for the A/B so the ratio means what
    the ISSUE says).

    Logprobs (bounded): one model-level ragged prefill over identical
    pools, f32 vs quantized — max |delta log-softmax| over valid rows
    must stay inside the per-kind band (int8 tight, fp8 loose: e4m3
    carries ~3 mantissa bits).

    Tokens (statistical): greedy engine A/B on a random-weight debug
    model. Near-tied logits mean a single early flip cascades down
    the whole stream, so agreement is gated LOOSELY per kind while
    FIRST tokens (prefill-dominated, no compounding) are gated tight.
    Throughput may pay the CPU gather-path dequant tax but must not
    collapse (the fused-dequant win is a TPU bandwidth effect the CPU
    tier cannot see)."""
    import dataclasses
    import uuid

    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.llm._internal.perfmodel import CostModel
    from ray_tpu.models import llama
    from ray_tpu.models.llama import LlamaConfig

    # -- part 1: model-level logprob delta bound -----------------------
    from ray_tpu.models.llama_infer import ragged_forward
    from ray_tpu.ops import kv_quant
    from ray_tpu.ops.paged_attention import scatter_kv, scatter_kv_quant

    mcfg = LlamaConfig(vocab_size=64, hidden=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=8, ffn=64, max_seq=64)
    params = llama.init_params(mcfg, jax.random.PRNGKey(0))
    L, KVH, D = mcfg.n_layers, mcfg.n_kv_heads, mcfg.head_dim
    n_pages, page = 8, 4
    rng = np.random.default_rng(1)
    T = 8
    tokens = jnp.asarray(rng.integers(0, 64, size=T).astype(np.int32))
    slot_ids = jnp.asarray(np.array([0] * 5 + [1, 0, 0], np.int32))
    positions = jnp.asarray(np.array([0, 1, 2, 3, 4, 3, 0, 0],
                                     np.int32))
    valid = jnp.asarray(np.array([1, 1, 1, 1, 1, 1, 0, 0], bool))
    start = jnp.asarray(np.array([0, 3], np.int32))
    last_idx = jnp.asarray(np.array([4, 5], np.int32))
    tables = jnp.asarray(np.array([[0, 1, 2], [3, 4, 5]], np.int32))
    ctx = jnp.asarray(rng.normal(size=(3, L, KVH, D))
                      .astype(np.float32) * 0.5)
    pos3 = jnp.asarray(np.array([0, 1, 2], np.int32))
    tb3 = jnp.tile(tables[1], (3, 1))
    val3 = jnp.ones(3, bool)

    kf = jnp.zeros((L, n_pages, page, KVH, D), jnp.float32)
    kf, vf = scatter_kv(kf, jnp.zeros_like(kf), ctx, ctx, tb3, pos3,
                        val3)
    lf, _, _ = ragged_forward(mcfg, params, tokens, slot_ids,
                              positions, valid, start, last_idx, kf,
                              vf, tables, impl="gather")
    lp_f = jax.nn.log_softmax(lf, axis=-1)
    logprob_delta = {}
    for kind in ("int8", "fp8"):
        kq = jnp.zeros((L, n_pages, page, KVH, D),
                       kv_quant.storage_dtype(kind))
        ks = jnp.zeros((L, n_pages, page, KVH), jnp.float32)
        kq, vq, ks, vs = scatter_kv_quant(
            kq, jnp.zeros_like(kq), ks, jnp.zeros_like(ks), ctx, ctx,
            tb3, pos3, val3, kind)
        lq, *_ = ragged_forward(mcfg, params, tokens, slot_ids,
                                positions, valid, start, last_idx, kq,
                                vq, tables, impl="gather",
                                kv_kind=kind, k_scales=ks,
                                v_scales=vs)
        lp_q = jax.nn.log_softmax(lq, axis=-1)
        # logits are per SLOT (each slot's last valid token; both
        # slots here hold valid work)
        delta = jnp.max(jnp.abs(lp_q - lp_f))
        logprob_delta[kind] = round(float(delta), 4)

    # -- part 2: engine greedy A/B + byte accounting -------------------
    cfg = dataclasses.replace(llama.config("debug"),
                              dtype=jnp.float32)
    batch, plen, gen, n_req = 4, 24, 24, 8
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, plen).tolist()
               for _ in range(n_req)]

    def run(kind):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, num_pages=256,
            page_size=16, kv_dtype=kind, seed=11,
            metrics_model_id=f"qab{uuid.uuid4().hex[:8]}"))

        def drive(tag):
            reqs = [Request(f"{tag}{i}", list(p),
                            SamplingParams(max_tokens=gen))
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.add_request(r)
            while eng.has_work():
                eng.step()
            return reqs

        reqs = drive("w")                # warmup run (compiles)
        t0 = time.perf_counter()
        timed = drive("t")
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in timed)
        return reqs, round(toks / dt, 1), eng.stats()

    f32_reqs, f32_tps, f32_st = run("f32")
    cm_f32 = CostModel(cfg, page_size=16)
    res = {"logprob_delta": logprob_delta,
           "f32_tokens_per_sec": f32_tps,
           "f32_page_bytes": f32_st["kv_page_bytes"]}
    for kind in ("int8", "fp8"):
        qreqs, qtps, qst = run(kind)
        agree = sum(
            sum(a == b for a, b in zip(x.output_tokens,
                                       y.output_tokens))
            for x, y in zip(f32_reqs, qreqs))
        total = sum(len(x.output_tokens) for x in f32_reqs)
        first = sum(x.output_tokens[0] == y.output_tokens[0]
                    for x, y in zip(f32_reqs, qreqs))
        cm_q = CostModel(cfg, page_size=16, kv_dtype=kind)
        res[kind] = {
            "tokens_per_sec": qtps,
            "tps_ratio_vs_f32": round(qtps / max(f32_tps, 1e-9), 3),
            "token_agreement": round(agree / max(total, 1), 3),
            "first_token_agreement": round(first / n_req, 3),
            "page_bytes": qst["kv_page_bytes"],
            "footprint_ratio": round(
                f32_st["kv_page_bytes"] / qst["kv_page_bytes"], 2),
            "kv_read_bytes_ratio": round(
                cm_f32.kv_bytes_per_token / cm_q.kv_bytes_per_token,
                2),
            "dispatches_per_step": qst["dispatches_per_step"],
        }
    if smoke:
        # bytes: exact arithmetic, the headline perf_opt claim
        for kind in ("int8", "fp8"):
            assert res[kind]["footprint_ratio"] >= 1.9, res[kind]
            assert res[kind]["kv_read_bytes_ratio"] >= 1.9, res[kind]
            assert res[kind]["dispatches_per_step"] == 1.0, res[kind]
        # logprobs: per-kind bands (calibrated at ~2x observed)
        assert res["logprob_delta"]["int8"] <= 0.25, res
        assert res["logprob_delta"]["fp8"] <= 0.80, res
        # tokens: loose stream agreement (flips cascade), tight first
        # tokens (prefill-dominated, no compounding)
        assert res["int8"]["token_agreement"] >= 0.55, res["int8"]
        assert res["fp8"]["token_agreement"] >= 0.35, res["fp8"]
        assert res["int8"]["first_token_agreement"] >= 0.75, res
        assert res["fp8"]["first_token_agreement"] >= 0.75, res
        # throughput gates only where the fused kernel runs: the CPU
        # smoke uses the XLA gather fallback whose whole-context
        # dequant tax is exactly what the Pallas kernel deletes, and
        # this shared VM's ambient load swings the ratio several x
        if on_tpu:
            assert res["int8"]["tps_ratio_vs_f32"] >= 0.6, res["int8"]
    return res


def bench_attribution(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 13 gate, two halves.

    Conservation: a bursty mixed prefill+decode workload with spills
    (half-capacity pages, offload on), greedy AND sampled rows — the
    summed per-request receipts must equal the PerfAccountant's tick
    totals EXACTLY (closed form, not banded) for every conserved
    field, and every request must end with a closed receipt.

    Overhead: the same workload with attribution + anomaly detection
    OFF as baseline (perf accounting stays ON in both arms, so the
    A/B isolates the ISSUE 13 cost: a dict update per slot per tick
    and a few float ops for the detector). Must be ~1.0x; the
    dispatch-guard suite separately proves zero transfers/compiles
    with both features enabled. The detector's auto-capture reactions
    (profile arming / black-box dump) are disabled in BOTH arms: they
    run only on ticks that already went anomalous — deliberately
    expensive evidence-gathering, exercised by the anomaly e2e test —
    so they are not part of the steady-state overhead contract."""
    import uuid

    from ray_tpu.llm._internal.attribution import CONSERVED_FIELDS
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine, Request,
                                              SamplingParams)
    from ray_tpu.models import llama

    if on_tpu and not smoke:
        cfg = _tpu_bench_model()
        batch, plen, n_req, gen0 = 8, 192, 18, 48
    else:
        cfg = llama.config("debug")
        batch, plen, n_req, gen0 = 3, 40, 12, 16

    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, cfg.vocab_size,
                            plen + 8 * (i % 3)).tolist()
               for i in range(n_req)]

    def run(enable):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=8,
            # roughly HALF the workload's worst-case page demand:
            # spills/restores are exercised, so d2h/h2d attribution
            # is part of the conservation sum
            num_pages=max(
                batch * (plen + 8 + gen0 + 8) // 8 // 2, 16),
            seed=7, max_prefill_tokens=16, kv_watermark_tokens=8,
            enable_kv_offload=True, enable_prefix_caching=False,
            enable_attribution=enable,
            enable_anomaly_detection=enable,
            anomaly={"auto_profile": False, "auto_dump": False},
            metrics_model_id=f"attr{uuid.uuid4().hex[:8]}"))

        def drive():
            reqs = [Request(
                f"a{uuid.uuid4().hex[:6]}", list(p),
                SamplingParams(
                    max_tokens=gen0 + 8 * (i % 2),
                    temperature=0.8 if i % 2 else 0.0,
                    top_k=20 if i % 2 else 0),
                tenant="tenant-b" if i % 3 == 0 else "")
                    for i, p in enumerate(prompts)]
            pending = list(reqs)
            steps = 0
            while eng.has_work() or pending:
                if pending and steps % 5 == 0:
                    for r in pending[:3]:
                        eng.add_request(r)
                    pending = pending[3:]
                eng.step()
                steps += 1
            return reqs

        drive()                          # warmup compiles
        import gc
        gc.collect()                     # align GC (see bench_async_ab)
        t0 = time.perf_counter()
        reqs = drive()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {"tokens_per_sec": round(toks / dt, 1)}, eng

    on_row, eng_on = run(True)
    off_row, eng_off = run(False)

    perf_tot = eng_on.perf.totals()
    attrib_tot = eng_on.attrib.totals()
    mismatches = [k for k, _ in CONSERVED_FIELDS
                  if perf_tot[k] != attrib_tot[k]]
    summ = eng_on.attrib.summary()
    res = {
        "attribution_on": on_row, "attribution_off": off_row,
        "overhead_ratio": round(
            on_row["tokens_per_sec"]
            / max(off_row["tokens_per_sec"], 1e-9), 3),
        "conserved": not mismatches,
        "conservation_mismatches": mismatches,
        "spills": eng_on.host_tier.spills_total,
        "receipts_finished": summ["requests_total"] - summ["live"],
        "live_receipts": summ["live"],
        "tenants": sorted(summ["tenants"]),
        "anomaly_ticks": eng_on.anomaly.stats()["ticks"],
        "attribution_off_disabled": (
            eng_off.stats()["attribution"].get("enabled") is False),
    }
    if smoke:
        assert res["conserved"], (
            "receipt conservation failed", mismatches,
            {k: (perf_tot[k], attrib_tot[k])
             for k, _ in CONSERVED_FIELDS})
        assert res["spills"] >= 1, res      # the gate covered spills
        assert res["live_receipts"] == 0, res
        assert set(res["tenants"]) == {"default", "tenant-b"}, res
        assert res["anomaly_ticks"] > 0, res
        assert res["attribution_off_disabled"], res
        # tripwire with CI-noise slack: per-slot dict arithmetic must
        # never make decode materially slower
        assert res["overhead_ratio"] >= 0.8, res
    return res


def bench_kernel_tick(on_tpu: bool) -> dict:
    """ISSUE 2 smoke gate: drive a small mixed workload through the
    unified engine with decode_impl=pallas_interpret (the Pallas
    ragged kernel in interpreter mode — unified ticks AND pure-decode
    ticks both run kernels) and require token-exact greedy output vs
    the dense gather engine. Asserts (CI fails loudly)."""
    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    cfg = llama.config("debug")
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (24, 9, 1)]

    def run(impl):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=3, page_size=8, num_pages=64,
            prefill_buckets=(16, 32), max_prefill_tokens=16, seed=5,
            enable_prefix_caching=False, decode_impl=impl))
        reqs = [Request(f"k{i}", list(p), SamplingParams(max_tokens=4))
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        ticks = 0
        while eng.has_work():
            eng.step()
            ticks += 1
        return [r.output_tokens for r in reqs], ticks

    out_g, _ = run("gather")
    out_k, ticks = run("pallas_interpret")
    exact = out_g == out_k
    assert exact, f"kernel tick diverged: {out_k} vs {out_g}"
    return {"token_exact": exact, "ticks": ticks,
            "impl": "pallas_interpret"}


def bench_long_ctx(on_tpu: bool) -> dict:
    """ISSUE 2 headline: bursty mixed prefill+decode at multi-
    thousand-token contexts, gather vs Pallas ragged kernel. This is
    the regime where the gather path's per-layer transient —
    T x ctx x KVH x D floats of per-token gathered context — is the
    dominant memory term and the kernel streams pages instead (it
    reads and writes the flat batch in place: T plus one query block
    of rows, O(T x H x D)). Reports tokens/s per impl plus
    the peak per-layer attention transient each path materializes.

    On CPU the kernel runs in interpreter mode (Python-speed grid
    steps), so shapes shrink and kernel tokens/s is NOT a hardware
    number — transient sizes and token agreement are the CPU signal;
    run on TPU for the real A/B.
    """
    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    if on_tpu:
        cfg = _tpu_bench_model()              # max_seq 2048
        batch, plen, n_req, chunk, budget = 8, 1792, 12, 256, 512
        gen = 32
        kernel_impl = "pallas"
    else:
        cfg = llama.config("tiny", vocab_size=512, hidden=128,
                           n_layers=2, n_heads=4, n_kv_heads=2,
                           head_dim=32, ffn=256, max_seq=2048)
        batch, plen, n_req, chunk, budget = 2, 1024, 3, 64, 96
        gen = 4
        kernel_impl = "pallas_interpret"
    page = 16
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size,
                            plen + 64 * (i % 3)).tolist()
               for i in range(n_req)]

    def run(impl):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=page,
            num_pages=max(512, batch * 192), seed=5,
            max_prefill_tokens=chunk, enable_prefix_caching=False,
            max_num_batched_tokens=budget, decode_impl=impl))
        reqs = [Request(f"L{i}", list(p),
                        SamplingParams(max_tokens=gen))
                for i, p in enumerate(prompts)]
        pending = list(reqs)
        t0 = time.perf_counter()
        steps = 0
        while eng.has_work() or pending:
            if pending and steps % 4 == 0:
                for r in pending[:batch // 2 or 1]:
                    eng.add_request(r)
                pending = pending[batch // 2 or 1:]
            eng.step()
            steps += 1
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return {"tokens_per_sec": round(toks / dt, 2),
                "wall_s": round(dt, 1), "steps": steps}, \
            [r.output_tokens for r in reqs]

    gather, out_g = run("gather")
    kernel, out_k = run(kernel_impl)

    # peak per-layer attention transient (bytes), analytic: the gather
    # path materializes k_ctx[slot_ids] + v_ctx[slot_ids] in f32; the
    # kernel reads Q / new-KV and writes O in place in the flat batch,
    # each padded by one query block (nothing is staged per slot), and
    # streams context pages through a fixed VMEM block
    from ray_tpu.ops.ragged_paged_attention import ragged_q_block
    t_bucket = 1 << max(budget - 1, 1).bit_length()
    max_ctx_tokens = -(-cfg.max_seq // page) * page
    kvh, h, d = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    dt_bytes = jnp.dtype(cfg.dtype).itemsize
    gather_bytes = 2 * t_bucket * max_ctx_tokens * kvh * d * 4
    kernel_bytes = ((t_bucket + ragged_q_block(t_bucket))
                    * (2 * h + 2 * kvh) * d * dt_bytes)
    return {
        "gather": gather, "kernel": kernel,
        "kernel_impl": kernel_impl,
        "kernel_speedup": round(
            kernel["tokens_per_sec"]
            / max(gather["tokens_per_sec"], 1e-9), 2),
        "token_match": round(
            sum(a == b for a, b in zip(out_g, out_k)) / n_req, 3),
        "peak_attn_transient_bytes": {
            "gather": gather_bytes, "kernel": kernel_bytes,
            "ratio": round(gather_bytes / max(kernel_bytes, 1), 1)},
        "batch": batch, "prompt_len": plen, "requests": n_req,
        "chunk": chunk, "token_budget": budget,
    }


def bench_prefix_cache(on_tpu: bool) -> dict:
    """Shared-prefix speedup: time-to-first-token of an identical prompt
    when its prefix KV is cache-hot vs cold (VERDICT r3 #6)."""
    from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    if on_tpu:
        cfg = _tpu_bench_model()
        prompt_len, chunk = 1024, 256
    else:
        cfg = llama.config("debug")
        prompt_len, chunk = 96, 32
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=2, num_pages=256,
        max_prefill_tokens=chunk))
    prompt = np.random.default_rng(1).integers(
        1, cfg.vocab_size, prompt_len).tolist()

    def ttft(rid):
        req = Request(rid, list(prompt), SamplingParams(max_tokens=2))
        eng.add_request(req)
        t0 = time.perf_counter()
        while not req.output_tokens:
            eng.step()
        dt = time.perf_counter() - t0
        while not req.finished:
            eng.step()
        return dt

    ttft("warmup")                       # compiles the cold chunk path
    ttft("warmup-hot")                   # compiles the cache-hit suffix
    eng.allocator.clear_cache()          # cold again (keep compiles)
    cold = ttft("cold")
    hot = ttft("hot")
    return {"ttft_cold_ms": round(cold * 1e3, 2),
            "ttft_cached_ms": round(hot * 1e3, 2),
            "prefix_speedup": round(cold / max(hot, 1e-9), 2),
            "hit_tokens": eng.allocator.cache_hit_tokens,
            "prompt_len": prompt_len}


def bench_kernel_scaling(on_tpu: bool) -> dict:
    """Per-layer decode attention at short vs long cached context with the
    SAME max_pages: if cost scales with max context (dense gather) the two
    times match; kernel times should scale with actual context."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    if on_tpu:
        B, H, KVH, D = 8, 16, 8, 128
        max_pages = 128                   # max ctx 2048
    else:
        B, H, KVH, D = 2, 4, 2, 64       # interpret mode is slow: tiny
        max_pages = 4
    page_size = 16
    num_pages = B * max_pages + 1
    rng = np.random.default_rng(0)
    k_pages = jnp.asarray(
        rng.normal(size=(num_pages, page_size, KVH, D)), jnp.bfloat16)
    v_pages = jnp.asarray(
        rng.normal(size=(num_pages, page_size, KVH, D)), jnp.bfloat16)
    tables = jnp.asarray(
        np.arange(B * max_pages).reshape(B, max_pages), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)

    fn = jax.jit(lambda q, k, v, t, s: paged_decode_attention(
        q, k, v, t, s, interpret=not on_tpu))

    def timed(seq_len):
        lens = jnp.full((B,), seq_len, jnp.int32)
        out = fn(q, k_pages, v_pages, tables, lens)
        np.asarray(out)                       # sync
        iters = 20 if on_tpu else 2
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k_pages, v_pages, tables, lens)
        np.asarray(out)
        return (time.perf_counter() - t0) / iters * 1e3

    short = timed(page_size * max(max_pages // 16, 1))
    long = timed(page_size * max_pages)
    return {"short_ctx_ms": round(short, 3), "long_ctx_ms": round(long, 3),
            "long_over_short": round(long / max(short, 1e-9), 2)}


def bench_speculative(on_tpu: bool) -> dict:
    """Greedy decode throughput, speculative vs plain. SELF-draft
    (the target's own weights) pins acceptance near 1.0, isolating the
    structural effect: 2 dispatches per round for ~k tokens vs 1 per
    token. That can only win where per-dispatch latency dominates
    (not measured on a chip); on CPU, where compute dominates and the
    draft doubles it, the row goes BELOW 1x by design."""
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              SamplingParams)
    from ray_tpu.models import llama

    if on_tpu:
        target = _tpu_bench_model()
        batch, gen = 4, 96
    else:
        target = llama.config("debug")
        batch, gen = 2, 32
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, target.vocab_size, 32).tolist()
               for _ in range(batch)]

    tparams = llama.init_params(target, jax.random.PRNGKey(5))

    def run(spec):
        # params passed EXPLICITLY to both engines: self-draft is true
        # by construction, not by seed coupling with the engine's init
        eng = InferenceEngine(EngineConfig(
            model=target, max_batch_size=batch, num_pages=256,
            seed=5, enable_prefix_caching=False, speculative=spec),
            params=tparams)
        # full-length warmup: later rounds cross ctx-bucket
        # boundaries and would otherwise compile inside the timed run
        eng.generate([list(p) for p in prompts],
                     SamplingParams(max_tokens=gen))
        t0 = time.perf_counter()
        reqs = eng.generate([list(p) for p in prompts],
                            SamplingParams(max_tokens=gen))
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return round(toks / dt, 1), eng.stats()

    plain_tps, _ = run(None)
    spec_k = int(os.environ.get("RAY_TPU_BENCH_SPEC_K", "4"))
    spec_tps, st = run({"draft_model": target,
                        "draft_params": tparams,
                        "num_speculative_tokens": spec_k})
    return {"plain_tokens_per_sec": plain_tps,
            "spec_tokens_per_sec": spec_tps,
            "spec_speedup": round(spec_tps / max(plain_tps, 1e-9), 2),
            "acceptance_rate": st.get("spec_acceptance_rate"),
            "tokens_per_round": st.get("spec_tokens_per_round")}


def bench_multi_step(on_tpu: bool) -> dict:
    """Greedy decode throughput at decode_steps_per_call = 1 vs K:
    K decode iterations per dispatch amortize the per-call overhead
    (its size on a chip: not measured); on CPU, where dispatch is
    ~free, the row hovers near 1x by design."""
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              SamplingParams)
    from ray_tpu.models import llama

    if on_tpu:
        target = _tpu_bench_model()
        batch, gen, ksteps = 8, 96, int(os.environ.get(
            "RAY_TPU_BENCH_DECODE_K", "8"))
    else:
        target = llama.config("debug")
        batch, gen, ksteps = 2, 32, 4
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, target.vocab_size, 32).tolist()
               for _ in range(batch)]

    def run(k):
        eng = InferenceEngine(EngineConfig(
            model=target, max_batch_size=batch, num_pages=256, seed=5,
            enable_prefix_caching=False, decode_steps_per_call=k))
        eng.generate([list(p) for p in prompts],
                     SamplingParams(max_tokens=gen))     # warm/compile
        t0 = time.perf_counter()
        reqs = eng.generate([list(p) for p in prompts],
                            SamplingParams(max_tokens=gen))
        dt = time.perf_counter() - t0
        return round(sum(len(r.output_tokens) for r in reqs) / dt, 1)

    single = run(1)
    multi = run(ksteps)
    return {"k": ksteps, "single_tokens_per_sec": single,
            "multi_tokens_per_sec": multi,
            "multi_speedup": round(multi / max(single, 1e-9), 2)}


def bench_fleet(on_tpu: bool) -> dict:
    """ISSUE 6 fleet A/B: 2 in-process engine replicas behind the
    FleetManager (prefix-affine router + bounded admission) vs the
    same replicas under round-robin, plus a 1-replica baseline —
    bursty traffic where G tenant groups share 64-char prompt
    prefixes. Affinity keeps each group's prefix pages hot on ONE
    replica (misses ~= G, the first request per group); round-robin
    sprays the group across the fleet so every replica pays the cold
    prefill (misses ~= G * replicas). The overload phase floods a
    max_concurrent=2/max_queue=4 front door and checks the admission
    contract: surplus sheds as 429 and the p99 queue wait of everyone
    else stays bounded by the SLO instead of growing with the burst.
    Throughput of 2 replicas vs 1 is honest-signal only on TPU (two
    chips); on CPU both replicas share one host so the row hovers
    near 1x by design."""
    import asyncio
    import uuid

    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.serve.llm import (AdmissionConfig, AdmissionRejected,
                                   AutoscaleConfig, FleetManager,
                                   LocalReplicaClient, RouterConfig)
    from ray_tpu.models import llama

    if on_tpu:
        cfg = _tpu_bench_model()
        groups, rounds, gen = 8, 8, 32
        pages, batch, chunk = 512, 8, 128
    else:
        cfg = llama.config("debug")
        groups, rounds, gen = 4, 6, 4
        pages, batch, chunk = 128, 4, 32
    # 64-char shared prefixes (multiple of the byte tokenizer's
    # page granularity) — one per tenant group
    prefixes = [(f"tenant {g} shared context block " + "x" * 64)[:64]
                for g in range(groups)]

    def make_servers(n):
        tag = f"bench{uuid.uuid4().hex[:8]}"
        return {f"r{i}": LLMServerImpl({
            "model_id": "bench", "model_source": cfg,
            "engine_kwargs": dict(
                max_batch_size=batch, page_size=8, num_pages=pages,
                seed=7, max_prefill_tokens=chunk,
                metrics_model_id=tag, metrics_replica_id=f"r{i}"),
        }) for i in range(n)}

    def fleet_over(servers, policy, **adm):
        admission = AdmissionConfig(**adm) if adm else AdmissionConfig(
            max_concurrent=64, max_queue=128, queue_wait_slo_s=60.0)
        return FleetManager(
            [LocalReplicaClient(rid, srv)
             for rid, srv in servers.items()],
            router=RouterConfig(policy=policy, prefix_depth=64,
                                spill_waiting=batch * 4),
            admission=admission,
            autoscale=AutoscaleConfig(min_replicas=len(servers),
                                      max_replicas=len(servers)))

    def run_traffic(policy, n_replicas):
        """Bursty rounds: every group fires one request per round,
        all groups concurrently. Fresh engines per run so prefix-cache
        state never leaks across the A/B arms."""
        servers = make_servers(n_replicas)
        fleet = fleet_over(servers, policy)

        async def main():
            t0 = time.perf_counter()
            toks = 0
            for r in range(rounds):
                # rotate the group order per round: with it, a
                # round-robin fleet genuinely sprays each group across
                # replicas (in dispatch order it would be accidentally
                # sticky whenever groups % replicas == 0)
                order = prefixes[r % groups:] + prefixes[:r % groups]
                outs = await asyncio.gather(*(
                    fleet.dispatch("completions", {
                        "prompt": p + f" q{r}", "max_tokens": gen})
                    for p in order))
                toks += sum(o["usage"]["completion_tokens"]
                            for o in outs)
            dt = time.perf_counter() - t0
            for srv in servers.values():
                if srv._pump is not None:
                    srv._pump.cancel()
            return toks, dt

        toks, dt = asyncio.run(main())
        hit = sum(s.engine.allocator.cache_hit_tokens
                  for s in servers.values())
        query = sum(s.engine.allocator.cache_query_tokens
                    for s in servers.values())
        return {
            "tokens_per_sec": round(toks / dt, 1),
            "prefix_hit_rate": round(hit / max(query, 1), 4),
            "router": fleet.router.stats(),
        }

    affinity = run_traffic("affinity", 2)
    rr = run_traffic("round_robin", 2)
    single = run_traffic("affinity", 1)
    # the headline contract: affinity re-lands each group on its warm
    # replica, so the fleet-wide prefix-cache hit rate beats spraying
    assert affinity["prefix_hit_rate"] > rr["prefix_hit_rate"], (
        affinity, rr)

    # overload phase: flood a tiny front door; the contract is 429s
    # for the surplus + SLO-bounded queue wait for everyone else
    servers = make_servers(2)
    slo_s = 8.0
    fleet = fleet_over(servers, "affinity", max_concurrent=2,
                       max_queue=4, queue_wait_slo_s=slo_s)

    async def overload():
        results = await asyncio.gather(
            *(fleet.dispatch("completions", {
                "prompt": f"overload probe {i}", "max_tokens": 2})
              for i in range(24)),
            return_exceptions=True)
        for srv in servers.values():
            if srv._pump is not None:
                srv._pump.cancel()
        return results

    results = asyncio.run(overload())
    ok = sum(1 for r in results if isinstance(r, dict))
    shed = sum(1 for r in results if isinstance(r, AdmissionRejected))
    other = [r for r in results
             if not isinstance(r, (dict, AdmissionRejected))]
    assert not other, other
    adm = fleet.admission.stats()
    assert shed > 0 and ok > 0, (ok, shed)
    assert adm["queue_wait_p99_s"] <= slo_s + 0.5, adm

    return {
        "affinity_2rep": affinity,
        "round_robin_2rep": rr,
        "single_replica": single,
        "fleet_speedup": round(
            affinity["tokens_per_sec"]
            / max(single["tokens_per_sec"], 1e-9), 2),
        "affinity_hit_advantage": round(
            affinity["prefix_hit_rate"] - rr["prefix_hit_rate"], 4),
        "overload": {"completed": ok, "shed_429": shed,
                     "queue_wait_p99_s": adm["queue_wait_p99_s"],
                     "queue_wait_slo_s": slo_s},
    }


def bench_fleet_tracing(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 7 gate, two halves. Correctness: fleet serving with
    distributed tracing + the SLO watchdog on actually produces the
    observability goods — every request's ingress spans land in the
    trace buffer, the replica's lifecycle timeline carries the SAME
    trace id, and the watchdog consumed the replicas' totals.
    Overhead: the identical workload with enable_tracing=False and
    the watchdog disabled is the baseline — trace minting is a few
    dict ops per request at ingress and the watchdog runs on the
    control loop, not the request path, so the instrumented run must
    not be slower beyond timer noise (the dispatch-guard suite
    separately proves zero transfers / compiles). In --smoke mode
    both halves assert."""
    import asyncio
    import uuid

    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                                   FleetManager, LocalReplicaClient,
                                   RouterConfig, WatchdogConfig,
                                   merge_fleet_traces)
    from ray_tpu.models import llama

    if on_tpu and not smoke:
        cfg = _tpu_bench_model()
        n_req, rounds, gen, pages, batch = 8, 6, 32, 512, 8
    else:
        cfg = llama.config("debug")
        n_req, rounds, gen, pages, batch = 4, 4, 12, 128, 4

    def run(enable_tracing):
        tag = f"trace{uuid.uuid4().hex[:8]}"
        servers = {"r0": LLMServerImpl({
            "model_id": "bench", "model_source": cfg,
            "engine_kwargs": dict(
                max_batch_size=batch, page_size=8, num_pages=pages,
                seed=7, metrics_model_id=tag,
                metrics_replica_id="r0"),
        })}
        fleet = FleetManager(
            [LocalReplicaClient(rid, srv)
             for rid, srv in servers.items()],
            router=RouterConfig(prefix_depth=64),
            admission=AdmissionConfig(max_concurrent=64,
                                      max_queue=128,
                                      queue_wait_slo_s=60.0),
            autoscale=AutoscaleConfig(min_replicas=1, max_replicas=1),
            watchdog=WatchdogConfig(enabled=enable_tracing),
            enable_tracing=enable_tracing)

        async def drive():
            toks = 0
            for r in range(rounds):
                outs = await asyncio.gather(*(
                    fleet.dispatch("completions", {
                        "prompt": f"trace bench {i} round {r}",
                        "max_tokens": gen})
                    for i in range(n_req)))
                toks += sum(o["usage"]["completion_tokens"]
                            for o in outs)
            for srv in servers.values():
                if srv._pump is not None:
                    srv._pump.cancel()
            return toks

        asyncio.run(drive())                 # warmup: compiles
        t0 = time.perf_counter()
        toks = asyncio.run(drive())
        dt = time.perf_counter() - t0
        if enable_tracing:
            # watchdog exercise rides the CONTROL loop in prod
            # (refresh cadence), not the request path — one tick
            # OUTSIDE the timed window proves the wiring without
            # biasing the overhead A/B against its own gate
            asyncio.run(fleet.autoscale_tick(now=0.0))
        return ({"tokens_per_sec": round(toks / dt, 1)},
                fleet, servers)

    on_row, fleet_on, servers_on = run(True)
    off_row, fleet_off, _ = run(False)

    # correctness half: the traced fleet produced the goods
    doc = merge_fleet_traces(
        {"r0": servers_on["r0"].engine.chrome_trace()},
        fleet_on.trace)
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    ingress_tids = {e["args"]["trace_id"] for e in evs
                    if e["name"] == "fleet_request"}
    replica_tids = {e["args"]["trace_id"] for e in evs
                    if e["name"] == "decode"
                    and "trace_id" in e["args"]}
    res = {
        "tracing_on": on_row, "tracing_off": off_row,
        "overhead_ratio": round(
            on_row["tokens_per_sec"]
            / max(off_row["tokens_per_sec"], 1e-9), 3),
        "ingress_spans": fleet_on.trace.stats()["total"],
        "traced_requests": len(ingress_tids),
        "trace_ids_joined": len(replica_tids & ingress_tids),
        "watchdog_observed": bool(fleet_on.watchdog.last),
        "untraced_buffer": fleet_off.trace.stats()["total"],
    }
    if smoke:
        assert res["ingress_spans"] > 0, res
        assert res["traced_requests"] == 2 * rounds * n_req, res
        assert res["trace_ids_joined"] > 0, (
            "no replica lifecycle joined an ingress trace id")
        assert res["watchdog_observed"], res
        assert res["untraced_buffer"] == 0, res
        # tripwire with slack for CI timer noise: ingress-side dict
        # ops must never make serving materially slower
        assert res["overhead_ratio"] >= 0.8, res
    return res


def bench_chaos(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 9 chaos gate: sever one replica mid-bursty-bench and
    prove the failure plane's contract — every client stream still
    completes, every transcript is token-exact vs a single-replica
    oracle (the failover continuation resumes the exact sequence),
    the dead replica leaves the ring, and p99 e2e stays bounded (the
    failover costs one re-route + one cached re-prefill, not a
    restart). Greedy decode is batching- and fleet-independent, so
    the oracle check covers the failover boundary exactly."""
    import asyncio
    import uuid

    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                                   ChaosReplicaClient, ChaosSchedule,
                                   FleetManager, HealthConfig,
                                   LocalReplicaClient, RouterConfig)
    from ray_tpu.serve.llm.fleet import UNHEALTHY
    from ray_tpu.models import llama

    if on_tpu and not smoke:
        cfg = _tpu_bench_model()
        n_req, rounds, gen, pages, batch = 8, 4, 24, 512, 8
    else:
        cfg = llama.config("debug")
        n_req, rounds, gen, pages, batch = 6, 3, 8, 128, 4
    tag = f"chaos{uuid.uuid4().hex[:8]}"
    servers = {f"r{i}": LLMServerImpl({
        "model_id": "bench", "model_source": cfg,
        "engine_kwargs": dict(
            max_batch_size=batch, page_size=8, num_pages=pages,
            seed=7, metrics_model_id=tag, metrics_replica_id=f"r{i}"),
    }) for i in range(2)}
    schedules = {rid: ChaosSchedule(seed=13) for rid in servers}
    victim = "r0"
    # the victim's SECOND stream dies after 2 chunks — mid-burst,
    # with sibling streams live on both replicas
    schedules[victim].sever_stream(
        after_chunks=2, method="completions_stream_tokens", at_call=1)
    fleet = FleetManager(
        [ChaosReplicaClient(LocalReplicaClient(rid, srv),
                            schedules[rid])
         for rid, srv in servers.items()],
        router=RouterConfig(prefix_depth=64, spill_waiting=batch * 4),
        admission=AdmissionConfig(max_concurrent=64, max_queue=128,
                                  queue_wait_slo_s=60.0),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        health=HealthConfig(open_cooldown_s=300.0),
        model_id="bench")

    def parse(chunks):
        toks, reasons = [], []
        for c in chunks:
            payload = c[len("data: "):].strip()
            if payload == "[DONE]":
                continue
            d = json.loads(payload)
            ch = d["choices"][0]
            toks += ch.get("token_ids") or []
            if ch["finish_reason"] is not None:
                reasons.append(ch["finish_reason"])
        return toks, reasons

    results = {}
    e2es = []

    async def one(prompt):
        t0 = time.perf_counter()
        chunks = []
        async for c in fleet.dispatch_stream(
                "completions_stream",
                {"prompt": prompt, "max_tokens": gen}):
            chunks.append(c)
        e2es.append(time.perf_counter() - t0)
        results[prompt] = parse(chunks)

    async def drive():
        for r in range(rounds):
            await asyncio.gather(*(
                one(f"chaos bench tenant {i} round {r}")
                for i in range(n_req)))
        for srv in servers.values():
            if srv._pump is not None:
                srv._pump.cancel()

    asyncio.run(drive())

    # oracle: fresh single replica, same weights seed
    oracle = LLMServerImpl({
        "model_id": "bench", "model_source": cfg,
        "engine_kwargs": dict(
            max_batch_size=batch, page_size=8, num_pages=pages,
            seed=7, metrics_model_id=f"or{uuid.uuid4().hex[:8]}")})

    async def oracle_toks(prompt):
        out = []
        async for c in oracle.completions_stream_tokens(
                {"prompt": prompt, "max_tokens": gen}):
            out.append(c)
        return [t for c in out for t in c["toks"]]

    async def oracle_drive():
        want = {}
        for p in results:
            want[p] = await oracle_toks(p)
        if oracle._pump is not None:
            oracle._pump.cancel()
        return want

    want = asyncio.run(oracle_drive())
    finished = sum(1 for toks, reasons in results.values()
                   if len(reasons) == 1)
    exact = sum(1 for p in results if results[p][0] == want[p])
    fired = [f for s in schedules.values() for f in s.fired]
    e2es.sort()
    p99 = e2es[min(len(e2es) - 1, int(len(e2es) * 0.99))]
    res = {
        "requests": len(results),
        "completed": finished,
        "token_exact": exact,
        "severs_fired": len(fired),
        "failovers": sum(
            v for _, v in fleet.metrics["failovers"]._samples()),
        "victim_evicted": fleet.replicas[victim].status == UNHEALTHY,
        "p99_e2e_s": round(p99, 3),
        "median_e2e_s": round(e2es[len(e2es) // 2], 3),
    }
    # the contract asserts in every mode: chaos must never corrupt
    assert res["severs_fired"] >= 1, res
    assert res["completed"] == res["requests"], res
    assert res["token_exact"] == res["requests"], res
    assert res["victim_evicted"], res
    assert res["p99_e2e_s"] <= 8.0, res
    return res


def bench_preemption(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 10 gate: a 2x page-oversubscribed bursty workload (device
    pages capped at half the fleet's worst-case KV demand, optimistic
    watermark admission) must COMPLETE every stream token-exact vs an
    un-oversubscribed oracle — "out of pages" is a latency tier
    (spill to the host tier, park, restore token-exact), not a hard
    reject — with at least one spill AND one restore actually
    observed, zero capacity rejects, zero error finishes, and the
    preempted tail's p99 e2e bounded (the cost of parking is waiting
    for pages, not corruption or restarts). BENCH_CORE.md: "KV memory
    hierarchy anatomy"."""
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama

    if on_tpu and not smoke:
        cfg = _tpu_bench_model()
        batch, plen, gen, burst, every = 8, 96, 64, 6, 12
    else:
        cfg = llama.config("debug")
        batch, plen, gen, burst, every = 4, 12, 44, 6, 10
    n_req = 18
    page = 8
    # worst case per request in pages, resident-batch demand, and the
    # 2x-oversubscribed device pool (usable = num_pages - 1)
    per = -(-(plen + gen) // page)
    demand = batch * per
    pages_over = demand // 2 + 1
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, cfg.vocab_size, plen).tolist()
               for _ in range(n_req)]

    def run(num_pages, offload):
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=batch, page_size=page,
            num_pages=num_pages, seed=5, prefill_buckets=(16, 32, 64,
                                                          128),
            max_prefill_tokens=32, enable_kv_offload=offload,
            kv_watermark_tokens=8 if offload else None))
        reqs = [Request(f"p{i}", list(p),
                        SamplingParams(max_tokens=gen))
                for i, p in enumerate(prompts)]
        done_at = {}
        t0 = time.perf_counter()
        submit_at = {}
        pending = list(reqs)
        steps = 0
        while eng.has_work() or pending:
            if pending and steps % every == 0:
                for r in pending[:burst]:
                    submit_at[r.request_id] = time.perf_counter()
                    eng.add_request(r)      # 0 capacity rejects
                pending = pending[burst:]
            for r in eng.step():
                if r.finished and r.request_id not in done_at:
                    done_at[r.request_id] = time.perf_counter()
            steps += 1
            assert steps < 100_000
        e2es = sorted(done_at[r.request_id]
                      - submit_at[r.request_id] for r in reqs)
        return eng, reqs, {
            "wall_s": round(time.perf_counter() - t0, 3),
            "p50_e2e_s": round(e2es[len(e2es) // 2], 3),
            "p99_e2e_s": round(
                e2es[min(len(e2es) - 1, int(len(e2es) * 0.99))], 3),
        }

    _, oracle_reqs, oracle_times = run(demand * 2, offload=False)
    eng, reqs, times = run(pages_over, offload=True)
    tier = eng.host_tier
    exact = sum(o.output_tokens == r.output_tokens
                for o, r in zip(oracle_reqs, reqs))
    res = {
        "requests": n_req,
        "completed": sum(r.finished for r in reqs),
        "token_exact": exact,
        "error_finishes": sum(r.finish_reason == "error"
                              for r in reqs),
        "device_pages": pages_over - 1,
        "worst_case_demand_pages": demand,
        "spills": tier.spills_total,
        "restores": tier.restores_total,
        "preemptions": dict(eng.preempt_counts),
        "host_pages_peak": tier.spilled_pages_total,
        "oversubscribed": times,
        "oracle": oracle_times,
    }
    # the contract asserts in every mode: oversubscription must never
    # reject, corrupt, or wedge
    assert res["completed"] == n_req, res
    assert res["token_exact"] == n_req, res
    assert res["error_finishes"] == 0, res
    assert res["spills"] >= 1 and res["restores"] >= 1, res
    assert times["p99_e2e_s"] <= max(8.0,
                                     8 * oracle_times["p99_e2e_s"]), res
    return res


def _disagg_servers(n, cfg, pages, batch, chunk):
    import uuid

    from ray_tpu.llm._internal.server import LLMServerImpl

    tag = f"kvt{uuid.uuid4().hex[:8]}"
    return {f"r{i}": LLMServerImpl({
        "model_id": "bench", "model_source": cfg,
        "engine_kwargs": dict(
            max_batch_size=batch, page_size=8, num_pages=pages,
            seed=7, max_prefill_tokens=chunk,
            enable_kv_offload=True,
            metrics_model_id=tag, metrics_replica_id=f"r{i}"),
    }) for i in range(n)}


def bench_disagg(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 12 disaggregation A/B: a mixed long-prompt/short-decode
    burst on 2 MIXED replicas vs 1 PREFILL + 1 DECODE over the fleet
    KV transport. In the mixed arm every long prompt's chunked
    prefill shares a tick budget with running decodes; in the
    disaggregated arm the prefill replica absorbs the long prompts
    and ships the parked sessions, so the decode replica's ticks
    stay pure decode — the client-side decode inter-token gap (ITL
    p99 over the short streams) is the headline. CPU numbers are
    honest-signal only for the CONTRACT (token-exact handoffs, ships
    observed); both arms share one host here, so the latency split
    shows its real gap on TPU. `--smoke` asserts the disaggregated
    path is token-exact vs a single-engine oracle."""
    import asyncio

    from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                                   FleetManager, LocalReplicaClient,
                                   RouterConfig, TransportConfig)
    from ray_tpu.models import llama

    if on_tpu:
        cfg = _tpu_bench_model()
        long_chars, gen_long, gen_short = 2048, 16, 64
        n_long, n_short, rounds = 4, 8, 3
        pages, batch, chunk = 512, 8, 128
    else:
        cfg = llama.config("debug")
        long_chars, gen_long, gen_short = 160, 6, 24
        n_long, n_short, rounds = 2, 4, 2
        pages, batch, chunk = 160, 4, 32

    def fleet_over(servers, roles):
        return FleetManager(
            [LocalReplicaClient(rid, srv)
             for rid, srv in servers.items()],
            router=RouterConfig(prefix_depth=64,
                                spill_waiting=batch * 4),
            admission=AdmissionConfig(max_concurrent=64,
                                      max_queue=128,
                                      queue_wait_slo_s=60.0),
            autoscale=AutoscaleConfig(min_replicas=len(servers),
                                      max_replicas=len(servers)),
            roles=roles,
            transport=TransportConfig(disagg_prompt_chars=128,
                                      enable_prefix_store=False))

    def run(roles):
        servers = _disagg_servers(2, cfg, pages, batch, chunk)
        fleet = fleet_over(servers, roles)
        gaps = []

        async def one(prompt, gen, collect):
            last = None
            async for c in fleet.dispatch_stream(
                    "completions_stream",
                    {"prompt": prompt, "max_tokens": gen}):
                if "[DONE]" in c:
                    continue
                now = time.perf_counter()
                if collect and last is not None:
                    gaps.append(now - last)
                last = now

        async def drive():
            t0 = time.perf_counter()
            for r in range(rounds):
                jobs = [one(f"long context r{r} i{i} "
                            + "x" * long_chars, gen_long, False)
                        for i in range(n_long)]
                jobs += [one(f"short q r{r} i{i}", gen_short, True)
                         for i in range(n_short)]
                await asyncio.gather(*jobs)
            dt = time.perf_counter() - t0
            for srv in servers.values():
                if srv._pump is not None:
                    srv._pump.cancel()
            return dt

        dt = asyncio.run(drive())
        gaps.sort()
        evs = [e["event"] for e in fleet.recorder.events()]
        hit = sum(s.engine.allocator.cache_hit_tokens
                  for s in servers.values())
        query = sum(s.engine.allocator.cache_query_tokens
                    for s in servers.values())
        toks = rounds * (n_long * gen_long + n_short * gen_short)
        return {
            "tokens_per_sec": round(toks / dt, 1),
            "decode_itl_p99_ms": round(
                gaps[min(len(gaps) - 1, int(len(gaps) * 0.99))]
                * 1e3, 3) if gaps else None,
            "decode_itl_p50_ms": round(
                gaps[len(gaps) // 2] * 1e3, 3) if gaps else None,
            "fleet_prefix_hit_rate": round(hit / max(query, 1), 4),
            "sessions_shipped": evs.count("disagg_handoff"),
            "disagg_fallbacks": evs.count("disagg_fallback"),
        }

    # correctness half (always, and the whole of --smoke): one long
    # prompt through the disaggregated fleet vs a single-engine
    # oracle, token-exact
    servers = _disagg_servers(2, cfg, pages, batch, chunk)
    fleet = fleet_over(servers, ["prefill", "decode"])
    body = {"prompt": "exactness probe " + "y" * long_chars,
            "max_tokens": gen_short}

    async def probe():
        toks = []
        async for c in fleet.dispatch_stream("completions_stream",
                                             dict(body)):
            if not c.startswith("data: "):
                continue
            d = c[len("data: "):].strip()
            if d == "[DONE]":
                continue
            toks += json.loads(d)["choices"][0].get("token_ids") \
                or []
        for srv in servers.values():
            if srv._pump is not None:
                srv._pump.cancel()
        return toks

    got = asyncio.run(probe())
    oracle = _disagg_servers(1, cfg, pages, batch, chunk)["r0"]

    async def oracle_probe():
        out = []
        async for c in oracle.completions_stream_tokens(dict(body)):
            out.append(c)
        if oracle._pump is not None:
            oracle._pump.cancel()
        return [t for c in out for t in c["toks"]]

    want = asyncio.run(oracle_probe())
    shipped = [e["event"] for e in fleet.recorder.events()] \
        .count("disagg_handoff")
    assert got == want, "disaggregated path diverged from oracle"
    assert shipped == 1, shipped
    exact = {"token_exact": True, "tokens": len(got),
             "sessions_shipped": shipped}
    if smoke:
        return {"exactness": exact}
    disagg = run(["prefill", "decode"])
    mixed = run(None)
    assert disagg["sessions_shipped"] >= rounds * n_long \
        - disagg["disagg_fallbacks"], disagg
    return {"exactness": exact, "disaggregated_1p1d": disagg,
            "mixed_2rep": mixed}


def bench_prefix_store(on_tpu: bool) -> dict:
    """ISSUE 12c A/B — the acceptance gate: on a shared-system-prompt
    workload, the fleet prefix-store hit rate must be STRICTLY above
    the PR 6 per-replica baseline (same fleet, same deterministic
    routing, store off). One warm request publishes the prefix; every
    other replica's FIRST request of that prefix then imports the
    pages instead of cold-prefilling — the per-replica cache
    multiplied by fleet size."""
    import asyncio
    import uuid

    from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                                   FleetManager, LocalReplicaClient,
                                   RouterConfig, TransportConfig)
    from ray_tpu.models import llama

    if on_tpu:
        cfg = _tpu_bench_model()
        gen, per_round, rounds = 16, 8, 2
        pages, batch, chunk = 512, 8, 128
    else:
        cfg = llama.config("debug")
        gen, per_round, rounds = 4, 4, 2
        pages, batch, chunk = 160, 4, 32
    # 64 chars = the router's prefix depth = 8 full byte-tokenizer
    # pages: exactly the chain the store ships
    sys_prompt = (f"system prompt {uuid.uuid4().hex[:8]} "
                  + "s" * 64)[:64]

    def run(store):
        servers = _disagg_servers(2, cfg, pages, batch, chunk)
        fleet = FleetManager(
            [LocalReplicaClient(rid, srv)
             for rid, srv in servers.items()],
            # round-robin pins IDENTICAL routing in both arms, so the
            # only difference is the store seeding the cold replica
            router=RouterConfig(policy="round_robin",
                                prefix_depth=64),
            admission=AdmissionConfig(max_concurrent=64,
                                      max_queue=128,
                                      queue_wait_slo_s=60.0),
            autoscale=AutoscaleConfig(min_replicas=2,
                                      max_replicas=2),
            transport=(TransportConfig(enable_disagg=False,
                                       prefix_min_chars=64)
                       if store else None))

        async def drive():
            # the system prompt is prefilled ONCE, sequentially —
            # with the store on, this publishes it fleet-wide
            await fleet.dispatch("completions", {
                "prompt": sys_prompt + " warmup", "max_tokens": gen})
            for r in range(rounds):
                await asyncio.gather(*(
                    fleet.dispatch("completions", {
                        "prompt": sys_prompt + f" user {r}-{i}",
                        "max_tokens": gen})
                    for i in range(per_round)))
            for srv in servers.values():
                if srv._pump is not None:
                    srv._pump.cancel()

        asyncio.run(drive())
        hit = sum(s.engine.allocator.cache_hit_tokens
                  for s in servers.values())
        query = sum(s.engine.allocator.cache_query_tokens
                    for s in servers.values())
        return {
            "fleet_prefix_hit_rate": round(hit / max(query, 1), 4),
            "store": (fleet.prefix_store.stats()
                      if fleet.prefix_store is not None else None),
        }

    baseline = run(False)
    store = run(True)
    # THE gate: the shared tier strictly beats per-replica caches
    assert store["fleet_prefix_hit_rate"] \
        > baseline["fleet_prefix_hit_rate"], (store, baseline)
    assert store["store"]["publishes"] >= 1
    assert store["store"]["hits"] >= 1
    return {
        "per_replica_baseline": baseline,
        "fleet_store": store,
        "hit_rate_advantage": round(
            store["fleet_prefix_hit_rate"]
            - baseline["fleet_prefix_hit_rate"], 4),
    }


def bench_sim(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 14 gate, three parts.

    Determinism: the same seed + trace replayed twice through the
    fleet simulator produce BYTE-identical run summaries (the
    what-if tool is useless if two runs of one scenario disagree).

    Calibration band: a small real-engine workload (measured wall)
    vs the simulator's prediction from the committed CPU calibration
    — the ratio must sit inside CALIBRATION_BAND, so a stale
    calibration file fails loudly instead of quietly skewing every
    capacity curve.

    Batch-lane A/B: identical interactive traffic with the lane off
    vs on (plus a bulk backlog): recovered batch tokens > 0, every
    job completes, and the interactive p99 TTFT is unchanged (the
    lane soaks troughs, it must never be the thing that queues a
    user). In --smoke mode all three assert."""
    import time as _t

    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              Request,
                                              SamplingParams)
    from ray_tpu.serve.llm.sim import (FleetSimulator, SimFleetConfig,
                                       SimSession, TraceConfig,
                                       batch_backlog,
                                       default_cpu_calibration,
                                       generate)
    from ray_tpu.serve.llm import AdmissionConfig
    from tools.simcal import check_against

    calib = default_cpu_calibration()
    tc = TraceConfig(kind="diurnal", sessions=20_000,
                     duration_s=7200.0, seed=23, prefix_groups=64,
                     prompt_tokens_mean=24, prompt_tokens_max=96,
                     out_tokens_mean=12, out_tokens_max=48)

    def cfg():
        return SimFleetConfig(
            replicas=4, min_replicas=2, slots_per_replica=8,
            pages_per_replica=2048, calibration=calib, seed=23,
            admission=AdmissionConfig(max_concurrent=96,
                                      max_queue=256,
                                      queue_wait_slo_s=5.0))

    # -- determinism --------------------------------------------------
    t0 = time.perf_counter()
    a = FleetSimulator(generate(tc), cfg())
    a.run()
    sim_wall = time.perf_counter() - t0
    b = FleetSimulator(generate(tc), cfg())
    b.run()
    identical = a.summary_json() == b.summary_json()

    # -- calibration band: real mini-workload vs sim prediction -------
    n, plen, out = 8, 24, 12
    eng = InferenceEngine(EngineConfig(
        model="debug", max_batch_size=8, page_size=16, num_pages=96,
        max_prefill_tokens=128, enable_blackbox=False, seed=0))
    warm = Request("warm", list(range(2, 2 + plen)),
                   SamplingParams(max_tokens=4))
    eng.add_request(warm)
    while not warm.finished:
        eng.step()
    reqs = [Request(f"w{i}", list(range(2 + i, 2 + i + plen)),
                    SamplingParams(max_tokens=out))
            for i in range(n)]
    t0 = _t.monotonic()
    for r in reqs:
        eng.add_request(r)
    while not all(r.finished for r in reqs):
        eng.step()
    real_wall = _t.monotonic() - t0
    sessions = [SimSession(0.0, "t", i, plen, out, sid=i)
                for i in range(n)]
    mini = FleetSimulator(
        iter(sessions),
        SimFleetConfig(replicas=1, min_replicas=1,
                       slots_per_replica=8, pages_per_replica=96,
                       calibration=calib, seed=23,
                       control_period_s=0.05))
    verdict = check_against(calib, mini.run(), real_wall)

    # -- batch-lane soak A/B ------------------------------------------
    def soak(jobs):
        sim = FleetSimulator(generate(tc), cfg(), batch_jobs=jobs)
        return sim.run()

    off = soak([])
    on = soak(batch_backlog(500, out_tokens=24))
    p99_off = off["latency"]["ttft"]["p99_ms"]
    p99_on = on["latency"]["ttft"]["p99_ms"]
    mean_off = off["latency"]["ttft"]["mean_ms"]
    mean_on = on["latency"]["ttft"]["mean_ms"]
    res = {
        "deterministic": identical,
        "sim_sessions_per_host_s": round(
            tc.sessions / max(sim_wall, 1e-9), 1),
        "calibration": verdict,
        "batch_ab": {
            "recovered_tokens": on["batch"]["tokens"],
            "batch_completed": on["batch"]["completed"],
            "interactive_p99_ttft_ms_off": p99_off,
            "interactive_p99_ttft_ms_on": p99_on,
            "interactive_mean_ttft_ms_off": mean_off,
            "interactive_mean_ttft_ms_on": mean_on,
        },
    }
    if smoke:
        assert identical, "sim summaries diverged for one seed"
        assert verdict["within_band"], verdict
        assert on["batch"]["completed"] == 500
        assert on["batch"]["tokens"] > 0
        # zero interactive TAIL regression (the acceptance
        # criterion): p99 slack is EXACTLY one 1.15x log-histogram
        # bin — quantization, not a regression window. The MEAN may
        # shift by a couple of tick-times: interactive sessions
        # co-resident with soaked batch work run in a larger batch
        # (slightly longer ticks) — that is the lane working as
        # designed, so it is bounded absolutely, not relatively
        assert p99_on <= p99_off * 1.16 + 1.0, res
        assert mean_on <= mean_off + 4 * calib.tick_point(8, "p50"), \
            res
    return res


def bench_sanitizer(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 18 gate: the runtime thread sanitizer's two contracts.

    Disarmed — the production default — make_lock hands the engine a
    plain threading.Lock (verified structurally: no wrapper, so
    serving pays zero sanitizer overhead). Armed, a bursty
    multithreaded run (the pump stepping while scrape threads hammer
    stats / lane_counts / fleet_counters / abort, prompts landing
    mid-decode) completes with ZERO recorded violations: the lock
    discipline racelint proves statically also holds at runtime under
    real contention."""
    import threading

    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              Request, SamplingParams)
    from ray_tpu.models import llama
    from ray_tpu.util import thread_sanitizer as ts

    cfg = llama.config("debug")
    n_req, max_tokens = (6, 24) if smoke else (12, 64)

    def build():
        eng = InferenceEngine(EngineConfig(
            model=cfg, max_batch_size=4, page_size=8, num_pages=160,
            prefill_buckets=(16, 32, 64), seed=7, unified_step=True))
        rng = np.random.default_rng(3)
        reqs = [Request(f"b{i}", rng.integers(2, 250, 12).tolist(),
                        SamplingParams(max_tokens=max_tokens))
                for i in range(n_req)]
        return eng, reqs

    # disarmed: the default engine must hold a bare stdlib lock
    eng, _ = build()
    plain = type(eng._step_lock) is type(threading.Lock())
    assert plain, "disarmed engine must hold a plain threading.Lock"

    t0 = time.perf_counter()
    with ts.sanitized():
        eng, reqs = build()     # built armed: traced step lock
        traced = isinstance(eng._step_lock, ts._TracedLock)
        assert traced, "armed engine must hold a traced lock"
        stop = threading.Event()
        errs: list = []

        def scrape():
            try:
                while not stop.is_set():
                    eng.stats()
                    eng.lane_counts()
                    eng.fleet_counters()
                    eng.has_work()
                    eng.abort("no-such-id")
            except BaseException as exc:   # pragma: no cover
                errs.append(exc)

        threads = [threading.Thread(target=scrape, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        for r in reqs[:2]:
            eng.add_request(r)
        admitted, ticks = 2, 0
        try:
            while not all(r.finished for r in reqs) and ticks < 5000:
                eng.step()
                ticks += 1
                if ticks % 5 == 0 and admitted < n_req:
                    # the burst: a new prompt lands mid-decode
                    eng.add_request(reqs[admitted])
                    admitted += 1
        finally:
            stop.set()
            for t in threads:
                t.join(30)
        viol = ts.violations()
    wall = time.perf_counter() - t0
    assert not errs, errs
    assert all(r.finished for r in reqs), "bursty workload must drain"
    assert viol == [], viol
    return {"disarmed_plain_lock": plain, "armed_traced_lock": traced,
            "ticks": ticks, "requests": n_req,
            "violations": len(viol), "wall_s": round(wall, 3)}


def bench_traffic_capture(on_tpu: bool, smoke: bool = False) -> dict:
    """ISSUE 20 gate: the traffic recorder's three production
    contracts, end to end.

    (1) Overhead: the same bursty workload runs with the capture
    disarmed (ring-only recording — the always-on default) and armed
    (segment encoding on every record); armed throughput must hold
    >= 0.7x disarmed (the encoding itself costs ~1%; the floor
    absorbs engine timing noise at smoke sizes). (2) Privacy: the capture bytes never contain
    the prompt tripwire. (3) Replay: the sealed capture replays
    through the fleet simulator deterministically (same bytes ->
    byte-identical summary) and the capture-diff lands inside the
    calibration band (p99 latency ratio, prefix-hit-rate and
    route-mix drift)."""
    import asyncio
    import uuid

    from ray_tpu.llm._internal.server import LLMServerImpl
    from ray_tpu.serve.llm import (AdmissionConfig, AutoscaleConfig,
                                   FleetManager, LocalReplicaClient,
                                   RouterConfig, WatchdogConfig)
    from ray_tpu.serve.llm.trafficlog import decode_capture
    from ray_tpu.models import llama
    from tools import tracereplay

    secret = "zanzibar beacon"                  # privacy tripwire
    if on_tpu:
        cfg = _tpu_bench_model()
        streams, rounds, gen = 24, 3, 32
        batch, pages = 8, 512
    else:
        cfg = llama.config("debug")
        streams, rounds, gen = 12, 2, 16
        batch, pages = 4, 128
    # 4 prefix chains: requests within a chain share an IDENTICAL
    # prompt (identical fingerprint -> one router group); one chain
    # carries the tripwire so the scrubbing proof covers real text.
    # Tiny prompts on purpose: the burst oversubscribes the engine
    # slots, so latency is queue/decode-dominated on both the real
    # and the simulated side rather than riding the prefill pricing.
    chains = [f"c{g}" if g else f"c0 {secret}" for g in range(4)]

    tag = f"cap{uuid.uuid4().hex[:8]}"
    servers = {f"r{i}": LLMServerImpl({
        "model_id": "capbench", "model_source": cfg,
        "engine_kwargs": dict(
            max_batch_size=batch, page_size=8, num_pages=pages,
            seed=7, metrics_model_id=tag,
            metrics_replica_id=f"r{i}")}) for i in range(2)}
    fleet = FleetManager(
        [LocalReplicaClient(rid, srv)
         for rid, srv in servers.items()],
        router=RouterConfig(prefix_depth=64),
        admission=AdmissionConfig(max_concurrent=2 * streams,
                                  max_queue=4 * streams),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=2),
        watchdog=WatchdogConfig(enabled=False),
        model_id=tag)

    async def burst(seed0):
        t0 = time.perf_counter()
        toks = 0
        for r in range(rounds):
            outs = await asyncio.gather(*(
                fleet.dispatch("completions", {
                    "prompt": chains[i % len(chains)],
                    "max_tokens": gen, "temperature": 0.5,
                    "seed": seed0 + i, "user": f"tenant-{i % 2}"})
                for i in range(streams)))
            toks += sum(o["usage"]["completion_tokens"]
                        for o in outs)
        return toks, time.perf_counter() - t0

    async def run_all():
        # two warmup bursts: the first compiles the fresh-prefill
        # shapes AND populates the prefix cache; the second hits that
        # cache and compiles the cached-prefix decode shapes the
        # steady state actually runs
        await burst(10_000)
        await burst(15_000)
        toks_off, dt_off = await burst(20_000)  # disarmed arm
        fleet.traffic.start_capture("bench")
        toks_on, dt_on = await burst(30_000)    # armed arm
        sealed = fleet.traffic.stop_capture()
        text = fleet.traffic.export()
        await fleet.stop()
        return toks_off, dt_off, toks_on, dt_on, sealed, text

    toks_off, dt_off, toks_on, dt_on, sealed, text = \
        asyncio.run(run_all())
    for srv in servers.values():
        if srv._pump is not None:
            srv._pump.cancel()

    tps_off = toks_off / dt_off
    tps_on = toks_on / dt_on
    overhead_ratio = tps_on / max(tps_off, 1e-9)

    # privacy: no prompt text in the capture bytes
    assert secret not in text
    for word in secret.split():
        assert word not in text

    # deterministic replay + the banded capture-diff
    cap = decode_capture(text)
    assert sealed["records"] == rounds * streams
    s1 = tracereplay.replay_sim(cap, replicas=2,
                                slots_per_replica=batch)
    s2 = tracereplay.replay_sim(cap, replicas=2,
                                slots_per_replica=batch)
    assert json.dumps(s1, sort_keys=True) == json.dumps(
        s2, sort_keys=True), "replay must be deterministic"
    diff = tracereplay.capture_diff(cap, s1)
    if smoke:
        assert overhead_ratio >= 0.7, (tps_off, tps_on)
        assert diff["pass"], diff["failures"]
    return {
        "records": sealed["records"],
        "capture_bytes": sealed["bytes"],
        "tokens_per_sec_disarmed": round(tps_off, 1),
        "tokens_per_sec_armed": round(tps_on, 1),
        "overhead_ratio": round(overhead_ratio, 3),
        "replay_pass": diff["pass"],
        "replay_failures": diff["failures"],
        "recorded_p99_e2e_ms":
            diff["recorded"]["latency"]["e2e"]["p99_ms"],
        "replayed_p99_e2e_ms":
            diff["replayed"]["latency"]["e2e"]["p99_ms"],
        "prefix_hit_rate": {
            "recorded": diff["recorded"]["prefix_hit_rate"],
            "replayed": diff["replayed"]["prefix_hit_rate"]},
    }


def main() -> None:
    import sys
    from ray_tpu.util.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if "--smoke" in sys.argv:
        # CI mode: tiny model, CPU, fast — one JSON line whose
        # dispatches_per_step and kernel_tick rows fail loudly on
        # scheduler / kernel regressions
        mixed = bench_mixed(on_tpu, smoke=True)
        kernel = bench_kernel_tick(on_tpu)
        async_ab = bench_async_ab(on_tpu, smoke=True)
        telemetry = bench_telemetry(on_tpu, smoke=True)
        fleet_tracing = bench_fleet_tracing(on_tpu, smoke=True)
        chaos = bench_chaos(on_tpu, smoke=True)
        preemption = bench_preemption(on_tpu, smoke=True)
        perf = bench_perf_accounting(on_tpu, smoke=True)
        # ISSUE 13: per-request receipts conserve exactly + on/off
        # overhead A/B within noise
        attribution = bench_attribution(on_tpu, smoke=True)
        # ISSUE 16: quantized-vs-f32 serving A/B — KV bytes >= 1.9x
        # narrower, logprob deltas and token agreement in band
        quant_ab = bench_quant_ab(on_tpu, smoke=True)
        # ISSUE 12: disaggregated prefill/decode must be token-exact
        # vs a single-engine oracle (the ship really happened)
        disagg = bench_disagg(on_tpu, smoke=True)
        # ISSUE 14: simulator determinism + calibration band +
        # batch-lane soak A/B (recovered tokens, zero interactive
        # p99 regression)
        sim = bench_sim(on_tpu, smoke=True)
        # ISSUE 18: disarmed engine holds a plain stdlib lock (zero
        # sanitizer overhead); armed bursty multithreaded run records
        # zero lock-discipline violations
        sanitizer = bench_sanitizer(on_tpu, smoke=True)
        # ISSUE 20: armed-capture overhead >= 0.7x disarmed, no
        # prompt text in capture bytes, and the sealed capture
        # replays deterministically inside the calibration band
        traffic = bench_traffic_capture(on_tpu, smoke=True)
        print(json.dumps({
            "metric": "llm_mixed_smoke",
            "value": mixed["unified"]["tokens_per_sec"],
            "unit": "tokens_per_sec",
            "detail": {**mixed, "kernel_tick": kernel,
                       "async_readback_ab": async_ab,
                       "telemetry": telemetry,
                       "fleet_tracing": fleet_tracing,
                       "chaos": chaos,
                       "preemption": preemption,
                       "perf": perf,
                       "attribution": attribution,
                       "quant_ab": quant_ab,
                       "disagg": disagg,
                       "sim": sim,
                       "sanitizer": sanitizer,
                       "traffic_capture": traffic},
        }))
        return
    if "--fleet" in sys.argv:
        # ISSUE 6 A/B: prefix-affine routing vs round-robin over a
        # 2-replica in-process fleet + admission overload contract;
        # ISSUE 12 rides along: the disaggregation A/B and the fleet
        # prefix-store-vs-per-replica-baseline gate
        fleet = bench_fleet(on_tpu)
        disagg = bench_disagg(on_tpu)
        store = bench_prefix_store(on_tpu)
        print(json.dumps({
            "metric": "llm_fleet" if on_tpu else "llm_fleet_cpu",
            "value": fleet["affinity_2rep"]["tokens_per_sec"],
            "unit": "tokens_per_sec",
            "detail": {**fleet, "disagg": disagg,
                       "prefix_store": store},
        }))
        return
    if "--long-ctx" in sys.argv:
        # ISSUE 2 A/B: gather vs Pallas ragged kernel at long context
        long_ctx = bench_long_ctx(on_tpu)
        print(json.dumps({
            "metric": "llm_long_ctx" if on_tpu
                      else "llm_long_ctx_cpu_interpret",
            "value": long_ctx["kernel"]["tokens_per_sec"],
            "unit": "tokens_per_sec",
            "detail": long_ctx,
        }))
        return
    eng = bench_engine(on_tpu)
    mixed = bench_mixed(on_tpu)
    async_ab = bench_async_ab(on_tpu)
    telemetry = bench_telemetry(on_tpu)
    perf = bench_perf_accounting(on_tpu)
    attribution = bench_attribution(on_tpu)
    scaling = bench_kernel_scaling(on_tpu)
    prefix = bench_prefix_cache(on_tpu)
    spec = bench_speculative(on_tpu)
    multi = bench_multi_step(on_tpu)
    print(json.dumps({
        "metric": "llm_decode_tokens_per_sec" if on_tpu
                  else "llm_decode_tokens_per_sec_cpu_fallback",
        "value": eng["decode_tokens_per_sec"],
        "unit": "tokens_per_sec",
        "detail": {"device": getattr(dev, "device_kind", str(dev)),
                   **eng, "mixed_prefill_decode": mixed,
                   "async_readback_ab": async_ab,
                   "telemetry": telemetry,
                   "perf": perf,
                   "attribution": attribution,
                   "paged_kernel_scaling": scaling,
                   "prefix_cache": prefix, "speculative": spec,
                   "multi_step_decode": multi},
    }))


if __name__ == "__main__":
    main()
