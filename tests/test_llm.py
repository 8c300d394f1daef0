"""LLM inference: paged attention, engine correctness, OpenAI serving.

The gold test: greedy incremental decode through the paged engine must
EXACTLY match argmax over a full forward pass re-run each step — this
pins prefill scatter, page tables, decode masking, RoPE positions, and
sampling all at once.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.llm import (ByteTokenizer, EngineConfig, InferenceEngine,
                         Request, SamplingParams)


def make_engine(**over):
    cfg = llama.config("debug", dtype=jnp.float32)
    kw = dict(model=cfg, max_batch_size=4, page_size=8, num_pages=64)
    kw.update(over)
    return InferenceEngine(EngineConfig(**kw))


# ------------------------------------------------------------- paged attn

def test_paged_attention_matches_dense():
    from ray_tpu.ops.paged_attention import (paged_attention_on_gathered,
                                             scatter_kv, gather_kv)
    rng = np.random.default_rng(0)
    B, CTX, L, KVH, H, D = 2, 24, 3, 2, 4, 16
    num_pages, page = 16, 8
    k_pages = jnp.zeros((L, num_pages, page, KVH, D))
    v_pages = jnp.zeros((L, num_pages, page, KVH, D))
    # seq 0 gets pages [0,1,2], seq 1 gets [3,4,5]
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    lens = np.array([20, 13])
    kd = rng.normal(size=(B, CTX, L, KVH, D)).astype(np.float32)
    vd = rng.normal(size=(B, CTX, L, KVH, D)).astype(np.float32)
    for b in range(B):
        rows_k = jnp.asarray(kd[b, :lens[b]])
        rows_v = jnp.asarray(vd[b, :lens[b]])
        t = jnp.tile(tables[b][None], (lens[b], 1))
        pos = jnp.arange(lens[b])
        k_pages, v_pages = scatter_kv(
            k_pages, v_pages, rows_k, rows_v, t, pos,
            jnp.ones(lens[b], bool))
    gk, gv = gather_kv(k_pages, v_pages, tables)   # [L, B, ctx, KVH, D]
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    for layer in range(L):
        out = paged_attention_on_gathered(
            q, gk[layer], gv[layer], jnp.asarray(lens, jnp.int32))
        # dense reference with GQA repeat
        for b in range(B):
            kk = np.repeat(kd[b, :lens[b], layer], H // KVH, axis=1)
            vv = np.repeat(vd[b, :lens[b], layer], H // KVH, axis=1)
            qq = np.asarray(q[b])                        # [H, D]
            sc = np.einsum("hd,chd->hc", qq, kk) / np.sqrt(D)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("hc,chd->hd", p, vv)
            np.testing.assert_allclose(np.asarray(out[b]), ref,
                                       rtol=2e-4, atol=2e-5)


def test_scatter_masks_invalid_rows_to_scratch():
    from ray_tpu.ops.paged_attention import scatter_kv
    k_pages = jnp.zeros((1, 4, 1, 2, 2))           # [L, pages, KVH, page, D]
    v_pages = jnp.zeros((1, 4, 1, 2, 2))
    rows = jnp.ones((1, 1, 1, 2))
    t = jnp.asarray([[0, 1]], jnp.int32)
    k2, v2 = scatter_kv(k_pages, v_pages, rows, rows, t,
                        jnp.asarray([0]), jnp.asarray([False]))
    assert float(jnp.abs(k2[:, :3]).sum()) == 0.0  # real pages untouched
    assert float(jnp.abs(k2[:, 3]).sum()) > 0.0    # scratch page took it


# ---------------------------------------------------------------- engine

def test_incremental_decode_matches_full_forward():
    eng = make_engine()
    cfg = eng.model_cfg
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(2, 200, n)) for n in (5, 9, 17)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=6,
                                                temperature=0.0))
    fwd = jax.jit(lambda p, t: llama.forward(cfg, p, t))
    for req, prompt in zip(reqs, prompts):
        toks = list(prompt)
        gold = []
        for _ in range(6):
            logits = fwd(eng.params, jnp.asarray([toks], jnp.int32))
            nxt = int(jnp.argmax(logits[0, -1]))
            gold.append(nxt)
            toks.append(nxt)
        assert req.output_tokens == gold


def test_continuous_batching_staggered_arrivals():
    eng = make_engine(max_batch_size=2)
    rng = np.random.default_rng(2)
    r1 = Request("a", list(rng.integers(2, 200, 4)),
                 SamplingParams(max_tokens=10))
    r2 = Request("b", list(rng.integers(2, 200, 6)),
                 SamplingParams(max_tokens=3))
    r3 = Request("c", list(rng.integers(2, 200, 5)),
                 SamplingParams(max_tokens=4))
    eng.add_request(r1)
    eng.add_request(r2)
    eng.add_request(r3)          # must wait: only 2 slots
    eng.step()
    assert eng.num_active() == 2 and len(eng.waiting) == 1
    while eng.has_work():
        eng.step()
    assert r1.finished and r2.finished and r3.finished
    assert len(r1.output_tokens) == 10
    assert len(r2.output_tokens) == 3
    assert len(r3.output_tokens) == 4
    # all pages reclaimed
    assert eng.stats()["free_pages"] == eng.stats()["total_pages"]


def test_admission_control_blocks_on_cache_pressure():
    eng = make_engine(num_pages=9)   # 8 usable pages of 8 tokens
    r1 = Request("a", [5] * 20, SamplingParams(max_tokens=12))  # 4 pages
    r2 = Request("b", [6] * 20, SamplingParams(max_tokens=12))  # 4 pages
    r3 = Request("c", [7] * 20, SamplingParams(max_tokens=12))
    for r in (r1, r2, r3):
        eng.add_request(r)
    eng.step()
    assert eng.num_active() == 2 and len(eng.waiting) == 1
    while eng.has_work():
        eng.step()
    assert r3.finished


def test_sampling_temperature_and_top_p():
    eng = make_engine()
    prompts = [[5, 6, 7, 8]]
    greedy1 = eng.generate(prompts, SamplingParams(max_tokens=5))
    greedy2 = eng.generate(prompts, SamplingParams(max_tokens=5))
    assert greedy1[0].output_tokens == greedy2[0].output_tokens
    hot = eng.generate(prompts * 2, SamplingParams(
        max_tokens=12, temperature=5.0, top_p=0.95))
    assert hot[0].output_tokens != hot[1].output_tokens
    assert all(0 <= t < eng.model_cfg.vocab_size
               for t in hot[0].output_tokens)


def test_seeded_sampling_reproducible_across_engines():
    """ISSUE 9 satellite: SamplingParams.seed makes the sampled path
    fully reproducible — two fresh engines (same weights seed), same
    prompt, same seed → identical token sequences; a different seed
    diverges. Without an explicit seed, the seed derives from the
    request id, so identical requests under DIFFERENT ids still
    diverge (a hot sampled batch must not collapse to one sequence)."""
    p = SamplingParams(max_tokens=10, temperature=0.9, top_p=0.9,
                      seed=123)
    a = make_engine(seed=7).generate([[5, 6, 7, 8]], p)
    b = make_engine(seed=7).generate([[5, 6, 7, 8]], p)
    assert a[0].output_tokens == b[0].output_tokens
    c = make_engine(seed=7).generate(
        [[5, 6, 7, 8]],
        SamplingParams(max_tokens=10, temperature=0.9, top_p=0.9,
                       seed=124))
    assert c[0].output_tokens != a[0].output_tokens


def test_seeded_sampled_replay_is_token_exact():
    """The failover-continuation property (ISSUE 9), engine-level:
    re-submitting prompt + the first k sampled outputs as the new
    prompt (same seed, max_tokens decremented) reproduces the
    remaining tokens EXACTLY — sampling keys derive from (seed,
    absolute token index), so the replay's prefill samples what the
    original's decode ticks would have."""
    prompt = [5, 6, 7, 8, 9]
    p = SamplingParams(max_tokens=10, temperature=0.8, top_p=0.95,
                      seed=999)
    full = make_engine(seed=7).generate(
        [prompt], p)[0].output_tokens
    assert len(full) == 10
    for k in (1, 4, 9):
        cont = make_engine(seed=7).generate(
            [prompt + full[:k]],
            SamplingParams(max_tokens=10 - k, temperature=0.8,
                           top_p=0.95, seed=999))[0].output_tokens
        assert cont == full[k:], (k, cont, full)


def test_deadline_expires_waiting_and_running_requests():
    """ISSUE 9 deadline propagation, engine half: a request past its
    deadline finishes with finish_reason="deadline" — straight out of
    the waiting queue if it never got a slot, or aborted at the next
    fold boundary if it was decoding (pages freed, slot reusable)."""
    import time as _time

    eng = make_engine()
    # waiting-queue expiry: deadline already past at the first tick
    r = Request("ddl-wait", [5, 6, 7], SamplingParams(max_tokens=5),
                deadline=_time.monotonic() - 1.0)
    eng.add_request(r)
    touched = eng.step()
    assert r.finished and r.finish_reason == "deadline"
    assert r in touched              # the finish event reaches streams
    assert not r.output_tokens

    # running-slot expiry: admit normally, then expire mid-decode
    r2 = Request("ddl-run", [5, 6, 7], SamplingParams(max_tokens=40),
                 deadline=_time.monotonic() + 3600.0)
    eng.add_request(r2)
    for _ in range(4):
        eng.step()
    assert not r2.finished and r2.output_tokens
    free_before = eng.allocator.free_pages
    r2.deadline = _time.monotonic() - 1.0
    eng.step()
    assert r2.finished and r2.finish_reason == "deadline"
    assert eng.allocator.free_pages > free_before   # pages freed
    # the engine is still healthy: a fresh request completes
    ok = eng.generate([[9, 8, 7]], SamplingParams(max_tokens=3))
    assert ok[0].finish_reason is not None
    kinds = [e["event"] for e in eng.telemetry.recorder.events()]
    assert "deadline_abort" in kinds


def test_stop_tokens():
    eng = make_engine()
    reqs = eng.generate([[5, 6, 7]], SamplingParams(max_tokens=30))
    tok = reqs[0].output_tokens[2]
    reqs2 = eng.generate([[5, 6, 7]], SamplingParams(
        max_tokens=30, stop_token_ids=(tok,)))
    assert reqs2[0].finish_reason == "stop"
    assert reqs2[0].output_tokens[-1] == tok
    assert len(reqs2[0].output_tokens) <= 3


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer(300)
    ids = tok.encode("hello world")
    assert tok.decode(ids) == "hello world"
    small = ByteTokenizer(256)        # debug vocab: folded bytes
    ids = small.encode("hi")
    assert all(i < 256 for i in ids)
    chat = tok.apply_chat_template(
        [{"role": "user", "content": "hi"}])
    assert "assistant" in chat


# --------------------------------------------------------------- serving

@pytest.mark.usefixtures("ray_start")
def test_openai_app_http(ray_start):
    import requests
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_openai_app

    app = build_openai_app({"llm_configs": [LLMConfig(
        model_id="m0", model_source="debug",
        engine_kwargs=dict(max_batch_size=4, page_size=8, num_pages=128))]})
    try:
        serve.run(app, name="llm", route_prefix="/",
                  http_options=serve.HTTPOptions(port=8126),
                  timeout_s=180)
        r = requests.get("http://127.0.0.1:8126/v1/models", timeout=30)
        assert r.status_code == 200
        assert r.json()["data"][0]["id"] == "m0"
        r = requests.post(
            "http://127.0.0.1:8126/v1/chat/completions",
            json={"model": "m0", "max_tokens": 6,
                  "messages": [{"role": "user", "content": "hey"}]},
            timeout=120)
        assert r.status_code == 200
        body = r.json()
        assert body["usage"]["completion_tokens"] <= 6
        assert body["choices"][0]["message"]["role"] == "assistant"
        r = requests.post(
            "http://127.0.0.1:8126/v1/chat/completions",
            json={"model": "nope", "messages": []}, timeout=60)
        assert r.status_code == 404
        # /stats smoke (ISSUE 4): tick-pipeline telemetry is
        # observable in serving — overlap ratio + lag/drain counters
        r = requests.get("http://127.0.0.1:8126/stats", timeout=30)
        assert r.status_code == 200
        eng_stats = r.json()["models"]["m0"]
        tt = eng_stats["tick_times"]
        assert {"wall_ms_avg", "host_ms_avg", "device_ms_avg",
                "overlap_ratio", "lagged_ticks",
                "drains"} <= set(tt)
        assert tt["async_readback"] is True
        assert eng_stats["dispatches"] >= 1
    finally:
        serve.shutdown()


def test_openai_streaming_sse(ray_start):
    """stream=true returns Server-Sent Events with incremental deltas,
    relayed proxy -> router replica -> model server replica over the
    actor streaming plane."""
    import json

    import requests
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_openai_app

    app = build_openai_app({"llm_configs": [LLMConfig(
        model_id="m0", model_source="debug",
        engine_kwargs=dict(max_batch_size=4, page_size=8, num_pages=128))]})
    try:
        serve.run(app, name="llm", route_prefix="/",
                  http_options=serve.HTTPOptions(port=8127),
                  timeout_s=180)
        r = requests.post(
            "http://127.0.0.1:8127/v1/chat/completions",
            json={"model": "m0", "max_tokens": 5, "stream": True,
                  "messages": [{"role": "user", "content": "hey"}]},
            stream=True, timeout=120)
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        events = []
        for line in r.iter_lines():
            if line.startswith(b"data: "):
                events.append(line[len(b"data: "):])
        assert events[-1] == b"[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        assert 1 <= len(chunks) <= 6
        assert chunks[0]["object"] == "chat.completion.chunk"
        assert chunks[-1]["choices"][0]["finish_reason"] is not None
    finally:
        serve.shutdown()


def test_sampling_top_k_and_repetition_penalty():
    """top_k masks everything outside the k best; repetition penalty
    (CTRL) suppresses seen tokens (VERDICT r3 weak #7)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm._internal.engine import _sample

    logits = jnp.asarray([[0.0, 5.0, 4.0, -2.0, 1.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    ones = jnp.ones(1, jnp.float32)

    # top_k=1 pins sampling to the argmax even at high temperature
    for seed in range(5):
        tok = _sample(logits, jax.random.PRNGKey(seed), ones * 5.0,
                      ones, top_ks=jnp.asarray([1]),
                      rep_pens=ones, seen=jnp.zeros((1, 5), bool))
        assert int(tok[0]) == 1

    # top_k=2 at high temperature: only the two best ever sampled
    picks = {int(_sample(logits, jax.random.PRNGKey(s), ones * 5.0,
                         ones, top_ks=jnp.asarray([2]), rep_pens=ones,
                         seen=jnp.zeros((1, 5), bool))[0])
             for s in range(30)}
    assert picks <= {1, 2} and len(picks) == 2

    # repetition penalty: the seen argmax (token 1) is suppressed below
    # the runner-up; greedy then picks token 2
    seen = jnp.zeros((1, 5), bool).at[0, 1].set(True)
    tok = _sample(logits, key, jnp.zeros(1), ones,
                  top_ks=jnp.zeros(1, jnp.int32),
                  rep_pens=jnp.asarray([3.0]), seen=seen)
    assert int(tok[0]) == 2


def test_engine_repetition_penalty_no_repeats():
    """End-to-end: a huge penalty forbids re-emitting prompt or
    generated tokens — every output token is fresh."""
    import jax.numpy as jnp
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine,
                                              SamplingParams)
    from ray_tpu.models import llama

    cfg = llama.config("debug", dtype=jnp.float32)
    eng = InferenceEngine(EngineConfig(model=cfg, max_batch_size=2,
                                       num_pages=64, seed=11))
    prompt = [7, 8, 9, 10]
    out = eng.generate([prompt], SamplingParams(
        max_tokens=10, repetition_penalty=1000.0))[0].output_tokens
    assert len(out) == 10
    assert len(set(out)) == len(out), out          # no repeats
    assert not (set(out) & set(prompt)), out       # prompt suppressed


def test_multi_lora_batched_adapters():
    """Multi-LoRA serving: different slots of one batch run different
    adapters; a zero adapter is an exact no-op (VERDICT r3 weak #7)."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine, Request,
                                              SamplingParams)
    from ray_tpu.models import llama

    cfg = llama.config("debug", dtype=jnp.float32)
    eng = InferenceEngine(EngineConfig(model=cfg, max_batch_size=4,
                                       num_pages=64, seed=4))
    L, h, q_dim, r = cfg.n_layers, cfg.hidden, cfg.q_dim, 4
    rng = np.random.default_rng(0)
    eng.register_lora("strong", {
        "wq": (rng.normal(0, 0.5, (L, h, r)),
               rng.normal(0, 0.5, (r, q_dim)) * np.ones((L, 1, 1))),
    })
    eng.register_lora("zero", {"wq": (np.zeros((L, h, r)),
                                      np.zeros((L, r, q_dim)))})
    prompt = [3, 4, 5, 6]
    sp = SamplingParams(max_tokens=6)

    def run(lora, rid):
        req = Request(rid, list(prompt), sp, lora=lora)
        eng.add_request(req)
        while not req.finished:
            eng.step()
        return req.output_tokens

    base = run(None, "base")
    strong = run("strong", "strong1")
    zero = run("zero", "zero1")
    assert zero == base, (zero, base)        # zero adapter = exact no-op
    assert strong != base, strong            # a real adapter changes logits

    # mixed batch: base + strong simultaneously must reproduce their
    # solo outputs (per-slot adapter gather is actually per-slot)
    r1 = Request("mix-base", list(prompt), sp)
    r2 = Request("mix-strong", list(prompt), sp, lora="strong")
    eng.add_request(r1)
    eng.add_request(r2)
    while not (r1.finished and r2.finished):
        eng.step()
    assert r1.output_tokens == base
    assert r2.output_tokens == strong

    with pytest.raises(ValueError, match="unknown LoRA"):
        eng.add_request(Request("bad", [1, 2], sp, lora="nope"))


def test_data_llm_batch_lora_column(ray_start):
    """data.llm batch inference: rows pick adapters via a 'lora'
    column, registered from the processor config."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu import data
    from ray_tpu.data.llm import LLMEngineProcessorConfig, \
        build_llm_processor
    from ray_tpu.models import llama

    cfg = llama.config("debug", dtype=jnp.float32)
    L, h, q, r = cfg.n_layers, cfg.hidden, cfg.q_dim, 4
    rng = np.random.default_rng(0)
    proc = build_llm_processor(LLMEngineProcessorConfig(
        model_source=cfg,
        engine_kwargs={"num_pages": 64, "seed": 2},
        sampling_params={"max_tokens": 4},
        lora_adapters={"styleA": {
            "wq": (rng.normal(0, 0.5, (L, h, r)),
                   rng.normal(0, 0.5, (L, r, q)))}},
        batch_size=4))
    ds = data.from_items([
        {"prompt": "hello", "lora": ""},
        {"prompt": "hello", "lora": "styleA"},
    ])
    rows = proc(ds).take_all()
    assert len(rows) == 2
    assert rows[0]["generated_tokens"] != rows[1]["generated_tokens"]


def test_deployment_chips_follow_engine_mesh():
    """accelerator_type replicas request tp chips (the reference
    sizes vLLM worker placement the same way, vllm_models.py:123-139)."""
    from ray_tpu.llm import LLMConfig, build_llm_deployment

    app = build_llm_deployment(LLMConfig(
        model_id="m", accelerator_type="TPU-V5E",
        engine_kwargs={"mesh": {"tp": 4, "fsdp": 1}}))
    assert app._deployment.config.ray_actor_options["num_tpus"] == 4
    with pytest.raises(ValueError, match="explicit tp size"):
        build_llm_deployment(LLMConfig(
            model_id="m3", accelerator_type="TPU-V5E",
            engine_kwargs={"mesh": {"tp": -1}}))

    app1 = build_llm_deployment(LLMConfig(
        model_id="m2", accelerator_type="TPU-V5E"))
    assert app1._deployment.config.ray_actor_options["num_tpus"] == 1


def test_async_readback_token_exact_mixed_finishes():
    """ISSUE 4 lagged retirement: the pipelined engine must match the
    sync engine token-for-token (and finish_reason-for-finish_reason)
    on a mixed batch whose requests retire at DIFFERENT ticks via
    max_tokens, a stop token, and a penalized stream — each
    length-finish happens while its successor tick is already in
    flight, so the one-token over-generation discard and the drain
    barrier are both exercised repeatedly."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 200, n).tolist() for n in (5, 11, 7, 16)]

    def run(async_rb, stop_tok):
        eng = make_engine(async_readback=async_rb,
                          enable_prefix_caching=False)
        params = [SamplingParams(max_tokens=6),
                  SamplingParams(max_tokens=13),
                  SamplingParams(max_tokens=20,
                                 stop_token_ids=(stop_tok,)),
                  SamplingParams(max_tokens=9,
                                 repetition_penalty=1.3)]
        reqs = [Request(f"x{i}", list(p), params[i])
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        while eng.has_work():
            eng.step()
        assert eng.stats()["free_pages"] == eng.stats()["total_pages"]
        return eng, [(r.output_tokens, r.finish_reason) for r in reqs]

    # pick the stop token from a reference pass so request 2 really
    # stops mid-stream, several ticks after request 0 retired
    _, ref = run(False, stop_tok=-1)
    stop_tok = ref[2][0][4]
    eng_s, out_sync = run(False, stop_tok)
    eng_a, out_async = run(True, stop_tok)
    assert out_async == out_sync
    assert out_async[2][1] == "stop"
    tt = eng_a.stats()["tick_times"]
    # the pipeline actually ran: folds lagged and retirements drained
    assert tt["lagged_ticks"] > 0 and tt["drains"] > 0
    assert eng_s.stats()["tick_times"]["lagged_ticks"] == 0


def test_async_finish_while_successor_in_flight():
    """Tightest lag case: a single request whose final token folds
    while the (over-generating) successor tick is in flight — output
    must truncate exactly at max_tokens, the discarded token must not
    leak, and the successor's KV write stays inside the slot's pages
    (the engine asserts that invariant at every fold)."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(2, 200, 9).tolist()
    outs = {}
    for async_rb in (False, True):
        eng = make_engine(async_readback=async_rb)
        req = Request("one", list(prompt), SamplingParams(max_tokens=2))
        eng.add_request(req)
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
        assert req.finished and req.finish_reason == "length"
        assert len(req.output_tokens) == 2
        outs[async_rb] = (req.output_tokens, steps)
    assert outs[True][0] == outs[False][0]
    # the async run needed exactly one extra step: the lagged fold
    assert outs[True][1] == outs[False][1] + 1


def test_abort_drain_does_not_strand_finishes():
    """An abort-triggered drain folds the in-flight tick OUTSIDE any
    step() — if that fold retires ANOTHER request, its finish event
    must not be stranded: has_work() stays true until the next step
    delivers it through the touched list (the server pump parks on
    has_work, so a stranded finish would hang its stream consumer)."""
    rng = np.random.default_rng(9)
    eng = make_engine(max_batch_size=2, enable_prefix_caching=False)
    r1 = Request("a", rng.integers(2, 200, 5).tolist(),
                 SamplingParams(max_tokens=9))
    r2 = Request("b", rng.integers(2, 200, 7).tolist(),
                 SamplingParams(max_tokens=4))
    eng.add_request(r1)
    eng.add_request(r2)
    # step until r2's FINAL token is in flight but not yet folded
    while not (eng._inflight is not None
               and len(r2.output_tokens) == 3):
        eng.step()
    assert eng.abort("a")
    # the abort's drain folded the in-flight tick: r2 finished
    # outside step(), its event parked in _pending_touched
    assert r2.finished and r2.finish_reason == "length"
    assert len(r2.output_tokens) == 4
    assert eng.has_work()               # one more step delivers it
    touched = eng.step()
    assert r2 in touched
    assert not eng.has_work()
    assert eng.stats()["free_pages"] == eng.stats()["total_pages"]


def test_async_stream_order_preserved():
    """ISSUE 4 server contract: the one-tick lag must not reorder,
    drop, or duplicate streamed chunks — two concurrent SSE-style
    streams through the engine pump must each reconstruct exactly
    their request's decoded output."""
    import asyncio

    from ray_tpu.llm._internal.server import LLMServerImpl

    srv = LLMServerImpl({
        "model_id": "m0", "model_source": "debug",
        "engine_kwargs": dict(max_batch_size=4, page_size=8,
                              num_pages=128)})
    assert srv.engine._async            # pipeline on by default

    async def consume(prompt_text, max_tokens):
        toks = srv.tokenizer.encode(prompt_text)
        deltas = []
        finishes = 0
        async for _, delta, finished, reason in srv._generate_stream(
                toks, SamplingParams(max_tokens=max_tokens)):
            if not delta and not finished:
                continue       # the SSE wrappers drop text-less
            deltas.append(delta)   # events (tokens ride them for the
            finishes += finished   # failover relay — ISSUE 9)
        return deltas, finishes

    async def main():
        out = await asyncio.gather(consume("hello world", 7),
                                   consume("quite different", 11))
        srv._pump.cancel()
        return out

    (d1, f1), (d2, f2) = asyncio.run(main())
    assert f1 == 1 and f2 == 1          # exactly one finish each
    # every chunk except possibly the closing one carries new text
    assert all(d for d in d1[:-1]) and all(d for d in d2[:-1])

    # byte-exact reconstruction vs a SYNCHRONOUS reference engine:
    # the lagged stream may deliver chunks later, but never permuted,
    # duplicated, or dropped (greedy decode is batching-independent,
    # so solo sync runs are the gold text)
    ref = InferenceEngine(EngineConfig(
        model="debug", max_batch_size=4, page_size=8, num_pages=128,
        async_readback=False))
    for deltas, (text, n) in zip(
            (d1, d2), (("hello world", 7), ("quite different", 11))):
        out = ref.generate([srv.tokenizer.encode(text)],
                           SamplingParams(max_tokens=n))
        assert "".join(deltas) == srv.tokenizer.decode(
            out[0].output_tokens)
    tt = srv.engine.stats()["tick_times"]
    assert tt["lagged_ticks"] > 0       # streams rode the pipeline
