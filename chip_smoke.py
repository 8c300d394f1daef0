"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: serve 1b, train 889M
    python chip_smoke.py --chips 4    # one 4-chip host: serve 8b at tp=4,
                                      # train 889M at fsdp=2 x tp=2

Drives today's main paths once, through the entry points a user calls, at
the full width of a model the repo supports (depth uncut too; weights
random from a seed):

  serve  LLMServerImpl (what build_openai_app deploys) with default
         EngineConfig dispatch answers OpenAI-style bodies — overlapping
         arrivals, a prompt longer than max_prefill_tokens, one streamed,
         one sampled with top-p and a repetition penalty — then checks the
         kernel path's logits against the dense gather path on the same
         chip and that an armed profile capture left a trace on disk.
  train  TrainStepBundle takes a few steps of bench.py's 889M
         configuration with the Pallas flash kernel on a repeated batch.

Each phase is a child process run to completion, one after the other; a
chip belongs to one process at a time, so this parent imports only the
standard library and never touches JAX. A child that finds no
accelerator (or a device_kind the peaks table does not name) exits
non-zero and this parent prints no result. On success the last line of
stdout is exactly `{"ok": true, "device": {"platform", "kind", "count"}}`
with the device as jax reported it; the line before it, `SUMMARY {...}`
(also chiprun_out/chip_smoke/summary.json), carries the per-phase detail.

`--rehearse-cpu` runs the same flow at the `debug` size on the CPU
backend with the kernels in interpret mode; it prints "rehearsal": true
and is never what the bare command does.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# the driver allows 1200 s, compilation included
DEADLINE_S = 1150
NO_CHIP_RC = 4
RESULT_TAG = "PHASE_RESULT "


def _args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug-size dry run on the CPU backend")
    ap.add_argument("--gspmd", action="store_true",
                    help="--chips 4 serve phase on the GSPMD engine "
                         "(mesh=MeshSpec(tp=4)) instead of mesh_shape")
    ap.add_argument("--only", choices=("serve", "train"),
                    help="run one phase (by-hand use)")
    ap.add_argument("--phase", choices=("serve", "train"),
                    help=argparse.SUPPRESS)       # child marker
    return ap.parse_args()


# --------------------------------------------------------------- parent

def _run_phase(phase: str, args, deadline: float) -> dict:
    """Run one phase as a child to completion; returns its result dict
    or exits this process with the child's non-zero code."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--chips", str(args.chips)]
    env = dict(os.environ)
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
    if args.gspmd:
        cmd.append("--gspmd")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            env=env, cwd=ROOT, start_new_session=True)

    def _kill(*_):
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    signal.signal(signal.SIGALRM, _kill)
    signal.alarm(max(int(deadline - time.monotonic()), 1))
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        signal.alarm(0)
        _kill()                      # nothing the child started outlives it
    if rc != 0 or result is None or not result.get("ok"):
        sys.stderr.write(f"chip_smoke: phase {phase!r} failed "
                         f"(exit {rc})\n")
        sys.exit(rc if rc > 0 else 1)
    return result


def main() -> None:
    args = _args()
    if args.phase:
        return _child(args)
    t0 = time.monotonic()
    deadline = t0 + DEADLINE_S
    phases = {}
    for phase in ([args.only] if args.only else ["serve", "train"]):
        phases[phase] = _run_phase(phase, args, deadline)
    first = next(iter(phases.values()))
    summary = {
        "ok": True,
        "device": first["device"],
        "versions": first["versions"],
        "cache_dir": first["cache_dir"],
        "wall_s": round(time.monotonic() - t0, 1),
        "phases": {k: {kk: vv for kk, vv in v.items()
                       if kk not in ("device", "versions", "cache_dir")}
                   for k, v in phases.items()},
    }
    if args.rehearse_cpu:
        summary["rehearsal"] = True
    summary["claim"] = None
    # the detail: next to the traces, and as the line before the last
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("SUMMARY " + json.dumps(summary))
    # the last line is the driver's contract: these keys and no others,
    # the device as jax reported it to the children
    dev = first["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)


# ---------------------------------------------------------------- child

def _child(args) -> None:
    t0 = time.monotonic()
    from ray_tpu.util.compile_cache import CompileWatch, ensure_compile_cache
    cache_dir = ensure_compile_cache()        # first: before any compile
    watch = CompileWatch()
    import jax

    from ray_tpu.llm._internal.perfmodel import DEVICE_KINDS
    devs = jax.devices()
    dev = devs[0]
    if args.rehearse_cpu:
        if dev.platform != "cpu":
            sys.exit(f"chip_smoke: --rehearse-cpu is for the CPU backend; "
                     f"found {dev.platform!r}")
    elif dev.platform != "tpu" or dev.device_kind not in DEVICE_KINDS:
        sys.stderr.write(
            f"chip_smoke: needs a TPU named in the peaks table "
            f"{sorted(DEVICE_KINDS)}; jax found platform "
            f"{dev.platform!r}, device_kind {dev.device_kind!r}\n")
        sys.exit(NO_CHIP_RC)
    if len(devs) < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} but jax "
                         f"found {len(devs)} device(s)\n")
        sys.exit(NO_CHIP_RC)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None

    body = _serve if args.phase == "serve" else _train
    detail = body(args, devs[:args.chips])
    comp = watch.snapshot()
    wall = time.monotonic() - t0
    result = {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs)},
        "versions": {"jax": jax.__version__, "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "cache_dir": cache_dir,
        "wall_s": round(wall, 1),
        # compile_s is time inside the backend compiler (a persistent-
        # cache hit costs its retrieval); run_s is everything else,
        # weight init and host work included
        "compile_s": comp["compile_s"],
        "run_s": round(wall - comp["compile_s"], 1),
        "programs_compiled": comp["programs"],
        "cache_hits": comp["cache_hits"],
        "cache_writes": comp["cache_writes"],
        "peak_hbm_bytes": [_peak_bytes(d) for d in devs[:args.chips]],
        **detail,
    }
    print(RESULT_TAG + json.dumps(result), flush=True)


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check(cond, what: str) -> None:
    """A phase check: `assert` would vanish under -O."""
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def _spread_over(tree, devs, what: str) -> list:
    """Every chip holds its share of `tree`, not the first alone: each
    leaf has a shard on every device, per-device bytes (summed from
    addressable_shards) are at least half an even split, and where the
    backend reports memory_stats() each chip has at least that much in
    use. Returns the per-device bytes."""
    import jax
    per_dev = {d.id: 0 for d in devs}
    leaves = jax.tree.leaves(tree)
    for leaf in leaves:
        on = {s.device.id for s in leaf.addressable_shards}
        if on != set(per_dev):
            _check(False, f"{what}: a {leaf.shape} leaf lives only on "
                          f"devices {sorted(on)}")
        for s in leaf.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    even = sum(per_dev.values()) / len(devs)
    for d in devs:
        in_use = (d.memory_stats() or {}).get("bytes_in_use")
        _check(per_dev[d.id] >= even / 2
               and (in_use is None or in_use >= per_dev[d.id]),
               f"{what}: device {d.id} holds {per_dev[d.id] / 2**20:.1f} "
               f"MiB of {len(leaves)} leaves"
               + ("" if in_use is None
                  else f" ({in_use / 2**30:.2f} GiB in use)"))
    return [per_dev[d.id] for d in devs]


# ---------------------------------------------------------- serve phase

def _serve(args, devs) -> dict:
    import asyncio
    return asyncio.run(_serve_async(args, devs))


async def _serve_async(args, devs) -> dict:
    import asyncio
    import glob

    from ray_tpu.llm._internal.server import LLMServerImpl

    rehearsal, chips = args.rehearse_cpu, args.chips
    if rehearsal:
        model = "debug" if chips == 1 else "tiny"   # tiny: 4 kv heads
    else:
        model = "1b" if chips == 1 else "8b"
    ekw = {}
    if rehearsal:
        # the CPU backend cannot compile the kernels; rehearse them in
        # interpret mode, on a table the debug model's 256-token
        # context allows
        ekw.update(decode_impl="pallas_interpret", max_prefill_tokens=64)
    if chips == 4 and args.gspmd:
        ekw["mesh"] = {"tp": 4, "fsdp": 1}
    elif chips == 4:
        ekw["mesh_shape"] = (1, 4)
    print(f"[serve] building LLMServerImpl model={model} "
          f"engine_kwargs={ekw}", flush=True)
    t0 = time.monotonic()
    server = LLMServerImpl({"model_id": "smoke", "model_source": model,
                            "engine_kwargs": ekw})
    eng = server.engine
    cfg, ec = eng.model_cfg, eng.config
    print(f"[serve] engine up in {time.monotonic() - t0:.1f}s: "
          f"{cfg.n_layers} layers, hidden {cfg.hidden}, "
          f"{cfg.n_heads}q/{cfg.n_kv_heads}kv heads, head_dim "
          f"{cfg.head_dim}, vocab {cfg.vocab_size}; pool "
          f"{eng.k_pages.shape} {eng.k_pages.dtype}", flush=True)
    want_impl = "pallas_interpret" if rehearsal else "pallas"
    _check(ec.decode_impl == ("auto" if not rehearsal else want_impl),
           "default EngineConfig dispatch (decode_impl)")
    _check(eng._resolve_impl() == want_impl,
           f"decode_impl resolved to {eng._resolve_impl()!r}")

    long_len = ec.max_prefill_tokens + ec.max_prefill_tokens // 3
    gen = 16 if rehearsal else 40

    async def streamed():
        # random weights over a 128k vocabulary rarely emit a byte
        # token, so text deltas are mostly empty: count the events
        chunks = [sse async for sse in server.completions_stream({
            "prompt": "The quick brown fox jumps over the lazy dog.",
            "max_tokens": gen, "stream": True})]
        last = json.loads(chunks[-2][len("data: "):])
        return {"finish_reason": last["choices"][0]["finish_reason"],
                "chunks": len(chunks), "done": chunks[-1].strip()}

    async def later(delay, coro_fn, body):
        # arrive while the streamed request is mid-decode (its prompt
        # tick and two decode ticks are behind it), so the prefill
        # rides a tick that also carries decode rows
        while eng.ticks < 3:
            await asyncio.sleep(0.005)
        await asyncio.sleep(delay)
        out = await coro_fn(body)
        return {"finish_reason": out["choices"][0]["finish_reason"],
                "usage": {k: v for k, v in out["usage"].items()
                          if k != "cost"}}

    t_req = time.monotonic()
    res = await asyncio.gather(
        streamed(),
        later(0.0, server.chat, {
            "messages": [{"role": "user", "content": "Say hello."}],
            "max_tokens": gen // 2}),
        later(0.02, server.completions, {
            "prompt": "x" * (long_len - 1), "max_tokens": 8}),
        later(0.04, server.completions, {
            "prompt": "Sample something.", "max_tokens": gen // 2,
            "temperature": 0.8, "top_p": 0.9,
            "repetition_penalty": 1.2, "seed": 7}))
    names = ["streamed", "chat", "long_prompt", "sampled"]
    for name, r in zip(names, res):
        _check(r["finish_reason"] in ("stop", "length"),
               f"request {name} finished: {r}")
    _check(res[0]["chunks"] >= 2 and res[0]["done"] == "data: [DONE]",
           "streamed request delivered its final chunk and [DONE]")
    _check(res[2]["usage"]["prompt_tokens"] == long_len > ec.max_prefill_tokens,
           f"long prompt ({long_len} tokens) exceeded max_prefill_tokens "
           f"({ec.max_prefill_tokens})")

    # one more request with the profiler armed: the capture is what the
    # benchmark's trace reduction will read, and the engine swallows a
    # profiler failure into a flight-recorder event
    prof_dir = os.path.join(OUT_DIR, f"profile_serve_{chips}chip")
    shutil.rmtree(prof_dir, ignore_errors=True)   # an earlier run's trace
    eng.profile_next_ticks(ticks=4, log_dir=prof_dir)
    out = await server.completions({
        "prompt": "The quick brown fox jumps over the lazy dog.",
        "max_tokens": 8})
    _check(out["choices"][0]["finish_reason"] in ("stop", "length"),
           "profiled request finished")
    serve_s = time.monotonic() - t_req

    stats = eng.stats()
    window = eng.perf.window()
    mixed = [s for s in window if s.kind == "ragged"
             and s.decode_tokens > 0 and s.prefill_tokens > 0]
    chunk_capped = [s for s in window if s.kind == "ragged"
                    and s.prefill_tokens >= ec.max_prefill_tokens]
    decode_only = [s for s in window if s.kind == "decode"]
    _check(mixed, f"{len(mixed)} mixed ragged tick(s) (decode rows + "
                  f"prefill chunk in one dispatch)")
    _check(chunk_capped, "chunked prefill: a tick carried a full "
                         f"{ec.max_prefill_tokens}-token chunk")
    _check(decode_only, f"{len(decode_only)} pure-decode tick(s)")
    _check(stats["tick_times"]["lagged_ticks"] > 0,
           f"async readback folded "
           f"{stats['tick_times']['lagged_ticks']} tick(s) one tick late")
    _check(any(not greedy for (_, _, greedy) in eng._ragged_fns),
           "the sampled (top-p, repetition penalty) program ran")
    traces = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                       recursive=True)
    _check(traces, f"profiler trace on disk: "
                   f"{[os.path.relpath(t, ROOT) for t in traces]}")

    detail = {
        "model": model,
        "decode_impl": eng._resolve_impl(),
        "requests": dict(zip(names, res)),
        "serve_s": round(serve_s, 1),
        "ticks": eng.ticks, "dispatches": eng.dispatches,
        "engine_programs": eng.compiles,
        "mixed_ticks": len(mixed), "decode_ticks": len(decode_only),
        "profile_trace": os.path.relpath(traces[0], ROOT),
    }
    if chips == 1:
        detail["logits"] = _logits_check(eng)
    else:
        # explicit-tp/GSPMD forwards only exist inside the engine's own
        # programs; the kernel-vs-gather logits check is the one-chip
        # phase's. Here: every chip holds its share of weights and KV.
        detail["mesh"] = "gspmd tp=4" if args.gspmd else "mesh_shape (1, 4)"
        detail["weight_bytes_per_chip"] = _spread_over(
            eng.params, devs, "weights")
        detail["kv_bytes_per_chip"] = _spread_over(
            (eng.k_pages, eng.v_pages), devs, "KV pool")
    return detail


def _logits_check(eng) -> dict:
    """One mixed tick and one decode tick through the model forwards the
    engine's programs call (`ragged_forward`, `decode_step`) on the
    engine's own weights and pool layout: kernel path vs dense gather
    path, same chip, last-token logits. Tokens flip on rounding with
    random weights, so logits are what is compared."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama_infer import decode_step, ragged_forward

    cfg, ec = eng.model_cfg, eng.config
    kernel = eng._resolve_impl()
    B, page = ec.max_batch_size, ec.page_size
    rng = np.random.default_rng(0)
    per_slot = 8                                   # pages per test slot
    tables = np.zeros((B, eng.max_pages_per_seq), np.int32)
    tables[:, :per_slot] = np.arange(B * per_slot).reshape(B, per_slot)
    tables = jnp.asarray(tables)
    chunk = min(40, ec.max_prefill_tokens)

    def pack(plan):
        """[(slot, start, n)] -> the flat ragged batch `_ragged_step`
        packs (tokens, slot_ids, positions, valid, start, last_idx)
        plus its static ctx-pages and segment bounds."""
        total = sum(n for _, _, n in plan)
        T = eng._token_bucket(total)
        toks = np.zeros(T, np.int32)
        slots = np.zeros(T, np.int32)
        pos = np.zeros(T, np.int32)
        valid = np.zeros(T, bool)
        start = np.zeros(B, np.int32)
        last = np.zeros(B, np.int32)
        cur = 0
        for s, st, n in plan:
            toks[cur:cur + n] = rng.integers(3, cfg.vocab_size, n)
            slots[cur:cur + n] = s
            pos[cur:cur + n] = np.arange(st, st + n)
            valid[cur:cur + n] = True
            start[s], last[s] = st, cur + n - 1
            cur += n
        arrs = tuple(jnp.asarray(a) for a in
                     (toks, slots, pos, valid, start, last))
        return arrs, eng._ctx_bucket(max(st for _, st, _ in plan))

    def ragged(impl, batch, k, v):
        (toks, slots, pos, valid, start, last), ctx = batch
        fn = jax.jit(functools.partial(
            ragged_forward, cfg, ctx_pages=ctx, impl=impl))
        return fn(eng.params, toks, slots, pos, valid, start, last, k, v,
                  tables)

    k0, v0 = jnp.zeros_like(eng.k_pages), jnp.zeros_like(eng.v_pages)
    # context: slot 0 holds 70 cached tokens, slot 1 holds 37
    _, k1, v1 = ragged("gather", pack([(0, 0, 70), (1, 0, 37)]), k0, v0)
    # mixed tick: slot 0 decodes, slot 1 continues a chunk against its
    # cached context, slot 2 starts a prompt
    mixed = pack([(0, 70, 1), (1, 37, chunk), (2, 0, 24)])
    lg_g, k2, v2 = ragged("gather", mixed, k1, v1)
    lg_k, k2k, _ = ragged(kernel, mixed, k1, v1)
    # decode tick over the three live slots
    toks = jnp.asarray(rng.integers(3, cfg.vocab_size, B), jnp.int32)
    posn = np.zeros(B, np.int32)
    posn[:3] = (71, 37 + chunk, 24)
    active = jnp.asarray(np.arange(B) < 3)
    dec = lambda impl: jax.jit(functools.partial(
        decode_step, cfg, impl=impl))(
            eng.params, toks, jnp.asarray(posn), k2, v2, tables, active)
    ld_g, ld_k = dec("gather")[0], dec(kernel)[0]

    # Tolerance. Both paths compute in bf16 with f32 softmax, but sum in
    # different orders (flash blocks vs one dense softmax) and round the
    # attention output to bf16 at different points, so they agree to
    # bf16 rounding carried through the layer stack, not bitwise: on
    # logits of unit scale that is an RMS gap near 2^-8 * sqrt(layers)
    # ~ 0.02. Bounds: RMS gap <= 3% of the logits' RMS, no single logit
    # off by more than 0.25 RMS. A wrong page, mask or head mapping
    # moves logits by ~1 RMS, so the bounds are far from both.
    REL_RMS, MAX_OVER_RMS = 0.03, 0.25
    out = {"tolerance": {"rel_rms": REL_RMS, "max_over_rms": MAX_OVER_RMS}}
    for name, a, b in (("mixed_tick", lg_g, lg_k),
                       ("decode_tick", ld_g, ld_k)):
        a = np.asarray(a, np.float32)[:3]
        b = np.asarray(b, np.float32)[:3]
        rms = float(np.sqrt(np.mean(a * a)))
        gap = float(np.sqrt(np.mean((a - b) ** 2)))
        worst = float(np.abs(a - b).max())
        _check(np.isfinite(a).all() and np.isfinite(b).all()
               and a.shape == (3, cfg.vocab_size),
               f"{name}: finite logits of shape {a.shape}")
        _check(gap <= REL_RMS * rms and worst <= MAX_OVER_RMS * rms,
               f"{name}: kernel vs gather logits rms gap "
               f"{gap / rms:.4f} of rms (<= {REL_RMS}), worst "
               f"{worst / rms:.4f} of rms (<= {MAX_OVER_RMS})")
        out[name] = {"rel_rms": round(gap / rms, 5),
                     "max_over_rms": round(worst / rms, 5),
                     "argmax_agree": int((a.argmax(-1)
                                          == b.argmax(-1)).sum())}
    if k2k.shape[-1] > cfg.head_dim:
        # the lane padding the pool layout adds must stay exact zeros
        _check(not bool(jnp.any(k2k[..., cfg.head_dim:] != 0)),
               f"pool pad lanes [{cfg.head_dim}:{k2k.shape[-1]}] stay zero")
    return out


# ---------------------------------------------------------- train phase

def _train(args, devs) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.models.training import TrainStepBundle, default_optimizer
    from ray_tpu.parallel import MeshSpec

    rehearsal, chips = args.rehearse_cpu, args.chips
    if rehearsal:
        base = llama.config("debug", attention_impl="pallas_interpret")
        ladder = [{"batch": 4, "seq": 128}]
    else:
        # bench.py's 889M configuration; one chip names the Pallas
        # kernel, four chips leave "auto" to resolve to it under
        # shard_map
        base = llama.config(
            "tiny", vocab_size=32768, hidden=2048, n_layers=12,
            n_heads=16, n_kv_heads=8, head_dim=128, ffn=8192,
            max_seq=2048, remat_policy="nothing",
            attention_impl="pallas" if chips == 1 else "auto")
        # stated first; the later rungs are tried, in order and out
        # loud, only if the chip refuses the one before for memory
        ladder = [{"batch": 4, "seq": 2048}, {"batch": 2, "seq": 2048},
                  {"batch": 1, "seq": 2048}]
    spec = (MeshSpec(dp=1, fsdp=1, sp=1, tp=1) if chips == 1
            else MeshSpec(fsdp=2, tp=2))
    mesh = spec.build(devs)
    steps = 4
    refused = []
    for rung in ladder:
        batch, seq = rung["batch"], rung["seq"]
        print(f"[train] {base.num_params() / 1e6:.0f}M params, mesh "
              f"{dict(mesh.shape)}, batch {batch} x seq {seq}, remat "
              f"{base.remat_policy}, attention {base.attention_impl}",
              flush=True)
        bundle = TrainStepBundle(
            base, mesh, optimizer=default_optimizer(
                total_steps=1000, mu_dtype=jnp.bfloat16))
        try:
            state = bundle.init_state(0)
            rng = np.random.default_rng(0)
            tokens = bundle.shard_batch(jnp.asarray(
                rng.integers(0, base.vocab_size, (batch, seq)),
                jnp.int32))
            losses, times = [], []
            for _ in range(steps):
                t0 = time.monotonic()
                state, metrics = bundle.step(state, tokens)
                losses.append(float(metrics["loss"]))
                times.append(round(time.monotonic() - t0, 3))
            break
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            why = str(e).splitlines()[0][:300]
            print(f"[train] CONFIG CHANGED: batch {batch} refused for "
                  f"memory: {why}", flush=True)
            refused.append({**rung, "why": why})
            state = bundle = None
    else:
        raise SystemExit("chip_smoke: no stated train configuration fits")
    print(f"[train] losses {losses} step wall {times}", flush=True)
    _check(all(np.isfinite(losses)), f"finite loss every step: {losses}")
    # the repeated batch is seen again each step; lr warms up from 0,
    # so the first steps may sit flat: 0.02 is the flat band
    _check(losses[-1] <= losses[0] + 0.02,
           f"loss decreasing or flat on a repeated batch "
           f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    impl = base.attention_impl
    detail = {
        "params": base.num_params(), "mesh": dict(mesh.shape),
        "batch": batch, "seq": seq, "remat": base.remat_policy,
        "attention_impl": impl, "steps": steps,
        "losses": [round(x, 4) for x in losses],
        "step_wall_s": times,
        "config_refused": refused,
    }
    if chips > 1:
        detail["state_bytes_per_chip"] = _spread_over(
            state, devs, "train state")
    return detail


if __name__ == "__main__":
    main()
