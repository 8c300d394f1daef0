"""Training cells: a `TrainStepBundle` in this process, fed a new packed
batch every step.

The next batch is made on the host while the device runs the current
step, and a step's loss is read one step late, so the host never stalls
the device. `train_tok_s` is every token of every optimizer step of the
window over the window, which ends when the last step's state is ready.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import List

from ..lib import checks, loadgen, peaks, program, reference, trace_reduce
from ..lib.harness import (Context, RunResult, say, trace_options,
                           trace_span)

# The bundle's loss (bf16 operands, f32 accumulation, chunked head)
# against the float32 reference on the same 1,024 tokens: the per-token
# error of a unit-scale logit is ~0.03 and of either sign, so the mean
# over 1,023 tokens lands within ~0.003 (measured: see PERF.md); 0.01 is
# three times that. With untrained weights the loss hardly depends on
# the model, so this binds only the target shift and the chunked head;
# the forward math is bound by `checks.train_logits` on the same tokens.
LOSS_TOLERANCE = 0.01
REFERENCE_TOKENS = 1024


def run(ctx: Context) -> RunResult:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.training import TrainStepBundle, default_optimizer
    from ray_tpu.parallel import MeshSpec

    job, tc, window_s = ctx.traffic, ctx.config["train"], float(ctx.seconds)
    cfg = program.llama_config(ctx.config, **tc["model"])
    batch, seq = tc["batch"], tc["seq"]
    devs = jax.devices()[:ctx.chips]
    mesh = MeshSpec(**tc["mesh"]).build(devs)
    say(f"[train] {cfg.num_params() / 1e6:.0f}M params, mesh "
        f"{dict(mesh.shape)}, batch {batch} x {seq}, remat "
        f"{cfg.remat_policy}, attention {cfg.attention_impl}")
    mu = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[tc["mu_dtype"]]
    bundle = TrainStepBundle(cfg, mesh, optimizer=default_optimizer(
        total_steps=tc["schedule_steps"], mu_dtype=mu))
    t0 = time.monotonic()
    state = bundle.init_state(ctx.program_seed)
    jax.block_until_ready(state)
    ctx.phase("state up", t0)
    batches = loadgen.packed_batches(job, cfg.vocab_size, batch, seq,
                                     ctx.seed)
    put = lambda b: bundle.shard_batch(jnp.array(b))
    first = next(batches)

    # correctness 1: the bundle's loss against the plain reference
    t0 = time.monotonic()
    n_ref = min(REFERENCE_TOKENS, seq)
    one = jnp.array(first[:1, :n_ref])
    got = float(bundle.eval_loss(state, one)["loss"])
    want = float(jax.jit(lambda p, t: reference.loss(ctx.config, p, t))(
        state[0], one[0]))
    loss_ok = math.isfinite(got) and abs(got - want) <= LOSS_TOLERANCE
    say(f"  {'ok' if loss_ok else 'FAILED'}: first-batch loss {got:.5f} "
        f"against the float32 reference {want:.5f} on {n_ref} tokens "
        f"(gap {abs(got - want):.5f} <= {LOSS_TOLERANCE})")
    # correctness 2: the forward the step differentiates, logits against
    # the reference's on that sequence
    logits_gap = checks.train_logits(cfg, mesh, ctx.config, state[0],
                                     one[0], say)
    ctx.phase("loss and logits against the reference", t0)

    losses: List[float] = []
    pending = None                   # the previous step's metrics

    def step(tokens):
        nonlocal state, pending
        state, metrics = bundle.step(state, tokens)
        if pending is not None:
            losses.append(float(pending["loss"]))     # one step late
        pending = metrics

    # warm-up: compile (or fetch) the step, run it twice
    t0 = time.monotonic()
    tokens = put(first)
    for _ in range(2):
        nxt = put(next(batches))
        step(tokens)
        tokens = nxt
    jax.block_until_ready(state)
    ctx.phase("two warm-up steps", t0)
    setup_s = ctx.setup_s(time.monotonic())

    log_dir = os.path.join(ctx.out_dir, "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    trace_at = trace_span(ctx)
    tracing = False
    steps = 0
    t_win = time.monotonic()
    while True:
        now = time.monotonic() - t_win
        if now >= window_s:
            break
        if trace_at and not tracing and now >= trace_at[0]:
            jax.profiler.start_trace(log_dir,
                                     profiler_options=trace_options())
            tracing = True
        elif tracing and now >= trace_at[1]:
            jax.profiler.stop_trace()
            tracing, trace_at = False, None
        nxt = put(next(batches))     # made while the device is busy
        step(tokens)
        tokens = nxt
        steps += 1
    jax.block_until_ready(state)
    elapsed = time.monotonic() - t_win
    if tracing:
        jax.profiler.stop_trace()
    losses.append(float(pending["loss"]))
    tok_s = steps * batch * seq / elapsed
    # run.py has refused a device the peaks table does not name; a test's
    # CPU rehearsal of this runner gets no MFU
    peak = peaks.PEAKS.get(devs[0].device_kind, {}).get("bf16_flops")
    mfu = (None if peak is None else tok_s / ctx.chips
           * peaks.train_flops_per_token(ctx.config, seq) / peak)
    ln_v = math.log(cfg.vocab_size)
    head, tail = losses[:10], losses[-10:]
    verdicts = {
        "loss_vs_reference": loss_ok,
        "logits_vs_reference": logits_gap["ok"],
        "finite": all(math.isfinite(x) for x in losses),
        # untrained unit-variance logits give ln(V) + 1/2
        "first_loss_near_ln_vocab": abs(losses[0] - ln_v) <= 1.0,
        "loss_falls": (len(losses) < 20
                       or sum(tail) / len(tail) < sum(head) / len(head)),
    }
    say(f"[train] {steps} steps in {elapsed:.3f}s: {tok_s:.1f} tokens/s, "
        f"{elapsed / max(steps, 1) * 1e3:.1f} ms/step, MFU {mfu} by "
        f"{peaks.train_flops_per_token(ctx.config, seq) / 1e9:.3f} "
        f"GFLOP/token; loss {losses[0]:.4f} -> {losses[-1]:.4f} "
        f"(ln V = {ln_v:.4f}); checks {verdicts}")
    events = trace_reduce.extract(log_dir) if ctx.trace else []
    run_data = {"events": events, "window_s": elapsed, "steps": steps,
                "losses": losses, "config": ctx.config, "traffic": job,
                "device_kind": devs[0].device_kind, "chips": ctx.chips}
    return RunResult(
        correct=all(verdicts.values()) and steps > 0,
        attempted=steps, failed=0,
        end_to_end={"setup_s": setup_s, "train_tok_s": tok_s},
        run=run_data,
        detail={"checks": verdicts, "mfu": mfu, "loss_first": losses[0],
                "loss_last": losses[-1], "loss_reference_gap":
                abs(got - want), "logits": logits_gap,
                "step_ms": elapsed / max(steps, 1) * 1e3})
