"""Llama train-step MFU and a serving probe on the local accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device",
"detail"}. Baseline: the north-star target from BASELINE.json —
Ray-Train-equivalent Llama training at 40% MFU
(vs_baseline = achieved_mfu / 0.40).

One process owns the chip and fails fast: no accelerator, an unknown
`device_kind`, or an exception in either section is a traceback and a
non-zero exit, never a substitute number. (`chip_smoke.py` is the
quickest proof the system starts on the chip; this file is the single
Llama-shaped cell ROADMAP A1 replaces with a workloads table.)
"""

from __future__ import annotations

import json
import os
import sys
import time


def _mesh_arg() -> str:
    """`--mesh DxT` (e.g. `--mesh 1x2`): run the serving probe on a
    tp-sharded engine (ISSUE 17); empty = single-chip engine."""
    if "--mesh" in sys.argv:
        i = sys.argv.index("--mesh")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--mesh needs a value, e.g. --mesh 1x2")
        return sys.argv[i + 1]
    return os.environ.get("RAY_TPU_BENCH_MESH", "")


def main() -> None:
    from ray_tpu.util.compile_cache import ensure_compile_cache
    ensure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm._internal.perfmodel import detect_envelope
    from ray_tpu.models import llama
    from ray_tpu.models.training import TrainStepBundle, default_optimizer
    from ray_tpu.parallel import MeshSpec

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit(
            "bench.py measures an accelerator and jax found only the "
            "CPU; nothing is reported from a CPU run")
    peak = detect_envelope(dev).peak_flops      # unknown kind raises

    # ~889M params: large enough to fill the chip's MXU (head_dim 128,
    # 2048-wide matmuls) while params + adam state fit a 16 GiB HBM.
    # RAY_TPU_BENCH_BATCH / _SEQ / _REMAT sweep without editing this file.
    batch = int(os.environ.get("RAY_TPU_BENCH_BATCH", "4"))
    seq = int(os.environ.get("RAY_TPU_BENCH_SEQ", "2048"))
    remat = os.environ.get("RAY_TPU_BENCH_REMAT", "nothing")
    cfg = llama.config(
        "tiny", vocab_size=32768, hidden=2048, n_layers=12, n_heads=16,
        n_kv_heads=8, head_dim=128, ffn=8192,
        max_seq=max(seq, 2048),
        attention_impl="pallas", remat_policy=remat)
    iters = 10

    mesh = MeshSpec(dp=1, fsdp=1, sp=1, tp=1).build([dev])
    bundle = TrainStepBundle(
        cfg, mesh, optimizer=default_optimizer(total_steps=1000,
                                               mu_dtype=jnp.bfloat16))
    state = bundle.init_state(0)
    rng = np.random.default_rng(0)
    tokens = bundle.shard_batch(jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32))

    # warm up (compile), then steady-state timing ended by
    # block_until_ready on the last step's outputs
    for _ in range(2):
        state, metrics = bundle.step(state, tokens)
    jax.block_until_ready((state, metrics))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = bundle.step(state, tokens)
    jax.block_until_ready((state, metrics))
    dt = (time.perf_counter() - t0) / iters
    final_loss = float(metrics["loss"])

    tokens_per_sec = batch * seq / dt
    mfu = llama.flops_per_token(cfg, seq) * tokens_per_sec / peak
    del state, bundle                    # free HBM for the engine
    serving = _serving_probe(_mesh_arg())

    print(json.dumps({
        "metric": "llama_train_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.40, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "detail": {
            "params": cfg.num_params(),
            "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
            "step_time_s": round(dt, 4),
            "batch": batch, "seq": seq, "remat": remat,
            "loss": round(final_loss, 4),
            "serving": serving,
        },
    }))


def _serving_probe(mesh_text: str) -> dict:
    """Steady-state continuous-batching decode through the paged-KV
    engine, reported as the engine's ANALYTIC serving MFU/MBU per chip
    (perfmodel closed forms over host tick wall, ROADMAP A9 — not a
    trace-derived utilization)."""
    import numpy as np

    from ray_tpu.llm._internal.engine import (EngineConfig,
                                              InferenceEngine, Request,
                                              SamplingParams)
    from ray_tpu.models import llama as llama_models

    cfg = llama_models.config(
        "tiny", vocab_size=32000, hidden=2048, n_layers=12,
        n_heads=16, n_kv_heads=8, head_dim=128, ffn=8192,
        max_seq=2048)
    batch, prompt_len, gen = 8, 128, 128
    # --mesh (ISSUE 17): shard the whole engine tp-wise across a named
    # mesh; the perf accountant divides the analytic envelope by the
    # mesh size, so mfu below stays PER CHIP
    ekw = {}
    if mesh_text:
        from ray_tpu.ops.tp_mesh import parse_mesh_shape
        shape = parse_mesh_shape(mesh_text)
        if shape[0] * shape[1] > 1:
            ekw["mesh_shape"] = shape
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=batch,
        num_pages=max(256, batch * 32), page_size=16, **ekw))
    rng = np.random.default_rng(0)
    reqs = [Request(f"s{i}",
                    rng.integers(1, cfg.vocab_size, prompt_len).tolist(),
                    SamplingParams(max_tokens=gen))
            for i in range(batch)]
    for r in reqs:
        eng.add_request(r)
    # warm until the whole batch decodes, then window pure decode
    while any(not r.output_tokens for r in reqs):
        eng.step()
    steps = 0
    while steps < gen - 8 and eng.has_work():
        eng.step()
        steps += 1
    perf = eng.stats()["perf"]
    return {
        # per-chip: the accountant's envelope is peak x n_chips
        "mfu": perf["mfu"],
        "mbu": perf["mbu"],
        "roof": perf["roof"],
        "envelope": perf["envelope"],
        "n_chips": perf["n_chips"],
        "mesh": mesh_text or None,
        "decode_impl": eng._resolve_impl(),
        "decode_tokens_per_s": perf["decode_tokens_per_s"],
        "decode_tokens_per_s_per_chip": round(
            perf["decode_tokens_per_s"] / max(perf["n_chips"], 1), 3),
        "params": cfg.num_params(),
        "batch": batch,
        "vs_target_0.40": round(perf["mfu"] / 0.40, 4),
    }


if __name__ == "__main__":
    main()
