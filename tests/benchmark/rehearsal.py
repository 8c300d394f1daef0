"""Debug-size configurations and traffic for CPU rehearsals of the
runners: through the runners' Python entry, never through run.py, so that
run.py itself never reports from a CPU."""

import time

from benchmarks.lib.harness import Context

DEBUG_MODEL = {
    "vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
}

SERVE_CONFIG = {**DEBUG_MODEL, "engine": {
    "max_batch_size": 4, "page_size": 16, "num_pages": 64,
    "max_prefill_tokens": 32}}

TRAIN_CONFIG = {**DEBUG_MODEL, "train": {
    "batch": 2, "seq": 64, "mesh": {"dp": 1, "fsdp": 1, "sp": 1, "tp": 1},
    "mu_dtype": "bfloat16", "schedule_steps": 100,
    "model": {"remat_policy": "nothing", "loss_chunk": 32}}}

CHAT = {
    "runner": "serve", "loop": "open", "rate_rps": 8.0, "cycle": 8,
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 8, "max": 60},
    "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
    "pair_stride": 3, "order_stride": 5, "gap_stride": 3,
    "arrival": {"dist": "exponential"},
    "sampling": {"temperature": 0.7, "top_p": 0.9},
    "ramp_s": 0.5, "grace_s": 20, "trace_s": 1,
}

JOB = {
    "runner": "train", "cycle": 16,
    "doc_tokens": {"dist": "lognormal", "median": 40, "sigma": 1.0,
                   "min": 4, "max": 200},
    "order_stride": 5, "zipf_exponent": 1.1, "trace_s": 1,
}


def context(config, traffic, out_dir, seconds=2.0, seed=3, trace=False):
    return Context(workload="rehearsal", config_name="debug", config=config,
                   traffic=traffic, chips=1, seed=seed, seconds=seconds,
                   trace=trace, out_dir=str(out_dir),
                   t_start=time.monotonic())
