"""Tensor-parallel serving: the engine jitted over a tp>1 mesh.

Gates VERDICT r3 item #2 the same way training is gated: decode over a
virtual tp=2 CPU mesh must match the single-device engine exactly
(greedy argmax is bit-stable under resharding for identical params).
Reference parity note: the reference reaches TP serving only by placing
external vLLM workers via PGs (vllm_models.py:123-159); here TP is
in-program GSPMD + a shard_map'd Pallas kernel.
"""

import jax
import numpy as np
import pytest

from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          SamplingParams)
from ray_tpu.parallel import MeshSpec

PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [100, 101]]


def _generate(**cfg_kwargs):
    import jax.numpy as jnp
    from ray_tpu.models import llama
    # float32 compute: greedy token equality must not hinge on bf16
    # psum reduction order (tp splits the wo/wd contraction dim)
    cfg = llama.config("debug", dtype=jnp.float32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=4, num_pages=64, seed=3,
        **cfg_kwargs))
    reqs = eng.generate([list(p) for p in PROMPTS],
                        SamplingParams(max_tokens=8))
    return [r.output_tokens for r in reqs]


def test_tp2_decode_matches_single_device():
    ref = _generate()
    tp2 = _generate(mesh=MeshSpec(tp=2))
    assert tp2 == ref


def test_tp2_pallas_kernel_matches_gather(cpu_mesh_subprocess):
    """The shard_map-wrapped Pallas decode kernel (interpret mode on
    CPU) over tp=2 must agree with the dense gather path. Runs in a
    fresh interpreter on an emulated 2-device mesh (the ISSUE 17
    fixture) so the equivalence gate exercises backend init with
    exactly the pod topology, not the suite's 8-device default."""
    cpu_mesh_subprocess("""
import jax, jax.numpy as jnp
from ray_tpu.llm._internal.engine import (EngineConfig,
                                          InferenceEngine,
                                          SamplingParams)
from ray_tpu.models import llama
from ray_tpu.parallel import MeshSpec

assert len(jax.devices()) == 2, jax.devices()
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [100, 101]]

def gen(**kw):
    cfg = llama.config("debug", dtype=jnp.float32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=4, num_pages=64, seed=3, **kw))
    reqs = eng.generate([list(p) for p in PROMPTS],
                        SamplingParams(max_tokens=8))
    return [r.output_tokens for r in reqs]

ref = gen(decode_impl="gather")
tp2 = gen(decode_impl="pallas_interpret", mesh=MeshSpec(tp=2))
assert tp2 == ref, (tp2, ref)
""", n_devices=2)


def test_tp2_decode_step_logits_close():
    """Direct logits comparison (not just sampled tokens)."""
    import jax.numpy as jnp
    from ray_tpu.models import llama
    from ray_tpu.models.llama_infer import decode_step, ragged_forward
    from ray_tpu.parallel.sharding import shard_tree
    from jax.sharding import NamedSharding, PartitionSpec

    cfg = llama.config("debug", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = MeshSpec(tp=2).build(jax.devices()[:2])

    B, pages, page = 2, 16, 16
    kv_shape = (cfg.n_layers, pages, page, cfg.n_kv_heads, cfg.head_dim)
    tables = jnp.asarray(
        np.arange(B * 4, dtype=np.int32).reshape(B, 4))
    rng = np.random.default_rng(0)
    # one segment a sequence, 8 and 6 tokens, nothing cached before
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, 14), jnp.int32)
    lens = jnp.asarray([8, 6], jnp.int32)

    def run(params, k_pages, v_pages):
        _, k_pages, v_pages = ragged_forward(
            cfg, params, prompt,
            jnp.asarray([0] * 8 + [1] * 6, jnp.int32),
            jnp.asarray(list(range(8)) + list(range(6)), jnp.int32),
            jnp.ones(14, bool), jnp.zeros(B, jnp.int32),
            jnp.asarray([7, 13], jnp.int32), k_pages, v_pages, tables,
            ctx_pages=0)
        return decode_step(
            cfg, params, jnp.asarray([11, 12], jnp.int32), lens,
            k_pages, v_pages, tables,
            jnp.asarray([True, True]), impl="gather")

    ref_logits, _, _ = jax.jit(run)(
        params, jnp.zeros(kv_shape, cfg.dtype),
        jnp.zeros(kv_shape, cfg.dtype))

    sp = shard_tree(params, llama.param_logical_axes(cfg), mesh)
    kv_sh = NamedSharding(mesh, PartitionSpec(None, None, None, "tp", None))
    tp_logits, _, _ = jax.jit(run)(
        sp, jax.device_put(jnp.zeros(kv_shape, cfg.dtype), kv_sh),
        jax.device_put(jnp.zeros(kv_shape, cfg.dtype), kv_sh))

    np.testing.assert_allclose(np.asarray(tp_logits),
                               np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_tp_mesh_validation():
    with pytest.raises(ValueError, match="not divisible"):
        InferenceEngine(EngineConfig(
            model="debug", mesh=MeshSpec(tp=3)))
