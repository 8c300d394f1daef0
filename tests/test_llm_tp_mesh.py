"""Pod-scale data plane (ISSUE 17): the whole serving engine
shard_map'd over a named (data, tp) mesh.

Unlike test_llm_tp.py's GSPMD path (mesh=MeshSpec, compiler-inferred
sharding), EngineConfig.mesh_shape builds an EXPLICIT Megatron
program: KV pools and weights sharded over heads along `tp`, page
tables and sampling state replicated, logits reduced with lax.psum
(or quantized_psum). The gates here are the acceptance criteria:
token-exactness against the single-chip oracle (greedy AND sampled,
including a preempt/restore cycle), the one-dispatch-per-tick
discipline at tp=2, KV movement across topologies, and per-chip perf
accounting. Everything runs on the conftest's emulated 8-device CPU
mesh (`xla_force_host_platform_device_count`).
"""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm._internal.engine import (EngineConfig, InferenceEngine,
                                          Request, SamplingParams)
from ray_tpu.models import llama
from ray_tpu.ops import tp_mesh
from ray_tpu.parallel import MeshSpec

PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [100, 101]]

# shared engine shape for the KV-movement gates: small pages so a
# 12-token prompt spans several, forcing real gather/scatter traffic
_COMMON = dict(max_batch_size=3, page_size=8, num_pages=64,
               max_prefill_tokens=16,
               seed=9)


def _mk(**kw):
    cfg = llama.config("debug", dtype=jnp.float32)
    return InferenceEngine(EngineConfig(model=cfg, **_COMMON, **kw))


def _drain(eng):
    while eng.has_work():
        eng.step()


def _gen(sp, **kw):
    cfg = llama.config("debug", dtype=jnp.float32)
    eng = InferenceEngine(EngineConfig(
        model=cfg, max_batch_size=4, num_pages=64, seed=3, **kw))
    reqs = eng.generate([list(p) for p in PROMPTS], sp)
    return [r.output_tokens for r in reqs], eng


# -- mesh construction ---------------------------------------------------

def test_parse_mesh_shape():
    assert tp_mesh.parse_mesh_shape("1x2") == (1, 2)
    assert tp_mesh.parse_mesh_shape("1,4") == (1, 4)
    assert tp_mesh.parse_mesh_shape("2") == (1, 2)
    with pytest.raises(ValueError):
        tp_mesh.parse_mesh_shape("banana")


def test_build_serving_mesh():
    mesh = tp_mesh.build_serving_mesh((1, 2))
    assert mesh.axis_names == (tp_mesh.DATA_AXIS, "tp")
    assert tp_mesh.mesh_chips(mesh) == 2


def test_build_serving_mesh_rejects_data_parallel():
    with pytest.raises(ValueError, match="data parallelism"):
        tp_mesh.build_serving_mesh((2, 1))


def test_build_serving_mesh_rejects_axis_collision():
    with pytest.raises(ValueError):
        tp_mesh.build_serving_mesh((1, 2), tp_axis=tp_mesh.DATA_AXIS)


def test_build_serving_mesh_rejects_too_many_devices():
    with pytest.raises(ValueError):
        tp_mesh.build_serving_mesh((1, 1024))


# -- engine config validation --------------------------------------------

def test_mesh_shape_and_mesh_mutually_exclusive():
    with pytest.raises(ValueError, match="mutually exclusive"):
        _mk(mesh_shape=(1, 2), mesh=MeshSpec(tp=2))


def test_mesh_shape_rejects_moe():
    cfg = llama.config("debug_moe", dtype=jnp.float32)
    with pytest.raises(ValueError, match="MoE"):
        InferenceEngine(EngineConfig(model=cfg, **_COMMON,
                                     mesh_shape=(1, 2)))


def test_mesh_shape_rejects_nondivisible_heads():
    # debug has n_kv_heads=2: tp=4 can't split them
    with pytest.raises(ValueError, match="not divisible"):
        _mk(mesh_shape=(1, 4))


def test_mesh_shape_rejects_lora():
    eng = _mk(mesh_shape=(1, 2))
    with pytest.raises(NotImplementedError, match="LoRA"):
        eng.register_loras({})


def test_mesh_shape_one_chip_is_plain_engine():
    eng = _mk(mesh_shape=(1, 1))
    assert eng.n_chips == 1
    reqs = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=4))
    assert len(reqs[0].output_tokens) == 4


# -- token-exactness vs the single-chip oracle ---------------------------

@pytest.mark.parametrize("sp", [
    SamplingParams(max_tokens=8),
    SamplingParams(max_tokens=8, temperature=0.9, top_p=0.95,
                   seed=11),
], ids=["greedy", "sampled"])
def test_tp2_token_exact_vs_single_chip(sp):
    """The sharded tick is the SAME program as the single-chip tick:
    f32 compute makes the psum reduction order immaterial, so tokens
    must match bit-for-bit — greedy and seeded-sampled alike."""
    ref, e1 = _gen(sp)
    tp2, e2 = _gen(sp, mesh_shape=(1, 2))
    assert (e1.n_chips, e2.n_chips) == (1, 2)
    assert tp2 == ref
    assert e2.stats()["chips"] == 2


def test_tp2_perf_accounting_is_per_chip():
    """stats()['perf'] divides the analytic envelope by the mesh
    size: the accountant's peak is peak_flops x n_chips, so the
    reported mfu/mbu are per chip against the 0.40 target."""
    _, e1 = _gen(SamplingParams(max_tokens=8))
    _, e2 = _gen(SamplingParams(max_tokens=8), mesh_shape=(1, 2))
    p1, p2 = e1.stats()["perf"], e2.stats()["perf"]
    assert (p1["n_chips"], p2["n_chips"]) == (1, 2)
    assert p2["peak_flops"] == pytest.approx(2 * p1["peak_flops"])
    assert 0.0 <= p2["mfu"] <= 1.0


def test_tp2_quantized_collectives_generates():
    """quantized_collectives=True routes the logits psum through
    ops.quantized_collectives.quantized_psum — tokens may differ
    from the exact-f32 reduction, but the engine must run clean."""
    eng = _mk(mesh_shape=(1, 2), quantized_collectives=True,
              async_readback=True)
    reqs = eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_tokens=8))
    assert len(reqs[0].output_tokens) == 8


# -- dispatch discipline at tp>1 -----------------------------------------

@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_tp2_steady_state_one_dispatch_per_tick(kv):
    """32 ticks = 32 dispatches, 0 host transfers, 0 compiles: the
    shard_map'd collective-bearing tick keeps the single-dispatch
    discipline (donation + async readback) the single-chip engine
    has, for raw and quantized KV alike."""
    eng = _mk(mesh_shape=(1, 2), kv_dtype=kv, async_readback=True)
    for i in range(3):
        eng.add_request(Request(request_id=f"r{i}",
                                prompt_tokens=list(range(1, 13)),
                                params=SamplingParams(max_tokens=64)))
    for _ in range(6):          # warm: prefill + first decode ticks
        eng.step()
    d0, c0 = eng.dispatches, eng.compiles
    with jax.transfer_guard("disallow"):
        for _ in range(32):
            eng.step()
    assert eng.dispatches - d0 == 32
    assert eng.compiles - c0 == 0


# -- KV movement across topologies ---------------------------------------

def test_tp2_spill_restore_token_exact():
    """A preempt/restore (host spill) cycle mid-stream on the tp=2
    engine must not perturb a sampled stream: token-exact vs a
    never-preempted single-chip oracle."""
    e0 = _mk()
    r0 = Request("a", list(range(1, 13)),
                 SamplingParams(max_tokens=20, temperature=0.8,
                                seed=7))
    e0.add_request(r0)
    _drain(e0)

    e1 = _mk(mesh_shape=(1, 2), enable_kv_offload=True)
    r1 = Request("a", list(range(1, 13)),
                 SamplingParams(max_tokens=20, temperature=0.8,
                                seed=7))
    e1.add_request(r1)
    for _ in range(6):
        e1.step()
    assert e1.preempt("a", reason="test")
    _drain(e1)
    assert r1.output_tokens == r0.output_tokens


def test_tp2_export_imports_into_tp1_token_exact():
    """Session wire format is topology-free: export gathers the full
    global KV (int8 pages + scales), so a tp=2 export resumes on a
    tp=1 engine with identical continuation tokens."""
    e2 = _mk(mesh_shape=(1, 2), kv_dtype="int8",
             enable_kv_offload=True)
    r2 = Request("m", list(range(1, 13)),
                 SamplingParams(max_tokens=20))
    e2.add_request(r2)
    for _ in range(6):
        e2.step()
    assert e2.preempt("m", reason="ship")
    state = e2.export_session("m")
    # full global shape, not a shard: (layers, pages, page, kv_heads, hd)
    assert state["k"].shape[3] == llama.config("debug").n_kv_heads

    e3 = _mk(kv_dtype="int8", enable_kv_offload=True)
    imported = e3.import_session(state)
    _drain(e3)

    e4 = _mk(kv_dtype="int8")
    r4 = Request("m", list(range(1, 13)),
                 SamplingParams(max_tokens=20))
    e4.add_request(r4)
    _drain(e4)
    assert imported.output_tokens == r4.output_tokens


def test_tp2_export_kind_mismatch_degrades_to_replay():
    """An int8 tp=2 export offered to an f32 engine must raise
    ValueError (the fleet's replay-fallback signal), never crash or
    silently reinterpret the payload."""
    e2 = _mk(mesh_shape=(1, 2), kv_dtype="int8",
             enable_kv_offload=True)
    e2.add_request(Request("m", list(range(1, 13)),
                           SamplingParams(max_tokens=20)))
    for _ in range(6):
        e2.step()
    assert e2.preempt("m", reason="ship")
    state = e2.export_session("m")
    e5 = _mk(enable_kv_offload=True)      # f32 KV
    with pytest.raises(ValueError):
        e5.import_session(dict(state))
