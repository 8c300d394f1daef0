"""Sharded train-step factory: params + optax state on the mesh, one jit.

Reference parity: this replaces the reference's torch DDP/FSDP wrap +
NCCL allreduce (train/torch/train_loop_utils.py:163, torch/config.py:66)
with a single pjit program — gradients are reduced by XLA collectives the
sharding implies (psum over dp, reduce-scatter over fsdp), and optimizer
state is sharded like its parameters (ZeRO by construction).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import llama
from ..parallel.mesh import BATCH_AXES, AXIS_SP, AXIS_PP, mesh_shape
from ..parallel.sharding import spec_for, tree_shardings
from ..util.compile_cache import ensure_compile_cache


def _path_key(entry) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def opt_state_shardings(opt_state_shapes, param_shardings, mesh: Mesh):
    """Shard optimizer-state leaves like the parameters they mirror.

    optax states (adam mu/nu etc.) embed subtrees with the params' structure;
    we match each state leaf to a param by path suffix, falling back to
    replication for scalars/counters.
    """
    param_by_path = {}
    for path, sh in jax.tree_util.tree_flatten_with_path(param_shardings)[0]:
        key = tuple(_path_key(p) for p in path)
        param_by_path[key] = sh
    replicated = NamedSharding(mesh, PartitionSpec())

    def assign(path, leaf):
        key = tuple(_path_key(p) for p in path)
        for start in range(len(key)):
            sh = param_by_path.get(key[start:])
            if sh is not None:
                return sh
        return replicated

    return jax.tree_util.tree_map_with_path(assign, opt_state_shapes)


def default_optimizer(learning_rate=3e-4, weight_decay=0.1,
                      warmup_steps=100, total_steps=10000,
                      b1=0.9, b2=0.95, grad_clip=1.0,
                      mu_dtype=None) -> optax.GradientTransformation:
    """mu_dtype=jnp.bfloat16 halves first-moment memory (the second moment
    stays f32); the standard trade on HBM-bound single-chip runs."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


class TrainStepBundle:
    """Everything needed to run sharded training of a Llama config."""

    def __init__(self, cfg: llama.LlamaConfig, mesh: Mesh,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 rules: Optional[Dict] = None,
                 donate_state: bool = True):
        ensure_compile_cache()
        self.cfg = cfg
        self.mesh = mesh
        self.optimizer = optimizer or default_optimizer()
        axes = llama.param_logical_axes(cfg)
        self.param_shardings = tree_shardings(axes, mesh, rules)
        self.batch_sharding = NamedSharding(
            mesh, PartitionSpec(BATCH_AXES, AXIS_SP))

        params_shape = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
        opt_shape = jax.eval_shape(self.optimizer.init, params_shape)
        self.opt_shardings = opt_state_shardings(
            opt_shape, self.param_shardings, mesh)
        self.state_shardings = (self.param_shardings, self.opt_shardings)

        self._init = jax.jit(
            self._init_impl, out_shardings=self.state_shardings)
        self._step = jax.jit(
            self._step_impl,
            in_shardings=(self.state_shardings, self.batch_sharding),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,) if donate_state else ())
        self._eval = jax.jit(
            lambda p, t: llama.loss_fn(self.cfg, p, t, self.mesh)[1])
        self.steps = 0                # step() calls, for the step span

    def _init_impl(self, key):
        params = llama.init_params(self.cfg, key)
        return params, self.optimizer.init(params)

    def _step_impl(self, state, tokens):
        params, opt_state = state
        if (self.cfg.pp_schedule == "1f1b"
                and mesh_shape(self.mesh).get(AXIS_PP, 1) > 1):
            from . import pipeline_1f1b
            loss, metrics, grads = pipeline_1f1b.loss_and_grads(
                self.cfg, params, tokens, self.mesh)
        else:
            grad_fn = jax.value_and_grad(
                lambda p: llama.loss_fn(self.cfg, p, tokens, self.mesh),
                has_aux=True)
            (loss, metrics), grads = grad_fn(params)
        with jax.named_scope("optimizer"):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
        return (params, opt_state), metrics

    # public API -----------------------------------------------------------
    #
    # Each call runs with this bundle's mesh as the ambient ABSTRACT
    # mesh, so the model's logical-axis sharding constraints
    # (with_logical_constraint) resolve against it at trace time —
    # without the context they no-op, which both loses the intended
    # activation shardings and (for MoE-inside-pipeline programs) trips
    # an XLA SPMD partitioner check-fail ("Invalid binary instruction
    # opcode copy"). Not jax.set_mesh: that also installs the CONCRETE
    # mesh, which shard_map lowering then prefers over the abstract one
    # and which carries no Manual axis types — a Mosaic kernel nested in
    # the pipeline's pp shard_map is then refused as "cannot be
    # automatically partitioned" although every axis is manual.

    def _mesh_ctx(self):
        return jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh)

    def init_state(self, seed: int = 0):
        with self._mesh_ctx():
            return self._init(jax.random.PRNGKey(seed))

    def init_state_from_checkpoint(self, ckpt_dir: str):
        """Init train state from an HF-layout safetensors checkpoint:
        params stream in pre-sharded (checkpoint_io windowed per-shard
        reads onto this bundle's mesh), optimizer state inits jitted
        under the same shardings."""
        from . import checkpoint_io
        params = checkpoint_io.load_llama_params(
            self.cfg, ckpt_dir, mesh=self.mesh)
        with self._mesh_ctx():
            opt_state = jax.jit(
                self.optimizer.init,
                out_shardings=self.opt_shardings)(params)
        return params, opt_state

    def step(self, state, tokens):
        # the host's span of the step on the profiler's clock, numbered
        # so that a trace viewer groups the device's events by step
        self.steps += 1
        with jax.profiler.StepTraceAnnotation("train.step",
                                              step_num=self.steps), \
                self._mesh_ctx():
            return self._step(state, tokens)

    def eval_loss(self, state, tokens):
        with self._mesh_ctx():
            return self._eval(state[0], tokens)

    def shard_batch(self, tokens):
        return jax.device_put(tokens, self.batch_sharding)
