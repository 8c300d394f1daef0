"""Logical-axis sharding rules: how tensors map onto the mesh.

This is the GSPMD-native equivalent of the reference's per-strategy code
paths (DDP wraps, FSDP wraps, vLLM TP placement — SURVEY.md §2.4): one rule
table assigns each *logical* tensor axis to mesh axes, and pjit/XLA derive
every collective from it. Changing parallelism = changing this table, not
the model.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import (AXIS_DP, AXIS_FSDP, AXIS_SP, AXIS_TP, AXIS_EP, AXIS_PP,
                   BATCH_AXES)

# Logical axis -> mesh axis (or tuple of mesh axes, or None = replicate).
# The default table implements DP+FSDP+TP+SP for transformer LMs:
#   - params: embed dim sharded over fsdp (ZeRO-3 style), heads/ffn over tp
#   - activations: batch over (dp, fsdp), sequence over sp
DEFAULT_RULES: Dict[str, Union[None, str, Tuple[str, ...]]] = {
    "batch": BATCH_AXES,
    "seq": AXIS_SP,
    "embed": AXIS_FSDP,
    "heads": AXIS_TP,
    "kv_heads": AXIS_TP,
    "head_dim": None,
    "mlp": AXIS_TP,
    "vocab": AXIS_TP,
    # Stacked layer dim sharded over pp: contiguous L/pp blocks land on
    # their pipeline stage, so stage params (and optimizer state) never
    # replicate across stages (models/pipeline.py).
    "layers": AXIS_PP,
    "experts": AXIS_EP,
    "act_embed": None,       # activation feature dim stays unsharded
}


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Dict] = None) -> PartitionSpec:
    rules = {**DEFAULT_RULES, **(rules or {})}
    parts = []
    used = set()
    for name in logical_axes:
        axis = rules.get(name) if name is not None else None
        # A mesh axis may appear only once in a PartitionSpec.
        if axis is not None:
            flat = (axis,) if isinstance(axis, str) else tuple(axis)
            if any(a in used for a in flat):
                axis = None
            else:
                used.update(flat)
        parts.append(axis)
    return PartitionSpec(*parts)


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   rules: Optional[Dict] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules))


def with_logical_constraint(x, *logical_axes: Optional[str],
                            rules: Optional[Dict] = None):
    """Annotate an intermediate value inside jit with its logical
    sharding against the ambient mesh (`jax.set_mesh` /
    `jax.sharding.use_abstract_mesh`). Outside any mesh context it is
    the identity (single device); inside one, a constraint the
    partitioner refuses raises."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(logical_axes, rules)))


def tree_shardings(tree_of_logical_axes: Any, mesh: Mesh,
                   rules: Optional[Dict] = None):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, spec_for(axes, rules)),
        tree_of_logical_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def shard_tree(tree: Any, axes_tree: Any, mesh: Mesh,
               rules: Optional[Dict] = None):
    """Device_put a pytree according to its logical axes."""
    shardings = tree_shardings(axes_tree, mesh, rules)
    return jax.tree.map(jax.device_put, tree, shardings)
