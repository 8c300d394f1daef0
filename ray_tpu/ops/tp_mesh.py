"""Named-mesh construction + collective routing for explicit-tp serving.

ISSUE 17 / ROADMAP item 4: a serving replica is a tp-sharded engine on
a pod-slice mesh, not a single chip. This module owns the two pieces
the engine and the fleet both need:

- build_serving_mesh: turn ``EngineConfig.mesh_shape`` into a named 2D
  ``jax.sharding.Mesh`` — (data, tp) with the data axis pinned to 1
  (replication across slices is the FLEET's job; in-engine dp would
  double-count KV pages and break the slot accounting).
- logits_psum_fn: the reduction applied to the row-parallel lm_head's
  partial logits inside the engine's shard_map — plain ``lax.psum`` by
  default, or the EQuARX-style block-scaled quantized all-reduce
  (ops/quantized_collectives) when ``EngineConfig.quantized_collectives``
  is armed. Only the (B, V) logits reduction is routed through the
  quantized path: per-layer residual psums are small (B, H) and stay
  exact so KV pool contents never see quantization error twice.

Distinct from parallel/mesh.py (MeshSpec — the GSPMD auto-partitioning
path): here sharding is explicit shard_map with hand-placed
collectives, built via jax.shard_map.

Tier-1 testability: `XLA_FLAGS=--xla_force_host_platform_device_count=N`
(`_private/cpu_mesh.py`) gives a virtual multi-chip CPU backend, so
tp=2 meshes run REAL psums in CI.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from .quantized_collectives import quantized_psum

# Leading (size-1) mesh axis name: reserved for cross-slice data
# parallelism, which the fleet implements as whole replicas.
DATA_AXIS = "data"


def parse_mesh_shape(text: str) -> Tuple[int, int]:
    """"1x2" / "1,2" / "2" -> (1, 2) — the bench/CLI surface for
    ``EngineConfig.mesh_shape``. A bare integer means (1, tp)."""
    s = text.strip().lower().replace(",", "x")
    parts = [p for p in s.split("x") if p]
    if len(parts) == 1:
        return (1, int(parts[0]))
    if len(parts) != 2:
        raise ValueError(f"mesh shape {text!r}: want DATAxTP, e.g. 1x2")
    return (int(parts[0]), int(parts[1]))


def build_serving_mesh(mesh_shape: Sequence[int], tp_axis: str = "tp",
                       devices: Optional[Sequence] = None) -> Mesh:
    """Build the named (DATA_AXIS, tp_axis) mesh for one engine replica.

    mesh_shape: (data, tp) — data must be 1 (see module docstring).
    devices: override the device list (tests); defaults to
    jax.devices(), taking the first data*tp entries.
    """
    shape = tuple(int(s) for s in mesh_shape)
    if len(shape) != 2:
        raise ValueError(
            f"mesh_shape must be 2D (data, tp), got {mesh_shape!r}")
    data, tp = shape
    if data != 1:
        raise ValueError(
            f"mesh_shape data axis must be 1 (got {data}): in-engine "
            "data parallelism is not supported — scale replicas via "
            "the fleet instead")
    if tp < 1:
        raise ValueError(f"mesh_shape tp axis must be >= 1, got {tp}")
    if not tp_axis or tp_axis == DATA_AXIS:
        raise ValueError(f"tp_axis must be a non-empty name other than "
                         f"{DATA_AXIS!r}, got {tp_axis!r}")
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < data * tp:
        raise ValueError(
            f"mesh_shape {shape} needs {data * tp} devices, backend "
            f"has {len(devs)} (tests: force a virtual CPU mesh via "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    grid = np.asarray(devs[: data * tp], dtype=object).reshape(data, tp)
    return Mesh(grid, (DATA_AXIS, tp_axis))


def mesh_chips(mesh: Optional[Mesh]) -> int:
    """Chips one replica occupies — the fleet's slice-accounting unit."""
    return int(mesh.devices.size) if mesh is not None else 1


def logits_psum_fn(kind: str = "f32"
                   ) -> Callable[[jax.Array, str], jax.Array]:
    """Reduction for the row-parallel lm_head partial logits.

    kind="f32" is exact lax.psum; "int8"/"fp8" route through
    quantized_psum (block-scaled wire format, ~4x less ICI traffic for
    the (B, V) tensor at int8 — the EQuARX trade documented in
    BENCH_CORE.md "Pod-scale serving anatomy")."""
    if kind == "f32":
        return lambda x, axis_name: jax.lax.psum(x, axis_name)

    def _q(x: jax.Array, axis_name: str) -> jax.Array:
        return quantized_psum(x, axis_name, kind=kind)

    return _q


def kv_pool_spec(tp_axis: str = "tp") -> PartitionSpec:
    """[L, pages, page, KVH, D] pools shard over the kv-head axis."""
    return PartitionSpec(None, None, None, tp_axis, None)


def kv_scale_spec(tp_axis: str = "tp") -> PartitionSpec:
    """[L, pages, page, KVH] quantized-pool row scales follow the heads."""
    return PartitionSpec(None, None, None, tp_axis)


__all__ = ["DATA_AXIS", "build_serving_mesh", "kv_pool_spec",
           "kv_scale_spec", "logits_psum_fn", "mesh_chips",
           "parse_mesh_shape"]
