"""Host-side actor collectives + in-mesh XLA collectives.

Modeled on python/ray/util/collective tests; the XLA path runs under
shard_map on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.parallel import MeshSpec
from ray_tpu.util.collective import xla as cx


@ray_tpu.remote
class CollectiveWorker:
    def __init__(self, rank, world):
        self.rank = rank
        self.world = world

    def setup(self, group):
        from ray_tpu.util import collective as col
        col.init_collective_group(self.world, self.rank, group_name=group)
        return True

    def do_allreduce(self, group):
        from ray_tpu.util import collective as col
        return col.allreduce(np.full((4,), float(self.rank + 1)),
                             group_name=group)

    def do_allgather(self, group):
        from ray_tpu.util import collective as col
        return col.allgather(self.rank * 10, group_name=group)

    def do_broadcast(self, group):
        from ray_tpu.util import collective as col
        return col.broadcast(f"from-{self.rank}", src_rank=2,
                             group_name=group)

    def do_sendrecv(self, group):
        from ray_tpu.util import collective as col
        if self.rank == 0:
            col.send({"x": 42}, dst_rank=1, group_name=group)
            return None
        elif self.rank == 1:
            return col.recv(src_rank=0, group_name=group)
        return None


def test_host_collectives(ray_start):
    world = 3
    workers = [CollectiveWorker.remote(r, world) for r in range(world)]
    assert all(ray_tpu.get([w.setup.remote("g1") for w in workers],
                           timeout=120))

    sums = ray_tpu.get([w.do_allreduce.remote("g1") for w in workers],
                       timeout=120)
    for s in sums:
        np.testing.assert_allclose(s, np.full((4,), 6.0))  # 1+2+3

    gathered = ray_tpu.get([w.do_allgather.remote("g1") for w in workers],
                           timeout=120)
    assert all(g == [0, 10, 20] for g in gathered)

    bcast = ray_tpu.get([w.do_broadcast.remote("g1") for w in workers],
                        timeout=120)
    assert bcast == ["from-2"] * world

    out = ray_tpu.get([w.do_sendrecv.remote("g1") for w in workers],
                      timeout=120)
    assert out[1] == {"x": 42}


def test_xla_collectives_in_mesh():
    mesh = MeshSpec(dp=8, fsdp=1, sp=1, tp=1).build()

    def fn(x):
        total = cx.allreduce(x, "dp")
        gathered = cx.allgather(x, "dp", axis=0)
        rank_val = cx.broadcast(x * 0 + cx.rank("dp").astype(x.dtype), "dp",
                                src_rank=3)
        return total, gathered, rank_val

    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("dp"),
        out_specs=(jax.sharding.PartitionSpec("dp"),
                   jax.sharding.PartitionSpec("dp"),
                   jax.sharding.PartitionSpec("dp")),
        check_vma=False)
    x = jnp.arange(8, dtype=jnp.float32)
    total, gathered, rank_val = sharded(x)
    np.testing.assert_allclose(np.asarray(total), np.full((8,), 28.0))
    np.testing.assert_allclose(np.asarray(rank_val), np.full((8,), 3.0))


def test_xla_reducescatter():
    mesh = MeshSpec(dp=4, fsdp=1, sp=1, tp=1).build(jax.devices()[:4])

    def fn(x):
        return cx.reducescatter(x, "dp", axis=0)

    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec("dp"), check_vma=False)
    x = jnp.ones((8, 2), jnp.float32)
    out = sharded(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 2), 4.0))
