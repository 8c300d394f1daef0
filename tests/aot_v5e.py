"""What the compiler gate's files share (`tests/test_tpu_aot_compile*.py`):
the described v5e, abstract arrays placed on one of its devices, and the
dense family's parameter and pool structures.

libtpu is part of the installation, and
`jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")` describes four `TPU v5 lite` devices with no
hardware behind them. `jax.jit(f).lower(<ShapeDtypeStructs sharded on
those devices>).compile()` then runs the same compilers the chip does.

Every file of the gate asks for `no_compile_cache`: each case is a
compile that nothing in a run repeats, for a chip no process here can
load a program onto, so the run's compile cache would only be written.
"""

import jax
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama
from ray_tpu.models.llama_infer import storage_dtypes
from ray_tpu.ops import paged_attention as pa

PAGE, PAGES, BATCH, TABLE = 16, 512, 8, 64


@pytest.fixture(scope="module")
def v5e():
    devs = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    assert [d.device_kind for d in devs] == ["TPU v5 lite"] * 4
    return devs


def _on(dev):
    s = SingleDeviceSharding(dev)
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s)


def _param_structs(cfg, S):
    """The tree as a serving engine stores it (PR 30): matrices and
    embedding in cfg.dtype, head and norms in float32."""
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda a, dt: S(a.shape, dt), shapes,
                        storage_dtypes(cfg))


def _pools(cfg, S):
    shape = (cfg.n_layers, PAGES, PAGE, cfg.n_kv_heads,
             pa.pool_head_dim(cfg.head_dim, "pallas"))
    return S(shape, cfg.dtype), S(shape, cfg.dtype)


def _row_write_is_one_scatter(text, calls, most=None):
    """A tick program's text: each call of `scatter_rows` is one
    `scatter` under scope `kv_write`, none of them a `while`; at most
    `most` instructions in all, read off PR 46's tree (+1.5%), so that
    a later form of the write does not unroll it by layer, slab or head
    (refused PR 45's multiplied a program's instructions, and warm
    set-up rose 25 s in `phi4flash-reason`)."""
    lines = text.splitlines()
    writes = [x for x in lines if " scatter(" in x and "kv_write" in x]
    assert len(writes) == calls, len(writes)
    loops = [x.strip()[:160] for x in lines if " while(" in x
             and ("kv_write" in x or "/scatter" in x)]
    assert not loops, loops
    if most is not None:
        assert sum(" = " in x for x in lines) <= most
