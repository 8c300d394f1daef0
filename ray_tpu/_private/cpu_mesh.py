"""Virtual multi-device CPU mesh bootstrap (the test-cluster equivalent).

Reference parity: SURVEY.md §4 — multi-node simulation via
``xla_force_host_platform_device_count``. One recipe, shared by
``tests/conftest.py`` (in-process) and ``__graft_entry__.dryrun_multichip``
(child process), so the two can't silently diverge.
"""

from __future__ import annotations

import os
from typing import MutableMapping

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def apply_cpu_mesh_env(env: MutableMapping[str, str],
                       n_devices: int = 8,
                       *,
                       keep_existing_count: bool = False) -> MutableMapping[str, str]:
    """Mutate *env* so a jax backend initialized under it boots a virtual
    n-device CPU mesh.

    XLA:CPU has no ``ragged-all-to-all``; the recipe also throws the
    explicit switch that makes ``ops/ragged_exchange`` emulate it
    (without the switch the op is the native collective or an error,
    on any backend). And the CPU tier writes no compile cache into the
    checkout: the persistent cache is off unless *env* already places
    one (``JAX_COMPILATION_CACHE_DIR``), as ``tests/conftest.py`` does
    with a directory that lives for one run of the tests.
    ``__graft_entry__.dryrun_multichip`` places none and keeps none
    (``util/compile_cache``'s ``<checkout>/.jax_cache`` is for the
    processes that own a chip).
    """
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_RAGGED_EMULATE"] = "1"
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    flags = env.get("XLA_FLAGS", "")
    if keep_existing_count and _COUNT_FLAG in flags:
        return env
    flags = " ".join(f for f in flags.split() if not f.startswith(_COUNT_FLAG))
    env["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    return env


def force_cpu_mesh(n_devices: int = 8) -> None:
    """Apply the recipe to this process. If jax is already imported, also
    flip its platform config — env alone is read only at backend init."""
    import sys

    # Respect an operator-set device count (e.g. a 16-device pytest run).
    apply_cpu_mesh_env(os.environ, n_devices, keep_existing_count=True)
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update(
            "jax_enable_compilation_cache",
            os.environ.get("JAX_ENABLE_COMPILATION_CACHE") != "false")
