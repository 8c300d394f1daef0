"""Test config: force JAX onto a virtual 8-device CPU mesh.

Reference parity for test strategy: SURVEY.md §4 — the in-process
multi-host simulation is `xla_force_host_platform_device_count=8`
(the Cluster-equivalent for SPMD code paths).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from ray_tpu._private.cpu_mesh import force_cpu_mesh

force_cpu_mesh(8)

import pytest


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'` (ROADMAP.md): mark long-running
    # benches `slow`; every interpret-mode kernel equivalence gate and
    # the TPU AOT-compile gate (test_tpu_aot_compile.py) stay un-marked
    # (tier-1). Nothing here needs a chip: what does lives in
    # chip_smoke.py.
    config.addinivalue_line(
        "markers",
        "slow: long-running; excluded from tier-1 CI")


@pytest.fixture()
def cpu_mesh_subprocess():
    """Run a python snippet in a FRESH interpreter on an emulated
    N-device CPU mesh (ISSUE 17). The parent process pinned its
    device count at backend init (8, above) — tests that need a
    DIFFERENT topology, or a backend not yet polluted by this
    process's jax config, get a subprocess with
    `xla_force_host_platform_device_count=N` instead. Returns
    CompletedProcess; asserts rc==0 with the child's output in the
    failure message unless check=False."""
    import subprocess

    from ray_tpu._private.cpu_mesh import apply_cpu_mesh_env

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        ".."))

    def run(code, n_devices=2, check=True, timeout=600, env=None):
        child_env = apply_cpu_mesh_env(dict(os.environ), n_devices)
        child_env["PYTHONPATH"] = (
            repo + os.pathsep + child_env.get("PYTHONPATH", "")
        ).rstrip(os.pathsep)
        child_env.update(env or {})
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=timeout, env=child_env)
        if check:
            assert proc.returncode == 0, (
                f"cpu-mesh subprocess failed rc={proc.returncode}\n"
                f"--- stdout ---\n{proc.stdout[-4000:]}\n"
                f"--- stderr ---\n{proc.stderr[-4000:]}")
        return proc

    return run


@pytest.fixture(scope="module")
def ray_start():
    import ray_tpu
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture()
def ray_local():
    import ray_tpu
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()
