"""Test config: force JAX onto a virtual 8-device CPU mesh.

Reference parity for test strategy: SURVEY.md §4 — the in-process
multi-host simulation is `xla_force_host_platform_device_count=8`
(the Cluster-equivalent for SPMD code paths).
"""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from ray_tpu._private.cpu_mesh import force_cpu_mesh

# The compile cache of ONE run. Nearly every test jits the same tiny
# programs again (an engine's closures on a debug preset), once a test
# in each of xdist's workers; kept for the length of the run, a program
# is compiled once. The controller (or the single process) makes the
# directory, outside the checkout, before xdist starts its workers; they
# and every child process inherit it through the variable jax itself
# reads (`util/compile_cache.ensure_compile_cache` honours it too), and
# `pytest_sessionfinish` removes it. A run starts from an empty cache,
# writes nothing into the checkout and leaves nothing behind.
_RUN_CACHE = None
if "PYTEST_XDIST_WORKER" not in os.environ:
    _RUN_CACHE = tempfile.mkdtemp(prefix="ray_tpu_test_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true"
# keep every program however quick its compile: measured on the whole
# run against 0.1 s (CHANGES.md, PR 53)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

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


def pytest_sessionfinish(session):
    if _RUN_CACHE is not None:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@pytest.fixture(autouse=True)
def _no_profiler_session_left_open(request):
    """jax has ONE profiler session a process. An engine flags a slow
    tick on a loaded host, arms a capture of the next four, and the test
    ends first: the session stays open in that xdist worker, and every
    later test there that captures waits a minute behind it and fails
    (three of `test_llm_telemetry.py` in a run of PR 53). Close it, and
    say who left it."""
    yield
    from ray_tpu.util import profiling
    if profiling.session_open():
        import jax
        try:
            jax.profiler.stop_trace()
        except RuntimeError:     # its owner closed it meanwhile
            return
        import warnings
        warnings.warn(f"{request.node.nodeid} left a jax profiler session "
                      "open; closed")


@pytest.fixture()
def no_compile_cache():
    """For a test that times a REAL compile: the run's cache off for
    this test alone (a hit is a retrieval, milliseconds)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    # jax reads the switch once a process and remembers: reset_cache
    # makes it read again
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


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
