"""The compile cache of one run of the tests (`tests/conftest.py`): a
temporary directory outside the checkout that every worker and child
process shares, and that the run removes when it ends."""

import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# one jit in a fresh interpreter; prints what `CompileWatch` counted
_JIT_ONCE = """
import os
import jax, jax.numpy as jnp
from ray_tpu.util.compile_cache import CompileWatch
watch = CompileWatch()
f = jax.jit(lambda x: jnp.tanh(x * {scale}) @ x.T)
f(jnp.ones((24, 40))).block_until_ready()
print("CACHE_DIR", os.environ["JAX_COMPILATION_CACHE_DIR"])
print("COUNTS", watch.cache_hits, watch.cache_writes, watch.programs)
"""


def _placed():
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def _counts(out):
    """(hits, writes, programs) off a child's COUNTS line."""
    return tuple(map(int, out.split("COUNTS")[1].split()[:3]))


def test_cache_dir_is_outside_the_checkout_and_in_use():
    import jax
    placed = os.path.realpath(_placed())
    assert os.path.isdir(placed)
    assert not placed.startswith(os.path.realpath(REPO) + os.sep)
    assert jax.config.jax_compilation_cache_dir == _placed()
    assert jax.config.jax_enable_compilation_cache
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_child_process_shares_the_directory(cpu_mesh_subprocess):
    out = cpu_mesh_subprocess(_JIT_ONCE.format(scale=1.25)).stdout
    assert f"CACHE_DIR {_placed()}\n" in out
    # the child wrote there, or read what an earlier run of this test
    # in the same session left
    hits, writes, programs = _counts(out)
    assert hits + writes >= 1 and programs >= 1


def test_second_fresh_process_hits_the_cache(cpu_mesh_subprocess):
    code = _JIT_ONCE.format(scale=2.75)
    cpu_mesh_subprocess(code)
    hits, writes, programs = _counts(cpu_mesh_subprocess(code).stdout)
    # every program of the second process was compiled by the first:
    # retrieved, none written, and still counted as a program
    assert hits >= 1 and writes == 0 and programs >= hits


def test_a_run_leaves_the_checkout_and_the_temp_dir_as_they_were(tmp_path):
    """A whole (tiny) run of pytest from this conftest: its cache
    directory exists while it runs and is gone when it ends, and
    `git status` of the checkout reads the same before and after."""
    def status():
        git = subprocess.run(["git", "status", "--porcelain"], cwd=REPO,
                             capture_output=True, text=True)
        if git.returncode == 0:
            return git.stdout
        # a checkout without its repository: the files themselves
        return sorted(
            (os.path.join(d, f), os.path.getsize(os.path.join(d, f)))
            for d, _, fs in os.walk(REPO) if "__pycache__" not in d
            for f in fs)

    probe = tmp_path / "seen.txt"
    inner = tmp_path / "test_inner.py"
    inner.write_text(
        "import os\n"
        "def test_inner():\n"
        "    import jax, jax.numpy as jnp\n"
        "    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()\n"
        "    d = os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        f"    open({str(probe)!r}, 'w').write(d + ' ' + str(len(os.listdir(d))))\n")
    before = status()
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_XDIST")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-c", os.devnull, "--rootdir", REPO,
         "--confcutdir", REPO, "-p", "tests.conftest", str(inner)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    placed, entries = probe.read_text().split()
    assert placed != _placed()              # a run makes its own
    assert int(entries) >= 1                # and writes there
    assert not os.path.exists(placed)       # and takes it away
    assert status() == before
