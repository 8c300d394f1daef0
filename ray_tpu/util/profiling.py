"""Profiling utilities.

Reference parity: dashboard/modules/reporter/profile_manager.py (py-spy
stack dumps, memray memory reports) — implemented with the standard
library (sys._current_frames / tracemalloc / /proc) so nothing external
is shipped — plus the TPU-native piece the reference lacks: a
jax.profiler trace context whose output feeds TensorBoard / xprof.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import traceback
from typing import Iterator


def dump_stacks() -> str:
    """All threads' current stacks, py-spy style."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sorted(sys._current_frames().items()):
        out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        out.extend(line.rstrip()
                   for line in traceback.format_stack(frame))
    return "\n".join(out)


def memory_summary() -> dict:
    """Process memory: RSS from /proc plus tracemalloc top allocations
    when tracing is active (start with tracemalloc.start())."""
    import tracemalloc

    summary = {"rss_bytes": None, "top_allocations": []}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    summary["rss_bytes"] = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    if tracemalloc.is_tracing():
        snap = tracemalloc.take_snapshot()
        for stat in snap.statistics("lineno")[:20]:
            summary["top_allocations"].append(
                {"where": str(stat.traceback), "bytes": stat.size,
                 "count": stat.count})
    return summary


def session_open() -> bool:
    """Is a jax.profiler session open in this process, someone else's
    or one still being written? `start_trace` then either raises or,
    while `stop_trace` exports (it holds the profiler's lock for the
    whole export: 14 to 40 s after a 4 s capture of a serving engine,
    PERF.md section 6), waits that long. jax has no public question for
    this; the session object is what `start_trace` itself tests. Where
    a jax keeps it elsewhere the answer is "not known", False: the
    caller goes on to `start_trace`, which refuses a second session
    itself. A session that opens between this answer and the caller's
    `start_trace` meets the same refusal."""
    try:
        from jax._src import profiler
        return profiler._profile_state.profile_session is not None
    except (ImportError, AttributeError):
        return False


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """jax.profiler trace scope: the device's timeline and the host's
    `TraceAnnotation` spans (the engine's `engine.*` tick phases among
    them) land in `log_dir` for TensorBoard/xprof (`tensorboard --logdir
    ...`). The Python tracer is off: it stops the process it watches for
    long enough to show in every latency it was meant to explain."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
