"""What `run.py` hands a runner, and what a runner hands back."""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def say(msg: str) -> None:
    """An earlier line of stdout: free text, never the result."""
    print(msg, flush=True)


@dataclasses.dataclass
class Context:
    workload: str
    config_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    t_start: float = dataclasses.field(default_factory=time.monotonic)
    # seconds this process spent inside its first `jax.devices()`: the
    # runtime attaching to the chip, which no code of the repo shortens
    chip_attach_s: float = 0.0
    compile_watch: Any = None         # run.py's CompileWatch, if any

    def setup_s(self, window_start: float) -> float:
        """`setup_s`: process start to window start, less the time the
        runtime took to attach to the chip. That time (5.8 to 13.8 s on
        the v5e, drifting upwards through a sequence of processes,
        PERF.md section 6) is the machine's, and is said on an earlier
        line; what is left is what the benchmark and the program do."""
        return window_start - self.t_start - self.chip_attach_s

    def phase(self, name: str, t0: float) -> None:
        """Say how long a phase of set-up took and what it compiled."""
        compiled = (self.compile_watch.snapshot()
                    if self.compile_watch is not None else "")
        say(f"[setup] {name}: {time.monotonic() - t0:.1f}s; compiled so "
            f"far {compiled}")

    @property
    def program_seed(self) -> int:
        """--seed folded into what a PRNGKey takes on every backend."""
        return self.seed % (2 ** 31 - 1)


@dataclasses.dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]          # the cell's end-to-end values
    # what the per-layer readers read: "events" (trace), "counters",
    # "client" (the load generator's summary), "samples", "config",
    # "traffic", "window_s"
    run: Dict[str, Any]
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)


def trace_span(ctx: "Context") -> Optional[Tuple[float, float]]:
    """(start, end) of the traced part of the window, in seconds from its
    start: `trace_s` of the traffic file (4 by default, at most half the
    window) from a quarter in; None in an untraced run."""
    if not ctx.trace:
        return None
    window_s = float(ctx.seconds)
    span = min(float(ctx.traffic.get("trace_s", 4.0)), window_s / 2)
    return window_s / 4, window_s / 4 + span


def trace_options():
    """Device timeline only: the Python and host tracers slow the host
    they watch."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
