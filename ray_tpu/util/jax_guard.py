"""Runtime enforcement of the engine's dispatch discipline.

jaxlint (tools/jaxlint) checks the invariant statically; this module
checks it at runtime: the steady-state engine tick must run with ZERO
host->device transfers (the decode loop is device-resident; tokens
feed back on device) and ZERO new XLA compilations (shape buckets are
warm). A single stray `jnp.asarray(host_array)` per tick or a
shape-bucket churn reintroduces exactly the host syncs / recompile
storms PR 1/2 removed — this harness turns them into test failures
instead of bench regressions.

Usage (see tests/test_dispatch_guard.py):

    with dispatch_guard() as report:
        for _ in range(32):
            engine.step()
    # raises GuardViolation on any compile; a host->device transfer
    # raises inside the block via jax.transfer_guard

Two mechanisms, both armed for the duration of the context:

- `jax.transfer_guard_host_to_device("disallow_explicit")`: any h2d
  transfer — implicit (scalar/ndarray commits during op dispatch) or
  explicit (`jax.device_put`, `jnp.asarray(host_array)`) — raises
  immediately at the offending call, so the traceback points at the
  exact engine line. Plain "disallow" would let explicit uploads
  through, which is precisely the `self._dev(jnp.asarray(...))` form
  a stray engine upload takes. Device->host stays ALLOWED by
  default: the engine's one per-tick token readback is the
  sanctioned sync point (pass d2h="disallow" to forbid it too).
- a log_compiles sentinel: `jax_log_compiles` emits one "Compiling
  <name> ..." record per XLA build; a logging.Handler on the jax
  loggers collects them, and leaving the context raises
  GuardViolation if more than `max_compiles` were seen.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import List

import jax

__all__ = ["GuardViolation", "GuardReport", "dispatch_guard"]

# the jax-internal loggers that carry compile events ("Compiling <fn>
# with global shapes and types ..."; kept broad so a jax upgrade
# moving the message keeps the sentinel alive)
_COMPILE_LOGGERS = (
    "jax._src.interpreters.pxla",
    "jax._src.dispatch",
    "jax._src.compiler",
)
_COMPILE_PREFIX = "Compiling "


class GuardViolation(RuntimeError):
    """Dispatch-discipline violation observed inside a dispatch_guard
    block (compiles over budget; transfer violations raise at the
    transfer site via jax.transfer_guard instead)."""


@dataclasses.dataclass
class GuardReport:
    """What the guard observed; yielded by dispatch_guard so tests can
    assert exact counts (e.g. allow N warmup compiles explicitly)."""
    compiles: List[str] = dataclasses.field(default_factory=list)

    @property
    def n_compiles(self) -> int:
        return len(self.compiles)


class _CompileSentinel(logging.Handler):
    def __init__(self, report: GuardReport):
        super().__init__(level=logging.DEBUG)
        self._report = report

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:            # never let logging break the run
            return
        if msg.startswith(_COMPILE_PREFIX):
            self._report.compiles.append(msg)


@contextlib.contextmanager
def dispatch_guard(max_compiles: int = 0,
                   h2d: str = "disallow_explicit",
                   d2h: str = "allow", raise_on_violation: bool = True,
                   recorder=None):
    """Arm transfer guards + the compile sentinel around a hot-path
    section.

    max_compiles: XLA builds tolerated before GuardViolation (0 for
        steady state; warmup sections can pass an explicit budget).
    h2d / d2h: jax.transfer_guard levels for host->device /
        device->host ("allow" | "log" | "disallow" | "log_explicit" |
        "disallow_explicit"; the h2d default is strict because a
        stray engine upload is usually an EXPLICIT jnp.asarray).
    raise_on_violation: False collects the report without raising
        (observability mode for benches) — "disallow" transfer
        levels are downgraded to their "log" forms so a stray
        transfer cannot crash the observed run either.
    recorder: optional flight recorder — any object with a
        `.record(kind, **fields)` method, e.g. the LLM engine's
        `telemetry.recorder` — given one, a compile-budget violation
        lands as a structured "guard_violation" event (ISSUE 5: the
        post-mortem dump at GET /debug/events shows it even when a
        retry layer swallows the raise, and report-only mode records
        without raising at all).
    """
    if not raise_on_violation:
        downgrade = {"disallow": "log",
                     "disallow_explicit": "log_explicit"}
        h2d = downgrade.get(h2d, h2d)
        d2h = downgrade.get(d2h, d2h)
    report = GuardReport()
    sentinel = _CompileSentinel(report)
    loggers = [logging.getLogger(name) for name in _COMPILE_LOGGERS]
    prev_log_compiles = bool(jax.config.jax_log_compiles)
    jax.config.update("jax_log_compiles", True)
    # fail CLOSED: a host app that muted logging (logging.disable or
    # raised logger levels — bench scripts do) would otherwise drop
    # the "Compiling ..." records before the sentinel sees them and
    # the guard would silently pass a recompile storm. Un-mute the
    # jax loggers for the guarded section, restore after.
    prev_disable = logging.root.manager.disable
    if prev_disable >= logging.WARNING:
        logging.disable(logging.NOTSET)
    prev_levels = [(lg, lg.level) for lg in loggers]
    for lg in loggers:
        if lg.getEffectiveLevel() > logging.WARNING:
            lg.setLevel(logging.WARNING)
        lg.addHandler(sentinel)
    try:
        with jax.transfer_guard_host_to_device(h2d), \
                jax.transfer_guard_device_to_host(d2h):
            yield report
    finally:
        for lg, level in prev_levels:
            lg.removeHandler(sentinel)
            lg.setLevel(level)
        logging.disable(prev_disable)
        jax.config.update("jax_log_compiles", prev_log_compiles)
    if report.n_compiles > max_compiles:
        if recorder is not None:
            try:
                recorder.record(
                    "guard_violation", cause="compile",
                    n_compiles=report.n_compiles,
                    budget=max_compiles,
                    first=report.compiles[0] if report.compiles
                    else "")
            except Exception:
                pass         # observability must never mask the raise
        if raise_on_violation:
            shown = "\n  ".join(report.compiles[:8])
            raise GuardViolation(
                f"{report.n_compiles} XLA compilation(s) inside a "
                f"dispatch_guard block (budget {max_compiles}) — shape "
                f"bucket churn or an untracked retrace on the hot path:"
                f"\n  {shown}")
