"""Runtime recompile guard: steady-state compiles are a paged-in bug.

A jit cache miss after warmup stalls the step loop (or a serve micro-batch)
for the full XLA compile — seconds on CPU, minutes on the TPU — and
it is always a program bug: an aval that should be static drifted (a new
batch shape leaking past the bucket padding, a dtype flip, a weak-type
mismatch on resume). PR 4 bounded serve compiles by construction and tested
it; this sentinel turns the bound into an *enforced runtime contract* for
both the serving engine (serve/engine.py::warmup) and the trainer's steady
state (train/loop.py), warn-only by default and fatal under
`--strict_compile`.

Mechanism: jax logs every XLA program build through the
`jax._src.interpreters.pxla` logger as "Compiling jit(<name>) with global
shapes and types [...]" — at DEBUG level even when `jax_log_compiles` is off, and
exactly once per executable built (cache hits are silent). The sentinel
attaches a logging handler there, so each captured event carries the
offending function name AND its aval signature — the two things you need to
find which caller's shapes drifted. A module-level refcount keeps the
logger's level at DEBUG only while at least one sentinel is armed.
"""

from __future__ import annotations

import logging
import re
import threading
import time
from typing import Any, Callable, List, NamedTuple, Optional

_PXLA_LOGGER = "jax._src.interpreters.pxla"
_COMPILE_RE = re.compile(
    r"Compiling jit\((\S+)\) with global shapes and types (.*)")

_logger_lock = threading.Lock()
_armed_count = 0
_saved_state: Optional[tuple] = None  # (level, propagate)


class CompileEvent(NamedTuple):
    """One observed XLA program build after arming."""

    name: str        # the jitted function's name ("step", "fn", …)
    signature: str   # the aval signature jax logged for it
    t: float         # time.monotonic() at capture


class SteadyStateRecompile(RuntimeError):
    """A compile landed after warmup with the sentinel in strict mode.

    Deterministic — the same program replays the same cache miss — so the
    CLIs map it to rc 2 (supervisors must not restart it; docs/analysis.md
    runbook)."""

    exit_code = 2


class _CaptureHandler(logging.Handler):
    def __init__(self, sentinel: "CompileSentinel"):
        super().__init__(level=logging.DEBUG)
        self._sentinel = sentinel

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_RE.match(record.getMessage())
        except Exception:
            return
        if m:
            self._sentinel._record(m.group(1), m.group(2))


def _acquire_logger() -> None:
    global _armed_count, _saved_state
    with _logger_lock:
        lg = logging.getLogger(_PXLA_LOGGER)
        if _armed_count == 0:
            _saved_state = (lg.level, lg.propagate)
            if lg.getEffectiveLevel() > logging.DEBUG:
                lg.setLevel(logging.DEBUG)
            # capturing at DEBUG must not spray every compile signature
            # through the root/absl handlers — the sentinel itself
            # re-surfaces the events that matter (steady-state ones)
            lg.propagate = False
        _armed_count += 1


def _release_logger() -> None:
    global _armed_count, _saved_state
    with _logger_lock:
        _armed_count -= 1
        if _armed_count == 0 and _saved_state is not None:
            lg = logging.getLogger(_PXLA_LOGGER)
            lg.setLevel(_saved_state[0])
            lg.propagate = _saved_state[1]
            _saved_state = None


class CompileSentinel:
    """Count (and attribute) XLA compiles observed while armed.

    Usage: `arm()` once warmup is over; call `take()` (drain) or `check()`
    (drain + warn/raise) at natural sync points — the trainer's epoch
    boundary and log cadence, the engine's batch boundary. Capture is
    process-wide (any jit in the process), which is the point: a stray
    compile ANYWHERE stalls the device pipeline."""

    def __init__(self, tag: str = "",
                 log: Optional[Callable[[str], Any]] = None):
        self.tag = tag
        self._log = log
        self._lock = threading.Lock()
        self._events: List[CompileEvent] = []
        self._handler: Optional[_CaptureHandler] = None
        self.total = 0       # compiles observed since first arm
        self.violations = 0  # events surfaced through check()

    # ------------------------------------------------------------ capture --
    def _record(self, name: str, signature: str) -> None:
        with self._lock:
            self._events.append(CompileEvent(name, signature, time.monotonic()))
            self.total += 1

    @property
    def armed(self) -> bool:
        return self._handler is not None

    def arm(self) -> "CompileSentinel":
        if self._handler is None:
            self._handler = _CaptureHandler(self)
            _acquire_logger()
            logging.getLogger(_PXLA_LOGGER).addHandler(self._handler)
        return self

    def disarm(self) -> None:
        if self._handler is not None:
            logging.getLogger(_PXLA_LOGGER).removeHandler(self._handler)
            self._handler = None
            _release_logger()

    # ------------------------------------------------------------- policy --
    def take(self) -> List[CompileEvent]:
        """Drain and return the events captured since the last drain."""
        with self._lock:
            events, self._events = self._events, []
        return events

    def check(self, strict: bool = False) -> List[CompileEvent]:
        """Drain; log one warning per event (with the offending signature);
        raise SteadyStateRecompile when strict and anything was captured."""
        events = self.take()
        if not events:
            return events
        self.violations += len(events)
        log = self._log or (lambda msg: logging.getLogger(__name__).warning(msg))
        for e in events:
            log(f"[compile-sentinel{':' + self.tag if self.tag else ''}] "
                f"steady-state recompile of `{e.name}` — signature drifted: "
                f"{e.signature}")
        if strict:
            raise SteadyStateRecompile(
                f"{len(events)} steady-state compile(s) after warmup "
                f"({self.tag or 'unarmed tag'}): "
                + "; ".join(f"{e.name} {e.signature}" for e in events[:3]))
        return events
