"""Mid-run hang watchdog for the trainer.

A device sync that never returns raises no exception, and
scripts/supervise.sh restarts on EXIT only — so a hang defeats the whole
failure-detection chain. Only sustained absence of progress tells a hang
from a slow step: the trainer marks host-observed progress with `touch()`
and a watchdog thread turns prolonged silence into a loud exit the
supervisor can restart (auto_resume continues from the last checkpoint).
"""

from __future__ import annotations

import os
import sys
import threading
import time


class StepHeartbeat:
    """`touch()` marks host-observed progress; a daemon thread exits the
    process loudly (os._exit(exit_code), default 7) when no touch lands
    within `timeout_s` (0 = never armed). The diagnostic is
    printed-and-flushed BEFORE the exit, but the exit CODE is the real
    contract — it is what supervise.sh restarts on."""

    def __init__(self, timeout_s: float, *, exit_code: int = 7,
                 where: str = "trainer"):
        self.timeout_s = float(timeout_s)
        self.exit_code = exit_code
        self.where = where
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "StepHeartbeat":
        if self.timeout_s > 0 and self._thread is None:
            self._thread = threading.Thread(target=self._watch, daemon=True)
            self._thread.start()
        return self

    def touch(self) -> None:
        self._last = time.monotonic()

    def stop(self) -> None:
        self._stop.set()

    def _watch(self) -> None:
        poll = min(max(self.timeout_s / 4.0, 0.05), 30.0)
        while not self._stop.wait(poll):
            stale = time.monotonic() - self._last
            if stale > self.timeout_s:
                print(f"# {self.where}: no progress for {stale:.0f}s "
                      f"(> hang_timeout_s={self.timeout_s:.0f}) — backend "
                      "hang suspected; exiting "
                      f"{self.exit_code} for the supervisor to restart "
                      "(auto_resume continues from the last checkpoint)",
                      file=sys.stderr, flush=True)
                os._exit(self.exit_code)
