"""The program's span recorder: what ran, on which thread, from when to when.

    with spans.span("input.load", step=b, loader="train"): ...
    for k, batch in spans.timed("train.input_wait", it, epoch=e): ...

One process-global `Recorder`, always on: a span costs two reads of
`time.perf_counter_ns()`, a tuple and a deque append, and is taken per
batch or step, never per sample (`input.noise`, data/diffusion.py, is taken
per ROW of tokens: thousands of tokens a row, a handful of rows a batch). There is no switch; what a run measures
with the recorder in it is the whole cost. Nothing is written to a file
here: readers take `snapshot()` (the newest `CAPACITY` spans) or
`totals()` (per-name count / total / max, which never drop) when they
want them — `Trainer._write_prom` publishes the totals at its own cadence,
the benchmark's per-layer readers take the ring after the window.

`parent` is the name of the span open around this one on the same thread.
`ids` carry what ties one batch's spans together across threads: `step`
(the batch's index in its epoch: the same integer in the loader, the
stager and the loop), `epoch` where known, `loader` (`train` / `val`).

While `annotate` is set (the trainer sets it for the length of a
`--profile_steps` capture) every span is also entered as the profiler
annotation it returns, so the capture shows the program's spans on the
profiler's own clock. Stdlib only: importing this initialises nothing.
"""

from __future__ import annotations

import threading
import time
from collections import deque, namedtuple
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Tuple

# a 20 s window of four spans a step at 50 steps a second, and set-up
CAPACITY = 32768

Span = namedtuple("Span", "name start_ns end_ns thread parent ids")
_CLOCK_PAIR = (time.perf_counter_ns(), time.time_ns())


class _Open:
    """One span while it is open; recorded when the `with` block ends."""

    __slots__ = ("_rec", "name", "ids", "start_ns", "end_ns", "_parent",
                 "_annotation", "_keep")

    def __init__(self, rec: "Recorder", name: str, ids: Dict[str, Any]):
        self._rec, self.name, self.ids = rec, name, ids
        self.start_ns = self.end_ns = 0
        self._keep = True

    def __enter__(self) -> "_Open":
        stack = self._rec._stack()
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        make = self._rec.annotate
        self._annotation = make(self.name, self.ids) if make else None
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._rec._stack().pop()
        if self._keep:
            self._rec._record(Span(
                self.name, self.start_ns, self.end_ns,
                threading.current_thread().name, self._parent, self.ids))
        return False

    def discard(self) -> None:
        """What this span was to time did not happen: record nothing."""
        self._keep = False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Recorder:
    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._totals: Dict[str, List[int]] = {}   # name -> count, total, max
        self._counters: Dict[Tuple[str, Tuple], int] = {}
        self._local = threading.local()
        # (name, ids) -> a context manager, or None outside a capture
        self.annotate: Optional[Callable[[str, Dict[str, Any]], Any]] = None

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _record(self, s: Span) -> None:
        ns = s.end_ns - s.start_ns
        with self._lock:
            self._ring.append(s)
            t = self._totals.get(s.name)
            if t is None:
                self._totals[s.name] = [1, ns, ns]
            else:
                t[0] += 1
                t[1] += ns
                t[2] = max(t[2], ns)

    def span(self, name: str, **ids) -> _Open:
        return _Open(self, name, ids)

    def timed(self, name: str, iterable: Iterable, **ids) -> Iterator[Tuple[int, Any]]:
        """`enumerate(iterable)` with each `next()` under a span `name` that
        carries `step=k`; the `next()` that ends the iteration records none.
        Closing the generator closes the iterator beneath it."""
        it = iter(iterable)
        try:
            k = 0
            while True:
                with self.span(name, step=k, **ids) as s:
                    try:
                        item = next(it)
                    except StopIteration:
                        s.discard()
                        return
                yield k, item
                k += 1
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def note(self, **attrs) -> None:
        """Add `attrs` to the ids of the innermost span open on this thread
        (no span open: nothing): what a callee learned about its caller's
        span, e.g. that the batch a `next()` waited for was not staged yet."""
        stack = self._stack()
        if stack:
            stack[-1].ids.update(attrs)

    def count(self, name: str, n: int = 1, **labels) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def snapshot(self) -> List[Span]:
        """The newest spans, oldest first, in the order they ended."""
        with self._lock:
            return list(self._ring)

    def totals(self) -> Dict[str, Tuple[int, int, int]]:
        """name -> (count, total_ns, max_ns) since the process started."""
        with self._lock:
            return {k: tuple(v) for k, v in self._totals.items()}

    def counters(self) -> Dict[Tuple[str, Tuple], int]:
        """(name, sorted label items) -> count since the process started."""
        with self._lock:
            return dict(self._counters)


RECORDER = Recorder()
span = RECORDER.span
timed = RECORDER.timed
note = RECORDER.note
count = RECORDER.count
snapshot = RECORDER.snapshot
totals = RECORDER.totals
counters = RECORDER.counters


def clock_pair() -> Tuple[int, int]:
    """One `(perf_counter_ns, time_ns)` pair, taken when this module was
    imported: Unix ns of a span = start_ns - pair[0] + pair[1]."""
    return _CLOCK_PAIR
