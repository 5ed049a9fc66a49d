"""Device-side prefetch: overlap batch assembly + H2D transfer with compute.

`ShardedLoader` overlaps JPEG decode/augment with the step loop, but the
*last* stage of the input path — host batch assembly plus the H2D staging
inside `parallel.mesh.make_global_array` (`jax.make_array_from_process_local_
data`) — used to run synchronously inside the Python step loop: every step
paid it before the next device step could dispatch. jax's async dispatch
hides device latency behind host code, not host latency behind device code,
so that per-step host time was pure pipeline stall (SURVEY §7.3 ranks input
throughput the #1 hard part). The stage is measured where it runs: the
`input.assemble` and `train.input_wait` spans (obs/spans.py), which the
benchmark's per-layer metrics read.

`DevicePrefetcher` moves that stage onto a background *stager* thread that
keeps up to `depth` fully-formed, globally-sharded device batches staged
ahead of the consumer in a bounded buffer. The step loop's per-step host
work shrinks to a queue get + dispatch. Teardown/error discipline mirrors
`ShardedLoader.__iter__` (data/loader.py): bounded queue, stop-event
protocol that cannot deadlock a producer on a full queue, worker exceptions
re-raised at the iteration site, `None` sentinel for end-of-iteration.

Memory cost: each staged batch holds device memory, so depth N keeps up to
N extra batches (plus one in the stager's hand) resident in HBM. Depth 0
degrades to the exact synchronous path — same calls, same order, inline.

Wire format: staging is dtype-transparent — `make_global_array` preserves
the host batch's dtype, so the uint8 dataplane (data.input_dtype) ships
uint8 global arrays end-to-end and each staged H2D copy moves ¼ the bytes
of the float32 wire (the two levers compose: fewer bytes per transfer AND
the transfer overlapped with compute).

Double-buffered H2D (`overlap=True`, config `data.h2d_overlap`): the single
stager thread serializes host-batch FETCH (pulling the ShardedLoader,
collation) with the H2D TRANSFER (`make_global_array`) — batch N+1's fetch
waits for batch N's transfer. Overlap mode splits them onto two threads —
a fetcher feeding a ONE-SLOT handoff queue (the bounded in-flight transfer
budget: at most one batch fetched ahead of the transfer in flight) and an
`h2d-stager` running assemble — so batch N+1's host fetch proceeds while
batch N's transfer is in flight. Same order, same calls, same error/
teardown discipline (BOTH threads are joined on exit, even mid-transfer);
depth 0 ignores the flag and stays bit-for-bit synchronous.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

from ..obs import spans


class DevicePrefetcher:
    """Iterates device-staged batches from a host-batch iterable.

    host_batches: any (re-)iterable yielding host batches — typically a
        `ShardedLoader`. Each `__iter__` call starts a fresh pass (and a
        fresh stager thread), so one prefetcher can serve many epochs;
        single consumer at a time.
    mesh: target mesh for the default assemble (`make_global_array`).
    depth: staged batches kept ahead of the consumer. 0 = synchronous
        fallback (bit-for-bit the pre-prefetch path).
    assemble: optional `(batch_idx, host_batch) -> device_batch` override.
        Runs ON THE STAGER THREAD, so per-batch host work placed here (e.g.
        the eval path's `valid_mask`) also leaves the critical path. Must
        be thread-safe with respect to the consumer.
    overlap: double-buffered H2D dispatch — fetch host batch N+1 on a
        separate thread while batch N's assemble/H2D transfer is in
        flight (one-slot in-flight budget). Ignored at depth 0.
    """

    def __init__(
        self,
        host_batches: Iterable[Any],
        mesh: Optional[Any] = None,
        *,
        depth: int = 2,
        assemble: Optional[Callable[[int, Any], Any]] = None,
        overlap: bool = False,
    ):
        if assemble is None:
            if mesh is None:
                raise ValueError(
                    "DevicePrefetcher needs a mesh (for the default "
                    "make_global_array assemble) or an explicit assemble fn")
            assemble = self._default_assemble(mesh)
        self.host = host_batches
        # the `loader` id of this pass's spans and counters (obs/spans.py)
        self._loader = getattr(host_batches, "name", "train")
        self.depth = max(int(depth), 0)
        self._assemble = assemble
        self.overlap = bool(overlap)
        # introspection for tests/benchmarks: total batches staged across
        # all passes, and the ident of the active stager thread (None while
        # synchronous) — cheap evidence of WHERE staging ran. In overlap
        # mode `stager_thread` is the h2d-stager (the thread running
        # assemble) and `fetch_thread` the host-batch fetcher.
        self.staged = 0
        self.stager_thread: Optional[int] = None
        self.fetch_thread: Optional[int] = None

    @staticmethod
    def _default_assemble(mesh) -> Callable[[int, Any], Any]:
        # late imports keep `data` importable without initializing jax
        from ..parallel import mesh as meshlib

        sharding = meshlib.batch_sharding(mesh)

        def assemble(batch_idx: int, host_batch: Any) -> Any:
            return meshlib.make_global_array(host_batch, mesh, sharding=sharding)

        return assemble

    def _stage(self, i: int, hb: Any) -> Any:
        """`assemble` under its span, on whichever thread stages."""
        parts = hb if isinstance(hb, (tuple, list)) else (hb,)
        with spans.span("input.assemble", step=i, loader=self._loader,
                        rows=len(parts[0]) if hasattr(parts[0], "__len__") else 0,
                        bytes=sum(getattr(a, "nbytes", 0) for a in parts)):
            out = self._assemble(i, hb)
        self.staged += 1
        return out

    def _handed(self, starved: bool) -> None:
        """Consumer side: one batch goes to the loop; `starved` = it was not
        staged yet when the loop asked. Also noted on the loop's open span
        (the trainer's `train.input_wait`), for a reading per step."""
        spans.count("input_batches_total", loader=self._loader)
        if starved:
            spans.count("input_starved_total", loader=self._loader)
        spans.note(starved=int(starved))

    def __iter__(self) -> Iterator[Any]:
        if self.depth == 0:
            # synchronous fallback: identical assembly calls in identical
            # order, inline on the consumer thread (overlap ignored); no
            # staged queue, so the loop waits for every batch
            self.stager_thread = None
            self.fetch_thread = None
            for i, hb in enumerate(self.host):
                out = self._stage(i, hb)
                self._handed(starved=True)
                yield out
            return

        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        error: list = []

        def put_or_stop(qq, item) -> bool:
            """Bounded put that gives up when the consumer abandoned us —
            never deadlocks a producer on a full queue at teardown."""
            while not stop.is_set():
                try:
                    qq.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        threads = []
        if self.overlap:
            # double-buffered H2D: fetch and transfer pipeline on two
            # threads. hq's ONE slot is the in-flight transfer budget —
            # at most one host batch fetched ahead of the assemble in
            # flight (plus the one in the fetcher's hand), so overlap
            # never grows host memory unboundedly.
            hq: "queue.Queue" = queue.Queue(maxsize=1)

            def fetcher():
                it = iter(self.host)
                try:
                    for i, hb in enumerate(it):
                        if stop.is_set():
                            return
                        if not put_or_stop(hq, (i, hb)):
                            return
                except BaseException as e:  # surfaces at the iteration site
                    error.append(e)
                finally:
                    close = getattr(it, "close", None)
                    if close is not None:
                        close()
                    put_or_stop(hq, None)

            def h2d():
                try:
                    while True:
                        try:
                            item = hq.get(timeout=0.1)
                        except queue.Empty:
                            if stop.is_set():
                                return
                            continue
                        if item is None:
                            return
                        i, hb = item
                        if not put_or_stop(q, self._stage(i, hb)):
                            return
                except BaseException as e:
                    error.append(e)
                finally:
                    put_or_stop(q, None)

            tf = threading.Thread(target=fetcher, daemon=True,
                                  name="host-fetcher")
            th = threading.Thread(target=h2d, daemon=True,
                                  name="h2d-stager")
            tf.start()
            th.start()
            self.fetch_thread = tf.ident
            self.stager_thread = th.ident
            threads = [tf, th]
            drains = [q, hq]
        else:
            def stager():
                it = iter(self.host)
                try:
                    for i, hb in enumerate(it):
                        if stop.is_set():
                            return
                        if not put_or_stop(q, self._stage(i, hb)):
                            return
                except BaseException as e:  # re-raised at the iteration site
                    error.append(e)
                finally:
                    # unwind the host iterator NOW (a ShardedLoader pass has
                    # its own producer thread + queue) rather than at GC time
                    close = getattr(it, "close", None)
                    if close is not None:
                        close()
                    put_or_stop(q, None)

            t = threading.Thread(target=stager, daemon=True,
                                 name="device-stager")
            t.start()
            self.fetch_thread = None
            self.stager_thread = t.ident
            threads = [t]
            drains = [q]

        try:
            while True:
                starved = q.empty()
                item = q.get()
                if item is None:
                    break
                self._handed(starved)
                yield item
            if error:
                # a silently truncated epoch would corrupt training
                # invisibly — surface the stager failure where it's consumed
                raise error[0]
        finally:
            stop.set()
            # drain so a producer blocked on a full queue can exit, then
            # JOIN every pipeline thread (overlap mode: fetcher AND the
            # h2d-stager, even one mid-transfer): generator close (the
            # trainer loops' try/finally, the sentinel's rc-8 drain, a
            # SIGTERM unwind) must not return with a thread still staging
            # H2D copies — a leaked thread would race the next epoch's
            # pass (or a supervise.sh restart) for device memory
            for qq in drains:
                while True:
                    try:
                        qq.get_nowait()
                    except queue.Empty:
                        break
            for t in threads:
                t.join(timeout=10.0)
