"""Micro-batching inference engine: bounded queue → deadline batcher →
bucketed jitted predict → per-request futures.

The serving problem is the inverse of training's: requests arrive one at a
time, but the device wants big fixed-shape batches. The classic answer
(Clipper-style adaptive batching) is what this engine implements with the
training stack's own primitives:

- **Bounded intake.** `submit()` puts a request on a `queue_depth`-bounded
  queue and returns a `concurrent.futures.Future`; a full queue raises
  `QueueFull` immediately (backpressure the caller — or the HTTP 503 layer —
  can act on) instead of letting latency grow without bound.
- **Deadline batcher.** One batcher thread collects up to `max_batch`
  requests, waiting at most `batch_timeout_ms` past the FIRST queued request
  before flushing a partial batch — a lone request pays bounded latency, a
  busy queue amortizes whole batches.
- **Bucketed compilation.** The collected batch pads (zero rows) to the
  smallest bucket that fits, so the jitted predict sees at most
  `len(buckets)` distinct shapes — compile count is bounded up front instead
  of jit-per-request-count. Pad rows are discarded on return (eval-mode
  forward has no cross-sample ops, so padding cannot perturb real rows —
  `train/steps.py::make_topk_predict_step`).
- **uint8 wire.** Requests cross H2D in the dataplane's wire format
  (`data.input_dtype`, default uint8 at ¼ the bytes); normalization runs in
  the same fused `device_input_epilogue` the train/eval steps use, with the
  same static dtype dispatch.
- **Atomic param swap.** `swap_state()` publishes new params which the
  batcher adopts at the next batch boundary — the hot-reload hook
  (serve/reload.py) never interleaves two checkpoints inside one batch.
- **Graceful drain.** `drain()` stops intake (further submits raise
  `EngineClosed`), flushes everything already queued, and joins the batcher
  — the SIGTERM contract of `cli/serve.py` (exit rc 0 with no dropped
  request).

The engine is fully exercisable in-process: construct it without `start()`
and drive `process_once()` directly — no thread, no socket (how the tier-1
tests use it). The stdlib HTTP front-end
(serve/http.py) is a thin layer over `submit()`.

One engine is one replica's data plane. The fleet control plane
(serve/fleet.py) layers on top without reaching in: the admission
controller wraps `submit()` (deadline shedding above this queue's memory
bound), the replica registry heartbeats around the watcher that calls
`swap_state()`, and the rolling wave serializes WHEN `swap_state` may be
called — the engine itself stays single-replica and policy-free.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np


class QueueFull(RuntimeError):
    """Intake queue at serve.queue_depth — backpressure, retry later."""


class EngineClosed(RuntimeError):
    """Engine is draining or closed — no new requests."""


@dataclass
class Prediction:
    """Per-request result: top-k class indices + softmax scores, plus the
    provenance of the params that answered (which checkpoint digest and
    generation the batch ran under — the S1 verified-serve evidence)."""

    indices: np.ndarray  # (k,) int32
    scores: np.ndarray   # (k,) float32
    latency_ms: float    # submit → result, end to end
    digest: str = "fresh"  # sha256 of the adopted checkpoint; "fresh" = init
    generation: int = -1   # adopted checkpoint epoch; -1 = never reloaded


@dataclass
class _Request:
    image: np.ndarray
    future: Future
    t_submit: float


class ServingEngine:
    """See module docstring. `predict` is a jitted
    `(state, images (B,H,W,3)) -> (scores (B,k), indices (B,k))` — built by
    `train/steps.py::make_topk_predict_step` so serving shares the training
    stack's forward exactly."""

    def __init__(
        self,
        state: Any,
        predict: Callable[[Any, np.ndarray], Tuple[Any, Any]],
        *,
        image_size: int,
        input_dtype: str = "uint8",
        max_batch: int = 8,
        batch_timeout_ms: float = 5.0,
        queue_depth: int = 64,
        buckets: Sequence[int] = (1, 2, 4, 8),
        metrics: Optional[Any] = None,
        transform: Optional[Any] = None,
        strict_compile: bool = False,
        mesh: Optional[Any] = None,
        aot_dir: str = "",
    ):
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive, got {buckets}")
        if max_batch > buckets[-1]:
            raise ValueError(
                f"max_batch={max_batch} exceeds largest bucket {buckets[-1]}")
        # data-parallel serving: padded bucket batches are assembled as
        # global arrays sharded over the mesh 'data' axis, so per-replica
        # throughput scales with the pod. Every bucket must split evenly
        # over dp — `ServeConfig.resolve_buckets(dp)` already enforces
        # this for config-driven engines; re-checked here for direct
        # construction (the error is load-bearing: an indivisible bucket
        # would fail inside jit at the first unlucky batch instead).
        self.mesh = mesh
        if mesh is not None:
            from ..parallel.mesh import DATA_AXIS, batch_sharding

            self.dp = int(mesh.shape[DATA_AXIS])
            self.serve_devices = int(mesh.size)
            self._batch_sh = batch_sharding(mesh)
            bad = [b for b in buckets if b % self.dp]
            if bad:
                raise ValueError(
                    f"serve buckets {bad} not divisible by the serve mesh's "
                    f"data-parallel width dp={self.dp} "
                    "(error: serve-bucket-dp-indivisible)")
        else:
            self.dp = 1
            self.serve_devices = 1
            self._batch_sh = None
        # AOT sidecar (serve/aot.py): "" disables; warmup() loads banked
        # executables from here (warm boot, zero compiles) or banks its
        # own after compiling (cold boot)
        self.aot_dir = aot_dir
        self.aot_hit = False
        # bucket → AOT/lower-compiled executable; _run_batch dispatches
        # through this (falling back to the plain jit for engines driven
        # without warmup, e.g. tests poking process_once directly)
        self._compiled: dict = {}
        self._state = state
        self._predict = predict
        self.image_size = int(image_size)
        self.input_dtype = input_dtype
        self._np_dtype = np.uint8 if input_dtype == "uint8" else np.float32
        self.max_batch = int(max_batch)
        self.batch_timeout_s = float(batch_timeout_ms) / 1e3
        self.buckets = buckets
        self.transform = transform  # val Transform for submit_image decode
        if metrics is None:
            from .metrics import ServeMetrics

            metrics = ServeMetrics()
        self.metrics = metrics
        self._q: "queue.Queue[_Request]" = queue.Queue(maxsize=int(queue_depth))
        self._swap_lock = threading.Lock()
        self._pending_state: Optional[Tuple[Any, str, int]] = None
        # provenance of the params currently answering: "fresh" until the
        # first verified checkpoint is adopted (swap_state with a digest)
        self._digest = "fresh"
        self._generation = -1
        self._closed = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # evidence for the compile-count bound: which padded shapes actually
        # ran (tests assert seen_buckets ⊆ buckets and the jit cache size)
        self.seen_buckets: set = set()
        # recompile guard (analysis/compile_sentinel.py): warmup() arms it
        # after prepaying the bucket programs; any steady-state compile is
        # counted + logged, and with strict_compile the engine stops intake
        # and surfaces SteadyStateRecompile via `fatal_error`
        self.strict_compile = bool(strict_compile)
        self.compile_sentinel: Optional[Any] = None
        self.fatal_error: Optional[BaseException] = None

    @classmethod
    def from_config(cls, cfg, state, predict, metrics=None, transform=None,
                    mesh=None, aot_dir=""):
        """Engine wired from a Config tree (serve + data sections). `mesh`
        turns on dp-sharded serving (buckets resolve against its data-axis
        width); `aot_dir` points at the executable sidecar."""
        dp = 1
        if mesh is not None:
            from ..parallel.mesh import DATA_AXIS

            dp = int(mesh.shape[DATA_AXIS])
        return cls(
            state, predict,
            image_size=cfg.data.image_size,
            input_dtype=cfg.data.input_dtype,
            max_batch=cfg.serve.max_batch,
            batch_timeout_ms=cfg.serve.batch_timeout_ms,
            queue_depth=cfg.serve.queue_depth,
            buckets=cfg.serve.resolve_buckets(dp),
            metrics=metrics, transform=transform,
            strict_compile=cfg.serve.strict_compile,
            mesh=mesh, aot_dir=aot_dir,
        )

    # -------------------------------------------------------------- intake --
    @property
    def queue_depth(self) -> int:
        return self._q.qsize()

    @property
    def queue_capacity(self) -> int:
        """The configured intake bound — a MEMORY guard, distinct from the
        admission layer's latency policy (serve/fleet.py), which sheds on
        measured wait long before this bound is reached."""
        return self._q.maxsize

    @property
    def closed(self) -> bool:
        return self._closed

    def submit(self, image: Any) -> Future:
        """Enqueue one request; resolves to a `Prediction`.

        `image` must already be the wire tensor: (image_size, image_size, 3)
        in the engine's input dtype — the shape/dtype contract is validated
        here because a mismatched row would otherwise poison a whole padded
        batch at jit time. Raw PIL images go through `submit_image`."""
        if self._closed:
            raise EngineClosed("engine is draining; intake stopped")
        arr = np.asarray(image)
        want = (self.image_size, self.image_size, 3)
        if arr.shape != want or arr.dtype != self._np_dtype:
            raise ValueError(
                f"request must be shape {want} dtype {np.dtype(self._np_dtype)}, "
                f"got {arr.shape} {arr.dtype} (decode with submit_image / the "
                "val transform)")
        req = _Request(arr, Future(), time.monotonic())
        try:
            self._q.put_nowait(req)
        except queue.Full:
            self.metrics.record_reject()
            raise QueueFull(
                f"intake queue full ({self._q.maxsize} pending)") from None
        self.metrics.record_submit()
        return req.future

    def submit_image(self, img: Any) -> Future:
        """Decode a PIL image (or anything the val transform accepts)
        through the SAME `data.transforms.Transform` the eval pipeline uses
        — resize/center-crop host-side, uint8 quantization for the wire —
        then submit."""
        if self.transform is None:
            raise ValueError("engine has no transform; pass the val "
                             "Transform (build_transform(train=False, "
                             "out_dtype=input_dtype)) at construction")
        arr = self.transform(img, np.random.default_rng(0))  # val: rng unused
        return self.submit(arr)

    # ---------------------------------------------------------- hot reload --
    def swap_state(self, new_state: Any, digest: str = "",
                   generation: int = -1) -> None:
        """Publish new params; adopted atomically at the next batch boundary
        (serve/reload.py calls this from the watcher thread). `digest` and
        `generation` name the verified checkpoint the params came from, so
        every Prediction (and /healthz) can attest which weights answered."""
        with self._swap_lock:
            self._pending_state = (new_state, digest or "fresh",
                                   int(generation))

    @property
    def params_digest(self) -> str:
        """sha256 of the checkpoint currently answering ("fresh" = init
        params, nothing adopted yet)."""
        with self._swap_lock:
            return self._digest

    @property
    def params_generation(self) -> int:
        with self._swap_lock:
            return self._generation

    def state_compatible(self, new_state: Any) -> bool:
        """Whether `new_state` can answer through the already-compiled
        bucket executables: same pytree structure, same leaf shapes and
        dtypes as the state serving now. The hot-reload watcher
        (serve/reload.py) gates swaps on this — an incompatible (but
        validly checksummed) checkpoint must be rejected at the swap
        boundary, not explode inside a compiled program mid-batch."""
        import jax

        try:
            cur, cur_def = jax.tree_util.tree_flatten(self._state)
            new, new_def = jax.tree_util.tree_flatten(new_state)
        except Exception:
            return False
        if cur_def != new_def or len(cur) != len(new):
            return False
        for c, n in zip(cur, new):
            if (getattr(c, "shape", None) != getattr(n, "shape", None)
                    or getattr(c, "dtype", None) != getattr(n, "dtype", None)):
                return False
        return True

    # ------------------------------------------------------------- serving --
    def _assemble(self, batch: np.ndarray) -> Any:
        """Padded host batch → device input: a data-sharded global array
        on a mesh engine (the training stack's own H2D path), the numpy
        batch unchanged on a single-device engine (jit moves it)."""
        if self.mesh is None:
            return batch
        from ..parallel.mesh import make_global_array

        return make_global_array(batch, self.mesh, self._batch_sh)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]  # unreachable: max_batch <= buckets[-1]

    def _collect(self, first_timeout_s: float):
        """Up to max_batch requests: block up to `first_timeout_s` for the
        first, then at most batch_timeout_ms past its arrival for company."""
        try:
            first = (self._q.get(timeout=first_timeout_s)
                     if first_timeout_s > 0 else self._q.get_nowait())
        except queue.Empty:
            return []
        reqs = [first]
        deadline = time.monotonic() + self.batch_timeout_s
        while len(reqs) < self.max_batch:
            remaining = deadline - time.monotonic()
            try:
                reqs.append(self._q.get(timeout=remaining)
                            if remaining > 0 else self._q.get_nowait())
            except queue.Empty:
                break
        return reqs

    def _run_batch(self, reqs) -> None:
        with self._swap_lock:
            if self._pending_state is not None:
                self._state, self._digest, self._generation = \
                    self._pending_state
                self._pending_state = None
            # capture under the lock: the whole batch is answered by ONE
            # params version even if a swap lands mid-flight
            digest, generation = self._digest, self._generation
        n = len(reqs)
        bucket = self._bucket_for(n)
        h = self.image_size
        batch = np.zeros((bucket, h, h, 3), self._np_dtype)
        for i, r in enumerate(reqs):
            batch[i] = r.image
        try:
            # warmup banks one executable per bucket (AOT-deserialized or
            # lower+compiled); dispatching through it keeps the warm path
            # compile-free. Engines driven without warmup fall back to the
            # plain jit call.
            fn = self._compiled.get(bucket, self._predict)
            scores, indices = fn(self._state, self._assemble(batch))
            scores = np.asarray(scores)   # device sync
            indices = np.asarray(indices)
        except Exception as e:
            # one bad batch must not kill the server: the requests carry the
            # failure, the batcher keeps serving
            self.metrics.record_error(n)
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        self.seen_buckets.add(bucket)
        now = time.monotonic()
        lats = []
        for i, r in enumerate(reqs):  # pad rows [n:] are discarded here
            lat_ms = (now - r.t_submit) * 1e3
            lats.append(lat_ms)
            r.future.set_result(Prediction(indices[i], scores[i], lat_ms,
                                           digest=digest,
                                           generation=generation))
        self.metrics.record_batch(bucket, n, lats)
        self._check_compile_sentinel()

    def _check_compile_sentinel(self) -> None:
        """Batch-boundary recompile check (requests already answered). A
        steady-state compile is counted + logged; under strict_compile the
        engine stops intake and raises — the batcher thread converts that
        into `fatal_error` for cli.serve to classify (rc 2)."""
        if self.compile_sentinel is None:
            return
        from ..analysis.compile_sentinel import SteadyStateRecompile

        try:
            events = self.compile_sentinel.check(strict=self.strict_compile)
        except SteadyStateRecompile as e:
            self.metrics.record_recompile(self.compile_sentinel.violations)
            self.fatal_error = e
            self._closed = True  # stop intake; queued work still flushes
            raise
        if events:
            self.metrics.record_recompile(len(events))

    def process_once(self, timeout_s: float = 0.0) -> int:
        """Collect and run ONE micro-batch inline; returns requests served
        (0 = nothing queued). The in-process driving surface tests and
        `drain()` use — identical code path to the batcher thread."""
        reqs = self._collect(timeout_s)
        if not reqs:
            return 0
        self._run_batch(reqs)
        return len(reqs)

    def warmup(self) -> None:
        """Ready every bucket executable up front so the first real request
        never pays a compile, and PROVE it with the compile sentinel:

        - **warm boot** (valid AOT sidecar at `aot_dir`): deserialize the
          banked executables and run each once — the sentinel must count
          ZERO predict compiles, the instant-cold-start contract.
        - **cold boot**: explicitly lower+compile each bucket (exactly
          `len(buckets)` programs on a cold predict — a warm/shared
          predict may dedupe to fewer, never more), then bank the
          executables into the sidecar for the next replica.

        The sentinel stays armed afterwards, so any steady-state compile
        (a shape leaking past the bucket padding) is caught at the batch
        boundary."""
        from ..analysis.compile_sentinel import CompileSentinel

        # "was this predict already warm?" — the jit dispatch cache when the
        # runtime exposes it, else the marker a previous engine's cold
        # warmup left on the fn (explicit lower/compile bypasses the
        # dispatch cache, and re-lowering known avals doesn't re-log, so
        # a shared warm predict would otherwise look like 0 compiles)
        pre = self.compiled_programs() or \
            getattr(self._predict, "_serve_warmed", 0)
        sentinel = CompileSentinel(tag="serve")
        sentinel.arm()
        try:
            h = self.image_size
            zeros = {b: self._assemble(np.zeros((b, h, h, 3), self._np_dtype))
                     for b in self.buckets}
            pname = getattr(self._predict, "__name__", "")

            def count_predict(events):
                return (len([e for e in events if e.name == pname]) if pname
                        else len(events))

            def lower_bucket(b):
                # trace only — no compile, no sentinel event
                return self._predict.lower(self._state, zeros[b])

            loaded = None
            if self.aot_dir:
                from . import aot

                loaded = aot.load_bucket_executables(
                    self.aot_dir, self.mesh, self.buckets, lower_bucket)
            if loaded is not None:
                self._compiled = dict(loaded)
                # the load's drift probe re-LOWERED one bucket — a trace,
                # but jax logs its "Compiling ..." line at lowering on the
                # sharded path, so drain those events: the zero-compile
                # assertion below must measure pure execution of the
                # deserialized executables
                sentinel.take()
                for b in self.buckets:
                    scores, _ = self._compiled[b](self._state, zeros[b])
                    np.asarray(scores)  # block: prove execution, not just load
                n_new = count_predict(sentinel.take())
                if n_new:
                    raise RuntimeError(
                        f"warm serve boot compiled {n_new} predict programs — "
                        "the AOT sidecar promised zero (deserialized "
                        "executables must not trigger compilation; "
                        "docs/serving.md AOT runbook)")
                self.aot_hit = True
            else:
                lowered = {}
                for b in self.buckets:
                    lowered[b] = lower_bucket(b)
                    self._compiled[b] = lowered[b].compile()
                    scores, _ = self._compiled[b](self._state, zeros[b])
                    np.asarray(scores)  # compile belongs to warmup, not a request
                n_new = count_predict(sentinel.take())
                if pre == 0 and n_new != len(self.buckets):
                    raise RuntimeError(
                        f"serve warmup compiled {n_new} predict programs, expected "
                        f"exactly {len(self.buckets)} (one per bucket "
                        f"{list(self.buckets)}) — the bucket→compile contract is "
                        "broken (docs/serving.md)")
                if n_new > len(self.buckets):
                    raise RuntimeError(
                        f"serve warmup compiled {n_new} predict programs for "
                        f"{len(self.buckets)} buckets — more shapes than the bucket "
                        "set admits")
                if self.aot_dir:
                    from . import aot

                    aot.save_bucket_executables(
                        self.aot_dir, lowered, self._compiled, self.mesh)
            try:
                self._predict._serve_warmed = len(self.buckets)
            except AttributeError:  # a predict that refuses attributes
                pass
        except BaseException:
            # a failed warmup must not leak an armed sentinel: the module
            # refcount would keep jax's pxla logger at DEBUG (with
            # propagation suppressed) for the rest of the process
            sentinel.disarm()
            raise
        self.compile_sentinel = sentinel  # armed: steady state begins

    def compiled_programs(self) -> Optional[int]:
        """How many predict programs this engine holds: the banked bucket
        executables after warmup (the at-most-len(buckets) evidence), else
        the predict's jit cache size when the runtime exposes it; None
        when neither is known."""
        if self._compiled:
            return len(self._compiled)
        probe = getattr(self._predict, "_cache_size", None)
        try:
            return int(probe()) if callable(probe) else None
        except Exception:
            return None

    # ------------------------------------------------------------ lifecycle --
    def start(self) -> "ServingEngine":
        if self._thread is not None:
            return self
        if self._closed:
            raise EngineClosed("cannot start a drained engine")

        def loop():
            from ..analysis.compile_sentinel import SteadyStateRecompile

            while not self._stop.is_set():
                try:
                    self.process_once(timeout_s=0.05)
                except SteadyStateRecompile:
                    # fatal_error is set and intake stopped; keep flushing
                    # the already-accepted queue so drain stays graceful
                    continue

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serve-batcher")
        self._thread.start()
        return self

    def drain(self, timeout_s: float = 30.0) -> None:
        """Graceful shutdown: stop intake, flush everything queued, join the
        batcher. Every request accepted before the drain gets its result —
        the SIGTERM rc-0 contract."""
        self._closed = True  # submit() now raises EngineClosed
        deadline = time.monotonic() + timeout_s
        if self._thread is not None:
            while not self._q.empty() and time.monotonic() < deadline:
                time.sleep(0.005)
            self._stop.set()
            self._thread.join(timeout=max(deadline - time.monotonic(), 0.1))
            self._thread = None
        # anything left (thread raced its stop flag, or engine never started)
        # flushes inline — same process_once the thread ran. A strict-mode
        # recompile during the flush must not break the rc-0 drain contract:
        # fatal_error is already recorded, the queued requests still answer.
        from ..analysis.compile_sentinel import SteadyStateRecompile

        try:
            while True:
                try:
                    if not self.process_once(timeout_s=0.0):
                        break
                except SteadyStateRecompile:
                    continue
        finally:
            # disarm is idempotent; the sentinel must not outlive the engine
            # even when the inline flush raises
            if self.compile_sentinel is not None:
                self.compile_sentinel.disarm()

    def close(self) -> None:
        """Abort: stop the batcher and fail whatever is still queued
        (EngineClosed on the pending futures). `drain()` is the graceful
        sibling."""
        self._closed = True
        self._stop.set()
        try:
            if self._thread is not None:
                self._thread.join(timeout=5.0)
                self._thread = None
            while True:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    break
                if not req.future.done():
                    req.future.set_exception(EngineClosed("engine closed"))
        finally:
            if self.compile_sentinel is not None:
                self.compile_sentinel.disarm()
