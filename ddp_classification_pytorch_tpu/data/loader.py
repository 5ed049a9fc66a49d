"""Per-host sharded, prefetching data loader.

This is the corrected, TPU-native replacement for the reference's
`DistributedSampler` + `DataLoader(num_workers=4, pin_memory=True)` stack
(BASELINE/main.py:127-131):

- **Global identity done right.** The reference passes *local* rank as global
  rank (`DistributedSampler(rank=args.local_rank)`, BASELINE/main.py:127 — a
  multi-node correctness bug, SURVEY §2.2). Here each host slices the epoch
  permutation by `jax.process_index()/process_count()`.
- **`set_epoch` semantics.** Epoch-seeded permutation identical across hosts
  (BASELINE/main.py:269) — all hosts derive the same permutation and take
  disjoint contiguous slices; padding wraps indices like DistributedSampler.
- **Worker parallelism** via a thread pool (PIL/numpy release the GIL in the
  hot paths) + a bounded background prefetch queue — the host-side analogue of
  `num_workers` + `pin_memory`.
- **Batches filled in place.** The Python path cuts a batch's rows into at
  most `num_workers` contiguous runs; each run is one pool task that writes
  its rows straight into the batch buffer: no future, generator or tuple a
  row, no second copy. A pass recycles a buffer once nothing but the pass
  owns it (`sys.getrefcount`), so a steady consumer stops paying the page
  faults of a fresh allocation every batch, and a batch a consumer holds is
  never written again.

The loader yields host-local numpy batches; `parallel/mesh.py:make_global_array`
assembles them into a globally-sharded `jax.Array` over the `data` axis, and
`data/device_prefetch.py:DevicePrefetcher` runs that assembly on a stager
thread so the H2D stage overlaps device compute (the full `pin_memory` +
`non_blocking` analogue).
"""

from __future__ import annotations

import queue
import sys
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Iterator, List, Optional, Tuple

import numpy as np

from ..obs import spans


def shard_indices_for_host(
    n: int,
    epoch: int,
    seed: int,
    batch_size: int,
    shuffle: bool = True,
    host_id: Optional[int] = None,
    num_hosts: Optional[int] = None,
    drop_last: bool = False,
) -> np.ndarray:
    """Deterministic per-host index shard for one epoch.

    All hosts compute the same permutation (seed ⊕ epoch), pad it by wrapping
    to a multiple of num_hosts·batch_size (DistributedSampler's pad-by-repeat),
    and take the host's contiguous slice.
    """
    import jax

    host_id = jax.process_index() if host_id is None else host_id
    num_hosts = jax.process_count() if num_hosts is None else num_hosts

    idx = np.arange(n, dtype=np.int64)
    if shuffle:
        rng = np.random.default_rng(np.uint32(seed) ^ np.uint32((epoch * 0x9E3779B9) & 0xFFFFFFFF))
        rng.shuffle(idx)
    chunk = num_hosts * batch_size
    if drop_last:
        idx = idx[: (n // chunk) * chunk]
    elif n % chunk:
        # np.resize tiles the permutation, so padding wraps repeatedly even
        # when the pad exceeds the dataset size (tiny val sets vs large
        # num_hosts·batch_size)
        idx = np.resize(idx, ((n // chunk) + 1) * chunk)
    per_host = len(idx) // num_hosts
    return idx[host_id * per_host : (host_id + 1) * per_host]


class _LazyRng:
    """`np.random.default_rng(key)`, built when first drawn from: seeding a
    generator costs 40 us under the GIL, and a dataset that never reads its
    `rng` (or only tests it: `rng or …`) never pays it. A dataset that draws
    gets the draws of the generator itself."""

    __slots__ = ("_key", "_rng")

    def __init__(self, key: Tuple[int, int, int, int]):
        self._key = key
        self._rng = None

    def __getattr__(self, name: str):
        if self._rng is None:
            self._rng = np.random.default_rng(self._key)
        return getattr(self._rng, name)


def _owners(held: List[np.ndarray], k: int) -> int:
    return sys.getrefcount(held[k])


# what `_owners` reads when the list is an array's one owner: taken from the
# interpreter, which decides how many references the call itself holds
_SOLE_OWNER = _owners([np.empty(0, np.uint8)], 0)


def _batch_buffer(held: List[np.ndarray], room: int, shape: Tuple[int, ...],
                  dtype: np.dtype) -> Tuple[np.ndarray, bool]:
    """An array of `shape` and `dtype` to fill, and whether it is one of
    `held` written before. `held` are the buffers one pass has handed out; one
    comes back only while `held` is its sole owner: a consumer's reference, a
    view (its `base`), a device transfer in flight or a CPU device array
    aliasing it all count. Past `room` entries a new buffer is not tracked,
    so a consumer that keeps every batch pins at most `room` more."""
    for k in range(len(held)):
        if (_owners(held, k) == _SOLE_OWNER and held[k].shape == shape
                and held[k].dtype == dtype):
            return held[k], True
    buf = np.empty(shape, dtype)
    if len(held) < room:
        held.append(buf)
    return buf, False


class ShardedLoader:
    """Iterates (images, labels) numpy batches for this host.

    dataset must support `__len__` and `__getitem__(i, rng)` →
    (HWC image, int label). The image dtype IS the H2D wire format: the
    batch buffer takes the shape and dtype of the batch's first row, every
    other row must match both (`ValueError` at the iteration site) and is
    written into it as it is, never cast or broadcast: uint8 datasets
    (data.input_dtype == "uint8", the default — ¼ the transfer bytes) yield
    uint8 batches the jitted step normalizes on device; float32 datasets
    yield the legacy pre-normalized wire. A yielded batch belongs to whoever
    holds it, for as long as they hold it.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 999,
        num_workers: int = 4,
        prefetch: int = 2,
        drop_last: bool = False,
        host_id: Optional[int] = None,
        num_hosts: Optional[int] = None,
        batcher=None,
        chaos=None,
        name: str = "train",
    ):
        # which loader this is, on every span and counter of its batches
        # (obs/spans.py): the trainer's val loader is "val"
        self.name = name
        # batcher: optional native batch assembler
        # `(indices, epoch, batch_idx) -> (images, labels)` (see data/native.py);
        # replaces the per-sample Python/PIL path when set
        self.batcher = batcher
        # which code fills this loader's batches, on every `input.load` span:
        # the batcher's own name for its path (`native_u8` / `native_f32`),
        # else the per-sample Python path
        self.path = "python" if batcher is None else batcher.path
        # chaos: optional utils.chaos.FaultPlan — loader_io faults raise
        # IOError from _load_batch (the transient-crash shape supervise.sh
        # retries with backoff); None = no injection code in the hot path
        self.chaos = chaos
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.num_workers = max(num_workers, 1)
        self.prefetch = max(prefetch, 1)
        self.drop_last = drop_last
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.epoch = 0
        # one O(n) permutation per (epoch, dataset length) — __len__ and
        # __iter__ used to recompute it on every call (review finding); the
        # key self-invalidates on set_epoch and on dataset growth/shrink
        self._cached_indices: Optional[np.ndarray] = None
        self._cache_key: Optional[Tuple[int, int]] = None
        # one pool for the loader's lifetime — a per-batch pool would pay
        # thread spawn/teardown on every batch of every epoch
        self._pool = (
            ThreadPoolExecutor(self.num_workers) if self.num_workers > 1 else None
        )

    def close(self) -> None:
        """Release worker threads (idempotent; also runs at GC)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def set_epoch(self, epoch: int) -> None:
        """Reshuffle hook (reference sampler.set_epoch, BASELINE/main.py:269)."""
        self.epoch = epoch

    def _epoch_indices(self) -> np.ndarray:
        key = (self.epoch, len(self.dataset))
        if self._cached_indices is None or self._cache_key != key:
            self._cached_indices = shard_indices_for_host(
                len(self.dataset), self.epoch, self.seed, self.batch_size,
                self.shuffle, self.host_id, self.num_hosts, self.drop_last,
            )
            self._cache_key = key
        return self._cached_indices

    def _per_host_len(self) -> int:
        """This host's padded epoch length, derived arithmetically —
        `shard_indices_for_host` pads the permutation to a multiple of
        num_hosts·batch_size and slices it evenly, so the length never
        needs the O(n) permutation itself."""
        import jax

        num_hosts = jax.process_count() if self.num_hosts is None else self.num_hosts
        n = len(self.dataset)
        chunk = num_hosts * self.batch_size
        if self.drop_last:
            total = (n // chunk) * chunk
        elif n % chunk:
            total = ((n // chunk) + 1) * chunk
        else:
            total = n
        return total // num_hosts

    def __len__(self) -> int:
        return self._per_host_len() // self.batch_size

    def valid_mask(self, batch_idx: int) -> np.ndarray:
        """(batch_size,) 1.0 where the row is a real sample, 0.0 where it is
        wrap-padding — exact-eval support (only meaningful for ordered,
        shuffle=False loaders, where the padded tail duplicates the head).
        Pure arithmetic (no permutation), so it is cheap and thread-safe to
        call from a `DevicePrefetcher` stager."""
        assert not self.shuffle, "valid_mask is defined for ordered loaders"
        import jax

        host = jax.process_index() if self.host_id is None else self.host_id
        per_host = self._per_host_len()
        start = host * per_host + batch_idx * self.batch_size
        pos = start + np.arange(self.batch_size)
        return (pos < len(self.dataset)).astype(np.float32)

    def _item(self, j: int, i: int) -> Tuple[np.ndarray, object]:
        """Row `j` of a batch: sample `i`, with the generator that is the
        row's own whatever thread loads it and however the batch is cut."""
        item = self.dataset.__getitem__(
            i, _LazyRng((self.seed, self.epoch, i, j)))
        # PLCDataset yields (image, label, index) (PLC/FolderDataset.py:56-75);
        # the trailing index is positional bookkeeping we recover from `i`
        return np.asarray(item[0]), item[1]

    def _load_batch(self, batch_idx: int, indices: np.ndarray,
                    held: List[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
        """One batch; the Python path writes its rows in place into a buffer
        from `held`, the buffers this pass has handed out (`_batch_buffer`)."""
        if self.chaos is not None:
            self.chaos.maybe_fail_loader(epoch=self.epoch, batch=batch_idx)
        if self.batcher is not None:
            return self.batcher(indices, self.epoch, batch_idx)

        indices = indices.tolist()
        rows = len(indices)
        first, label = self._item(0, indices[0])
        # a steady pass has one buffer being filled, `prefetch` queued, one
        # in the consumer's hand and a few with a stager and its transfers
        images, reused = _batch_buffer(
            held, self.prefetch + 4, (rows,) + first.shape, first.dtype)
        # a label is a class id, or a row of them (data/tokens.py)
        labels = np.empty((rows,) + np.shape(label), np.int32)
        images[0], labels[0] = first, label

        def fill(lo: int, hi: int) -> None:
            for j in range(lo, hi):
                image, label = self._item(j, indices[j])
                # an assignment would broadcast a (224, 224, 1) row and cast
                # a float one; `np.stack` refused the first
                if (image.shape != first.shape or image.dtype != first.dtype
                        or np.shape(label) != labels.shape[1:]):
                    raise ValueError(
                        f"row {j} of the batch (sample {indices[j]}) is "
                        f"{image.dtype.name}{list(image.shape)} with a label "
                        f"of shape {list(np.shape(label))}; row 0 is "
                        f"{first.dtype.name}{list(first.shape)} with "
                        f"{list(labels.shape[1:])}: all rows of a batch "
                        "must have the same shape and dtype")
                images[j], labels[j] = image, label

        # contiguous runs of rows 1.., one task each: at most one a worker
        chunks = max(min(self.num_workers, rows - 1), 1) if self._pool else 1
        if chunks == 1:
            fill(1, rows)
        else:
            cuts = [1 + (rows - 1) * c // chunks for c in range(chunks + 1)]
            tasks = [self._pool.submit(fill, lo, hi)
                     for lo, hi in zip(cuts, cuts[1:])]
            # every task has left the buffer before an error leaves here
            wait(tasks)
            for task in tasks:
                task.result()
        spans.count("input_batch_buffers_total", loader=self.name,
                    reused=str(int(reused)))
        spans.note(rows=rows, chunks=chunks)
        return images, labels

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        indices = self._epoch_indices()
        n_batches = len(indices) // self.batch_size
        if n_batches == 0:
            return
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        error: list = []

        def put_or_stop(item) -> bool:
            """Bounded put that gives up when the consumer abandoned us —
            avoids deadlocking the producer on a full queue at teardown."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            held: List[np.ndarray] = []
            try:
                for b in range(n_batches):
                    if stop.is_set():
                        return
                    sl = indices[b * self.batch_size : (b + 1) * self.batch_size]
                    # the span ends before the put: a producer blocked on a
                    # full queue is waiting, not loading
                    with spans.span("input.load", step=b, epoch=self.epoch,
                                    loader=self.name, path=self.path):
                        batch = self._load_batch(b, sl, held)
                    if self.batcher is not None:
                        # the wire as the batch carries it, not as configured
                        spans.count("input_native_batches_total",
                                    loader=self.name, wire=batch[0].dtype.name)
                    if not put_or_stop(batch):
                        return
            except BaseException as e:  # re-raised in the consumer
                error.append(e)
            finally:
                put_or_stop(None)

        t = threading.Thread(target=producer, daemon=True,
                             name="loader-producer")
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    break
                yield item
            if error:
                # a silent short epoch would corrupt training invisibly —
                # surface the worker failure at the iteration site
                raise error[0]
        finally:
            stop.set()
            # drain so the producer can exit
            while t.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
