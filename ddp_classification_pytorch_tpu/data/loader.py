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

The loader yields host-local numpy batches; `parallel/mesh.py:make_global_array`
assembles them into a globally-sharded `jax.Array` over the `data` axis, and
`data/device_prefetch.py:DevicePrefetcher` runs that assembly on a stager
thread so the H2D stage overlaps device compute (the full `pin_memory` +
`non_blocking` analogue).
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Tuple

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


class ShardedLoader:
    """Iterates (images, labels) numpy batches for this host.

    dataset must support `__len__` and `__getitem__(i, rng)` →
    (HWC image, int label). The image dtype IS the H2D wire format and is
    preserved verbatim through batching (`np.stack`): uint8 datasets
    (data.input_dtype == "uint8", the default — ¼ the transfer bytes) yield
    uint8 batches the jitted step normalizes on device; float32 datasets
    yield the legacy pre-normalized wire.
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

    def _load_batch(self, batch_idx: int, indices: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        if self.chaos is not None:
            self.chaos.maybe_fail_loader(epoch=self.epoch, batch=batch_idx)
        if self.batcher is not None:
            return self.batcher(indices, self.epoch, batch_idx)

        def load(j_and_i):
            j, i = j_and_i
            rng = np.random.default_rng(
                (self.seed, self.epoch, int(i), j)
            )
            item = self.dataset.__getitem__(int(i), rng)
            # PLCDataset yields (image, label, index) (PLC/FolderDataset.py:56-75);
            # the trailing index is positional bookkeeping we recover from `i`
            return item[0], item[1]

        if self._pool is not None:
            items = list(self._pool.map(load, enumerate(indices)))
        else:
            items = [load(ji) for ji in enumerate(indices)]
        images = np.stack([im for im, _ in items])
        labels = np.asarray([lb for _, lb in items], np.int32)
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
            try:
                for b in range(n_batches):
                    if stop.is_set():
                        return
                    sl = indices[b * self.batch_size : (b + 1) * self.batch_size]
                    # the span ends before the put: a producer blocked on a
                    # full queue is waiting, not loading
                    with spans.span("input.load", step=b, epoch=self.epoch,
                                    loader=self.name, path=self.path):
                        batch = self._load_batch(b, sl)
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
