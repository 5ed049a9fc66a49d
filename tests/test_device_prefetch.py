"""DevicePrefetcher tests on the virtual 8-device CPU mesh.

The prefetch pipeline moves batch assembly + H2D staging
(`make_global_array`) onto a background stager thread. These tests pin the
contract: staged batches are bit-identical to the synchronous path and in
order; worker exceptions surface at the iteration site; teardown on early
exit cannot deadlock; the buffer is depth-bounded; and the Trainer's hot
loop really does stage off the consumer thread (depth 0 really doesn't).
"""

import threading
import time

import numpy as np
import pytest
from tiny import tiny_cfg

import jax

from ddp_classification_pytorch_tpu.data.device_prefetch import DevicePrefetcher
from ddp_classification_pytorch_tpu.data.loader import ShardedLoader
from ddp_classification_pytorch_tpu.data.synthetic import SyntheticDataset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.loop import Trainer


def _loader(n=64, batch=8, image=4, **kw):
    ds = SyntheticDataset(n, image, 4, seed=7)
    kw.setdefault("shuffle", False)
    return ShardedLoader(ds, batch, seed=7, num_workers=1,
                         host_id=0, num_hosts=1, **kw)


def _get(batch):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(batch))


def test_batches_match_undecorated_loader_in_order():
    loader = _loader()
    mesh = meshlib.make_mesh()
    sync = [_get(b) for b in DevicePrefetcher(loader, mesh, depth=0)]
    staged = [_get(b) for b in DevicePrefetcher(loader, mesh, depth=2)]
    assert len(sync) == len(staged) == len(loader)
    for (si, sl), (pi, pl) in zip(sync, staged):
        np.testing.assert_array_equal(si, pi)
        np.testing.assert_array_equal(sl, pl)


def test_reiterable_across_epochs():
    loader = _loader(n=32, batch=8, shuffle=True)
    mesh = meshlib.make_mesh()
    pre = DevicePrefetcher(loader, mesh, depth=1)
    loader.set_epoch(0)
    e0 = [_get(b)[1] for b in pre]
    loader.set_epoch(1)
    e1 = [_get(b)[1] for b in pre]
    assert len(e0) == len(e1) == 4
    # different epoch → different permutation of the same label multiset
    assert not all(np.array_equal(a, b) for a, b in zip(e0, e1))
    np.testing.assert_array_equal(np.sort(np.concatenate(e0)),
                                  np.sort(np.concatenate(e1)))


class _Poisoned:
    def __len__(self):
        return 64

    def __getitem__(self, i, rng=None):
        if i == 40:
            raise RuntimeError("corrupt sample")
        return np.zeros((4, 4, 3), np.float32), 0


def test_dataset_exception_propagates_through_both_threads():
    loader = ShardedLoader(_Poisoned(), 8, shuffle=False, num_workers=2,
                           host_id=0, num_hosts=1)
    pre = DevicePrefetcher(loader, meshlib.make_mesh(), depth=2)
    with pytest.raises(RuntimeError, match="corrupt sample"):
        list(pre)


def test_assemble_exception_propagates():
    def explode(i, hb):
        if i == 2:
            raise ValueError("bad stage")
        return hb

    pre = DevicePrefetcher(_loader(), depth=2, assemble=explode)
    with pytest.raises(ValueError, match="bad stage"):
        list(pre)


def test_early_break_tears_down_and_reiterates():
    loader = _loader(n=128, batch=8)
    mesh = meshlib.make_mesh()
    pre = DevicePrefetcher(loader, mesh, depth=1)
    for i, _ in enumerate(pre):
        if i == 1:
            break  # abandon mid-epoch: stager + loader producer must exit
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if not any(t.name == "device-stager" and t.is_alive()
                   for t in threading.enumerate()):
            break
        time.sleep(0.05)
    else:
        pytest.fail("stager thread still alive after abandoned iteration")
    # a fresh full pass must work — would hang if teardown deadlocked
    assert len(list(pre)) == 16


def test_buffer_is_depth_bounded():
    depth = 2
    staged = []
    consumed = []
    overshoot = []

    def assemble(i, hb):
        staged.append(i)
        overshoot.append(len(staged) - len(consumed))
        return hb

    pre = DevicePrefetcher(_loader(n=96, batch=8), depth=depth,
                           assemble=assemble)
    for b in pre:
        consumed.append(b)
        time.sleep(0.02)  # slow consumer: the stager runs far ahead if unbounded
    assert len(staged) == 12
    # stager may be ahead by: `depth` queued + 1 in its own hand + 1 popped
    # but not yet recorded by the consumer — never more (an unbounded
    # buffer would reach 11 here with this consumer pacing)
    assert max(overshoot) <= depth + 2, max(overshoot)


def test_staging_runs_on_stager_thread():
    idents = []

    def assemble(i, hb):
        idents.append(threading.get_ident())
        return hb

    pre = DevicePrefetcher(_loader(n=32, batch=8), depth=2, assemble=assemble)
    list(pre)
    assert pre.staged == 4
    assert pre.stager_thread is not None
    assert set(idents) == {pre.stager_thread}
    assert threading.get_ident() not in idents

    # depth 0: inline on the consumer thread, stager_thread stays None
    idents.clear()
    sync = DevicePrefetcher(_loader(n=32, batch=8), depth=0, assemble=assemble)
    list(sync)
    assert sync.stager_thread is None
    assert set(idents) == {threading.get_ident()}


def test_requires_mesh_or_assemble():
    with pytest.raises(ValueError, match="mesh"):
        DevicePrefetcher(_loader())


# ---------------------------------------------------- double-buffered H2D --

def _gone(*names, deadline_s=5.0):
    """True once no live thread carries any of the given names."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if not any(t.name in names and t.is_alive()
                   for t in threading.enumerate()):
            return True
        time.sleep(0.05)
    return False


def test_overlap_batches_bit_identical_and_in_order():
    loader = _loader()
    mesh = meshlib.make_mesh()
    sync = [_get(b) for b in DevicePrefetcher(loader, mesh, depth=0)]
    over = [_get(b) for b in DevicePrefetcher(loader, mesh, depth=2,
                                              overlap=True)]
    assert len(sync) == len(over) == len(loader)
    for (si, sl), (oi, ol) in zip(sync, over):
        np.testing.assert_array_equal(si, oi)
        np.testing.assert_array_equal(sl, ol)


def test_overlap_splits_fetch_and_h2d_onto_distinct_threads():
    """The dispatch evidence: host-batch fetch and assemble/H2D run on
    two different named threads, neither of them the consumer; at depth 0
    the flag is ignored bit-for-bit (inline, no threads)."""
    fetch_idents = []
    h2d_idents = []

    class Spy:
        def __init__(self, host):
            self.host = host

        def __iter__(self):
            for hb in self.host:
                fetch_idents.append(threading.get_ident())
                yield hb

    def assemble(i, hb):
        h2d_idents.append(threading.get_ident())
        return hb

    pre = DevicePrefetcher(Spy(_loader(n=32, batch=8)), depth=2,
                           assemble=assemble, overlap=True)
    list(pre)
    assert pre.staged == 4
    assert pre.fetch_thread is not None and pre.stager_thread is not None
    assert pre.fetch_thread != pre.stager_thread
    assert set(fetch_idents) == {pre.fetch_thread}
    assert set(h2d_idents) == {pre.stager_thread}
    assert threading.get_ident() not in fetch_idents + h2d_idents

    # depth 0 ignores overlap: inline, synchronous, no thread idents
    h2d_idents.clear()
    sync = DevicePrefetcher(_loader(n=32, batch=8), depth=0,
                            assemble=assemble, overlap=True)
    list(sync)
    assert sync.stager_thread is None and sync.fetch_thread is None
    assert set(h2d_idents) == {threading.get_ident()}


def test_overlap_pipelines_fetch_behind_transfer():
    """The deterministic timing smoke: with fetch and assemble each
    costing ~delay per batch, the single-stager path pays fetch+assemble
    serially (~2·delay/batch) while overlap pipelines them (~delay/batch
    steady-state). Generous margins keep this robust to scheduler noise:
    the overlapped wall must land below 0.75× the serial wall."""
    delay, n = 0.04, 6

    class Sleepy:
        def __iter__(self):
            for i in range(n):
                time.sleep(delay)
                yield (np.full((8, 4, 4, 3), i, np.float32),
                       np.full((8,), i, np.int32))

    def assemble(i, hb):
        time.sleep(delay)
        return hb

    t0 = time.perf_counter()
    serial = [b for b in DevicePrefetcher(Sleepy(), depth=2,
                                          assemble=assemble)]
    t_serial = time.perf_counter() - t0
    t0 = time.perf_counter()
    overlapped = [b for b in DevicePrefetcher(Sleepy(), depth=2,
                                              assemble=assemble,
                                              overlap=True)]
    t_overlap = time.perf_counter() - t0
    assert len(serial) == len(overlapped) == n
    # serial ≈ n·2·delay = 480 ms; overlap ≈ (n+1)·delay = 280 ms
    assert t_overlap < 0.75 * t_serial, (t_overlap, t_serial)


def test_overlap_exception_mid_transfer_joins_both_threads():
    """Satellite fix: an assemble failure mid-pipeline must surface at the
    iteration site AND leave neither the fetcher nor the h2d-stager
    running — an orphaned H2D thread would race the sentinel's rc-8
    drain (or a supervise.sh restart) for device memory."""

    def explode(i, hb):
        if i == 2:
            raise ValueError("bad transfer")
        return hb

    pre = DevicePrefetcher(_loader(), depth=2, assemble=explode,
                           overlap=True)
    with pytest.raises(ValueError, match="bad transfer"):
        list(pre)
    assert _gone("host-fetcher", "h2d-stager"), (
        "overlap pipeline thread still alive after assemble exception")
    # the prefetcher stays reusable: a fresh pass re-raises, not hangs
    with pytest.raises(ValueError, match="bad transfer"):
        list(pre)


def test_overlap_early_break_joins_threads_mid_transfer():
    """Generator close (the trainer loops' try/finally, a SIGTERM unwind)
    while a transfer is IN FLIGHT must drain and join both pipeline
    threads, then support a fresh full pass."""

    def slow_assemble(i, hb):
        time.sleep(0.1)
        return hb

    pre = DevicePrefetcher(_loader(n=64, batch=8), depth=1,
                           assemble=slow_assemble, overlap=True)
    for i, _ in enumerate(pre):
        if i == 1:
            break  # batch 3's transfer is mid-flight on the h2d-stager
    assert _gone("host-fetcher", "h2d-stager"), (
        "overlap pipeline thread still alive after abandoned iteration")
    assert len(list(pre)) == 8


# ---------------------------------------------------------------- trainer --

def _tiny_cfg(prefetch_depth):
    cfg = tiny_cfg("baseline")
    cfg.data.synthetic_size = 64  # four steps
    cfg.data.num_workers = 2
    cfg.data.device_prefetch = prefetch_depth
    return cfg


def test_trainer_prefetch_stages_off_thread_and_matches_sync_bitwise(monkeypatch):
    """Two acceptance criteria through ONE Trainer (the compile is the cost
    here; `device_prefetch` is read per epoch, so the same trainer replays
    the same epoch from a state snapshot under both depths):

    - with device_prefetch >= 1, the per-step host time between dispatches
      no longer includes batch assembly/H2D — every make_global_array call
      in train AND eval lands on a stager thread (and with depth 0, every
      call is back inline on the consumer thread);
    - depth 0 falls back to the synchronous path bit-for-bit: identical
      epoch metrics on the synthetic dataset (the prefetcher changes WHERE
      assembly runs, never WHAT is computed)."""
    main_ident = threading.get_ident()
    idents = []
    real = meshlib.make_global_array

    def spy(batch, mesh, sharding=None):
        idents.append(threading.get_ident())
        return real(batch, mesh, sharding=sharding)

    monkeypatch.setattr(meshlib, "make_global_array", spy)

    tr = Trainer(_tiny_cfg(2))
    # deep copy: the train step DONATES the state buffers (steps.py), so an
    # alias would be invalidated by the first epoch
    state0 = jax.tree_util.tree_map(jax.numpy.copy, tr.state)
    train_pre = tr.train_epoch(0)
    eval_pre = tr.evaluate()
    assert idents, "make_global_array never called"
    assert main_ident not in idents

    # same trainer, same starting state, synchronous depth-0 replay
    idents.clear()
    tr.state = state0
    tr.cfg.data.device_prefetch = 0
    train_sync = tr.train_epoch(0)
    eval_sync = tr.evaluate()
    assert idents and set(idents) == {main_ident}

    assert train_sync == train_pre, (train_sync, train_pre)
    assert eval_sync == eval_pre, (eval_sync, eval_pre)
