"""Loader and schedule regression tests for review findings:
- producer exceptions must surface at the iteration site, not truncate epochs;
- valid_mask marks wrap-padding exactly;
- warmup overlays the decay schedule without shifting its milestones;
- 3-tuple datasets (PLC (image, label, index)) load through ShardedLoader;
- a batch filled in place by chunked worker tasks is the batch the per-row
  `np.stack` path made, and a recycled buffer is never one somebody holds.
"""

import numpy as np
import pytest

from ddp_classification_pytorch_tpu.config import OptimConfig
from ddp_classification_pytorch_tpu.data.loader import ShardedLoader
from ddp_classification_pytorch_tpu.obs import spans
from ddp_classification_pytorch_tpu.train.schedule import build_schedule


class ExplodingDataset:
    def __len__(self):
        return 64

    def __getitem__(self, i, rng=None):
        if i == 40:
            raise RuntimeError("corrupt sample")
        return np.zeros((4, 4, 3), np.float32), 0


def test_loader_surfaces_worker_errors():
    loader = ShardedLoader(ExplodingDataset(), batch_size=8, shuffle=False,
                           num_workers=2, host_id=0, num_hosts=1)
    with pytest.raises(RuntimeError, match="corrupt sample"):
        list(loader)


class TripleDataset:
    """PLC-style (image, label, index) items."""

    def __len__(self):
        return 16

    def __getitem__(self, i, rng=None):
        return np.full((2, 2, 3), i, np.float32), i % 3, i


def test_loader_handles_plc_triples():
    loader = ShardedLoader(TripleDataset(), batch_size=8, shuffle=False,
                           num_workers=1, host_id=0, num_hosts=1)
    batches = list(loader)
    assert len(batches) == 2
    images, labels = batches[0]
    assert images.shape == (8, 2, 2, 3)
    np.testing.assert_array_equal(labels, np.arange(8) % 3)


def test_valid_mask_marks_padding():
    class Tiny:
        def __len__(self):
            return 10

        def __getitem__(self, i, rng=None):
            return np.zeros((2, 2, 3), np.float32), 0

    loader = ShardedLoader(Tiny(), batch_size=4, shuffle=False,
                           host_id=0, num_hosts=1)
    # 10 samples pad to 12 → batches of 4,4,4; last two rows of batch 2 padded
    assert len(loader) == 3
    np.testing.assert_array_equal(loader.valid_mask(0), [1, 1, 1, 1])
    np.testing.assert_array_equal(loader.valid_mask(1), [1, 1, 1, 1])
    np.testing.assert_array_equal(loader.valid_mask(2), [1, 1, 0, 0])


def test_valid_mask_multihost_padding_on_last_host():
    class Tiny:
        def __len__(self):
            return 10

        def __getitem__(self, i, rng=None):
            return np.zeros((2, 2, 3), np.float32), 0

    # 2 hosts × batch 4 → chunk 8, pad 10 → 16, per-host 8 (2 batches each)
    m0 = [ShardedLoader(Tiny(), 4, shuffle=False, host_id=0, num_hosts=2).valid_mask(b)
          for b in range(2)]
    m1 = [ShardedLoader(Tiny(), 4, shuffle=False, host_id=1, num_hosts=2).valid_mask(b)
          for b in range(2)]
    np.testing.assert_array_equal(np.concatenate(m0), [1] * 8)       # rows 0-7
    np.testing.assert_array_equal(np.concatenate(m1), [1, 1] + [0] * 6)  # rows 8-9 real


def test_tiny_dataset_pads_to_full_batch():
    class Tiny:
        def __len__(self):
            return 5

        def __getitem__(self, i, rng=None):
            return np.zeros((2, 2, 3), np.float32), i

    # pad (123) far exceeds n (5): the permutation must tile, not truncate
    loader = ShardedLoader(Tiny(), batch_size=128, shuffle=False,
                           host_id=0, num_hosts=1)
    assert len(loader) == 1
    batches = list(loader)
    assert batches[0][0].shape[0] == 128
    np.testing.assert_array_equal(loader.valid_mask(0)[:5], [1] * 5)
    assert loader.valid_mask(0)[5:].sum() == 0


def test_len_and_valid_mask_skip_the_permutation_and_cache_indices(monkeypatch):
    """__len__/valid_mask used to recompute the full O(n) epoch permutation
    on EVERY call (review finding): derive lengths arithmetically, compute
    the permutation once per epoch, and invalidate on set_epoch."""
    import ddp_classification_pytorch_tpu.data.loader as loader_mod

    class Tiny:
        def __len__(self):
            return 10

        def __getitem__(self, i, rng=None):
            return np.zeros((2, 2, 3), np.float32), 0

    calls = []
    real = loader_mod.shard_indices_for_host
    monkeypatch.setattr(loader_mod, "shard_indices_for_host",
                        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])

    loader = ShardedLoader(Tiny(), batch_size=4, shuffle=False,
                           host_id=0, num_hosts=1)
    assert len(loader) == 3 and len(loader) == 3
    loader.valid_mask(0)
    loader.valid_mask(2)
    assert calls == []  # pure arithmetic — no permutation materialized

    idx0 = loader._epoch_indices()
    assert loader._epoch_indices() is idx0  # cached within the epoch
    assert calls == [1]
    loader.set_epoch(1)
    idx1 = loader._epoch_indices()
    assert calls == [1, 1]  # set_epoch invalidated the cache
    assert loader._epoch_indices() is idx1
    np.testing.assert_array_equal(idx0, idx1)  # shuffle=False: same order


def test_abandoned_iteration_does_not_deadlock():
    class Slow:
        def __len__(self):
            return 64

        def __getitem__(self, i, rng=None):
            return np.zeros((2, 2, 3), np.float32), 0

    import threading

    loader = ShardedLoader(Slow(), batch_size=8, shuffle=False, prefetch=1,
                           host_id=0, num_hosts=1)
    it = iter(loader)
    next(it)
    del it  # abandon mid-epoch; producer must exit, not hang on a full queue
    for _ in range(50):
        if threading.active_count() <= 2:
            break
        import time
        time.sleep(0.1)
    # no strict assert on thread count (pytest has helpers), but a second
    # full iteration must work — would hang if the producer deadlocked
    assert len(list(loader)) == 8


# --- the Python path fills a batch in place ---------------------------------

SEED, EPOCH = 4321, 2


class RowsDataset:
    """40 samples of one kind; `kind` picks what a row is and whether it
    reads its generator."""

    def __init__(self, kind):
        self.kind = kind

    def __len__(self):
        return 40

    def __getitem__(self, i, rng=None):
        kind = self.kind
        if kind == "uint8_hwc":
            return np.full((5, 4, 3), i, np.uint8) + np.arange(3, dtype=np.uint8), i % 7
        if kind == "uint8_pages":  # a batch large enough to be mapped, which
            # is when the CPU backend's device arrays alias the host's
            return np.full((128, 128, 3), i, np.uint8), i
        if kind == "float32":
            return np.full((3, 3, 3), i / 7, np.float32), np.int64(i)
        if kind == "plc_triple":
            return np.full((2, 2, 3), i, np.float32), i % 3, i
        if kind == "tokens":  # data/tokens.py: two halves of one int32 row
            row = np.arange(i, i + 9, dtype=np.int32)
            return row[:-1], row[1:]
        if kind == "draws":  # every draw the repo's transforms make
            a = rng.uniform(0.08, 1.0)
            b = rng.integers(0, 9)
            c = rng.normal(size=(2, 3))
            d = rng.permutation(6)[:3]
            return (c * a + d).astype(np.float32), int(b)
        if kind == "rng_or":  # data/cifar.py:70
            rng = rng or np.random.default_rng()
            return rng.integers(0, 256, (4, 4, 3), dtype=np.uint8), i
        raise AssertionError(kind)


def stack_reference(dataset, indices, seed, epoch):
    """The algorithm this loader had: a generator a row, a list, `np.stack`."""
    items = []
    for j, i in enumerate(indices):
        rng = np.random.default_rng((seed, epoch, int(i), j))
        item = dataset.__getitem__(int(i), rng)
        items.append((item[0], item[1]))
    return (np.stack([im for im, _ in items]),
            np.asarray([lb for _, lb in items], np.int32))


def assert_batches_are_reference(loader, batches):
    indices = loader._epoch_indices()
    assert len(batches) == len(loader) > 0
    for b, (images, labels) in enumerate(batches):
        sl = indices[b * loader.batch_size:(b + 1) * loader.batch_size]
        ref_images, ref_labels = stack_reference(
            loader.dataset, sl, loader.seed, loader.epoch)
        for got, ref in ((images, ref_images), (labels, ref_labels)):
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert got.tobytes() == ref.tobytes(), f"batch {b}"


@pytest.mark.parametrize("batch,workers", [
    (16, 1), (16, 2), (16, 8),
    (3, 8),    # fewer rows than workers
    (13, 8),   # rows 1.. not divisible into equal runs
    (1, 8),    # row 0 is the batch
])
@pytest.mark.parametrize("kind", ["uint8_hwc", "float32", "plc_triple",
                                  "tokens", "draws", "rng_or"])
def test_in_place_batches_are_the_stacked_batches(kind, batch, workers):
    loader = ShardedLoader(RowsDataset(kind), batch_size=batch, shuffle=True,
                           seed=SEED, num_workers=workers, host_id=0,
                           num_hosts=1)
    loader.set_epoch(EPOCH)
    assert_batches_are_reference(loader, list(loader))


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_row_generator_is_keyed_by_seed_epoch_sample_and_row(workers):
    """The draws of row j are `default_rng((seed, epoch, i, j))`'s, whatever
    thread loads the row and however the batch is cut."""
    loader = ShardedLoader(RowsDataset("draws"), batch_size=8, shuffle=True,
                           seed=SEED, num_workers=workers, host_id=0,
                           num_hosts=1)
    loader.set_epoch(EPOCH)
    indices = loader._epoch_indices()
    for b, (images, labels) in enumerate(loader):
        for j in range(8):
            rng = np.random.default_rng((SEED, EPOCH, int(indices[b * 8 + j]), j))
            a, lb = rng.uniform(0.08, 1.0), rng.integers(0, 9)
            c = rng.normal(size=(2, 3))
            d = rng.permutation(6)[:3]
            np.testing.assert_array_equal(images[j], (c * a + d).astype(np.float32))
            assert labels[j] == lb


class OddRowDataset:
    """Sample 11 differs from the rest in `what`."""

    def __init__(self, what):
        self.what = what

    def __len__(self):
        return 16

    def __getitem__(self, i, rng=None):
        odd = i == 11
        if self.what == "shape" and odd:  # would broadcast into (4, 4, 3)
            return np.ones((4, 4, 1), np.uint8), 0
        if self.what == "dtype" and odd:  # would be cast to uint8
            return np.full((4, 4, 3), 0.5, np.float32), 0
        if self.what == "label_shape" and odd:
            return np.ones((4, 4, 3), np.uint8), np.zeros(2, np.int32)
        return np.ones((4, 4, 3), np.uint8), 0


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("what", ["shape", "dtype", "label_shape"])
def test_row_unlike_row_zero_raises_at_the_iteration_site(what, workers):
    loader = ShardedLoader(OddRowDataset(what), batch_size=8, shuffle=False,
                           num_workers=workers, host_id=0, num_hosts=1)
    it = iter(loader)
    next(it)  # batch 0 holds samples 0-7: fine
    with pytest.raises(ValueError, match="same shape and dtype"):
        next(it)


def buffer_counts(name):
    c = spans.counters()
    return [c.get(("input_batch_buffers_total",
                   (("loader", name), ("reused", r))), 0) for r in "01"]


@pytest.mark.parametrize("keep", ["batches", "views", "device_arrays"])
def test_kept_batches_are_never_written_again(keep):
    """Whoever holds a batch, a view of it, or a CPU device array that
    aliases it, owns it: at the end of the epoch every one is intact. A
    buffer comes back only where freeing it would have been as safe: never
    under a batch or a view, and under a device array only where the
    backend copied."""
    name = f"keeps_{keep}"
    loader = ShardedLoader(RowsDataset("uint8_pages"), batch_size=8,
                           shuffle=True, seed=SEED, num_workers=4, prefetch=1,
                           host_id=0, num_hosts=1, name=name)
    if keep == "batches":
        kept = list(loader)
    elif keep == "views":
        kept = [(images[:], labels) for images, labels in loader]
    else:
        from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

        mesh = meshlib.make_mesh()  # conftest's eight CPU devices, a row each
        kept = [meshlib.make_global_array(batch, mesh) for batch in loader]
        kept = [(np.asarray(images), np.asarray(labels)) for images, labels in kept]
    assert_batches_are_reference(loader, kept)
    fresh, reused = buffer_counts(name)
    assert fresh + reused == len(loader)
    assert reused == 0 or keep == "device_arrays"


def test_dropped_batches_give_their_buffers_back():
    loader = ShardedLoader(RowsDataset("uint8_hwc"), batch_size=4,
                           shuffle=True, seed=SEED, num_workers=2, prefetch=1,
                           host_id=0, num_hosts=1, name="drops")
    seen = 0
    for b, (images, labels) in enumerate(loader):
        ref = stack_reference(loader.dataset,
                              loader._epoch_indices()[b * 4:(b + 1) * 4],
                              SEED, 0)
        assert images.tobytes() == ref[0].tobytes()
        seen += 1
    fresh, reused = buffer_counts("drops")
    assert fresh + reused == seen == 10
    # one being filled, one queued, one in this loop's hand, one more the
    # producer still names
    assert fresh <= 4 and reused >= 6
    load = [s for s in spans.snapshot()
            if s.name == "input.load" and s.ids["loader"] == "drops"]
    assert [(s.ids["rows"], s.ids["chunks"]) for s in load] == [(4, 2)] * 10


def test_warmup_does_not_shift_milestones():
    cfg = OptimConfig(lr=1.0, schedule="multistep", milestones=(2, 4),
                      gamma=0.1, warmup_iters=10, warmup_start_lr=0.0)
    sched = build_schedule(cfg, steps_per_epoch=10)
    # milestones anchored at global steps 20 and 40 despite 10-iter warmup
    assert float(sched(5)) == pytest.approx(0.5)      # mid-warmup ramp
    assert float(sched(15)) == pytest.approx(1.0)     # post-warmup, pre-decay
    assert float(sched(20)) == pytest.approx(0.1)     # first milestone on time
    assert float(sched(40)) == pytest.approx(0.01)    # second milestone on time


def test_warmup_rescales_under_grad_accum():
    # warmup_iters counts ITERATIONS; with accumulation k=2 the schedule
    # advances once per optimizer step, so warmup spans warmup_iters/k steps
    cfg = OptimConfig(lr=1.0, schedule="constant", warmup_iters=10,
                      warmup_start_lr=0.0)
    sched = build_schedule(cfg, steps_per_epoch=10, grad_accum=2)
    assert float(sched(4)) == pytest.approx(0.8)   # 4/5 through a 5-step ramp
    assert float(sched(5)) == pytest.approx(1.0)


def test_lr_trace_identical_across_grad_accum():
    """LR-schedule semantics under accumulation: K=4 and K=1 runs with the
    SAME optimizer-step budget produce IDENTICAL LR traces. grad_accum
    slices microbatches out of one loader batch inside the jitted step, so
    steps_per_epoch already counts optimizer steps and milestones need no
    rescaling; only warmup_iters (reference semantics: microbatch
    ITERATIONS) converts ÷K — equal optimizer-step warmups (K=4 ×
    warmup 20 vs K=1 × warmup 5) must then trace identically everywhere.
    A reintroduced per-microbatch schedule step (the classic off-by-K
    accumulation bug) shifts every milestone by K× and fails here."""
    base = dict(lr=1.0, schedule="multistep", milestones=(2, 4), gamma=0.1,
                warmup_start_lr=0.0)
    k4 = build_schedule(OptimConfig(warmup_iters=20, **base),
                        steps_per_epoch=10, grad_accum=4)
    k1 = build_schedule(OptimConfig(warmup_iters=5, **base),
                        steps_per_epoch=10, grad_accum=1)
    trace4 = [float(k4(s)) for s in range(50)]
    trace1 = [float(k1(s)) for s in range(50)]
    assert trace4 == pytest.approx(trace1)
    # and the trace is the REAL one: warmup ramp then on-time milestones
    assert trace4[2] == pytest.approx(0.4)
    assert trace4[20] == pytest.approx(0.1)
    assert trace4[40] == pytest.approx(0.01)


def test_optimizer_applies_schedule_once_per_update_under_grad_accum():
    """The accumulated step hands build_optimizer ONE summed/meaned
    gradient per loader batch — every tx.update IS an optimizer step. A
    resurrected optax.MultiSteps wrapper (which would treat each update
    as a microbatch and only apply every K-th) shifts the whole decay
    trace and fails here."""
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.train.schedule import build_optimizer

    params = {"w": jnp.ones((3,))}
    grads = {"w": jnp.ones((3,))}
    cfg = OptimConfig(optimizer="sgd", momentum=0.0, lr=1.0,
                      schedule="multistep", milestones=(1,), gamma=0.1)
    tx = build_optimizer(cfg, steps_per_epoch=2, grad_accum=4)
    opt_state = tx.init(params)
    mags = []
    for _ in range(4):
        updates, opt_state = tx.update(grads, opt_state, params)
        mags.append(float(-updates["w"][0]))
    # milestone (epoch 1 = optimizer step 2) lands after two UPDATES,
    # exactly as in a grad_accum=1 run
    assert mags == pytest.approx([1.0, 1.0, 0.1, 0.1])


def test_head_param_group_hyperparams():
    # The reference's single optimizer spans TWO param groups (backbone, ARC
    # margin head — arc_main.py:248-253). head_lr/head_weight_decay diverge
    # the groups; unset they inherit and the optimizer is one transform.
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.train.schedule import build_optimizer

    params = {
        "backbone": {"w": jnp.ones((3,))},
        "margin": {"weight": jnp.ones((3,))},
    }
    grads = {
        "backbone": {"w": jnp.ones((3,))},
        "margin": {"weight": jnp.ones((3,))},
    }

    cfg = OptimConfig(optimizer="sgd", momentum=0.0, lr=0.1, head_lr=0.2,
                      schedule="constant")
    tx = build_optimizer(cfg, steps_per_epoch=10)
    updates, _ = tx.update(grads, tx.init(params), params)
    assert float(updates["backbone"]["w"][0]) == pytest.approx(-0.1)
    assert float(updates["margin"]["weight"][0]) == pytest.approx(-0.2)

    # head_weight_decay=0 while base decays: only backbone feels the decay
    cfg = OptimConfig(optimizer="sgd", momentum=0.0, lr=0.1,
                      weight_decay=0.5, head_weight_decay=0.0,
                      schedule="constant")
    tx = build_optimizer(cfg, steps_per_epoch=10)
    updates, _ = tx.update(grads, tx.init(params), params)
    # base: -(lr·(g + wd·p)) = -0.1·1.5 ; head: -0.1·1.0
    assert float(updates["backbone"]["w"][0]) == pytest.approx(-0.15)
    assert float(updates["margin"]["weight"][0]) == pytest.approx(-0.1)

    # unset → identical hyperparams per group, single-transform path
    cfg = OptimConfig(optimizer="sgd", momentum=0.0, lr=0.1, schedule="constant")
    tx = build_optimizer(cfg, steps_per_epoch=10)
    updates, _ = tx.update(grads, tx.init(params), params)
    assert float(updates["margin"]["weight"][0]) == pytest.approx(-0.1)


def test_head_group_flags_reject_headless_tree():
    # --head_lr on a workload without a margin head must fail loudly, not
    # silently train everything at the base hyperparams
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.train.schedule import build_optimizer

    params = {"backbone": {"w": jnp.ones((3,))}}
    cfg = OptimConfig(optimizer="sgd", momentum=0.0, lr=0.1, head_lr=0.2,
                      schedule="constant")
    tx = build_optimizer(cfg, steps_per_epoch=10)
    with pytest.raises(ValueError, match="no head param group"):
        tx.init(params)
