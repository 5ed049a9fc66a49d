"""The unified training loop — one Trainer for all five reference silos.

Replaces the four near-duplicate epoch loops (`train_and_valid`
BASELINE/main.py:258-317 and ARCFACE/arc_main.py:302-417, `train`+`evaluate`
CDR/main.py:218-386, `Train`+`TestNested` NESTED/train.py:227-453) with one
loop parameterized by the config tree. Shape of one epoch, matching the
reference's observable behavior:

    loader.set_epoch(e)              # sampler.set_epoch, BASELINE/main.py:269
    for each batch: jitted train step (+ every-N console line with ETA, :284-303)
    evaluate (exact cross-shard reduction; nested: vectorized all-K sweep)
    record epoch line → output.txt / history.json   (:254-256; NESTED:444-445)
    checkpoint (per-epoch and/or best-only; host-0 writes)

TPU-first details the reference has no analogue for:
- batches cross host→device as raw uint8 pixels by default
  (`data.input_dtype` — ¼ the H2D bytes of normalized float32), with
  normalization + the train flip fused into the jitted step's input read
  (train/steps.py::device_input_epilogue);
- batches go host→device through `make_global_array` (per-host shard of a
  global batch-sharded jax.Array) on a background stager thread
  (`data/device_prefetch.py`) that keeps `data.device_prefetch` device
  batches staged ahead of the step loop — async dispatch hides device
  latency, the stager hides the HOST assembly+H2D latency (the full
  pin_memory/non_blocking overlap; `--device_prefetch 0` restores
  synchronous in-loop assembly);
- metrics come back as device scalars only when a log line is actually
  printed (the reference syncs `.item()` every logged step);
- LR schedule/warmup live inside the optimizer (schedule.py), so there is no
  host-side `scheduler.step()` ordering bug (CDR/main.py:366 decays one epoch
  early; documented divergence — we follow correct semantics).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ..analysis.compile_sentinel import CompileSentinel
from ..config import Config
from ..data.device_prefetch import DevicePrefetcher
from ..data.loader import ShardedLoader
from ..data.imagefolder import ImageFolderDataset
from ..data import native as native_mod
from ..data.native import NativeBatcher
from ..data.synthetic import SyntheticDataset
from ..data.transforms import build_transform
from ..models.factory import model_report
from ..obs import spans
from ..obs.registry import Registry
from ..ops.nested import best_k
from ..parallel import fleet as fleetlib
from ..parallel import mesh as meshlib
from ..utils import cache as progcache
from ..utils import chaos as chaoslib
from ..utils.logging import EtaLogger, RecordWriter, host0_print, is_host0
from .checkpoint import CheckpointManager
from .heartbeat import StepHeartbeat
from .sentinel import SentinelDiverged, StepSentinel
from .state import create_train_state, param_count
from .steps import make_eval_step, make_nested_eval_step, make_train_step


def dataset_transform_preset(d) -> Optional[str]:
    """Transform-preset name `build_datasets` uses for this DataConfig, or
    None when the dataset kind has no image transform (synthetic). Delegates
    to `data.transforms.preset_for_dataset`, the single source of truth it
    shares with the train step's device-flip gate."""
    from ..data.transforms import preset_for_dataset

    return preset_for_dataset(d.dataset, d.transform)


def make_native_batcher(ds, cfg: Config, train: bool) -> Optional[NativeBatcher]:
    """NativeBatcher for `ds` iff the C++ dataplane applies to this config
    (same eligibility the Trainer uses), else None. Honors the wire format:
    with `data.input_dtype == "uint8"` the batcher emits quantized uint8
    pixels (train flip deferred to the device epilogue)."""
    d = cfg.data
    if (d.native_loader and d.dataset == "imagefolder"
            and d.transform in NativeBatcher.SUPPORTED
            and hasattr(ds, "paths") and NativeBatcher.available()):
        return NativeBatcher(ds, d.transform, train, d.image_size,
                             d.train_crop_size, cfg.run.seed, d.num_workers,
                             out_dtype=d.input_dtype)
    return None


def build_datasets(cfg: Config) -> Tuple[Any, Any]:
    """(train_ds, val_ds) from DataConfig — the reference's per-silo dataset
    blocks (BASELINE/main.py:124-125, CDR/main.py:296, NESTED/train.py:342)."""
    d = cfg.data
    from ..data.transforms import INPUT_DTYPES

    if d.input_dtype not in INPUT_DTYPES:
        # construction-time ValueError → the CLI maps it to rc 2
        raise ValueError(
            f"unknown data.input_dtype {d.input_dtype!r}; one of {INPUT_DTYPES}")
    if d.dataset == "synthetic":
        size = d.synthetic_size or 512
        train = SyntheticDataset(size, d.image_size, d.num_classes,
                                 seed=cfg.run.seed, out_dtype=d.input_dtype)
        val = SyntheticDataset(max(size // 4, d.batch_size), d.image_size,
                               d.num_classes, seed=cfg.run.seed,
                               item_offset=size, out_dtype=d.input_dtype)
        return train, val
    if d.dataset == "tokens":
        from ..data.tokens import TokenDataset

        seq_len = model_report(cfg.model).token_row_length()
        return (TokenDataset(d.train_dir, seq_len),
                TokenDataset(d.val_dir or d.train_dir, seq_len))
    preset = dataset_transform_preset(d)
    if preset is None:
        raise ValueError(f"unknown dataset {d.dataset!r}")
    t_train = build_transform(preset, train=True, image_size=d.image_size,
                              crop_size=d.train_crop_size,
                              out_dtype=d.input_dtype)
    t_val = build_transform(preset, train=False, image_size=d.image_size,
                            crop_size=d.train_crop_size,
                            out_dtype=d.input_dtype)
    if d.dataset == "imagefolder":
        train = ImageFolderDataset.from_root(
            d.train_dir, t_train, d.imgs_per_class, d.max_classes)
        val = ImageFolderDataset.from_root(
            d.val_dir or d.train_dir, t_val, d.imgs_per_class, d.max_classes)
        return train, val
    if d.dataset in ("cifar10", "cifar100"):
        from ..data.cifar import CIFARDataset

        train = CIFARDataset(d.train_dir, True, t_train, kind=d.dataset)
        val = CIFARDataset(d.val_dir or d.train_dir, False, t_val, kind=d.dataset)
        if d.num_classes != train.num_classes:
            raise ValueError(
                f"data.num_classes={d.num_classes} but {d.dataset} has "
                f"{train.num_classes} classes — the CLI sets both defaults "
                "when --dataset cifar10/cifar100 is passed")
        return train, val
    if d.dataset == "plc":
        # Clothing1M annotation layout (PLC/FolderDataset.py:9-75):
        # train_dir/val_dir are the data roots; annotations live under
        # <root>/annotations with key-list + label files per split
        from ..data.plc import PLCDataset

        train = PLCDataset.from_annotations(d.train_dir, "train", t_train,
                                            cls_size=d.imgs_per_class or 0)
        val = PLCDataset.from_annotations(d.val_dir or d.train_dir, "val", t_val)
        return train, val
    raise RuntimeError(  # unreachable unless the preset map and the branches drift
        f"dataset {d.dataset!r} has a transform preset but no build branch")


# HELP lines of the counters kept in obs/spans.py (the input pipeline's, and
# what the kernels count as the step is traced) in metrics.prom
_SPAN_COUNTER_HELP = {
    "input_batches_total": "batches the input pipeline handed to a loop",
    "input_starved_total": "of those, batches that were not staged yet when "
                           "the loop asked",
    "input_native_batches_total": "batches the native dataplane filled, by "
                                  "the dtype it wrote them in",
    "input_batch_buffers_total": "batches the loader's Python path filled, "
                                 "by whether the buffer was a recycled one",
    "flash_backward_total": "attention calls traced, by the backward their "
                            "shapes chose: one fused kernel or the split",
    "flash_tiles_total": "(q block, kv block) tiles of the attention "
                         "kernels launched as the step was traced, by the "
                         "call's mask: those that hold an allowed pair "
                         "(live) and those skipped or never walked",
    "vit_attention_total": "ViT attention modules traced, by the core their "
                           "shapes chose: the whole-row kernel pair (rows), "
                           "the dense op, the streaming kernels (flash) or "
                           "the ring",
}


class Trainer:
    def __init__(
        self,
        cfg: Config,
        train_ds: Optional[Any] = None,
        val_ds: Optional[Any] = None,
        mesh: Optional[Any] = None,
    ):
        with spans.span("setup.trainer") as whole:
            phases = self._setup(cfg, train_ds, val_ds, mesh)
        host0_print(
            f"[trainer] set-up: {whole.seconds:.2f} s ("
            + ", ".join(
                f"{p.name.split('.', 1)[1]} {p.seconds:.2f}"
                + "".join(f" {k}={v}" for k, v in p.ids.items())
                for p in phases)
            + ")")

    def _setup(self, cfg: Config, train_ds, val_ds, mesh) -> list:
        """Everything `__init__` builds -> its setup.* spans, in order."""
        phases: list = []

        def phase(name: str):
            phases.append(spans.span("setup." + name))
            return phases[-1]

        self.cfg = cfg
        # mid-run hang detector (inert at the default hang_timeout_s=0):
        # armed FIRST — mesh/loader/state construction below already does
        # real backend work (param placement), so arming any later would
        # leave exactly the hang window this exists to close. The
        # timeout must exceed the slowest legitimate silent stretch (first
        # compile included — see RunConfig.hang_timeout_s).
        self._heartbeat = StepHeartbeat(
            cfg.run.hang_timeout_s, where=f"trainer[{cfg.workload}]").start()
        # fault injection (off unless run.fault_spec / CHAOS_FAULT_SPEC):
        # one-shot state persists under <out_dir>/chaos so a supervised
        # restart does not replay host-side faults. A malformed spec raises
        # ValueError here — construction-time, so the CLI maps it to rc 2.
        # process_index feeds the CHAOS_HOST per-host gate on pod drills.
        self.chaos = chaoslib.plan_for_run(cfg.run.fault_spec, cfg.run.out_dir,
                                           process_index=jax.process_index())
        if self.chaos:
            host0_print(f"[chaos] fault plan active: {self.chaos}")
        # observability spine: ONE registry per Trainer; the sentinel and
        # fleet register their instruments into it, and host 0 atomically
        # rewrites $OUT/metrics.prom at the log cadence + epoch end — a
        # scrape-by-file surface with no server and no hot-path cost
        # (updates happen only at existing host-sync points)
        self.obs = Registry()
        self._steps_counter = self.obs.counter(
            "train_steps_total", "optimizer steps dispatched")
        self._epochs_counter = self.obs.counter(
            "train_epochs_total", "epochs completed (train+eval+save cycle)")
        self._loss_gauge = self.obs.gauge(
            "train_loss", "mean train loss of the last completed epoch")
        self._val_top1_gauge = self.obs.gauge(
            "val_top1", "top-1 accuracy at the last eval")
        self._epoch_seconds_gauge = self.obs.gauge(
            "train_epoch_seconds", "wall seconds of the last epoch cycle")
        # pod coordination (parallel/fleet.py): epoch-boundary abort
        # propagation + SIGTERM deferral, multi-process runs only — a
        # single-process Trainer keeps today's behavior bit-for-bit.
        # Elastic pods keep the coordinator even at process_count()==1:
        # a lone survivor must still heartbeat its lease and detect a
        # recovered peer's fresh lease (PodReform) at epoch boundaries.
        elastic = fleetlib.elastic_enabled() and bool(cfg.run.out_dir)
        self.fleet = (fleetlib.FleetCoordinator(out_dir=cfg.run.out_dir
                                                if elastic else "",
                                                registry=self.obs)
                      if jax.process_count() > 1 or elastic else None)
        if self.fleet is not None and jax.process_count() > 1:
            self._defer_sigterm_to_epoch_boundary()
        # non-finite step policy: skip counting + rc-8 escalation
        # (train/sentinel.py); the streak carries across epochs
        self.sentinel = StepSentinel(cfg.run.max_bad_steps,
                                     registry=self.obs)
        # recompile guard (analysis/compile_sentinel.py): armed by run()
        # once the first eval'd epoch completes — by then every steady-state
        # program (train step, eval step, checkpoint gather) has compiled,
        # so any later compile is a signature drift worth flagging
        self.compile_sentinel = CompileSentinel(
            tag=f"trainer[{cfg.workload}]", log=host0_print)
        self._compile_sentinel_ready = False
        if train_ds is None:
            with phase("datasets"):
                train_ds, val_ds = build_datasets(cfg)
        # a model whose objective makes its inputs in the loader wraps them
        # here, whoever built them (the CLI above, or a caller)
        train_ds, val_ds = model_report(cfg.model).datasets(
            train_ds, val_ds, cfg.run.seed)
        self.train_ds, self.val_ds = train_ds, val_ds

        with phase("mesh"):
            spec = meshlib.MeshSpec(cfg.parallel.data_axis, cfg.parallel.model_axis,
                                    max(cfg.parallel.pipeline_stages, 1))
            if mesh is not None:
                self.mesh = mesh
            elif cfg.parallel.dcn_slices:
                # make_hybrid_mesh rejects pipeline_parallel > 1 (two-axis
                # layout only) — the spec is passed whole so that validation
                # actually sees the requested stages
                self.mesh = meshlib.make_hybrid_mesh(
                    spec, dcn_data_parallel=cfg.parallel.dcn_slices)
            else:
                self.mesh = meshlib.make_mesh(spec)

        with phase("loaders"):
            train_batcher = make_native_batcher(train_ds, cfg, train=True)
            val_batcher = make_native_batcher(val_ds, cfg, train=False)
            self.native_dataplane = train_batcher is not None
            if self.native_dataplane:
                host0_print("[trainer] native C++ dataplane active")
            elif native_mod.build_error:
                # the PIL fallback stays, but never silently: say what the
                # compiler / loader said
                host0_print("[trainer] native dataplane unavailable, PIL "
                            f"fallback: {native_mod.build_error}")

            self.train_loader = ShardedLoader(
                train_ds, cfg.data.batch_size, shuffle=True, seed=cfg.run.seed,
                num_workers=cfg.data.num_workers, prefetch=cfg.data.prefetch,
                batcher=train_batcher, chaos=self.chaos or None)
            self.val_loader = ShardedLoader(
                val_ds, cfg.data.batch_size, shuffle=False, seed=cfg.run.seed,
                num_workers=cfg.data.num_workers, prefetch=cfg.data.prefetch,
                batcher=val_batcher, name="val")

        self.steps_per_epoch = max(len(self.train_loader), 1)
        with phase("init_state"):
            n0, s0 = progcache.compiled()
            self.model, self.tx, self.state = create_train_state(
                cfg, self.mesh, self.steps_per_epoch)
            # one jitted program and the seed's key: an eager op that
            # creeps into set-up shows here before it shows in seconds
            n1, s1 = progcache.compiled()
            spans.note(compiles=n1 - n0, compile_s=round(s1 - s0, 3))
            # what the model says it was built as, and its static counters
            self.report = model_report(cfg.model, self.mesh,
                                       cfg.parallel.pipeline_microbatches)
            spans.note(**self.report.built(
                cfg.data.batch_size * jax.process_count(), self.obs,
                cfg.data.image_size))

        with phase("build_steps"):
            self.train_step = make_train_step(cfg, self.model, self.tx,
                                              mesh=self.mesh,
                                              chaos=self.chaos or None)
            self.eval_step = make_eval_step(cfg, self.model, mesh=self.mesh)
            self.nested_eval_step = (
                make_nested_eval_step(cfg, self.model)
                if cfg.model.head == "nested" else None
            )

        self._setup_profiler()
        self.records = RecordWriter(cfg.run.out_dir) if cfg.run.write_records else None
        self.tb = None
        if cfg.run.tensorboard and is_host0():
            from ..utils.tensorboard import SummaryWriter

            self.tb = SummaryWriter(os.path.join(cfg.run.out_dir, "tb"))
        with phase("checkpoint"):
            self.ckpt = CheckpointManager(
                cfg.run.out_dir,
                save_every_epoch=cfg.run.save_every_epoch,
                best_only=cfg.run.save_best_only,
                keep=cfg.run.keep_checkpoints,
                async_save=cfg.run.async_checkpoint,
                chaos=self.chaos or None,
            )
            self.start_epoch = 0
            if cfg.run.resume:
                self.state = self.ckpt.restore(self.state, cfg.run.resume)
                # meta lives next to the checkpoint being resumed (which may be a
                # previous run's out_dir, not this one's)
                meta = CheckpointManager.meta_for_checkpoint(cfg.run.resume)
                self.start_epoch = int(meta.get("last_epoch", -1)) + 1
                self.ckpt.best_metric = meta.get("best_metric", float("-inf"))
                host0_print(f"resumed from {cfg.run.resume} at epoch {self.start_epoch}")
            elif cfg.run.auto_resume:
                # preemption recovery: restart command == start command; fresh
                # runs fall through with start_epoch 0 (nothing in out_dir yet).
                # On pods this is the resume CONSENSUS: host 0 alone scans/
                # verifies/quarantines and broadcasts its choice; every host
                # restores that exact file and the pod proves agreement with an
                # all-gathered digest (mismatch ⇒ PodInconsistent, rc 9 at the
                # CLI — never a silent split-brain resume). Single-process runs
                # take the plain restore_latest path unchanged.
                self.state, self.start_epoch = fleetlib.consensus_restore_latest(
                    self.ckpt, self.state)
                if self.start_epoch:
                    host0_print(
                        f"auto-resumed from {cfg.run.out_dir} at epoch {self.start_epoch}")
        if self.start_epoch and self.records is not None:
            # keep the pre-preemption curve: reload history.json truncated to
            # the restored epoch so the resumed run appends, not overwrites
            self.records.resume_at(self.start_epoch)
        if self.records is not None and self.native_dataplane:
            # the committed record itself proves which input path fed the run
            self.records.append_txt("# native C++ dataplane active")

        # host-side mirror of the global step counter (coordinates for the
        # sigterm fault hook): one device sync at init, then pure counting
        self._host_step = int(self.state.step) if self.chaos else 0

        dev0 = jax.devices()[0]
        host0_print(
            f"[trainer] workload={cfg.workload} arch={cfg.model.arch} "
            f"params={param_count(self.state):,} "
            f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
            f"devices={len(jax.devices())} "
            f"mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))} "
            f"steps/epoch={self.steps_per_epoch}"
        )
        return phases

    # ---------------------------------------------------------------- fleet --
    def _defer_sigterm_to_epoch_boundary(self) -> None:
        """Pod-mode SIGTERM: record abort intent instead of dying
        mid-collective. A single host exiting mid-epoch leaves its peers
        hanging at the next step's collective (the reference's fate);
        deferring to the epoch-boundary abort exchange turns one host's
        preemption into the SAME rc 143 on every host, which the
        supervisors then restart into one coordinated generation.
        Single-host runs keep the default die-now semantics."""
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return  # tests construct Trainers off-thread; signals need main

        def on_sigterm(signum, frame):
            self.fleet.note_abort(143, "SIGTERM received (preemption)")

        signal.signal(signal.SIGTERM, on_sigterm)

    def _sentinel_flush(self) -> None:
        """`sentinel.flush`, pod-aware: single-host raises straight to the
        CLI (rc 8, today's behavior); on a pod the divergence becomes
        abort intent and THIS host keeps issuing the epoch's remaining
        step collectives — its updates are identity while non-finite, and
        stopping early would hang every peer mid-epoch. The intent
        surfaces as rc 8 on every host at the epoch-boundary exchange."""
        try:
            self.sentinel.flush()
        except SentinelDiverged as e:
            if self.fleet is None:
                raise
            self.fleet.note_abort(SentinelDiverged.exit_code, str(e))

    def _write_prom(self) -> None:
        """Atomically rewrite ``$OUT/metrics.prom`` (host 0 only; inert
        without an out_dir). Called at the log cadence and epoch end —
        existing host-sync points, so the scrape file adds no new sync."""
        if self.cfg.run.out_dir and is_host0():
            self._publish_spans()
            self.obs.write_prom(
                os.path.join(self.cfg.run.out_dir, "metrics.prom"))

    def _publish_spans(self) -> None:
        """The span recorder's per-name totals and the input pipeline's
        counters (obs/spans.py; process totals) as counters of this
        registry: `span_seconds_total{span="train.input_wait"} /
        train_steps_total` is the input wait per step."""
        def publish(name, help_text, labels, value):
            c = self.obs.counter(name, help_text, labels)
            c.inc(max(value - c.value, 0.0))

        for name, (count, total_ns, _) in spans.totals().items():
            publish("span_seconds_total", "seconds spent inside spans of this "
                    "name (obs/spans.py)", {"span": name}, total_ns / 1e9)
            publish("span_count_total", "spans of this name recorded",
                    {"span": name}, count)
        for (name, labels), n in spans.counters().items():
            publish(name, _SPAN_COUNTER_HELP.get(name, ""),
                    {k: str(v) for k, v in labels}, n)

    # -------------------------------------------------------------- profile --
    def _setup_profiler(self) -> None:
        """Resolve the jax.profiler window once (SURVEY §5 tracing row)."""
        cfg = self.cfg
        self._prof_steps = cfg.run.profile_steps
        self._prof_dir = cfg.run.profile_dir or f"{cfg.run.out_dir}/profile"
        self._prof_active = False
        # skip a few warmup/compile steps when the epoch affords it
        self._prof_start_step = min(10, max(self.steps_per_epoch - self._prof_steps, 0))

    def _maybe_profile_start(self, epoch: int, step: int) -> None:
        if (self._prof_steps and epoch == 0 and not self._prof_active
                and step == self._prof_start_step):
            jax.profiler.start_trace(self._prof_dir)
            self._prof_active = True
            # for the length of the capture the program's spans are also
            # the profiler's: same names, on its clock, beside the device ops
            spans.RECORDER.annotate = self._profiler_annotation

    @staticmethod
    def _profiler_annotation(name: str, ids: Dict[str, Any]):
        if name == "train.step_dispatch":
            return jax.profiler.StepTraceAnnotation(name, step_num=ids["step"])
        return jax.profiler.TraceAnnotation(name, **ids)

    def _profile_stop(self) -> None:
        spans.RECORDER.annotate = None
        jax.profiler.stop_trace()
        self._prof_active = False

    def _maybe_profile_stop(self, epoch: int, step: int, metrics) -> None:
        if not self._prof_active:
            return
        done = step - self._prof_start_step + 1 >= self._prof_steps
        if done or step == self.steps_per_epoch - 1:  # never leak past epoch 0
            jax.block_until_ready(metrics)
            self._profile_stop()
            self._prof_steps = 0
            host0_print(f"[trainer] profiler trace captured → {self._prof_dir}")

    # ---------------------------------------------------------------- train --
    def _device_prefetcher(self, loader, assemble=None) -> DevicePrefetcher:
        """Staged-batch view of `loader` at the configured depth: batch
        assembly + H2D run on a stager thread (depth 0 = inline). With
        `data.h2d_overlap`, fetch and H2D transfer additionally pipeline
        on two threads (double-buffered dispatch)."""
        return DevicePrefetcher(loader, self.mesh,
                                depth=self.cfg.data.device_prefetch,
                                assemble=assemble,
                                overlap=self.cfg.data.h2d_overlap)

    def _log_sync(self, epoch: int, step: int, metrics, eta) -> None:
        """What the loop does every `log_every` steps; the one place it
        waits for the device inside an epoch."""
        if eta is not None:
            # the only host sync per log_every steps (reference syncs .item()
            # on the same cadence, BASELINE:284-303)
            eta.maybe_log(epoch, step,
                          **{k: float(v) for k, v in metrics.items()
                             if v.ndim == 0})
        self.report.logged_step(metrics, self.obs)
        # flush is a device round-trip too, so reaching here is proof the
        # backend is answering — heartbeat it. It also raises
        # SentinelDiverged on a sustained-NaN streak (pod mode: noted as
        # abort intent instead — see _sentinel_flush).
        self._sentinel_flush()
        self._heartbeat.touch()
        if self.fleet is not None:
            # elastic lease heartbeat on the same cadence: a live mid-epoch
            # host must never look dead to a rejoiner's lease scan (inert on
            # non-elastic pods)
            self.fleet.refresh_lease()
        if self.compile_sentinel.armed:
            # mid-epoch recompile detection at the same cadence; warn-only
            # here — strict enforcement waits for the epoch boundary so a pod
            # never aborts mid-collective
            self.compile_sentinel.check(strict=False)
        # refresh the scrape file on the same cadence (atomic rewrite; host 0
        # only)
        self._write_prom()

    def train_epoch(self, epoch: int, eta: Optional[EtaLogger] = None) -> Dict[str, float]:
        self.train_loader.set_epoch(epoch)
        sums = None  # device-side accumulation: no per-step host sync, so the
        n_batches = 0  # host keeps dispatching ahead of the device
        # every next() of the staged-batch iterator is a train.input_wait span
        it = spans.timed("train.input_wait",
                         self._device_prefetcher(self.train_loader), epoch=epoch)
        try:
            with spans.span("train.epoch", epoch=epoch):
                for step, batch in it:
                    self._maybe_profile_start(epoch, step)
                    # the call returns once the step is enqueued (first call:
                    # traced, lowered, compiled or loaded from the cache), or
                    # later where the runtime's queue of steps is full
                    with spans.span("train.step_dispatch", step=step, epoch=epoch):
                        self.state, metrics = self.train_step(self.state, *batch)
                    self._maybe_profile_stop(epoch, step, metrics)
                    n_batches += 1
                    self._steps_counter.inc()  # host-side int; no device touch
                    sums = metrics if sums is None else jax.tree_util.tree_map(
                        jax.numpy.add, sums, metrics)
                    # device scalar only — the sentinel syncs it at flush points
                    self.sentinel.observe(metrics["step_ok"])
                    if self.chaos:
                        self._host_step += 1
                        self.chaos.maybe_sigterm(step=self._host_step - 1)
                        self.chaos.maybe_peer_dead(step=self._host_step - 1)
                        self.chaos.maybe_peer_slow(step=self._host_step - 1)
                        self.chaos.maybe_host_lost(step=self._host_step - 1)
                    if step % self.cfg.run.log_every == 0:
                        with spans.span("train.log_sync", step=step, epoch=epoch):
                            self._log_sync(epoch, step, metrics, eta)
        finally:
            # a mid-epoch exception (divergence, injected fault, loader IO)
            # must stop and join the stager thread — a leaked stager would
            # keep the old epoch's H2D copies running across a supervise.sh
            # restart
            it.close()
        self._sentinel_flush()
        if sums is None:
            return {"loss": 0.0, "top1": 0.0, "top3": 0.0,
                    "step_ok": 1.0, "grad_norm": 0.0}
        out = {k: float(v) / n_batches for k, v in sums.items()
               if v.ndim == 0}  # host sync; per-expert arrays stay out
        self._heartbeat.touch()
        return out

    # ----------------------------------------------------------------- eval --
    def _stage_eval_batch(self, b_idx: int, host_batch) -> Any:
        """Eval assemble hook, run on the stager thread: the per-batch
        `valid_mask` (wrap-padding mask, pure index arithmetic) is computed
        here so it also leaves the step loop's critical path."""
        images, labels = host_batch
        valid = self.val_loader.valid_mask(b_idx)
        return meshlib.make_global_array((images, labels, valid), self.mesh)

    def evaluate(self) -> Dict[str, float]:
        if self.nested_eval_step is not None:
            return self._evaluate_nested()
        totals = None  # device-side accumulation: a float() per batch would
        # serialize eval dispatch (4 device-gets/batch); sync once at the end
        it = iter(self._device_prefetcher(self.val_loader,
                                          assemble=self._stage_eval_batch))
        try:
            for batch in it:
                out = self.eval_step(self.state, *batch)
                totals = out if totals is None else jax.tree_util.tree_map(
                    jax.numpy.add, totals, out)
        finally:
            it.close()  # stop + join the stager on a mid-eval exception
        if totals is None:
            return {"val_loss": 0.0, "val_top1": 0.0, "val_top3": 0.0}
        totals = {k: float(v) for k, v in totals.items()}  # the one host sync
        self._heartbeat.touch()  # that sync proves the backend is answering
        n = max(totals["n"], 1.0)
        # an eval step may count its top-k hits over fewer positions than its
        # loss (block diffusion: the masked ones)
        n_top = max(totals.get("n_top", n), 1.0)
        return {
            "val_loss": totals["loss_sum"] / n,
            "val_top1": totals["top1"] / n_top,
            "val_top3": totals["top3"] / n_top,
        }

    def _evaluate_nested(self) -> Dict[str, float]:
        t1 = t3 = n_dev = None  # accumulate on device; one sync at the end
        it = iter(self._device_prefetcher(self.val_loader,
                                          assemble=self._stage_eval_batch))
        try:
            for batch in it:
                out = self.nested_eval_step(self.state, *batch)
                t1 = out["top1_k"] if t1 is None else t1 + out["top1_k"]
                t3 = out["top3_k"] if t3 is None else t3 + out["top3_k"]
                n_dev = out["n"] if n_dev is None else n_dev + out["n"]
        finally:
            it.close()  # stop + join the stager on a mid-eval exception
        if t1 is None:  # val set smaller than one global batch
            return {"val_top1": 0.0, "val_top3": 0.0, "best_k": 0}
        n = float(n_dev)  # host sync
        self._heartbeat.touch()
        acc, k = best_k(t1, np.float32(max(n, 1.0)))
        return {
            "val_top1": float(acc),
            "val_top3": float(t3[int(k)] / max(n, 1.0)),
            "best_k": int(k),
        }

    # ------------------------------------------------------------------ run --
    def run(self) -> Dict[str, float]:
        cfg = self.cfg
        eta = EtaLogger(self.steps_per_epoch, cfg.run.epochs, cfg.run.log_every)
        last: Dict[str, float] = {}
        if cfg.run.eval_first and self.start_epoch == 0:
            init_m = self.evaluate()
            host0_print("[initial eval] " +
                        " ".join(f"{k}={v:.4f}" for k, v in init_m.items()))
        try:
            for epoch in range(self.start_epoch, cfg.run.epochs):
                if self.compile_sentinel.armed:
                    # epoch-boundary enforcement point: every host compiles
                    # the same programs deterministically, so a strict raise
                    # here lands on every pod member together (same rc 2)
                    self.compile_sentinel.check(strict=cfg.run.strict_compile)
                elif self._compile_sentinel_ready:
                    # one full epoch cycle (train + eval + save) has
                    # completed — arming any earlier would flag the
                    # eval/gather first compiles; arming a cycle later (not
                    # at save time) keeps the async checkpoint's background
                    # compile out of scope
                    self.compile_sentinel.arm()
                    host0_print("[compile-sentinel] armed: steady state "
                                f"begins (strict={cfg.run.strict_compile})")
                t0 = time.time()
                train_m = self.train_epoch(epoch, eta)
                if self.fleet is not None:
                    # epoch-boundary control collective (the ONLY per-epoch
                    # pod sync): every host arrives here after the same
                    # number of step collectives, exchanges abort intent,
                    # and raises the same PodAbort rc when any host carries
                    # one — a deterministic stop propagates within one epoch
                    # instead of hanging peers (or tripping a misleading
                    # heartbeat rc 7). Runs BEFORE eval/save so a diverged
                    # epoch is neither evaluated nor checkpointed.
                    self.fleet.check()
                val_m = self.evaluate() if (epoch + 1) % cfg.run.eval_every == 0 else {}
                last = {**train_m, **val_m, "epoch_time": time.time() - t0}
                self._epochs_counter.inc()
                self._loss_gauge.set(last.get("loss", 0.0))
                for part in self.report.epoch_gauges(last):
                    self.obs.gauge(
                        f"train_{part}", "mean of the step's metric "
                        f"`{part}` over the last completed epoch "
                        "(docs/observability.md)").set(last[part])
                if "val_top1" in last:
                    self._val_top1_gauge.set(last["val_top1"])
                self._epoch_seconds_gauge.set(last["epoch_time"])
                self._write_prom()
                host0_print(
                    f"[epoch {epoch}] " + " ".join(f"{k}={v:.4f}" for k, v in last.items())
                )
                if self.records is not None:
                    self.records.log_epoch(epoch, **{k: v for k, v in last.items()})
                if self.tb is not None:
                    for k, v in last.items():
                        group = "val" if k.startswith("val_") else "train"
                        self.tb.add_scalar(f"{group}/{k}", v, epoch)
                    self.tb.flush()
                metric = val_m.get("val_top1")
                self.ckpt.save(self.state, epoch, metric=metric,
                               **({"best_k": val_m["best_k"]} if "best_k" in val_m else {}))
                if val_m:
                    self._compile_sentinel_ready = True  # arm at next epoch top
            # the drain below can block on device_gets for an in-flight
            # async save — that is backend work, so it stays under the
            # heartbeat (writes are atomic, so a fire mid-drain cannot
            # truncate; the supervisor's restart then auto-resumes into an
            # already-complete run and exits cleanly)
            self._heartbeat.touch()
            if self.compile_sentinel.armed:
                # surface the last epoch's recompiles before the release
                self.compile_sentinel.check(strict=cfg.run.strict_compile)
        finally:
            # every exit path — completion, strict-compile raise, PodAbort,
            # sentinel divergence, SIGTERM — must release the pxla DEBUG
            # logger; disarm is idempotent (refcounted module handler)
            self.compile_sentinel.disarm()
            # and must neither leak an in-flight profiler trace (a rc 8 /
            # PodAbort / PodReform exit mid-capture would leave the backend
            # tracing into a dead run dir) ...
            if self._prof_active:
                try:
                    self._profile_stop()
                except Exception:
                    pass  # teardown must not mask the original exception
                self._prof_active = False
            # ... nor drop buffered tensorboard scalars (close flushes;
            # idempotent, so the normal path needs no second call)
            if self.tb is not None:
                self.tb.close()
        self.ckpt.wait()  # land any in-flight async checkpoint before returning
        self._heartbeat.stop()
        self._write_prom()  # final scrape snapshot reflects the last epoch
        return last
