"""obs/spans.py — the program's span recorder — and the spans the trainer,
the loader and the prefetcher record through it. No assertion here is about
how long anything took: only which spans exist, with which ids, on which
threads, and in which order."""

import ast
import glob
import os
import sys
import threading
import time
from collections import Counter, defaultdict

import pytest
from tiny import tiny_cfg as base_cfg

from ddp_classification_pytorch_tpu.obs import spans
from ddp_classification_pytorch_tpu.train.loop import Trainer

PER_STEP = ("input.load", "input.assemble", "train.input_wait", "train.step_dispatch")


# ------------------------------------------------------------ the recorder --
def test_parent_is_the_open_span_of_the_same_thread():
    rec = spans.Recorder()
    seen = {}

    def other():
        with rec.span("worker.outer"):
            with rec.span("worker.inner"):
                seen["worker"] = True

    with rec.span("main.outer"):
        t = threading.Thread(target=other, name="other-thread")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive() and seen["worker"]
        with rec.span("main.inner"):
            pass
    by = {s.name: s for s in rec.snapshot()}
    assert by["main.outer"].parent is None and by["main.inner"].parent == "main.outer"
    # the other thread's spans opened while main.outer was open, and are not its children
    assert by["worker.outer"].parent is None and by["worker.inner"].parent == "worker.outer"
    assert by["worker.inner"].thread == "other-thread"
    assert by["main.inner"].thread == threading.current_thread().name
    for s in by.values():
        assert s.end_ns >= s.start_ns


def test_ids_survive_and_note_lands_on_the_innermost_open_span():
    rec = spans.Recorder()
    rec.note(lost=1)  # no span open: nothing happens
    with rec.span("a", step=3, epoch=1, loader="val"):
        with rec.span("b", step=3):
            rec.note(starved=1)
        rec.note(rows=7)
    a, b = (next(s for s in rec.snapshot() if s.name == n) for n in "ab")
    assert a.ids == {"step": 3, "epoch": 1, "loader": "val", "rows": 7}
    assert b.ids == {"step": 3, "starved": 1}


def test_ring_is_bounded_and_totals_keep_counting():
    rec = spans.Recorder(capacity=8)
    for k in range(50):
        with rec.span("x", step=k):
            pass
        assert len(rec.snapshot()) <= 8
    snap = rec.snapshot()
    assert [s.ids["step"] for s in snap] == list(range(42, 50))  # the newest, oldest first
    count, total_ns, max_ns = rec.totals()["x"]
    assert count == 50 and total_ns >= max_ns >= 0
    assert spans.RECORDER._ring.maxlen == spans.CAPACITY and 16384 <= spans.CAPACITY <= 65536


def test_timed_enumerates_and_records_one_span_per_item():
    rec = spans.Recorder()
    closed = []

    def source():
        try:
            yield from "abc"
        finally:
            closed.append(True)

    assert list(rec.timed("wait", source(), epoch=2)) == [(0, "a"), (1, "b"), (2, "c")]
    got = rec.snapshot()
    # three items, three spans: the next() that ended the iteration left none
    assert [(s.name, s.ids) for s in got] == [
        ("wait", {"step": k, "epoch": 2}) for k in range(3)]
    assert closed == [True]
    it = rec.timed("wait", source())
    assert next(it) == (0, "a")
    it.close()  # closing the generator closes the iterator beneath it
    assert closed == [True, True]
    assert rec._stack() == []


def test_a_span_that_raises_is_recorded_and_the_stack_unwinds():
    rec = spans.Recorder()
    with pytest.raises(KeyError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise KeyError("x")
    assert [s.name for s in rec.snapshot()] == ["inner", "outer"]
    assert rec._stack() == []


def test_counters_count_per_label_set():
    rec = spans.Recorder()
    rec.count("input_batches_total", loader="train")
    rec.count("input_batches_total", loader="train")
    rec.count("input_batches_total", loader="val")
    rec.count("input_starved_total", 3, loader="train")
    assert rec.counters() == {
        ("input_batches_total", (("loader", "train"),)): 2,
        ("input_batches_total", (("loader", "val"),)): 1,
        ("input_starved_total", (("loader", "train"),)): 3}


def test_annotate_wraps_spans_only_while_it_is_set():
    rec = spans.Recorder()
    log = []

    class Annotation:
        def __init__(self, name, ids):
            self.name = name
            log.append(("made", name, dict(ids)))

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    with rec.span("before"):
        pass
    assert log == []  # outside a capture no annotation object is made
    rec.annotate = Annotation
    with rec.span("during", step=1):
        rec.annotate = None  # the capture ends inside the span: it still exits its own
    with rec.span("after"):
        pass
    assert log == [("made", "during", {"step": 1}), ("enter", "during"), ("exit", "during")]
    assert [s.name for s in rec.snapshot()] == ["before", "during", "after"]


def test_many_threads_lose_no_update():
    rec = spans.Recorder(capacity=64)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for k in range(500):
                with rec.span("shared", step=k):
                    rec.count("hits")

        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.totals()["shared"][0] == 16 * 500
    assert rec.counters()[("hits", ())] == 16 * 500
    assert len(rec.snapshot()) == 64


def test_clock_pair_is_on_the_spans_clock():
    perf, unix = spans.clock_pair()
    with spans.Recorder().span("x") as s:
        pass
    assert perf <= s.start_ns
    # a span's Unix time through the pair agrees with the wall clock (loosely:
    # the two clocks drift, and this is no timing assertion)
    assert abs((s.start_ns - perf + unix) - time.time_ns()) < 60e9


def test_module_imports_only_the_standard_library():
    with open(spans.__file__) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "a relative import pulls in the package"
            names = [node.module]
        else:
            continue
        for n in names:
            assert n.split(".")[0] in sys.stdlib_module_names | {"__future__"}, n


# ---------------------------------------------- the trainer's own spans --
def tiny_cfg(out_dir):
    cfg = base_cfg("baseline", out_dir)
    cfg.data.image_size = 16
    cfg.data.synthetic_size = 96  # six steps of 16: two log syncs at log_every 4
    return cfg


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    mark = time.perf_counter_ns()
    tr = Trainer(tiny_cfg(tmp_path_factory.mktemp("spans")))
    tr.setup_spans = since(mark)
    yield tr
    tr.train_loader.close()
    tr.val_loader.close()


def since(mark_ns):
    """The process-global recorder's spans that started after `mark_ns`."""
    return [s for s in spans.snapshot() if s.start_ns >= mark_ns]


def test_setup_spans_nest_under_setup_trainer(trainer, capsys):
    by = {s.name: s for s in trainer.setup_spans}
    whole = by["setup.trainer"]
    assert whole.parent is None
    phases = ["setup.datasets", "setup.mesh", "setup.loaders", "setup.init_state",
              "setup.build_steps", "setup.checkpoint"]
    for a, b in zip(phases, phases[1:]):
        assert by[a].end_ns <= by[b].start_ns
    for name in phases:
        assert by[name].parent == "setup.trainer" and by[name].thread == whole.thread
        assert whole.start_ns <= by[name].start_ns and by[name].end_ns <= whole.end_ns
    # datasets handed in (as the benchmark does): no setup.datasets span
    mark = time.perf_counter_ns()
    cfg = tiny_cfg(trainer.cfg.run.out_dir)
    Trainer(cfg, train_ds=trainer.train_ds, val_ds=trainer.val_ds)
    names = [s.name for s in since(mark)]
    assert "setup.datasets" not in names and "setup.init_state" in names
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[trainer] set-up: ")]
    assert len(line) == 1 and "init_state" in line[0] and "datasets" not in line[0]
    init = [s for s in since(mark) if s.name == "setup.init_state"][-1]
    assert f"compiles={init.ids['compiles']} compile_s={init.ids['compile_s']}" in line[0]


def test_init_state_span_says_what_it_compiled(trainer):
    """One jitted program and the seed's key (train/state.py): the count and
    the seconds of the backend compiles that fell inside the span."""
    init = {s.name: s for s in trainer.setup_spans}["setup.init_state"]
    assert 1 <= init.ids["compiles"] <= 3
    assert 0.0 < init.ids["compile_s"] <= (init.end_ns - init.start_ns) * 1e-9


@pytest.mark.parametrize("depth,overlap,stager", [
    (2, False, "device-stager"), (2, True, "h2d-stager"), (0, False, None)])
def test_every_step_has_one_span_of_each_stage_with_the_same_step(
        trainer, depth, overlap, stager):
    trainer.cfg.data.device_prefetch = depth
    trainer.cfg.data.h2d_overlap = overlap
    before = spans.counters()
    mark = time.perf_counter_ns()
    trainer.train_epoch(0)
    got = since(mark)
    steps = trainer.steps_per_epoch
    assert steps == 6
    by = defaultdict(dict)
    for s in got:
        if s.name in PER_STEP:
            assert s.ids.get("loader", "train") == "train"
            assert s.ids["step"] not in by[s.name], f"two {s.name} for one step"
            by[s.name][s.ids["step"]] = s
    main = threading.current_thread().name
    for name in PER_STEP:
        assert sorted(by[name]) == list(range(steps)), name
    for k in range(steps):
        load, asm, wait, disp = (by[n][k] for n in PER_STEP)
        assert load.end_ns <= asm.start_ns and asm.end_ns <= wait.end_ns
        assert wait.end_ns <= disp.start_ns
        assert wait.parent == disp.parent == "train.epoch"
        assert wait.thread == disp.thread == main
        assert load.thread == "loader-producer"
        assert asm.thread == (stager or main)
        assert len({load.thread, asm.thread, wait.thread}) == (3 if stager else 2)
        assert load.ids["epoch"] == wait.ids["epoch"] == disp.ids["epoch"] == 0
        assert asm.ids["rows"] == 16 and asm.ids["bytes"] == 16 * 16 * 16 * 3 + 16 * 4
        assert wait.ids["starved"] in (0, 1)
    epoch = [s for s in got if s.name == "train.epoch"]
    assert len(epoch) == 1 and epoch[0].ids == {"epoch": 0} and epoch[0].parent is None
    syncs = [s for s in got if s.name == "train.log_sync"]
    assert [s.ids["step"] for s in syncs] == [0, 4]  # log_every = 4
    assert all(s.parent == "train.epoch" for s in syncs)
    # the counters: every batch handed over, the starved ones among them
    after = spans.counters()
    delta = {k[0]: after[k] - before.get(k, 0) for k in after
             if dict(k[1]).get("loader") == "train"}
    assert delta["input_batches_total"] == steps
    starved = sum(by["train.input_wait"][k].ids["starved"] for k in range(steps))
    assert delta.get("input_starved_total", 0) == starved
    if depth == 0:
        assert starved == steps  # no staged queue: the loop waits for every batch
    else:
        # the stager threads may stage batch 0 before the loop's first look
        assert by["train.input_wait"][0].ids["starved"] in (0, 1)


def test_the_val_loaders_spans_say_so(trainer):
    trainer.cfg.data.device_prefetch = 2
    trainer.cfg.data.h2d_overlap = False
    mark = time.perf_counter_ns()
    trainer.evaluate()
    got = since(mark)
    names = Counter(s.name for s in got)
    assert names["input.load"] == names["input.assemble"] == len(trainer.val_loader) > 0
    assert all(s.ids["loader"] == "val" for s in got if s.name.startswith("input."))
    assert not any(s.name.startswith("train.") for s in got)


def test_metrics_prom_holds_the_span_totals_and_input_counters(trainer):
    trainer.train_epoch(0)
    trainer._write_prom()
    with open(os.path.join(trainer.cfg.run.out_dir, "metrics.prom")) as f:
        prom = f.read()
    assert "# TYPE span_seconds_total counter" in prom
    rows = dict(ln.rsplit(" ", 1) for ln in prom.splitlines() if not ln.startswith("#"))
    totals = spans.totals()
    for name in PER_STEP + ("train.epoch", "train.log_sync", "setup.trainer",
                            "setup.init_state"):
        assert float(rows[f'span_count_total{{span="{name}"}}']) == totals[name][0]
        assert float(rows[f'span_seconds_total{{span="{name}"}}']) == pytest.approx(
            totals[name][1] / 1e9)
    batches = float(rows['input_batches_total{loader="train"}'])
    assert batches == spans.counters()[("input_batches_total", (("loader", "train"),))]
    assert 0 < float(rows['input_starved_total{loader="train"}']) <= batches
    # a second write publishes the same totals, not twice the totals
    trainer._write_prom()
    with open(os.path.join(trainer.cfg.run.out_dir, "metrics.prom")) as f:
        assert f.read() == prom


def test_profile_steps_capture_holds_the_programs_spans(tmp_path):
    """While `--profile_steps` captures, the program's spans are profiler
    annotations of the same names (the pattern of
    test_train_loop.test_profiler_window_captures_trace)."""
    from jax.profiler import ProfileData

    cfg = tiny_cfg(tmp_path)
    cfg.run.profile_steps = 2
    tr = Trainer(cfg)
    tr.run()
    assert tr._prof_active is False and spans.RECORDER.annotate is None
    paths = glob.glob(os.path.join(str(tmp_path), "profile", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert paths, "no xplane.pb under <out>/profile"
    names = Counter(e.name for plane in ProfileData.from_file(paths[-1]).planes
                    for line in plane.lines for e in line.events
                    if e.name.startswith(("train.", "input.")))
    assert names["train.step_dispatch"] == 2, names
    assert names["train.input_wait"] >= 1, names
