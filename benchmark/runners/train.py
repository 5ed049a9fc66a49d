"""The training runner: one `Trainer.train_epoch(0)` built from the
configuration's `cli.train` argv, measured from outside.

The benchmark adds nothing to the program and syncs its loop nowhere. Two
attributes of the built trainer are wrapped, in this file only:

(a) the iterator `trainer._device_prefetcher(...)` returns: each `next()`
    is timed (the input wait) and the epoch ends once the deadline passed;
(b) `trainer.train_step`: called unchanged; each step's `step_ok` goes to a
    watcher thread that waits for them in order and stamps every step's
    completion on the host clock.

The first `warmup_steps` completed steps are set-up (compile or cache load,
pipeline fill); the window is the next `--seconds` seconds of completions.

`correct` follows the first three steps of that same trainer (its own call
and feed) with the plain float32 reference: each step's loss, the first
gradient's per-leaf norms as the optimizer got them (from its state after
one step) and the per-leaf norm of what three steps changed. Weights are
made here from the seed (reference/common.py `make_params`) and installed
into the program; the reference runs after the window, once the program's
state is freed.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import queue
import shutil
import statistics
import threading
import time

import numpy as np

STEPS_COMPARED = 3
_FLIP_FOLD = 0x464C4950  # the program's fold_in tag for the flip stream


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; what they feed here (a uint32 key for
    the weights, numpy generators for the data) takes this. Same seed, same
    run. The program's own `--seed` stays at its default: the train step
    bakes `PRNGKey(seed + 1)` in as a constant, so a new program seed is a
    new program and compiles for 40 s (my chip run, PR 25)."""
    return seed % (2 ** 31 - 2)


def flip_masks(seed: int, steps: int, batch: int):
    """(steps, batch) bool: the rows the program's step mirrors on the
    device, derived as the program derives them — PRNGKey(seed + 1),
    fold_in(step), fold_in(_FLIP_FOLD), bernoulli(0.5)."""
    import jax

    base = jax.random.PRNGKey(seed + 1)
    return np.stack([
        np.asarray(jax.random.bernoulli(
            jax.random.fold_in(jax.random.fold_in(base, s), _FLIP_FOLD),
            0.5, (batch,)))
        for s in range(steps)])


def leaf_names(tree):
    import jax

    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
             for path, _ in paths]
    return names, [leaf for _, leaf in paths], treedef


def first_moment(opt_state, opt: dict):
    """The optimizer's first-moment tree and the factor that turns it into
    the first gradient after ONE step: sgd's momentum trace is g itself,
    adam's mu is (1 - b1) g."""
    field, factor = {"sgd": ("trace", 1.0),
                     "adam": ("mu", 1.0 / (1.0 - opt.get("b1", 0.9)))}[opt["kind"]]
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if field in getattr(node, "_fields", ()):  # an optax state tuple
            return getattr(node, field), factor
        if isinstance(node, (tuple, list)):
            stack.extend(node)
        elif isinstance(node, dict):
            stack.extend(node.values())
    raise ValueError(f"no {field!r} in the optimizer state")


class _Run:
    """What the two wrappers and the watcher share."""

    def __init__(self, warmup: int, seconds: float, t0: float):
        self.warmup, self.seconds, self.t0 = warmup, seconds, t0
        self.deadline = None          # host clock; set when warm-up completes
        self.window_start = None
        self.waits = []               # (start, seconds) of every next()
        self.dispatches = []          # (start, seconds) of every train_step call
        self.done = []                # (step, completion time, step_ok)
        self.compiles = []            # host times of backend compiles
        self.dispatched = 0
        self.watch_q: "queue.Queue" = queue.Queue()


def _timed_prefetcher(inner, run: _Run):
    class Timed:
        def __iter__(self):
            it = iter(inner)

            def gen():
                try:
                    while True:
                        if run.deadline is not None and time.perf_counter() > run.deadline:
                            return
                        t = time.perf_counter()
                        try:
                            batch = next(it)
                        except StopIteration:
                            return
                        run.waits.append((t, time.perf_counter() - t))
                        yield batch
                finally:
                    it.close()

            return gen()

    return Timed()


def _watcher(run: _Run, first: dict) -> None:
    import jax

    while True:
        item = run.watch_q.get()
        if item is None:
            return
        step, ok = item
        ok = float(np.asarray(ok))  # waits for the step, in dispatch order
        t = time.perf_counter()
        run.done.append((step, t, ok))
        if step == 0 and "moment" in first:
            # during warm-up: the first gradient leaves the device, so that
            # the window's memory is the program's own
            moment = first.pop("moment")
            first["moment_host"] = jax.device_get(moment)
            for leaf in jax.tree_util.tree_leaves(moment):
                leaf.delete()
        if step == run.warmup - 1:
            run.window_start = t
            run.deadline = t + run.seconds


def bench_marker(v):
    """A program of its own name on the device: where it ends in the trace
    and when the host saw it end tie the two clocks together."""
    return v + 1


def _tracer(run: _Run, trace_dir: str, out: dict, marker, mark_arg) -> None:
    """Profile a slice of a few seconds inside the window. Host tracing is
    off: the runtime's own host events (millions of `Transpose` a second
    while batches are laid out for the device) made a 3 s trace 130 MB and
    `stop_trace` 100 s (my chip run, PR 25). The wrappers' spans are kept on
    the host clock instead and a marker program at each end of the slice
    gives the offset between that clock and the trace's."""
    import jax

    while run.window_start is None:
        if out.get("abort"):
            return
        time.sleep(0.01)
    offset = min(2.0, 0.1 * run.seconds)
    length = min(3.0, 0.4 * run.seconds)
    time.sleep(max(0.0, run.window_start + offset - time.perf_counter()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    out["start"] = time.perf_counter()
    out["anchors"] = []
    for pause in (length, 0.0):
        marker(mark_arg).block_until_ready()
        out["anchors"].append(time.perf_counter())
        time.sleep(pause)
    out["stop"] = time.perf_counter()
    jax.profiler.stop_trace()
    out["stopped"] = time.perf_counter()


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def build_trainer(ctx, devices):
    """Config from the configuration's argv through the user's own parser,
    datasets from the mix's generator, Trainer on a mesh of the cell's
    chips. -> (trainer, cfg, arch, global batch)"""
    from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.loop import Trainer
    from ddp_classification_pytorch_tpu.utils.seeding import set_seed

    conf = ctx.config["rehearse"] if ctx.rehearse else ctx.config
    arch = conf["arch"]
    batch = conf["batch_per_chip"] * ctx.cell["chips"]
    gen = importlib.import_module(f"benchmark.traffic.{ctx.traffic['generator']}")
    os.makedirs(ctx.cache_dir, exist_ok=True)
    out_dir = os.path.join(ctx.cache_dir, "runs", ctx.cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    argv = (list(conf["argv"]) + gen.argv(ctx.traffic, ctx.cache_dir, ctx.rehearse)
            + ["--batchsize", str(batch), "--out", out_dir])
    log("cli.train argv: " + " ".join(argv))
    cfg = config_from_args(build_parser().parse_args(argv))
    set_seed(cfg.run.seed)
    train_ds, val_ds = gen.datasets(ctx.traffic, cfg, program_seed(ctx.seed),
                                    batch, ctx.rehearse)
    spec = meshlib.MeshSpec(cfg.parallel.data_axis, cfg.parallel.model_axis,
                            max(cfg.parallel.pipeline_stages, 1))
    mesh = meshlib.make_mesh(spec, devices=devices)
    trainer = Trainer(cfg, train_ds=train_ds, val_ds=val_ds, mesh=mesh)
    return trainer, cfg, arch, batch


def install_weights(trainer, ref, arch, seed: int):
    """Seeded weights from the reference's own spec, made on the device in
    one jitted call with the program's shardings, installed in the
    program's state. -> (leaf names, a second copy for the three-step
    difference). A name or shape the program does not have is an error."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference.common import make_params

    spec = ref.param_spec(arch)
    names, leaves, treedef = leaf_names(trainer.state.params)
    have = {n: tuple(x.shape) for n, x in zip(names, leaves)}
    want = {n: tuple(s[0]) for n, s in spec.items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise SystemExit(f"the reference's leaves are not the program's: {odd}")
    shardings = jax.tree_util.tree_unflatten(treedef, [x.sharding for x in leaves])

    def make(seed_):
        flat = make_params(spec, seed_)
        return jax.tree_util.tree_unflatten(treedef, [flat[n] for n in names])

    make = jax.jit(make, out_shardings=shardings)
    s = jnp.asarray(program_seed(seed), jnp.uint32)
    trainer.state = trainer.state.replace(params=make(s))
    return names, make(s)


def run(ctx, devices) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import monitoring
    from ddp_classification_pytorch_tpu.utils import cache as progcache

    progcache.enable_persistent_cache()
    conf = ctx.config["rehearse"] if ctx.rehearse else ctx.config
    warmup = int(ctx.config["warmup_steps"])
    run_ = _Run(warmup, ctx.seconds, ctx.t0)
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: run_.compiles.append(time.perf_counter())
        if event == "/jax/core/compile/backend_compile_duration" else None)
    ref = importlib.import_module(f"benchmark.reference.{ctx.config['reference']}")

    t_build = time.perf_counter()
    trainer, cfg, arch, batch = build_trainer(ctx, devices)
    t_weights = time.perf_counter()
    names, p0 = install_weights(trainer, ref, arch, ctx.seed)
    opt = ctx.config["optimizer"]

    def norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(tree)]

    norms_fn = jax.jit(norms)
    copy_fn = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    diff_fn = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(jnp.subtract, a, b)))

    # ---- the two wrappers ------------------------------------------------
    real_prefetcher = trainer._device_prefetcher
    trainer._device_prefetcher = (
        lambda loader, assemble=None:
        _timed_prefetcher(real_prefetcher(loader, assemble), run_))
    real_step = trainer.train_step
    first = {"batches": [], "loss": [], "p0": p0}

    def step(state, images, labels):
        i = run_.dispatched
        run_.dispatched += 1
        if i < STEPS_COMPARED:
            first["batches"].append((images, labels))
        t = time.perf_counter()
        state, metrics = real_step(state, images, labels)
        run_.dispatches.append((t, time.perf_counter() - t))
        if i < STEPS_COMPARED:
            first["loss"].append(metrics["loss"])
            if i == 0:
                moment, factor = first_moment(state.opt_state, opt)
                first["grad0"] = (norms_fn(moment), factor)
                # the gradient itself, for the watcher to take to the host
                # (a copy: the state's own buffers are donated to step 1)
                first["moment"] = copy_fn(moment)
            if i == STEPS_COMPARED - 1:
                first["dparam"] = diff_fn(state.params, first.pop("p0"))
        first["last"] = metrics
        run_.watch_q.put((i, metrics["step_ok"]))
        return state, metrics

    trainer.train_step = step
    del p0

    watcher = threading.Thread(target=_watcher, args=(run_, first), daemon=True,
                               name="bench-watcher")
    watcher.start()
    trace_dir = os.path.join(ctx.cache_dir, "trace", ctx.cell["name"])
    trace_out: dict = {}
    tracer = None
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        marker = jax.jit(bench_marker)
        mark_arg = jax.device_put(np.int32(0), devices[0])
        marker(mark_arg).block_until_ready()  # compiled in set-up
        tracer = threading.Thread(
            target=_tracer, args=(run_, trace_dir, trace_out, marker, mark_arg),
            daemon=True, name="bench-tracer")
        tracer.start()

    # ---- the one call ----------------------------------------------------
    t_epoch = time.perf_counter()
    try:
        trainer.train_epoch(0)
    finally:
        trace_out["abort"] = True
        run_.watch_q.put(None)
        watcher.join(timeout=120)
        if tracer is not None:
            tracer.join(timeout=120)
    t_end = time.perf_counter()
    if run_.window_start is None:
        raise SystemExit(f"the epoch ended after {len(run_.done)} steps, before "
                         f"{warmup} warm-up steps completed")

    ws, we = run_.window_start, run_.window_start + ctx.seconds
    in_window = [(s, t, ok) for s, t, ok in run_.done if ws < t <= we]
    attempted = len(in_window)
    if not attempted:
        raise SystemExit(f"no step completed in the window of {ctx.seconds:g} s")
    failed = sum(1 for _, _, ok in in_window if ok != 1.0)
    compiles_in_window = sum(1 for t in run_.compiles if ws <= t <= we)
    final_loss = float(first["last"]["loss"])
    setup_s = ws - ctx.t0
    chips = ctx.cell["chips"]
    # completion-to-completion intervals of consecutive steps, every step of
    # the window a sample (the first from the completion that opened it)
    times = [ws] + [t for _, t, _ in in_window]
    intervals = [(b - a) * 1e3 for a, b in zip(times, times[1:])]
    # the allocator keeps a program's temporaries apart, as "reserved": the
    # sum matches the compiler's count of the step (5.04 against 4.75 GB for
    # ResNet-50, 11.0 against 10.4 GB for ViT-B/16; my chip runs, PR 25)
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0))
                      + int(m.get("peak_bytes_reserved", 0)) for m in stats)
    log(f"set-up split: imports+backend {t_build - ctx.t0:.2f} s, datasets+trainer "
        f"{t_weights - t_build:.2f} s, weights+wrappers {t_epoch - t_weights:.2f} s, "
        f"first {warmup} steps {ws - t_epoch:.2f} s")
    log(f"memory_stats of device 0: {json.dumps(stats[0])}")

    # ---- what the program's first three steps gave -----------------------
    got_loss = [float(x) for x in first["loss"]]
    g_norms, factor = first["grad0"]
    got_grad0 = {n: float(v) * factor for n, v in zip(names, g_norms)}
    got_dparam = {n: float(v) for n, v in zip(names, first["dparam"])}
    got_g0 = {n: np.asarray(v) * np.float32(factor) for n, v in
              zip(names, jax.tree_util.tree_leaves(first["moment_host"]))}
    images = jnp.stack([b[0] for b in first["batches"]])
    labels = jnp.stack([b[1] for b in first["batches"]])
    flips = None
    if cfg.data.dataset != "synthetic" and cfg.data.input_dtype == "uint8":
        flips = flip_masks(cfg.run.seed, STEPS_COMPARED, batch)

    # ---- free the program, then the reference ----------------------------
    mesh = trainer.mesh
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    trainer.state = None
    first.clear()
    t_ref = time.perf_counter()
    numbers = compare(ctx, ref, arch, mesh, (images, labels, flips),
                      (got_loss, got_grad0, got_dparam, got_g0))
    log(f"reference: {STEPS_COMPARED} steps in {time.perf_counter() - t_ref:.2f} s")

    limits = conf["limits"]
    correct = True
    for name, value in numbers.items():
        ok = math.isfinite(value) and value <= limits[name]
        correct &= ok
        log(f"compared {name} = {value:.6g}  limit {limits[name]:.6g}  "
            f"{'ok' if ok else 'OVER'}")
    for name, value, limit in (("failed_steps", failed, 0),
                               ("compiles_in_window", compiles_in_window, 0)):
        correct &= value <= limit
        log(f"compared {name} = {value}  limit {limit}  "
            f"{'ok' if value <= limit else 'OVER'}")
    ok = math.isfinite(final_loss)
    correct &= ok
    log(f"compared final_loss = {final_loss:.6g} (finite, {attempted} steps)  "
        f"{'ok' if ok else 'BAD'}")

    # ---- metrics ---------------------------------------------------------
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    values = {
        # all the images of the steps completed in the window over all of
        # the window's seconds, whatever part of them a stall took
        "img_per_s_per_chip": attempted * batch / ctx.seconds / chips,
        "step_ms_p90": _percentile(intervals, 90),
        "setup_s": setup_s,
    }
    log("step intervals ms: " + " ".join(f"{x:.2f}" for x in intervals))
    log(f"window: {attempted} steps of batch {batch} in {ctx.seconds:g} s; step "
        f"interval median {statistics.median(intervals):.4f} "
        f"ms over {len(intervals)} steps; loss at the end {final_loss:.4f}; "
        f"epoch call {t_end - t_epoch:.2f} s")

    def finite(d):  # the last line has to stay JSON: no Infinity, no NaN
        return {k: (v if math.isfinite(v) else None) for k, v in d.items()}

    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "compared": finite(numbers)}
    units = {m["name"]: m["unit"] for m in
             ctx.spec["end_to_end"] + ctx.spec["per_layer"]}
    if not ctx.trace:
        wanted = [m["name"] for m in ctx.spec["end_to_end"]
                  if ctx.cell["name"] in m.get("workloads", [ctx.cell["name"]])]
        result["metrics"] = {n: {"value": values[n], "unit": units[n]}
                             for n in wanted}
    else:
        from benchmark import trace_reduce

        samples = {
            "waits": [(t, d) for t, d in run_.waits
                      if trace_out.get("start", 0) <= t <= trace_out.get("stop", 0)],
            "steps_in_slice": sum(1 for _, t, _ in run_.done
                                  if trace_out.get("start", 0) < t <= trace_out.get("stop", 0)),
            "slice_s": trace_out.get("stop", 0) - trace_out.get("start", 0),
            "cache": dict(progcache._stats),
        }
        log(f"trace: slice {samples['slice_s']:.2f} s, stop_trace took "
            f"{trace_out.get('stopped', 0) - trace_out.get('stop', 0):.2f} s")
        reduced = trace_reduce.reduce_dir(
            trace_dir, chips,
            host_spans={"bench_input_wait": run_.waits,
                        "bench_step_dispatch": run_.dispatches},
            anchors=trace_out.get("anchors", []))
        read_ctx = {"trace": reduced, "samples": samples, "batch": batch,
                    "chips": chips, "arch": arch, "config": ctx.config,
                    "image_size": cfg.data.image_size,
                    "device_kind": dev0.device_kind, "platform": dev0.platform}
        metrics = {}
        for m in ctx.spec["per_layer"]:
            if ctx.cell["name"] not in m.get("workloads", [ctx.cell["name"]]):
                continue
            reader = importlib.import_module(f"benchmark.layers.{m['name']}")
            value = reader.read(read_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
    result["device"] = device
    trainer.train_loader.close()
    trainer.val_loader.close()
    return result


def follow(ctx, ref, arch, mesh, batches, precision: str):
    """The plain reference over the same three batches from the same seeded
    weights, computed in `precision` -> (losses, first gradient's leaf
    norms, leaf norms of the three steps' change, the first gradient)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmark.reference import common

    conf = ctx.config["rehearse"] if ctx.rehearse else ctx.config
    opt = ctx.config["optimizer"]
    spec = ref.param_spec(arch)
    params = jax.jit(lambda s: common.make_params(spec, s),
                     out_shardings=NamedSharding(mesh, P()))(
        jnp.asarray(program_seed(ctx.seed), jnp.uint32))
    step = common.make_step(ref.forward_for(arch, precision), opt,
                            conf.get("row_block", 0))
    out = common.trajectory(step, opt, params, *batches)
    return ([float(x) for x in out["loss"]],
            {k: float(v) for k, v in out["grad0_norms"].items()},
            {k: float(v) for k, v in out["dparam"].items()},
            out["grad0"])


def gaps(who: str, got, want, mesh) -> dict:
    """The numbers held to limits: `got` (the program's, or a control's)
    against `want` (the float32 reference's), both as `follow` returns."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmark.reference import common

    loss, grad0, dparam, g0 = got
    ref_loss, ref_grad0, ref_dparam, ref_g0 = want
    rep = NamedSharding(mesh, P())
    log(f"loss {who} {['%.6f' % x for x in loss]} float32 reference "
        f"{['%.6f' % x for x in ref_loss]}")
    grad_gap, grad_leaf = common.worst_leaf_gap(grad0, ref_grad0)
    dp_gap, dp_leaf = common.worst_leaf_gap(dparam, ref_dparam)
    log(f"worst leaves of {who}: grad0 {grad_leaf}, dparam {dp_leaf}")
    return {
        "loss_gap": (max(abs(a - b) / abs(b) for a, b in zip(loss, ref_loss))
                     if all(map(math.isfinite, loss)) else float("inf")),
        "grad0_mean_gap": common.mean_leaf_gap(grad0, ref_grad0),
        "dparam_mean_gap": common.mean_leaf_gap(dparam, ref_dparam),
        "grad0_worst_gap": grad_gap,
        "dparam_worst_gap": dp_gap,
        "grad0_diff_gap": common.difference_gap(
            {k: jax.device_put(v, rep) for k, v in g0.items()}, ref_g0),
    }


def compare(ctx, ref, arch, mesh, batches, got) -> dict:
    """The program's first three steps against the float32 reference."""
    return gaps("program", got, follow(ctx, ref, arch, mesh, batches, "float32"), mesh)
