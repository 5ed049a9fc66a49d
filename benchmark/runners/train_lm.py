"""The training runner for token batches: `runners/train.py` with the
reference step of a language model and with no parameter-sized copy on the
device while a step runs.

It measures the same window the same way — one `Trainer.train_epoch(0)` built
from the configuration's `cli.train` argv, the same two wrappers, the same
watcher and tracer, the same three end-to-end formulas (one ROW of the batch
is one "image") — and imports all of that from `runners/train.py`. What
differs, and why `run` is written again here:

- the batch is (token ids (B, T), the same rows shifted by one), so the
  reference step is `reference/<name>.loss_for` and not `common.make_step`
  (which normalises NHWC pixels and flips them);
- the configuration's parameters, gradient and Adam moments fill two thirds
  of the chip. So nothing parameter-sized is kept beside them: the first
  moment goes to the host before step 1 is dispatched, the three-step change
  is taken against weights made again from the seed inside the program that
  takes the norms, and the reference (after the window, once the program's
  state is freed) keeps its first gradient on the host too. All of that is
  set-up or after the window;
- each step's `moe_load` (the step's own metrics, a few dozen integers) is
  kept for the readers of the expert layer's counters.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import statistics
import threading
import time

import numpy as np

from benchmark.runners.train import (  # noqa: F401 — `gaps`, `build_trainer`: hooks
    STEPS_COMPARED,
    _percentile,
    _Run,
    _timed_prefetcher,
    _tracer,
    _watcher,
    bench_marker,
    build_trainer,
    first_moment,
    gaps,
    leaf_names,
    log,
    program_seed,
)


def seeded_weights(ref, arch, names, treedef, shardings):
    """-> jitted `make(seed)`: the reference's seeded weights as the
    program's tree, on the program's shardings."""
    import jax
    from benchmark.reference.common import make_params

    spec = ref.param_spec(arch)

    def make(seed_):
        flat = make_params(spec, seed_)
        return jax.tree_util.tree_unflatten(treedef, [flat[n] for n in names])

    return jax.jit(make, out_shardings=shardings)


def install_weights(trainer, ref, arch, seed: int):
    """Seeded weights from the reference's own spec into the program's
    state -> (leaf names, `make`). A name or shape the program does not
    have is an error. No second copy is made."""
    import jax
    import jax.numpy as jnp

    names, leaves, treedef = leaf_names(trainer.state.params)
    have = {n: tuple(x.shape) for n, x in zip(names, leaves)}
    want = {n: tuple(s[0]) for n, s in ref.param_spec(arch).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise SystemExit(f"the reference's leaves are not the program's: {odd}")
    shardings = jax.tree_util.tree_unflatten(treedef, [x.sharding for x in leaves])
    make = seeded_weights(ref, arch, names, treedef, shardings)
    del leaves
    for leaf in jax.tree_util.tree_leaves(trainer.state.params):
        leaf.delete()   # the program's own initial weights go first
    trainer.state = trainer.state.replace(
        params=make(jnp.asarray(program_seed(seed), jnp.uint32)))
    return names, make


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
    names, make = install_weights(trainer, ref, arch, ctx.seed)
    opt = ctx.config["optimizer"]
    seed_arg = jnp.asarray(program_seed(ctx.seed), jnp.uint32)

    def norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(tree)]

    norms_fn = jax.jit(norms)
    # the three steps' change against the seeded weights, made again inside
    # this program leaf by leaf: no second copy stands on the device
    diff_fn = jax.jit(lambda a, s: norms(
        jax.tree_util.tree_map(jnp.subtract, a, make(s))))

    # ---- the two wrappers ------------------------------------------------
    real_prefetcher = trainer._device_prefetcher
    trainer._device_prefetcher = (
        lambda loader, assemble=None:
        _timed_prefetcher(real_prefetcher(loader, assemble), run_))
    real_step = trainer.train_step
    first = {"batches": [], "loss": []}
    loads = []   # (step, the step's moe_load): device arrays of L x e numbers

    def step(state, tokens, targets):
        i = run_.dispatched
        run_.dispatched += 1
        if i < STEPS_COMPARED:
            first["batches"].append((tokens, targets))
        t = time.perf_counter()
        state, metrics = real_step(state, tokens, targets)
        run_.dispatches.append((t, time.perf_counter() - t))
        if i < STEPS_COMPARED:
            first["loss"].append(metrics["loss"])
            if i == 0:
                # the first gradient as the optimizer got it, to the host
                # before step 1 is dispatched (these buffers are donated to
                # it): warm-up, so set-up time
                moment, factor = first_moment(state.opt_state, opt)
                first["grad0"] = (norms_fn(moment), factor)
                first["moment_host"] = jax.device_get(moment)
            if i == STEPS_COMPARED - 1:
                first["dparam"] = diff_fn(state.params, seed_arg)
        if "moe_load" in metrics:
            loads.append((i, metrics["moe_load"]))
        first["last"] = metrics
        run_.watch_q.put((i, metrics["step_ok"]))
        return state, metrics

    trainer.train_step = step

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
    times = [ws] + [t for _, t, _ in in_window]
    intervals = [(b - a) * 1e3 for a, b in zip(times, times[1:])]
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
    tokens = jnp.stack([b[0] for b in first["batches"]])
    targets = jnp.stack([b[1] for b in first["batches"]])
    window_steps = {s for s, _, _ in in_window}
    window_loads = [np.asarray(v) for s, v in loads if s in window_steps]

    # ---- free the program, then the reference ----------------------------
    mesh = trainer.mesh
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    trainer.state = None
    first.clear()
    loads.clear()
    t_ref = time.perf_counter()
    numbers = compare(ctx, ref, arch, mesh, (tokens, targets, None),
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
        # one row of the batch is one "image": the rows of the steps
        # completed in the window over all of the window's seconds
        "img_per_s_per_chip": attempted * batch / ctx.seconds / chips,
        "step_ms_p90": _percentile(intervals, 90),
        "setup_s": setup_s,
    }
    log("step intervals ms: " + " ".join(f"{x:.2f}" for x in intervals))
    log(f"window: {attempted} steps of batch {batch} x {tokens.shape[-1]} tokens in "
        f"{ctx.seconds:g} s; step interval median {statistics.median(intervals):.4f} "
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
            "moe_load": window_loads,
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
                    "image_size": cfg.data.image_size, "trace_dir": trace_dir,
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
    norms, leaf norms of the three steps' change, the first gradient on the
    HOST). It holds parameters, one gradient and the two moments, and
    nothing else parameter-sized: the first gradient leaves for the host,
    and the change is taken against weights made again from the seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmark.reference import common

    opt = ctx.config["optimizer"]
    spec = ref.param_spec(arch)
    rep = NamedSharding(mesh, P())
    seed = jnp.asarray(program_seed(ctx.seed), jnp.uint32)
    params = jax.jit(lambda s: common.make_params(spec, s), out_shardings=rep)(seed)
    loss_fn = ref.loss_for(arch, precision)

    def step(params, state, tokens, targets, t):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
            new, state = common.optimizer_step(opt, params, state, grads, t)
        return loss, grads, new, state

    step = jax.jit(step, donate_argnums=(0, 1))
    tokens, targets, _ = batches
    state = common.optimizer_init(opt, params)
    losses, grad0, grad0_norms = [], None, None
    for s in range(tokens.shape[0]):
        loss, grads, params, state = step(params, state, tokens[s], targets[s],
                                          jnp.asarray(s + 1, jnp.float32))
        losses.append(float(loss))
        if s == 0:
            grad0_norms = jax.jit(common.leaf_norms)(grads)
            grad0 = jax.device_get(grads)
        for leaf in jax.tree_util.tree_leaves(grads):
            leaf.delete()
    dparam = jax.jit(lambda p, s: common.leaf_norms(
        {k: p[k] - v for k, v in common.make_params(spec, s).items()}))(params, seed)
    dparam = {k: float(v) for k, v in dparam.items()}
    for leaf in jax.tree_util.tree_leaves((params, state)):
        leaf.delete()
    return (losses, {k: float(v) for k, v in grad0_norms.items()}, dparam, grad0)


def compare(ctx, ref, arch, mesh, batches, got) -> dict:
    """The program's first three steps against the float32 reference."""
    return gaps("program", got, follow(ctx, ref, arch, mesh, batches, "float32"), mesh)
