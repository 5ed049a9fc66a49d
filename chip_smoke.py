#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, at the
full width of the flagship (ResNet-50, 224 px, 1000 classes, bf16 compute,
batch 128, uint8 input wire; weights random from a seed):

  train    `cli.train` on synthetic data (>= 8 optimizer steps, eval,
           checkpoint, one short profiler trace, --strict_compile), then the
           same command at the same shapes on a generated image folder, so the
           host input path (loader, native dataplane, DevicePrefetcher, uint8
           H2D) runs and the persistent compile cache is seen to hit
  serve    `cli.serve --selfcheck` from the trainer's checkpoint (cold boot,
           AOT bank written), then `cli.serve --port` (warm boot from the
           bank) answering a few HTTP POSTs
  kernels  each Pallas kernel family once at real shapes, forward and
           backward, compiled (the lowered program holds the Mosaic custom
           call) and compared with the repo's jax.numpy references (the
           delta rule's kernels: with the same chunks in plain XLA)

`--chips 4` runs ONLY the data-parallel arm and what it is compared with:
ResNet-50 DP=4 at global batch 512 (ZeRO-1 auto-on, bf16 gradient wire), then
the same command, batch, seed and steps in a child that sees one chip.

One process per chip: this parent never imports JAX. Every phase is a child
process, started strictly after the previous one has exited; each child is
told its platform explicitly, so with no TPU it fails at start-up instead of
falling to the CPU. `--platform cpu --tiny` is the rehearsal the tier-1 test
runs (small shapes, same control flow).

The last stdout line is the contract's JSON object and nothing else.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")          # checkpoints, logs, dataset
OUT = os.path.join(REPO, "chiprun_out")           # what chiprun brings back
                                                  # (a chip run's record only)
PKG = "ddp_classification_pytorch_tpu"
BUDGET_S = 1140.0                                  # the contract allows 1200

# Tolerances, stated here once.
# Kernels vs jax.numpy references on bf16 operands with f32 accumulation:
# max|a-b| / max|b| per compared array (the delta rule's kernels against the
# same chunks in plain XLA, neither the other's reference: 5.7e-3 at most, on
# dg, on the chip in PR 43).
KERNEL_REL_TOL = 2e-2
# DP=4 (bf16 gradient wire, sharded BN reductions) vs one chip (no wire), same
# global batch/seed: |loss_dp4 - loss_1| per step. Step 0 differs only by
# reduction order; later steps add the bf16 rounding of every gradient
# (on four v5e chips, PR 23: 0.0024 at step 0, at most 0.077 after).
DP_LOSS_TOL_FIRST = 2e-2
DP_LOSS_TOL = 1e-1

REAL = dict(model="resnet50", variant="", image=224, classes=1000, batch=128,
            dtype="bfloat16", lr="0.01", steps=8, folder_steps=3, src_px=256,
            selfcheck=16, posts=4, max_batch=8)
TINY = dict(model="resnet18", variant="cifar", image=32, classes=8, batch=8,
            dtype="float32", lr="0.002", steps=8, folder_steps=2, src_px=40,
            selfcheck=4, posts=2, max_batch=2)

_T0 = time.monotonic()
_DEVICE_RE = re.compile(r"platform=(\w+) device_kind='([^']*)' devices=(\d+)")
_STEP_RE = re.compile(r"^Epoch: (\d+)\tstep: (\d+)/(\d+)\t(.*)$", re.M)
_CACHE_RE = re.compile(r"compile cache: dir=(\S+) hits=(\d+) misses=(\d+) "
                       r"compile_s=([\d.]+)")
_MEM_RE = re.compile(r"peak_bytes_in_use per device: (.*)")


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# what exposes ONE chip of a four-chip host to a child (libtpu reads either
# spelling of the bounds; the machine may preset the older one)
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1", "TPU_HOST_BOUNDS": "1,1,1"}


def child_env(platform: str, devices: int = 1, all_chips: bool = True) -> dict:
    """The child's environment. On the CPU the virtual device count is set
    here whatever the caller's XLA_FLAGS say (pytest's conftest forces 8);
    on the TPU a child sees every chip of the host unless told otherwise."""
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if platform == "cpu":
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={devices}"])
    elif not all_chips:
        env.update(ONE_CHIP_ENV)
    return env


def run_child(name: str, argv: list, env: dict) -> dict:
    """Run one child to its end; the next one starts only after this
    returns. Output goes to a log under WORK and comes back as text."""
    os.makedirs(os.path.join(WORK, "logs"), exist_ok=True)
    log = os.path.join(WORK, "logs", f"{name}.log")
    say(f"$ {' '.join(argv)}")
    t0 = time.monotonic()
    timeout = max(remaining(), 5.0)
    with open(log, "w") as f:
        proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=f,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            rc = 124
    secs = time.monotonic() - t0
    with open(log, errors="replace") as f:
        text = f.read()
    say(f"{name}: rc={rc} {secs:.1f}s")
    if rc != 0:
        say(f"{name} output tail:\n" + text[-3000:])
        raise PhaseFailed(f"{name} exited {rc}")
    return {"name": name, "seconds": round(secs, 1), "text": text}


def parse_device(text: str, where: str) -> dict:
    m = _DEVICE_RE.search(text)
    check(m is not None, f"{where}: no device line in the banner")
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3))}


def parse_steps(text: str) -> list:
    """[(epoch, step, {metric: value})] from the trainer's per-step lines."""
    out = []
    for m in _STEP_RE.finditer(text):
        metrics = {}
        for part in m.group(4).split("\t"):
            k, _, v = part.partition(": ")
            try:
                metrics[k] = float(v)
            except ValueError:
                pass  # the trailing "N-step time" / "ETA" fields
        out.append((int(m.group(1)), int(m.group(2)), metrics))
    return out


def report_run(rec: dict) -> dict:
    """Print and return what one trainer/server child said about itself."""
    text = rec["text"]
    info: dict = {"seconds": rec["seconds"]}
    m = _CACHE_RE.search(text)
    if m:
        info.update(cache_dir=m.group(1), cache_hits=int(m.group(2)),
                    cache_misses=int(m.group(3)),
                    compile_s=float(m.group(4)))
    m = _MEM_RE.search(text)
    if m:
        info["peak_bytes_in_use"] = m.group(1).split()
    say(f"{rec['name']}: " + " ".join(f"{k}={v}" for k, v in info.items()))
    return info


# ------------------------------------------------------------------ train --

def train_argv(s: dict, platform: str, out: str, data: list, epochs: int,
               batch: int, extra: tuple = ()) -> list:
    argv = [sys.executable, "-m", f"{PKG}.cli.train", "baseline",
            "--model", s["model"], "--num_classes", str(s["classes"]),
            "--image_size", str(s["image"]), "--crop_size", str(s["image"]),
            "--dtype", s["dtype"], "--input_dtype", "uint8",
            "--batchsize", str(batch), "--lr", s["lr"], "--seed", "0",
            "--epochs", str(epochs), "--log_every", "1", "--strict_compile",
            "--save_best_only", "--out", out, "--platform", platform,
            *data, *extra]
    if s["variant"]:
        argv += ["--variant", s["variant"]]
    return argv


def check_train(rec: dict, out: str, want_steps: int, want_platform: str,
                want_devices: int) -> dict:
    text = rec["text"]
    dev = parse_device(text, rec["name"])
    check(dev["platform"] == want_platform,
          f"{rec['name']}: ran on {dev['platform']}, wanted {want_platform}")
    check(dev["count"] == want_devices,
          f"{rec['name']}: saw {dev['count']} devices, wanted {want_devices}")
    steps = parse_steps(text)
    losses = [m["loss"] for _, _, m in steps]
    check(len(steps) >= want_steps,
          f"{rec['name']}: {len(steps)} step lines, wanted >= {want_steps}")
    check(all(math.isfinite(x) for x in losses),
          f"{rec['name']}: non-finite loss in {losses}")
    check(all(m.get("step_ok") == 1.0 for _, _, m in steps),
          f"{rec['name']}: a step reported step_ok != 1")
    check("val_top1=" in text, f"{rec['name']}: no evaluation line")
    for f in ("history.json", "meta.json", "ckpt_best.msgpack"):
        check(os.path.exists(os.path.join(out, f)),
              f"{rec['name']}: {f} was not written")
    info = report_run(rec)
    info.update(device=dev, steps=len(steps), losses=losses)
    say(f"{rec['name']}: steps={len(steps)} first_loss={losses[0]:.4f} "
        f"last_loss={losses[-1]:.4f} step_ok=all")
    return info


def make_image_folder(root: str, s: dict, seed: int) -> None:
    """A few hundred seeded JPEGs in the reference's class-directory layout
    (as tests/test_imagefolder_native_e2e.py builds them)."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    n_cls = min(s["classes"], 8)
    means = rng.integers(40, 215, size=(n_cls, 3))
    per = {"train": s["batch"] * s["folder_steps"] // n_cls,
           "val": max(s["batch"] // n_cls, 1)}
    px = s["src_px"]
    for split, n in per.items():
        for c in range(n_cls):
            d = os.path.join(root, split, f"class{c}")
            os.makedirs(d, exist_ok=True)
            for i in range(n):
                img = np.clip(means[c] + rng.normal(0, 25, (px, px, 3)),
                              0, 255).astype(np.uint8)
                Image.fromarray(img).save(os.path.join(d, f"{i}.jpg"),
                                          quality=92)


def phase_train(s: dict, platform: str, result: dict) -> None:
    if platform == "tpu":
        # built from what git would commit: never a binary left on disk.
        # (The library is keyed by its source's hash, so a stale one is
        # never loaded anyway; the CPU rehearsal shares its tree with other
        # tests that are loading the library, and leaves it alone.)
        shutil.rmtree(os.path.join(REPO, "native", "build"),
                      ignore_errors=True)
    out = os.path.join(WORK, "train_synthetic")
    shutil.rmtree(out, ignore_errors=True)
    # two epochs of `steps`: the compile sentinel arms after the first
    # evaluated epoch, so the second runs under --strict_compile proper
    rec = run_child("train_synthetic", train_argv(
        s, platform, out,
        ["--dataset", "synthetic", "--synthetic_size",
         str(s["batch"] * s["steps"])],
        epochs=2, batch=s["batch"], extra=("--profile_steps", "2")),
        child_env(platform))
    info = check_train(rec, out, 2 * s["steps"], platform, 1)
    check("[compile-sentinel] armed" in rec["text"],
          "train_synthetic: the compile sentinel never armed")
    traces = glob.glob(os.path.join(out, "profile", "**", "*.xplane.pb"),
                       recursive=True)
    check(bool(traces), "train_synthetic: --profile_steps wrote no trace")
    info["trace_bytes"] = {os.path.basename(t): os.path.getsize(t)
                           for t in traces}
    say(f"train_synthetic: profiler trace {info['trace_bytes']}")
    result["train_synthetic"] = info
    result["device"] = info["device"]

    root = os.path.join(WORK, "imagefolder")
    shutil.rmtree(root, ignore_errors=True)
    t0 = time.monotonic()
    make_image_folder(root, s, seed=0)
    n_jpeg = len(glob.glob(os.path.join(root, "*", "*", "*.jpg")))
    say(f"imagefolder: {n_jpeg} JPEGs generated in "
        f"{time.monotonic() - t0:.1f}s")
    out2 = os.path.join(WORK, "train_imagefolder")
    shutil.rmtree(out2, ignore_errors=True)
    rec = run_child("train_imagefolder", train_argv(
        s, platform, out2,
        ["--dataset", "imagefolder", "--train_dir",
         os.path.join(root, "train"), "--val_dir", os.path.join(root, "val"),
         "--num_workers", "8"],
        epochs=1, batch=s["batch"]), child_env(platform))
    info2 = check_train(rec, out2, s["folder_steps"], platform, 1)
    if "native C++ dataplane active" in rec["text"]:
        info2["dataplane"] = "native"
    else:
        m = re.search(r"native dataplane unavailable, PIL fallback: (.*)",
                      rec["text"])
        info2["dataplane"] = "pil"
        info2["dataplane_reason"] = m.group(1) if m else "not reported"
    say(f"train_imagefolder: dataplane: {info2['dataplane']}"
        + (f" ({info2['dataplane_reason']})"
           if info2["dataplane"] == "pil" else ""))
    if platform == "tpu":
        # same shapes as the synthetic run, so its programs come from disk
        check(info2.get("cache_hits", 0) > 0,
              "train_imagefolder: the persistent compile cache did not hit")
    result["train_imagefolder"] = info2


# ------------------------------------------------------------------ serve --

def serve_argv(s: dict, platform: str, ckpt: str, extra: list) -> list:
    argv = [sys.executable, "-m", f"{PKG}.cli.serve", "baseline",
            "--model", s["model"], "--num_classes", str(s["classes"]),
            "--image_size", str(s["image"]), "--dtype", s["dtype"],
            "--input_dtype", "uint8", "--max_batch", str(s["max_batch"]),
            "--ckpt", ckpt, "--strict_compile", "--seed", "0",
            "--out", os.path.join(WORK, "serve"), "--platform", platform,
            *extra]
    if s["variant"]:
        argv += ["--variant", s["variant"]]
    return argv


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def phase_serve(s: dict, platform: str, result: dict) -> None:
    ckpt = os.path.join(WORK, "train_synthetic", "ckpt_best.msgpack")
    check(os.path.exists(ckpt),
          f"serve: no trainer checkpoint at {ckpt} (run the train phase)")
    shutil.rmtree(os.path.join(os.path.dirname(ckpt), "aot"),
                  ignore_errors=True)

    rec = run_child("serve_cold", serve_argv(
        s, platform, ckpt, ["--selfcheck", str(s["selfcheck"])]),
        child_env(platform))
    dev = parse_device(rec["text"], "serve_cold")
    check(dev["platform"] == platform,
          f"serve_cold: ran on {dev['platform']}, wanted {platform}")
    check("cold boot:" in rec["text"] and "banked to AOT sidecar"
          in rec["text"], "serve_cold: the AOT bank was not written")
    check(f"selfcheck ok: {s['selfcheck']} requests" in rec["text"],
          "serve_cold: selfcheck did not complete")
    info = report_run(rec)
    info["aot"] = "bank written"
    result["serve_cold"] = info
    result.setdefault("device", dev)

    # warm boot over HTTP: the server is the only child alive meanwhile
    port = free_port()
    argv = serve_argv(s, platform, ckpt, ["--port", str(port)])
    say(f"$ {' '.join(argv)}")
    log = os.path.join(WORK, "logs", "serve_http.log")
    t0 = time.monotonic()
    with open(log, "w") as f:
        proc = subprocess.Popen(argv, cwd=REPO, env=child_env(platform),
                                stdout=f, stderr=subprocess.STDOUT,
                                start_new_session=True)
    answers = []
    try:
        url = f"http://127.0.0.1:{port}"
        while True:
            check(proc.poll() is None, "serve_http: server exited early")
            check(remaining() > 0, "serve_http: out of time waiting for boot")
            try:
                with urllib.request.urlopen(url + "/healthz", timeout=2) as r:
                    if r.status == 200:
                        break
            except OSError:
                time.sleep(0.5)
        boot_s = time.monotonic() - t0
        jpeg = sorted(glob.glob(os.path.join(
            WORK, "imagefolder", "val", "*", "*.jpg")))
        check(bool(jpeg), "serve_http: no generated JPEG to post "
                          "(run the train phase)")
        for path in (jpeg * s["posts"])[:s["posts"]]:
            with open(path, "rb") as f:
                req = urllib.request.Request(url + "/predict", data=f.read(),
                                             method="POST")
            with urllib.request.urlopen(req, timeout=120) as r:
                answers.append(json.loads(r.read()))
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGTERM)  # graceful drain, rc 0
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    with open(log, errors="replace") as f:
        text = f.read()
    if proc.returncode != 0 or "drained clean" not in text:
        say("serve_http output tail:\n" + text[-3000:])
        raise PhaseFailed(f"serve_http exited {proc.returncode}")
    check("warm boot:" in text and "AOT-deserialized" in text,
          "serve_http: second boot did not come from the AOT bank")
    for a in answers:
        scores = [sc for _, sc in a["topk"]]
        check(len(a["topk"]) == min(5, s["classes"])
              and all(math.isfinite(x) and 0.0 <= x <= 1.0 for x in scores)
              and scores == sorted(scores, reverse=True)
              and sum(scores) <= 1.0 + 1e-3,
              f"serve_http: malformed answer {a}")
    check(len({a["digest"] for a in answers}) == 1,
          "serve_http: answers came from different parameter digests")
    say(f"serve_http: warm boot hit the AOT bank, ready in {boot_s:.1f}s; "
        f"{len(answers)} POST /predict answered, top-1 "
        f"{[a['topk'][0] for a in answers]}, latency_ms "
        f"{[a['latency_ms'] for a in answers]}")
    result["serve_http"] = {"boot_seconds": round(boot_s, 1),
                            "aot": "warm-boot hit", "posts": len(answers)}


# ---------------------------------------------------------------- kernels --

def phase_kernels(s: dict, platform: str, result: dict) -> None:
    argv = [sys.executable, os.path.abspath(__file__), "--kernels-child",
            "--platform", platform] + (["--tiny"] if s is TINY else [])
    rec = run_child("kernels", argv, child_env(platform))
    for line in rec["text"].splitlines():
        if line.startswith("[kernels]"):
            print(line, flush=True)
    m = re.search(r"^KERNELS_JSON (.*)$", rec["text"], re.M)
    check(m is not None, "kernels: child printed no result")
    info = json.loads(m.group(1))
    check(info["device"]["platform"] == platform,
          f"kernels: ran on {info['device']['platform']}, wanted {platform}")
    bad = [c["name"] for c in info["cases"] if not c["ok"]]
    check(not bad, f"kernels: failed or beyond tolerance {KERNEL_REL_TOL}: "
                   f"{bad}")
    if platform == "tpu":
        interp = [c["name"] for c in info["cases"] if not c["mosaic_calls"]]
        check(not interp, f"kernels: ran in interpret mode: {interp}")
    result["kernels"] = info
    result.setdefault("device", info["device"])


def kernels_child(platform: str, tiny: bool) -> None:
    """Runs in its own process (the only code here that imports JAX)."""
    import importlib

    import jax

    jax.config.update("jax_platforms", platform)
    import jax.numpy as jnp

    from ddp_classification_pytorch_tpu.utils.cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    fa = importlib.import_module(f"{PKG}.ops.flash_attention")
    pk = importlib.import_module(f"{PKG}.ops.pallas_kernels")
    from ddp_classification_pytorch_tpu.ops.attention import attention

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    dt = jnp.float32 if tiny else jnp.bfloat16

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-6))

    def run_case(name, kernel_fn, ref_fn, args, n_diff):
        """Forward + VJP (a random cotangent) of both, compared leaf by
        leaf; the kernel side must lower to the Mosaic custom call."""
        def with_grads(fn):
            def f(*a):
                out, vjp = jax.vjp(fn, *a[:n_diff])
                return (out,) + vjp(a[n_diff].astype(out.dtype))
            return jax.jit(f)

        kf, rf = with_grads(kernel_fn), with_grads(ref_fn)
        try:
            mosaic = kf.lower(*args).as_text().count("tpu_custom_call")
            got, want = kf(*args), rf(*args)
            errs = [rel(g, w) for g, w in zip(got, want)]
            finite = all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                         for g in got)
        except Exception as e:  # noqa: BLE001 — a refused lowering, an OOM
            print(f"[kernels] {name}: FAIL {type(e).__name__}: {e}",
                  flush=True)
            return {"name": name, "mosaic_calls": 0, "ok": False,
                    "error": f"{type(e).__name__}: {e}"[:500]}
        ok = finite and max(errs) <= KERNEL_REL_TOL
        print(f"[kernels] {name}: mosaic_calls={mosaic} "
              f"rel_err(out,grads)={[f'{e:.1e}' for e in errs]} "
              f"tol={KERNEL_REL_TOL} {'ok' if ok else 'FAIL'}", flush=True)
        return {"name": name, "mosaic_calls": mosaic, "rel_err": errs,
                "ok": ok}

    def bn_ref(x, scale, bias):
        xf = x.astype(jnp.float32)
        red = tuple(range(x.ndim - 1))
        mean = jnp.mean(xf, axis=red)
        var = jnp.mean(jnp.square(xf), axis=red) - jnp.square(mean)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias
        return jnp.where(y >= 0, y, 0.01 * y).astype(x.dtype)

    cases = []
    key = jax.random.PRNGKey(0)
    # TResNet-M's fused BN+LeakyReLU at the stem end and at the top
    for shape in ([(2, 8, 8, 16)] if tiny else
                  [(128, 56, 56, 64), (128, 7, 7, 2048)]):
        kx, ks, kb, kg, key = jax.random.split(key, 5)
        c = shape[-1]
        args = (jax.random.normal(kx, shape, dt) * 2 + 0.5,
                jax.random.normal(ks, (c,), jnp.float32) + 1.0,
                jax.random.normal(kb, (c,), jnp.float32),
                jax.random.normal(kg, shape, dt))
        cases.append(run_case(
            f"bn_leaky_relu {shape} {jnp.dtype(dt).name}",
            lambda x, sc, b: pk.batch_norm_leaky_relu(x, sc, b)[0],
            bn_ref, args, 3))
    # flash attention: ViT-B/16 widths (single-block path, 197 tokens) and
    # a long sequence (blocks of 512), causal and not
    for (b, t, h, d), causal in ([((1, 197, 2, 64), False),
                                  ((1, 1024, 1, 64), True)] if tiny else
                                 [((128, 197, 12, 64), False),
                                  ((1, 8192, 4, 64), False),
                                  ((1, 8192, 4, 64), True)]):
        kq, kk, kv, kg, key = jax.random.split(key, 5)
        args = tuple(jax.random.normal(k_, (b, t, h, d), dt)
                     for k_ in (kq, kk, kv, kg))
        name = (f"flash_attention {(b, t, h, d)} causal={causal} "
                f"{jnp.dtype(dt).name}")
        if not fa._supported(t):
            print(f"[kernels] {name}: FAIL T={t} would fall through to the "
                  "dense op", flush=True)
            cases.append({"name": name, "mosaic_calls": 0, "ok": False,
                          "error": "falls through to the dense op"})
            continue
        cases.append(run_case(
            name,
            lambda q, k, v, c_=causal: fa.flash_attention(q, k, v, causal=c_),
            lambda q, k, v, c_=causal: attention(q, k, v, causal=c_),
            args, 3))
    # Kimi delta attention's recurrence (ops/kda.py) at the width of
    # `ling3_ep64_8k`'s layers: the three kernels against the same chunks in
    # plain XLA (`_grouped`, the path every narrower head takes), both over
    # operands of `dt`
    kda = importlib.import_module(f"{PKG}.ops.kda")
    b, t, h, d = (1, 128, 2, 128) if tiny else (1, 8192, 32, 128)
    kq, kk, kv, kf, kb, kg, key = jax.random.split(key, 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    args = ((unit(jax.random.normal(kq, (b, t, h, d))) * d ** -0.5).astype(dt),
            unit(jax.random.normal(kk, (b, t, h, d))).astype(dt),
            jax.random.normal(kv, (b, t, h, d), dt),
            kda.LOWER_BOUND * jax.nn.sigmoid(
                2.0 * jax.random.normal(kf, (b, t, h, d)) - 2.0),
            jax.nn.sigmoid(jax.random.normal(kb, (b, t, h))),
            jax.random.normal(kg, (b, t, h, d)))
    name = f"kda {(b, t, h, d)} {jnp.dtype(dt).name}"
    if not kda.takes_kernel(t, d, d):
        print(f"[kernels] {name}: FAIL these shapes would take plain XLA",
              flush=True)
        cases.append({"name": name, "mosaic_calls": 0, "ok": False,
                      "error": "falls through to plain XLA"})
    else:
        cases.append(run_case(
            name, functools.partial(kda.kda_chunked, dtype=dt),
            functools.partial(kda._grouped, dtype=dt), args, 5))
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", "n/a")
    print(f"[kernels] peak_bytes_in_use={peak}", flush=True)
    print("KERNELS_JSON " + json.dumps({"device": device, "cases": cases}),
          flush=True)


# ------------------------------------------------------------- four chips --

def phase_dp4(s: dict, platform: str, result: dict) -> None:
    """DP=4 through cli.train, then the same command seeing one chip."""
    batch = 4 * s["batch"]
    data = ["--dataset", "synthetic", "--synthetic_size",
            str(batch * s["steps"])]
    extra = ("--grad_reduce_dtype", "bfloat16")
    runs = {}
    for name, n in (("train_dp4", 4), ("train_one_chip", 1)):
        out = os.path.join(WORK, name)
        shutil.rmtree(out, ignore_errors=True)
        rec = run_child(name, train_argv(s, platform, out, data, epochs=1,
                                         batch=batch, extra=extra),
                        child_env(platform, devices=n, all_chips=n == 4))
        check(f"'data': {n}" in rec["text"],
              f"{name}: the mesh is not data={n}")
        runs[name] = check_train(rec, out, s["steps"], platform, n)
        result.setdefault("device", runs[name]["device"])
    a, b = runs["train_dp4"]["losses"], runs["train_one_chip"]["losses"]
    say("dp4 losses:      " + " ".join(f"{x:.4f}" for x in a))
    say("one-chip losses: " + " ".join(f"{x:.4f}" for x in b))
    diffs = [abs(x - y) for x, y in zip(a, b)]
    say("abs diff:        " + " ".join(f"{x:.4f}" for x in diffs)
        + f" (tolerance {DP_LOSS_TOL_FIRST} on step 0, {DP_LOSS_TOL} after)")
    check(len(a) == len(b), "dp4 and one-chip runs logged different steps")
    check(diffs[0] <= DP_LOSS_TOL_FIRST and max(diffs) <= DP_LOSS_TOL,
          f"dp4 and one-chip losses disagree: {diffs}")
    peaks = runs["train_dp4"].get("peak_bytes_in_use", [])
    say(f"dp4 peak_bytes_in_use per device: {peaks}; "
        f"one chip: {runs['train_one_chip'].get('peak_bytes_in_use')}")
    if platform == "tpu":
        check(len(peaks) == 4 and all(int(p) > 0 for p in peaks),
              f"dp4: not all four devices report memory in use: {peaks}")
    result.update(runs)


# ------------------------------------------------------------------- main --

PHASES = {"train": phase_train, "serve": phase_serve,
          "kernels": phase_kernels}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="what every child is pinned to (default tpu; with "
                         "no TPU the first child fails at start-up)")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, same control flow (the CPU "
                         "rehearsal the tier-1 test runs)")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list of one-chip phases to run: "
                         + ", ".join(PHASES))
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the DP=4 arm and its one-chip "
                         "comparison (builder-run; the driver never asks)")
    ap.add_argument("--kernels-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernels_child:
        kernels_child(args.platform, args.tiny)
        return 0

    s = TINY if args.tiny else REAL
    names = [p for p in args.phases.split(",") if p]
    unknown = [p for p in names if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; one of {list(PHASES)}")
    plan = ([("dp4", phase_dp4)] if args.chips == 4
            else [(n, PHASES[n]) for n in names])

    result: dict = {}
    failures = []
    for name, fn in plan:
        say(f"=== phase {name} ===")
        t0 = time.monotonic()
        try:
            fn(s, args.platform, result)
            say(f"phase {name}: ok in {time.monotonic() - t0:.1f}s")
        except PhaseFailed as e:
            failures.append(f"{name}: {e}")
            say(f"phase {name}: FAILED — {e}")
        except Exception as e:  # noqa: BLE001 — an HTTP error, a malformed
            # answer, an OSError: whatever it was, the phase failed, and a
            # failed phase still ends in the contract's line
            failures.append(f"{name}: {type(e).__name__}: {e}")
            say(f"phase {name}: FAILED — {type(e).__name__}: {e}\n"
                + traceback.format_exc())
    device = result.get("device")
    if not failures:
        if device is None or device["platform"] != args.platform:
            failures.append(f"device {device} is not {args.platform}")
        elif device["count"] != args.chips:
            failures.append(f"saw {device['count']} devices, "
                            f"--chips {args.chips}")
    ok = not failures
    # the CPU rehearsal keeps its record beside its own work files
    out_dir = OUT if args.platform == "tpu" else WORK
    os.makedirs(out_dir, exist_ok=True)
    for rec in result.values():
        if isinstance(rec, dict):
            rec.pop("text", None)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({"ok": ok, "failures": failures, "phases": result,
                   "seconds": round(time.monotonic() - _T0, 1)}, f, indent=1)
    say(f"total {time.monotonic() - _T0:.1f}s"
        + ("" if ok else f"; failures: {failures}"))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
