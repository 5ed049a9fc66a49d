"""Console + file logging: ETA console lines, output.txt appends,
history.json, and an xlua-style progress bar.

Reference behaviors reproduced:
- rank-0 console lines with per-20-step wall time and ETA in minutes
  (BASELINE/main.py:283-303);
- `output.txt` per-epoch appends (BASELINE/main.py:254-256,
  NESTED/train.py:430-432);
- result txt with `.bak` rotation (CDR/main.py:288-292);
- `history.json` (NESTED/train.py:421,444-445);
- in-place progress bar with step/total time (NESTED/utils.py:49-132).

All file writes are guarded to JAX process 0 — the reference's every-rank
checkpoint/record write race (BASELINE/main.py:308-310) is fixed by design.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from typing import Any, Dict

import jax


def is_host0() -> bool:
    return jax.process_index() == 0


def host0_print(*a: Any, **kw: Any) -> None:
    if is_host0():
        print(*a, **kw)


def format_time(seconds: float) -> str:
    """Days/hours/minutes/seconds/ms formatting (NESTED/utils.py:102-132)."""
    seconds = float(seconds)
    days = int(seconds // 86400)
    seconds -= days * 86400
    hours = int(seconds // 3600)
    seconds -= hours * 3600
    minutes = int(seconds // 60)
    seconds -= minutes * 60
    secs = int(seconds)
    ms = int((seconds - secs) * 1000)
    out, parts = "", 0
    for val, suffix in ((days, "D"), (hours, "h"), (minutes, "m"), (secs, "s"), (ms, "ms")):
        if val > 0 and parts < 2:
            out += f"{val}{suffix}"
            parts += 1
    return out or "0ms"


class ProgressBar:
    """In-place console bar (NESTED/utils.py:49-99 UX, simplified plumbing)."""

    def __init__(self, total: int, width: int = 30):
        self.total = total
        self.width = width
        self.begin = time.time()
        self.last = self.begin

    def step(self, current: int, msg: str = "") -> None:
        if not is_host0():
            return
        now = time.time()
        step_t, tot_t = now - self.last, now - self.begin
        self.last = now
        filled = int(self.width * (current + 1) / max(self.total, 1))
        bar = "=" * filled + ">" + "." * (self.width - filled)
        line = (
            f"\r [{bar}] {current + 1}/{self.total} "
            f"| Step: {format_time(step_t)} | Tot: {format_time(tot_t)} {msg}"
        )
        sys.stdout.write(line)
        if current + 1 >= self.total:
            sys.stdout.write("\n")
        sys.stdout.flush()


def device_memory_line() -> str:
    """Per-device peak HBM as the backend reports it (`n/a` where it does
    not, e.g. CPU) — one figure per local device, so a state that landed
    whole on the first chip shows."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", "n/a")
             for d in jax.local_devices()]
    return "peak_bytes_in_use per device: " + " ".join(map(str, peaks))


class EtaLogger:
    """Per-N-step console line with batch time and ETA in minutes
    (BASELINE/main.py:295-303)."""

    def __init__(self, steps_per_epoch: int, epochs: int, log_every: int = 20):
        self.steps_per_epoch = steps_per_epoch
        self.epochs = epochs
        self.log_every = log_every
        self.t0 = time.time()

    def maybe_log(self, epoch: int, step: int, **metrics: float) -> None:
        if step % self.log_every != 0 or not is_host0():
            return
        now = time.time()
        elapsed = now - self.t0
        self.t0 = now
        done = epoch * self.steps_per_epoch + step
        total = self.epochs * self.steps_per_epoch
        remain = max(total - done, 0)
        eta_min = (elapsed / max(self.log_every, 1)) * remain / 60.0
        parts = "\t".join(f"{k}: {v:.4f}" for k, v in metrics.items())
        print(
            f"Epoch: {epoch}\tstep: {step}/{self.steps_per_epoch}\t{parts}"
            f"\t{self.log_every}-step time: {elapsed:.2f}s\tETA: {eta_min:.1f} min"
        )


class RecordWriter:
    """output.txt / result-txt-with-.bak / history.json writer (process-0 only)."""

    def __init__(self, out_dir: str, rotate_bak: bool = False):
        self.out_dir = out_dir
        self.txt_path = os.path.join(out_dir, "output.txt")
        self.history_path = os.path.join(out_dir, "history.json")
        self.history: Dict[str, list] = {}
        if not is_host0():
            return
        os.makedirs(out_dir, exist_ok=True)
        if rotate_bak and os.path.exists(self.txt_path):
            # CDR/main.py:288-292 keeps one .bak of a previous run's results
            shutil.move(self.txt_path, self.txt_path + ".bak")

    def append_txt(self, line: str) -> None:
        if not is_host0():
            return
        with open(self.txt_path, "a") as f:
            f.write(line.rstrip("\n") + "\n")

    def resume_at(self, start_epoch: int) -> None:
        """Reload an existing history.json and truncate it to `start_epoch`
        so a resumed run APPENDS to the pre-preemption curve instead of
        rewriting history.json with only post-resume epochs (observed:
        runs/digits_plc_fixed/history.json carried epochs 16-24 while
        output.txt had all 25). Truncation keeps history consistent with
        the checkpoint actually restored."""
        if not is_host0():
            return
        if os.path.exists(self.history_path):
            try:
                with open(self.history_path) as f:
                    prior = json.load(f)
            except (json.JSONDecodeError, OSError):
                prior = {}  # a torn write must not kill the resumed run
            for k, v in prior.items():
                if isinstance(v, list):
                    self.history[k] = [
                        float(x) if x is not None else None
                        for x in v[:start_epoch]
                    ]
            self.flush_history()

    def log_epoch(self, epoch: int, **metrics: float) -> None:
        """One epoch record → both output.txt and the in-memory history.

        The invariant is `history[k][e] == epoch e's value`: lists shorter
        than `epoch` (a resume whose prior history was torn or had already
        lost its head) are padded with JSON nulls so the curve never shifts
        — epoch 16's loss must not masquerade as epoch 0's."""
        self.append_txt(
            f"epoch:{epoch}\t" + "\t".join(f"{k}:{v:.6f}" for k, v in metrics.items())
        )
        for k, v in metrics.items():
            lst = self.history.setdefault(k, [])
            if len(lst) > epoch:
                lst[epoch] = float(v)  # re-logged epoch overwrites in place
            else:
                while len(lst) < epoch:
                    lst.append(None)
                lst.append(float(v))
        self.flush_history()

    def flush_history(self) -> None:
        if not is_host0():
            return
        # atomic tmp+replace (same pattern as train/checkpoint.py): a
        # preemption mid-write must leave the previous epoch's complete file,
        # not a torn one — resume_at treats a torn file as empty, which
        # would drop the whole pre-preemption curve
        tmp = self.history_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.history, f, indent=1)
        os.replace(tmp, self.history_path)
