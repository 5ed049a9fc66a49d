"""AOT-serialized serve executables: instant replica cold start.

`ServingEngine.warmup()` normally pays `len(buckets)` XLA compiles before
a replica can take traffic — ~0.5 s/bucket on CPU, tens of seconds for a
real model on TPU, multiplied by every replica that joins a serving
fleet. The compiled programs are identical across replicas (same model,
same buckets, same mesh shape), so the first replica to warm up banks
them: each bucket executable is AOT-serialized via
`jax.experimental.serialize_executable` into an `aot/` sidecar directory
next to the checkpoint, and a joining replica deserializes instead of
compiling — the compile sentinel asserts ZERO compile events on a warm
boot (tests/test_serve_aot.py).

Why not the XLA persistent compilation cache (utils/cache.py)? That
cache deserializes numerically-wrong executables on CPU (observed
2026-08-04, which is why `enable_persistent_cache` refuses CPU), and it
keys opaquely — no way to assert "this serve boot compiled nothing".
`serialize_executable` round-trips the already-compiled executable
bit-identically on CPU and TPU alike, and the manifest fingerprint below
makes staleness explicit instead of silent.

Sidecar layout (all writes atomic tmp + os.replace; manifest LAST, so a
torn publish leaves payloads without a manifest = plain cache miss):

    <aot_dir>/manifest.json      fingerprint + per-bucket digests
    <aot_dir>/aot_b{B}.pkl       pickle of (payload, in_tree, out_tree)

Staleness/corruption ladder on load (each rung falls back to the normal
compile path — a stale or torn sidecar must never take down a replica):

  - manifest missing / unparseable JSON        → miss (unparseable also
    quarantined: it claims to be a manifest and is not)
  - environment fingerprint mismatch (jax or jaxlib version, backend
    platform, device count, mesh shape, bucket set)  → miss
  - program drift: the smallest bucket is re-LOWERED (one trace, no
    compile) and its StableHLO digest compared to the manifest — model
    code changed since the bank → miss
  - payload bytes don't hash to the manifest digest (torn write, bit
    rot) → that payload quarantined to *.corrupt exactly like a torn
    checkpoint (train/checkpoint.py::quarantine_file), whole load → miss
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
from typing import Any, Callable, Dict, Optional, Sequence

import jax

from ..train.checkpoint import quarantine_file
from ..utils.logging import host0_print

MANIFEST = "manifest.json"
FORMAT_VERSION = 1


def payload_path(aot_dir: str, bucket: int) -> str:
    return os.path.join(aot_dir, f"aot_b{bucket}.pkl")


def _sha256_bytes(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _hlo_digest(lowered: Any) -> str:
    """sha256 of the lowered program's StableHLO text — the 'same program?'
    check. Lowering is a trace (sub-second), not a compile, so the warm
    path stays compile-free while still catching model-code drift."""
    return _sha256_bytes(lowered.as_text().encode())


def env_fingerprint(mesh: Any, buckets: Sequence[int]) -> Dict[str, Any]:
    """Everything that invalidates a serialized executable besides the
    program itself: an executable compiled by a different XLA build, for
    a different platform, or for a different device layout deserializes
    wrong (or not at all) — refuse early and explicitly."""
    return {
        "format_version": FORMAT_VERSION,
        "jax_version": jax.__version__,
        "jaxlib_version": getattr(
            __import__("jaxlib"), "__version__", "unknown"),
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "mesh_shape": dict(mesh.shape) if mesh is not None else {},
        "buckets": sorted(int(b) for b in buckets),
    }


def _atomic_write(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)


def save_bucket_executables(
    aot_dir: str,
    lowered: Dict[int, Any],
    compiled: Dict[int, Any],
    mesh: Any,
) -> bool:
    """Bank the warm engine's compiled bucket executables. Returns True on
    a complete publish. Failures are reported, never raised — banking is
    an optimization; the replica that just compiled serves fine without
    it. Payloads land first, manifest strictly LAST: a crash mid-publish
    leaves a manifest-less (or stale-manifest) dir that the next load
    treats as a miss, never as truth."""
    from jax.experimental.serialize_executable import serialize

    try:
        os.makedirs(aot_dir, exist_ok=True)
        manifest = env_fingerprint(mesh, sorted(compiled))
        entries: Dict[str, Any] = {}
        for bucket in sorted(compiled):
            payload, in_tree, out_tree = serialize(compiled[bucket])
            blob = pickle.dumps((payload, in_tree, out_tree))
            _atomic_write(payload_path(aot_dir, bucket), blob)
            entries[str(bucket)] = {
                "payload_sha256": _sha256_bytes(blob),
                "hlo_sha256": _hlo_digest(lowered[bucket]),
                "bytes": len(blob),
            }
        manifest["entries"] = entries
        _atomic_write(os.path.join(aot_dir, MANIFEST),
                      json.dumps(manifest, indent=1, sort_keys=True).encode())
        return True
    except Exception as e:  # noqa: BLE001 — banking must never kill serving
        host0_print(f"[serve] AOT sidecar publish failed ({e!r}) — replicas "
                    "will cold-compile until the next successful warmup")
        return False


def load_bucket_executables(
    aot_dir: str,
    mesh: Any,
    buckets: Sequence[int],
    lower_smallest: Callable[[int], Any],
) -> Optional[Dict[int, Any]]:
    """Deserialize the banked bucket executables, or None = cache miss
    (caller compiles normally). `lower_smallest(bucket)` must return the
    caller's `predict.lower(...)` for that bucket — re-lowering exactly
    one bucket is the cheap program-drift probe (the other buckets are
    covered transitively: same factory, same model, only the leading dim
    differs, and their payload digests still gate torn bytes)."""
    from jax.experimental.serialize_executable import deserialize_and_load

    manifest_path = os.path.join(aot_dir, MANIFEST)
    try:
        with open(manifest_path, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    try:
        manifest = json.loads(raw)
    except ValueError:
        quarantine_file(manifest_path, "aot manifest unparseable",
                        kind="aot manifest")
        return None

    want = env_fingerprint(mesh, buckets)
    got = {k: manifest.get(k) for k in want}
    if got != want:
        drift = sorted(k for k in want if got[k] != want[k])
        host0_print(f"[serve] AOT sidecar fingerprint mismatch on {drift} — "
                    "falling back to compile")
        return None
    entries = manifest.get("entries", {})
    try:
        banked = sorted(int(b) for b in entries)
    except ValueError:
        return None
    if banked != sorted(int(b) for b in buckets):
        return None

    smallest = min(int(b) for b in buckets)
    if _hlo_digest(lower_smallest(smallest)) != \
            entries[str(smallest)]["hlo_sha256"]:
        host0_print("[serve] AOT sidecar program drift (model code changed "
                    "since bank) — falling back to compile")
        return None

    out: Dict[int, Any] = {}
    for bucket in sorted(int(b) for b in buckets):
        path = payload_path(aot_dir, bucket)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        if _sha256_bytes(blob) != entries[str(bucket)]["payload_sha256"]:
            quarantine_file(path, "aot payload digest mismatch",
                            kind="aot payload")
            return None
        try:
            payload, in_tree, out_tree = pickle.loads(blob)
            # the executable runs on the serve mesh's devices, which may be
            # a prefix of the backend's (serve_devices < visible devices)
            out[bucket] = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(mesh.devices.flat))
        except Exception as e:  # noqa: BLE001 — a poisoned payload = miss
            quarantine_file(path, f"aot payload undeserializable ({e!r})",
                            kind="aot payload")
            return None
    return out
