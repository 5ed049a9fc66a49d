"""Device mesh construction and sharding rules.

This module is the whole replacement for the reference's distribution layer
(SURVEY §2.3): `dist.init_process_group('nccl', ...)` + DDP + SyncBatchNorm +
DistributedSampler (BASELINE/main.py:35-38,127-131,147-149) collapse into

    mesh = make_mesh()                       # ('data', 'model') over ICI/DCN
    batch = make_global_array(host_batch, mesh)   # per-host shard → jax.Array
    step  = jax.jit(train_step, in_shardings=..., donate_argnums=...)

XLA then inserts the gradient allreduce (implicit in the sharded-batch mean),
the BN cross-replica stats, and any tensor-parallel collectives — over ICI
when the axis fits inside a slice, DCN across slices. There is nothing to
rendezvous: on pods, `jax.distributed.initialize()` is the only setup call.

The 'model' axis exists for class-dim tensor parallelism of wide heads
(ArcFace identity matrices) — the vision analogue of sequence parallelism
(SURVEY §5). Default mesh shape puts all devices on 'data'.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """data_parallel=0 → all devices on the data axis.

    pipeline_parallel > 1 adds a third 'pipe' axis so GPipe stages can
    compose with class-dim TP on 'model' (dp×tp×pp in one program) —
    with the default of 1, meshes stay two-axis and every existing
    sharding rule is unchanged. Axis order is (data, model, pipe):
    'pipe' innermost keeps each stage ring on contiguous ICI neighbor
    links, the latency-critical hop (one ppermute per pipeline tick)."""

    data_parallel: int = 0
    model_parallel: int = 1
    pipeline_parallel: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int]:
        mp = max(self.model_parallel, 1)
        pp = max(self.pipeline_parallel, 1)
        dp = self.data_parallel or n_devices // (mp * pp)
        if dp * mp * pp != n_devices:
            raise ValueError(
                f"mesh {dp}×{mp}×{pp} does not cover {n_devices} devices"
            )
        return dp, mp, pp


def viable_world(spec: MeshSpec, n_devices: int) -> bool:
    """Whether `spec` resolves over `n_devices` — the elastic membership
    round's viability gate (parallel/fleet.py check_viable): a survivor
    world whose device count cannot cover the configured mesh must be
    the deterministic pod-unviable rc, not a construction-time crash
    after rendezvous."""
    if n_devices < 1:
        return False
    try:
        spec.resolve(n_devices)
    except ValueError:
        return False
    return True


def make_mesh(spec: MeshSpec = MeshSpec(), devices: Optional[Sequence[Any]] = None) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    dp, mp, pp = spec.resolve(len(devices))
    shape = (dp, mp, pp) if pp > 1 else (dp, mp)
    axes = (DATA_AXIS, MODEL_AXIS, PIPE_AXIS) if pp > 1 else (DATA_AXIS, MODEL_AXIS)
    if (mp > 1 or pp > 1) and devices[0].platform == "tpu":
        # ICI-aware layout: contiguous (ring-neighbor) device groups on the
        # model/pipe axes, so ppermute rings (ring attention, GPipe handoffs)
        # and TP collectives ride ICI neighbor links instead of striding the
        # torus. A failure here raises: on the chip a naive reshape would
        # silently put the rings on the wrong links.
        from jax.experimental import mesh_utils

        return Mesh(mesh_utils.create_device_mesh(shape, devices=devices), axes)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, axes)


def composed_audit_meshes(devices: Optional[Sequence[Any]] = None
                          ) -> "dict[str, Mesh]":
    """The analysis passes' composed multi-device meshes, by name:
    `dp2` (2×1, data-only), `dp2tp2` (2×2, dp×tp), and `dp4` (4×1, the
    serve-fleet width: one data axis wide enough that the dp-split top-k
    gather is non-trivial), built over a
    deterministic PREFIX of the device list so the audited program — and
    therefore the checked-in baseline (analysis/baselines.json) — is
    identical whether the host exposes 4, 8, or 256 devices. Meshes the
    device count cannot cover are simply absent from the dict; callers
    that require one (analysis/sharding_audit.py) raise their own error
    naming the forced-device-count fix."""
    devices = list(devices) if devices is not None else jax.devices()
    out: "dict[str, Mesh]" = {}
    if len(devices) >= 2:
        out["dp2"] = make_mesh(MeshSpec(2, 1), devices=devices[:2])
    if len(devices) >= 4:
        out["dp2tp2"] = make_mesh(MeshSpec(2, 2), devices=devices[:4])
        out["dp4"] = make_mesh(MeshSpec(4, 1), devices=devices[:4])
    return out


def serve_mesh(n_devices: int = 0,
               devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Pure data-parallel mesh for the serving engine: every device on
    'data' (the predict step has no model axis to feed — class-dim TP in
    serving arrives via an explicitly composed mesh, not this helper).
    `n_devices=0` takes the whole host/pod; a positive count takes a
    deterministic prefix so replicas of different pod shapes can pin the
    same serve width. Raises ValueError (the cli.serve rc-2 family) when
    the request exceeds what exists."""
    devices = list(devices) if devices is not None else jax.devices()
    if n_devices < 0:
        raise ValueError(f"serve_devices must be >= 0, got {n_devices}")
    if n_devices > len(devices):
        raise ValueError(
            f"serve_devices={n_devices} exceeds the {len(devices)} visible "
            "devices — lower --serve_devices or widen the deployment")
    if n_devices:
        devices = devices[:n_devices]
    return make_mesh(MeshSpec(), devices=devices)


def make_hybrid_mesh(spec: MeshSpec = MeshSpec(), *,
                     dcn_data_parallel: int = 0) -> Mesh:
    """Multi-slice mesh: data parallelism split across DCN-connected slices,
    model axis kept inside a slice (ICI).

    On a multi-slice TPU deployment (e.g. 2× v5e-256), collectives between
    slices cross DCN — orders of magnitude slower than ICI — so the only
    axis that should span slices is pure-DP gradient averaging (one
    allreduce per step), while TP/SP/PP rings stay intra-slice. This is the
    standard two-tier layout `mesh_utils.create_hybrid_device_mesh` encodes;
    the reference's NCCL backend has no equivalent concept (its multi-node
    path is broken anyway — SURVEY §2.2 rank bug).

    dcn_data_parallel: number of slices (0 = infer from
    jax.devices()' slice_index when present, else 1 → plain make_mesh).
    """
    devices = jax.devices()
    if max(spec.pipeline_parallel, 1) > 1:
        # the two-tier hybrid layout is (data, model) only; silently
        # dropping the requested 'pipe' axis would hand back a different
        # parallelism program than asked for
        raise ValueError(
            "dcn_slices does not compose with pipeline_stages yet: the "
            "hybrid mesh is two-axis (data, model) — drop --pp_stages "
            "(stages ride the model axis) or --dcn_slices")
    n_slices = dcn_data_parallel
    if not n_slices:
        slice_ids = {getattr(d, "slice_index", 0) for d in devices}
        n_slices = len(slice_ids)
    if n_slices <= 1:
        return make_mesh(spec, devices)
    from jax.experimental import mesh_utils

    per_slice = len(devices) // n_slices
    dp_ici, mp, _ = MeshSpec(
        spec.data_parallel // n_slices if spec.data_parallel else 0,
        spec.model_parallel).resolve(per_slice)
    try:
        arr = mesh_utils.create_hybrid_device_mesh(
            (dp_ici, mp), (n_slices, 1), devices=devices)
    except ValueError:
        if any(hasattr(d, "slice_index") for d in devices):
            # real multi-slice hardware: a layout error here means the
            # requested slice count doesn't match the machine — falling
            # back silently would put rings/TP on DCN, the exact failure
            # this flag exists to prevent
            raise
        # CPU/simulated devices carry no slice topology; keep the same
        # two-tier LOGICAL layout (slice-major data axis) with a plain
        # reshape — the virtual-mesh tests exercise this path
        arr = np.asarray(devices).reshape(n_slices, dp_ici, mp).reshape(
            n_slices * dp_ici, mp)
    # Resulting shape is (n_slices·dp_ici, mp): the two DP tiers flatten
    # into one 'data' axis — shardings stay identical to the single-slice
    # case; XLA routes the gradient allreduce hierarchically (ICI within a
    # slice, DCN across)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis (batch) sharding over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def make_global_array(host_batch: Any, mesh: Mesh,
                      sharding: Optional[NamedSharding] = None) -> Any:
    """Assemble per-host numpy batches into a globally batch-sharded
    jax.Array (the H2D step; replaces `.cuda(non_blocking=True)` +
    DistributedSampler semantics, BASELINE/main.py:273-274).

    Safe to call from a background stager thread (data/device_prefetch.py
    overlaps this stage with device compute): it only constructs arrays,
    touching no global backend state. `sharding` lets per-batch hot loops
    reuse a prebuilt `batch_sharding(mesh)` instead of reconstructing it."""
    if sharding is None:
        sharding = batch_sharding(mesh)

    def put(x):
        x = np.asarray(x)
        global_shape = (x.shape[0] * jax.process_count(),) + x.shape[1:]
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    return jax.tree_util.tree_map(put, host_batch)


# -------------------------------------------------------------- parameters --

def _spec_for_param(path: str, value: Any, model_axis_size: int,
                    pipe_axis_size: int = 1) -> P:
    """Sharding rule for one parameter.

    Everything is replicated under pure DP. With a >1 'model' axis, the wide
    class-dim matrices are sharded on their class dimension:
    - ArcMarginHead 'weight' (C, D) → P('model', None)
    - final fc / NetClassifier kernels (D, C) → P(None, 'model')
    This is the ArcFace-at-10⁶-identities headroom (SURVEY §5): the (B, C)
    logits then shard over 'model' and XLA turns softmax-CE into a
    psum-over-axis reduction.

    GPipeViT stacked block params (leading dim = depth) shard over the
    dedicated 'pipe' axis when the mesh has one (3-axis dp×tp×pp), else
    over 'model' (the legacy 2-axis one-role-per-config layout).
    """
    stage_axis, stage_size = (
        (PIPE_AXIS, pipe_axis_size) if pipe_axis_size > 1
        else (MODEL_AXIS, model_axis_size))
    if ("['blocks']" in path and value.ndim >= 1 and stage_size > 1
            and value.shape[0] % stage_size == 0):
        # stacked block params (L, ...): depth dim → pipeline stages
        return P(stage_axis)
    if model_axis_size <= 1:
        return P()
    if "margin" in path and path.endswith("weight']") and value.ndim == 2:
        return P(MODEL_AXIS, None)
    if any(f"'{name}'" in path for name in
           ("moe_w_in", "moe_b_in", "moe_w_out", "moe_b_out",
            "w_gate", "w_up", "w_down")) and (
            value.shape[0] % model_axis_size == 0):
        # Exactly the MoE expert banks (E, ...) — matched by name, not by a
        # 'moe_' substring, so a future moe_-prefixed non-bank param can't be
        # silently expert-sharded. Expert dim → expert-parallel shards
        # (ops/moe.py); moe_router stays replicated (every token gates over
        # every expert)
        return P(*([MODEL_AXIS] + [None] * (value.ndim - 1)))
    if value.ndim == 2 and "kernel" in path and (
            "classifier" in path or "']['fc']" in path):
        return P(None, MODEL_AXIS)
    return P()


def param_shardings(variables: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree matching `variables` (params + batch_stats)."""
    mp = mesh.shape[MODEL_AXIS]
    pp = dict(mesh.shape).get(PIPE_AXIS, 1)
    flat, treedef = jax.tree_util.tree_flatten_with_path(variables)
    specs = [
        NamedSharding(
            mesh, _spec_for_param(jax.tree_util.keystr(path), value, mp, pp))
        for path, value in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ZeRO-1 (Rajbhandari et al. 2020): optimizer-state leaves below this
# size stay replicated — slicing a 4 KiB bias momentum over 256 data
# shards buys nothing and costs an all-gather launch per leaf.
ZERO_MIN_BYTES = 64 * 1024


def zero_opt_enabled(setting: str, mesh: Mesh) -> bool:
    """Resolve a `parallel.zero_opt` setting against a mesh: 'auto' and
    'on' both mean ZeRO iff the data axis actually spans devices (at
    dp=1 the partition would be the identity — keep the specs clean
    instead), 'off' disables unconditionally."""
    if setting not in ("auto", "on", "off"):
        raise ValueError(
            f"parallel.zero_opt must be auto|on|off, got {setting!r}")
    return setting != "off" and mesh.shape[DATA_AXIS] > 1


def _zero_spec(spec: P, value: Any, data_axis_size: int) -> P:
    """Extend a model/pipe-axis spec with a 'data' partition on the first
    free dimension the data axis divides — the ZeRO-1 shard. Scalars and
    small leaves (< ZERO_MIN_BYTES) keep the base spec; leaves no
    dimension of which divides evenly stay replicated rather than pad."""
    spec = tuple(spec)
    if not hasattr(value, "ndim") or value.ndim == 0:
        return P(*spec)
    size = int(np.prod(value.shape)) * np.dtype(value.dtype).itemsize
    if size < ZERO_MIN_BYTES:
        return P(*spec)
    full = list(spec) + [None] * (value.ndim - len(spec))
    for d in range(value.ndim):
        if full[d] is None and value.shape[d] > 0 \
                and value.shape[d] % data_axis_size == 0:
            full[d] = DATA_AXIS
            return P(*full)
    return P(*spec)


def opt_shardings(opt_state: Any, mesh: Mesh, zero_data: bool = False) -> Any:
    """NamedSharding pytree for an optax state.

    jit(tx.init) does NOT propagate parameter shardings into the momentum
    tree (outputs land on one device), so optimizer state gets explicit
    shardings: momentum/trace subtrees mirror the parameter tree's key paths,
    so the same `_spec_for_param` rules apply — class-sharded weights get
    class-sharded momentum, everything else replicates. Without this, a
    restored state (device_put onto the template's shardings) mixes
    single-device opt leaves with mesh-wide params and jit rejects the step.

    zero_data=True additionally partitions each big leaf over the 'data'
    axis (`_zero_spec`), composing with the model/pipe rules: a
    class-sharded momentum stays class-sharded AND gains a data split on
    a remaining free dim. Works on concrete arrays and on avals/tracers
    alike (only shape/dtype are read), so the step factories reuse it for
    output sharding constraints.
    """
    if not zero_data:
        # momentum key paths embed the param key paths, so the param rules
        # apply
        return param_shardings(opt_state, mesh)
    mp = mesh.shape[MODEL_AXIS]
    pp = dict(mesh.shape).get(PIPE_AXIS, 1)
    dp = mesh.shape[DATA_AXIS]
    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
    shardings = [
        NamedSharding(mesh, _zero_spec(
            _spec_for_param(jax.tree_util.keystr(path), value, mp, pp),
            value, dp))
        for path, value in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, shardings)
