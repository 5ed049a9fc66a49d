"""Jitted train/eval step builders for every workload.

Each builder closes over the static pieces (model, optimizer, workload
algorithm) and returns ONE jitted function. Under jit with a batch-sharded
global array, XLA inserts every collective the reference performs explicitly:

- DDP's bucketed gradient allreduce (BASELINE/main.py:149, backward hooks) is
  implicit in the mean-over-global-batch loss;
- SyncBatchNorm's stat reduction (BASELINE/main.py:148) is implicit in
  BatchNorm's mean over the sharded batch axis;
- the eval `dist.reduce` the reference *approximates away*
  (BASELINE/main.py:247-249 scales one rank's counts by world_size) is an
  exact cross-shard sum here, for free.

Train steps donate the state buffer (in-place device update). Metrics are
computed in-jit from the same logits used for the loss — the reference pays a
separate `.item()` device→host sync per log line (BASELINE/main.py:284-303).

Donation policy (audited by analysis/jaxpr_audit.py, `cli.analyze`):

- **train steps donate arg 0 (state)** and the audit asserts EVERY donated
  byte is aliased in the compiled executable — no state leaf round-trips
  HBM between steps (measured: 100% coverage, params+BN+opt all aliased).
- **eval/predict steps deliberately donate nothing.** The state is live
  across calls — the same TrainState feeds every val/serve batch, and a
  donated buffer is deleted after its first use. The per-batch inputs ARE
  dead after each call, but they have no same-shape/dtype outputs to alias
  (uint8 images → f32 activations, i32 labels → f32 scalars), so donating
  them buys no reuse and only triggers XLA "donation not used" stalls.
  Each no-donate entry carries this reason in the audit registry; removing
  a donation from a train step (or adding a donation here) turns the
  analyzer red.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..config import Config
from ..data.transforms import (
    IMAGENET_MEAN,
    IMAGENET_STD,
    preset_for_dataset,
)
from ..models.factory import feat_dim_for
from ..ops.nested import (
    gaussian_dist,
    nested_all_k_counts,
    prefix_mask,
    sample_mask_dims,
)
from ..utils.metrics import topk_correct, topk_hits
from .state import TrainState, state_shardings

Batch = Tuple[jnp.ndarray, jnp.ndarray]  # (images NHWC u8|f32, labels i32)

# Device-side names (`jax.named_scope`) of what a train step does OUTSIDE
# value_and_grad, where autodiff writes no mark of its own into an op's
# `op_name`: the uint8 epilogue, the gradient exchange (ZeRO constraints, the
# sections' pmeans), the non-finite guard (norm, isfinite, the keep-selects),
# the optimizer update, the metrics. Forward, backward and recomputation have
# no scope: `jvp(`, `transpose(` and `rematted_computation` in the same path
# are their names (docs/observability.md, Device-side names).
STEP_SCOPES = ("step.input", "step.exchange", "step.guard", "step.opt",
               "step.metrics")
_INPUT, _EXCHANGE, _GUARD, _OPT, _METRICS = STEP_SCOPES

# fold_in tag deriving the flip stream from the step rng WITHOUT consuming
# it — the float32 wire's mask/dropout derivations stay bit-identical
_FLIP_FOLD = 0x464C4950  # "FLIP"


def device_input_epilogue(images: jnp.ndarray,
                          rng: Optional[jax.Array] = None,
                          flip: bool = False) -> jnp.ndarray:
    """uint8 wire → normalized float32 NHWC, in-jit.

    The uint8 dataplane (data.input_dtype == "uint8") ships raw pixels
    across H2D at ¼ the bytes and defers `(x/255 − μ)/σ` — same f32 op
    order as the host `transforms.normalize`, so the two wires match to
    float tolerance on identical crops — to this epilogue, which XLA fuses
    into the first conv's input read (elementwise producer fusion: no extra
    HBM pass). With `flip`, a per-sample horizontal flip (the train
    augmentation the uint8 transforms skip host-side) draws its mask from
    `fold_in(rng, _FLIP_FOLD)` — deterministic per step key, and fold_in
    leaves the caller's rng stream untouched.

    Dtype dispatch is static (jit specializes per input aval): float32
    inputs pass through UNTOUCHED, so the legacy host-normalized path
    compiles to exactly the pre-uint8 program."""
    if images.dtype != jnp.uint8:
        return images
    x = images.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)
    if flip and rng is not None:
        mask = jax.random.bernoulli(
            jax.random.fold_in(rng, _FLIP_FOLD), 0.5, (images.shape[0],))
        # NHWC: axis 2 is width — the host path's arr[:, ::-1] per sample
        x = jnp.where(mask[:, None, None, None], x[:, :, ::-1, :], x)
    return x


def _train_flip_enabled(cfg: Config) -> bool:
    """Device-side flip applies exactly where the float32 wire would have
    host-flipped: train transforms of every image preset include one
    (synthetic data has no transform → no flip)."""
    return (cfg.data.input_dtype == "uint8"
            and preset_for_dataset(cfg.data.dataset, cfg.data.transform)
            is not None)


def _cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax-CE — semantics of the reference's LogSoftmax+NLLLoss pair
    (BASELINE/main.py:139,152) in one fused, stable op."""
    with jax.named_scope("loss"):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean()


def _train_metrics(loss, logits, labels) -> Dict[str, jnp.ndarray]:
    n = labels.size
    return {
        "loss": loss,
        "top1": topk_correct(logits, labels, 1) / n,
        "top3": topk_correct(logits, labels, 3) / n,
    }


def make_train_step(
    cfg: Config,
    model: Any,
    tx: optax.GradientTransformationExtraArgs,
    base_rng: Optional[jax.Array] = None,
    mesh: Optional[Any] = None,
    chaos: Optional[Any] = None,
) -> Callable[[TrainState, jnp.ndarray, jnp.ndarray], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """One jitted `(state, images, labels) -> (state, metrics)` for the
    workload in `cfg` (baseline/cdr: plain CE; arcface: margin logits;
    nested: per-batch prefix mask k ~ Gaussian, NESTED/train.py:247-250).

    With `parallel.arcface_sharded_ce` (and a model axis > 1), the ArcFace
    loss runs the partial-FC path: embeddings + class-sharded weight feed
    `ops.sharded_head.arc_margin_ce_sharded`, so no (B, C) logits exist —
    `mesh` is required for that mode.

    `chaos` (utils/chaos.py FaultPlan): nan_loss faults poison the loss on
    their step windows inside jit — the staged version of a real
    divergence, which the step's non-finite guard must absorb.

    With a mesh whose data axis spans devices, `parallel.zero_opt`
    (default auto=on) makes the step ZeRO-1: gradients and optimizer
    state carry data-axis sharding constraints so XLA compiles
    reduce-scatter → shard-local update → param all-gather instead of
    replicated all-reduce + N identical updates — same arithmetic, 1/dp
    of the optimizer HBM. `parallel.grad_reduce_dtype=bfloat16`
    additionally routes fwd/bwd through a shard_map section that casts
    gradients to bf16 for ONE cross-replica mean (half the wire payload)
    and accumulates back into the f32 master params.

    `parallel.grad_accum=K` (default 1 = exactly today's program — the
    dispatch is static, so K=1 compiles the legacy HLO byte-for-byte)
    turns the step into a K-microbatch ACCUMULATED step: the batch
    reshapes to (K, mb, ...) and a `lax.scan` runs the same loss/grad
    per microbatch into an f32 accumulator; the cross-replica gradient
    reduction (f32, or the bf16 wire — they compose for a ÷2K payload),
    the ZeRO-1 reduce-scatter → update → all-gather, and the sentinel's
    all-finite gate all run ONCE per K microbatches, at the optimizer
    boundary. Construction rejects (`grad-accum-indivisible`) a
    per-replica batch K cannot slice evenly, and composition with the
    pipeline schedule or `arcface_sharded_ce` (each already owns its own
    microbatch loop)."""
    from ..parallel.mesh import DATA_AXIS, zero_opt_enabled

    workload = cfg.model.head
    if base_rng is None:
        base_rng = jax.random.PRNGKey(cfg.run.seed + 1)

    flip = _train_flip_enabled(cfg)
    zero = mesh is not None and zero_opt_enabled(cfg.parallel.zero_opt, mesh)

    reduce_dtype = cfg.parallel.grad_reduce_dtype
    if reduce_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            "parallel.grad_reduce_dtype must be float32|bfloat16, got "
            f"{reduce_dtype!r}")
    want_bf16 = (reduce_dtype == "bfloat16" and mesh is not None
                 and dict(mesh.shape).get(DATA_AXIS, 1) > 1)

    grad_accum = max(int(cfg.parallel.grad_accum), 1)
    if grad_accum > 1:
        _require_accum_compatible(cfg, mesh, grad_accum)

    if cfg.parallel.arcface_sharded_ce and workload == "arcface":
        if want_bf16:
            raise ValueError(
                "grad_reduce_dtype=bfloat16 does not compose with "
                "arcface_sharded_ce (the partial-FC loss is its own "
                "shard_map program) — drop one of the two")
        _require_sharded_ce_mesh(mesh)
        loss_fn, metrics_fn = _arcface_sharded_loss(cfg, model, mesh)
        return _build_step(tx, base_rng, loss_fn, metrics_fn, chaos=chaos,
                           flip=flip, mesh=mesh, zero=zero)

    grad_section = None
    if want_bf16:
        if workload == "nested":
            # the per-batch prefix mask k is sampled ONCE for the global
            # batch (NESTED/train.py:247-250); a per-shard section would
            # draw divergent k per replica and silently train a different
            # objective
            raise ValueError(
                "grad_reduce_dtype=bfloat16 does not support the nested "
                "workload (per-batch mask k must be sampled globally)")
        if (dict(mesh.shape).get("model", 1) > 1
                or max(cfg.parallel.pipeline_stages, 1) > 1
                or cfg.parallel.pipeline_microbatches > 0):
            raise ValueError(
                "grad_reduce_dtype=bfloat16 is the pure-DP fast path; it "
                "does not compose with a model/pipe axis — use float32 "
                "reduction there")
        grad_section = (_accum_grad_section(cfg, mesh, grad_accum,
                                            jnp.bfloat16)
                        if grad_accum > 1
                        else _reduced_grad_section(cfg, mesh, jnp.bfloat16))
    elif grad_accum > 1 and mesh is not None:
        # f32-wire accumulation: the same deferred-reduction section with
        # the summed gradients crossing replicas once at float32
        grad_section = _accum_grad_section(cfg, mesh, grad_accum,
                                           jnp.float32)

    if cfg.model.arch == "decoder_lm":
        if grad_section is not None:
            raise ValueError(
                "decoder_lm takes its head and loss in row blocks inside the "
                "step; grad_accum > 1 and grad_reduce_dtype=bfloat16 (the "
                "shard_map gradient sections) do not carry that loss yet")
        loss_fn, metrics_fn = _lm_loss(cfg, model)
        return _build_step(tx, base_rng, loss_fn, metrics_fn, chaos=chaos,
                           flip=False, mesh=mesh, zero=zero)

    return _build_step(tx, base_rng, _dense_loss_fn(cfg, model),
                       lambda loss, logits, labels: _train_metrics(loss, logits, labels),
                       chaos=chaos, flip=flip, mesh=mesh, zero=zero,
                       grad_section=grad_section, grad_accum=grad_accum)


def _require_accum_compatible(cfg: Config, mesh, grad_accum: int) -> None:
    """Up-front `grad-accum-indivisible` rejections (rc 2 through
    cli.train's config-error mapping, mirroring the grad_reduce_dtype
    pattern). Every microbatch must be the same size on every data
    replica — a ragged last microbatch would silently re-weight its
    samples' gradients — and grad_accum cannot compose with programs
    that already own their own microbatch loop."""
    from ..parallel.mesh import DATA_AXIS

    if (max(cfg.parallel.pipeline_stages, 1) > 1
            or cfg.parallel.pipeline_microbatches > 0):
        raise ValueError(
            "grad-accum-indivisible: grad_accum > 1 does not compose with "
            "the pipeline schedule (pipeline_microbatches already owns the "
            "microbatch loop) — pick one microbatching scheme")
    if cfg.parallel.arcface_sharded_ce and cfg.model.head == "arcface":
        raise ValueError(
            "grad-accum-indivisible: grad_accum > 1 does not compose with "
            "arcface_sharded_ce (the partial-FC loss is its own shard_map "
            "program whose batch the accumulation scan cannot slice) — "
            "drop one of the two")
    dp = dict(mesh.shape).get(DATA_AXIS, 1) if mesh is not None else 1
    batch = cfg.data.batch_size
    if batch % dp or (batch // dp) % grad_accum:
        raise ValueError(
            f"grad-accum-indivisible: per-replica batch {batch}/{dp} does "
            f"not split into grad_accum={grad_accum} equal microbatches — "
            "pick K dividing batch_size/dp (equal microbatches keep the "
            "accumulated mean exact)")


def _dense_loss_fn(cfg: Config, model: Any):
    """The dense-logits train loss shared by every non-partial-FC workload:
    `loss_fn(params, batch_stats, images, labels, rng) -> (loss,
    (new_batch_stats, logits))` with the per-workload forward dispatch
    (baseline/cdr: plain CE; arcface: margin logits; nested: per-batch
    prefix mask k ~ Gaussian, NESTED/train.py:247-250)."""
    workload = cfg.model.head
    if workload == "nested":
        dist = jnp.asarray(gaussian_dist(0.0, cfg.model.nested_std, feat_dim_for(cfg.model)))
        feat_dim = feat_dim_for(cfg.model)

    def loss_fn(params, batch_stats, images, labels, rng):
        variables = {"params": params, "batch_stats": batch_stats}
        mask_rng, drop_rng = jax.random.split(rng)
        # 'losses' collects sown auxiliary penalties (MoE router balance);
        # models without them just leave it empty
        kwargs = dict(train=True, mutable=["batch_stats", "losses"],
                      rngs={"dropout": drop_rng})
        if workload == "arcface":
            logits, mutated = model.apply(variables, images, labels, **kwargs)
        elif workload == "nested":
            k = sample_mask_dims(mask_rng, dist)          # one k per batch (:248)
            mask = prefix_mask(k, feat_dim)
            logits, mutated = model.apply(variables, images, mask, **kwargs)
        else:
            logits, mutated = model.apply(variables, images, **kwargs)
        loss = _cross_entropy(logits, labels)
        aux = sum(jax.tree_util.tree_leaves(mutated.get("losses", {})))
        if cfg.model.moe_aux_weight:
            loss = loss + cfg.model.moe_aux_weight * aux
        return loss, (mutated.get("batch_stats", batch_stats), logits)

    return loss_fn


def _lm_sums(cfg: Config, model: Any, params, tokens, targets, train: bool,
             weights=None, mtp: bool = False):
    """Token decoder: Σ cross-entropy, top-1 and top-3 counts over every
    position (each scaled by `weights`), and the routing layers' expert
    loads — the hidden states go through head and loss in row blocks. With
    `mtp` (a configuration with a prediction module) a fifth value: the
    module's Σ cross-entropy against the token after next, through the same
    head and the same embedding, the row's last position weighted 0. A tied
    model's head is its embedding transposed (`head_kernel`). Of a looped
    stack the LAST pass is read (what it serves; its training loss over all
    the passes: `_exit_sums`)."""
    from ..models.decoder_lm import head_kernel
    from ..ops.lm_head import blocked_cross_entropy

    kernel = head_kernel(params, cfg.model.decoder)

    def head_sums(hidden, targets, weights):
        return blocked_cross_entropy(
            hidden.reshape(-1, hidden.shape[-1]), kernel,
            targets.reshape(-1), cfg.model.decoder.head_block,
            jnp.dtype(cfg.model.dtype), weights=weights)

    hidden, load, *h_mtp = model.apply(
        {"params": params}, tokens, train=train, method="hidden",
        **({"targets": targets} if mtp else {}))
    if cfg.model.decoder.loops > 1:
        hidden = hidden[-1]
    with jax.named_scope("lm_head"):
        ce, t1, t3 = head_sums(hidden, targets, weights)
    if not mtp:
        return ce, t1, t3, load
    with jax.named_scope("mtp"), jax.named_scope("lm_head"):
        # position i predicts targets[i + 1]; the last has nothing to predict
        # (under causal attention its state reaches no other position's loss)
        after_next = jnp.roll(targets, -1, axis=1)
        live = jnp.ones(targets.shape, jnp.float32).at[:, -1].set(0.0)
        ce_mtp, _, _ = head_sums(h_mtp[0], after_next, live.reshape(-1))
    return ce, t1, t3, load, ce_mtp


LEVEL_BANDS = 4     # the noise levels' quartiles a step's loss is split by


def _diffusion_sums(cfg: Config, model: Any, params, tokens, targets,
                    train: bool, valid=None):
    """Block diffusion's sums over a batch as the loader makes it
    (data/diffusion.py): `tokens` x_0 (B, L), `targets` [x_t ; j] (B, 2, L).
    The two-stream pass gives the noised stream's L states a row; position i
    of block b counts iff x_t[i] is the mask id, weighted 1 / t_b, against
    x_0[i] itself (no shift) → (Σ weighted cross-entropy, the same by band
    of t (LEVEL_BANDS,), top-1 and top-3 counts over the masked positions,
    the masked count, positions by band (LEVEL_BANDS,), the expert loads).
    ONE pass through the head: `weights` = mask / t beside its split by band
    and the bare mask. `valid` (B,) takes wrap-padded rows out of all of
    them."""
    from ..data.diffusion import LEVELS, level_of
    from ..models.decoder_lm import head_kernel
    from ..ops.lm_head import blocked_cross_entropy

    dc = cfg.model.decoder
    hidden, load = model.apply({"params": params}, tokens, train=train,
                               method="hidden", targets=targets)
    noised, level = targets[:, 0].reshape(-1), targets[:, 1].reshape(-1)
    rows = (jnp.ones(noised.shape, jnp.float32) if valid is None
            else jnp.repeat(valid.astype(jnp.float32), targets.shape[-1]))
    masked = rows * (noised == dc.mask_token)
    band = jnp.minimum((level - 1) * LEVEL_BANDS // LEVELS, LEVEL_BANDS - 1)
    in_band = band[:, None] == jnp.arange(LEVEL_BANDS)          # (N, bands)
    weight = masked / level_of(level.astype(jnp.float32), dc.diffusion_eps)
    with jax.named_scope("lm_head"):
        ce, t1, t3 = blocked_cross_entropy(
            hidden.reshape(-1, hidden.shape[-1]), head_kernel(params, dc),
            tokens.reshape(-1), dc.head_block, jnp.dtype(cfg.model.dtype),
            weights=jnp.concatenate(
                [weight[:, None], weight[:, None] * in_band, masked[:, None]],
                axis=1))
    return (ce[0], ce[1:1 + LEVEL_BANDS], t1[-1], t3[-1], masked.sum(),
            (rows[:, None] * in_band).sum(axis=0), load)


def _exit_sums(cfg: Config, model: Any, params, tokens, targets):
    """A looped decoder's training sums over the N = B·T targets: with p
    (R, N) the exit gate's distribution over the R passes
    (models/decoder_lm.py::exit_distribution) → (Σ_t Σ_n p CE, Σ_n H(p), each
    pass's unweighted Σ CE (R,), the last pass's top-1 and top-3 counts, each
    pass's Σ p (R,)). The R x N rows go through the head ONCE, `weights` = p
    beside the passes' indicators; p is differentiated (through the head's
    sums: a row's cross-entropy; and through H), H(p) = −Σ_t p log max(p,
    1e-9)."""
    from ..models.decoder_lm import exit_distribution, head_kernel
    from ..ops.lm_head import blocked_cross_entropy

    dc = cfg.model.decoder
    states, _ = model.apply({"params": params}, tokens, train=True,
                            method="hidden")                 # (R, B, T, C)
    r, n = states.shape[0], targets.size
    with jax.named_scope("exit"):
        p = exit_distribution(params["exit_gate"], states).reshape(r, n)
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-9)))
        of_pass = jnp.repeat(jnp.eye(r, dtype=jnp.float32), n, axis=0)
        with jax.named_scope("lm_head"):
            ce, t1, t3 = blocked_cross_entropy(
                states.reshape(r * n, -1), head_kernel(params, dc),
                jnp.tile(targets.reshape(-1), r), dc.head_block,
                jnp.dtype(cfg.model.dtype),
                weights=jnp.concatenate([p.reshape(-1, 1), of_pass], axis=1))
    return ce[0], entropy, ce[1:], t1[r], t3[r], p.sum(axis=1)


def _lm_loss(cfg: Config, model: Any):
    """Loss/metrics pair of the token decoder (models/decoder_lm.py): mean
    next-token cross-entropy over every position, head and loss in row
    blocks (ops/lm_head.py — the (B·T, V) float32 logits never stand whole).
    `images` are token ids (B, T) and `labels` the same rows shifted by one.
    With a multi-token-prediction module (`decoder.mtp_layers`) the loss is
    loss_main + mtp_weight · loss_mtp, the second the mean over the T − 1
    positions that have a token after next; `loss` is the total and
    `loss_main` / `loss_mtp` stand beside it.
    Under `decoder.objective` "block_diffusion" (`_diffusion_sums`) the batch
    is (x_0, [x_t ; j]) and `loss` = Σ over the masked positions of
    CE(x_0[i]) / t over ALL B·L positions; `loss_level1..4` the same by
    quartile of t (over that quartile's positions), `masked_tokens` the
    step's count of masked positions, top-1 and top-3 over the masked
    positions.
    A looped stack (`decoder.loops` = R > 1) minimises the mean over the
    targets of Σ_t p(t) CE(t) − exit_beta · H(p) (`_exit_sums`); `loss` is
    that objective, `loss_ut1..R` each pass's unweighted mean cross-entropy,
    `exit_p1..R` the mean of p(t); top-1 and top-3 are the last pass's.
    The step's metrics also carry `moe_load` (L, e): the token-slots each
    held expert took in each routing layer — the loop's gauges and the
    benchmark's imbalance metric read it; nothing in the step depends on it."""
    dc = cfg.model.decoder
    mtp = bool(dc.mtp_layers)

    def looped_loss_fn(params, batch_stats, tokens, targets, rng):
        ce, entropy, ce_ut, t1, t3, p = _exit_sums(cfg, model, params, tokens,
                                                   targets)
        n = targets.size
        return ((ce - dc.exit_beta * entropy) / n,
                (batch_stats, (t1, t3, ce_ut / n, p / n)))

    def looped_metrics_fn(loss, aux, labels):
        t1, t3, ce_ut, p = aux
        return {"loss": loss,
                **{f"loss_ut{i + 1}": ce_ut[i] for i in range(dc.loops)},
                **{f"exit_p{i + 1}": p[i] for i in range(dc.loops)},
                "top1": t1 / labels.size, "top3": t3 / labels.size}

    if dc.loops > 1:
        return looped_loss_fn, looped_metrics_fn

    def diffusion_loss_fn(params, batch_stats, tokens, targets, rng):
        ce, *rest = _diffusion_sums(cfg, model, params, tokens, targets, True)
        return ce / tokens.size, (batch_stats, tuple(rest))

    def diffusion_metrics_fn(loss, aux, labels):
        ce_band, t1, t3, masked, n_band, load = aux
        by_band = ce_band / jnp.maximum(n_band, 1.0)
        seen = jnp.maximum(masked, 1.0)
        return {"loss": loss,
                **{f"loss_level{i + 1}": by_band[i] for i in range(LEVEL_BANDS)},
                "masked_tokens": masked, "top1": t1 / seen, "top3": t3 / seen,
                "moe_load": load.astype(jnp.float32)}

    if dc.diffusion:
        return diffusion_loss_fn, diffusion_metrics_fn

    def loss_fn(params, batch_stats, tokens, targets, rng):
        ce, t1, t3, load, *ce_mtp = _lm_sums(cfg, model, params, tokens,
                                             targets, True, mtp=mtp)
        main = ce / targets.size
        if not mtp:
            return main, (batch_stats, (t1, t3, load))
        after_next = ce_mtp[0] / (targets.shape[0] * (targets.shape[1] - 1))
        return (main + dc.mtp_weight * after_next,
                (batch_stats, (t1, t3, load, main, after_next)))

    def metrics_fn(loss, aux, labels):
        t1, t3, load, *parts = aux
        return {"loss": loss, **dict(zip(("loss_main", "loss_mtp"), parts)),
                "top1": t1 / labels.size, "top3": t3 / labels.size,
                "moe_load": load.astype(jnp.float32)}

    return loss_fn, metrics_fn


def _require_sharded_ce_mesh(mesh) -> None:
    """arcface_sharded_ce exists to avoid (B, C) logits; silently falling
    back to the dense path would defeat it (and OOM at the scale it
    targets) — one validation shared by the train and eval builders."""
    from ..parallel.mesh import MODEL_AXIS

    if (mesh is None or MODEL_AXIS not in mesh.axis_names
            or mesh.shape[MODEL_AXIS] <= 1):
        raise ValueError(
            "arcface_sharded_ce requires a mesh with a model axis > 1 "
            "(--mp N); got "
            + ("no mesh" if mesh is None else f"mesh {dict(mesh.shape)}"))


def _reduced_grad_section(cfg: Config, mesh: Any, reduce_dtype: Any):
    """shard_map fwd/bwd section for reduced-precision gradient exchange:
    each data shard runs its own forward/backward on its batch slice,
    casts the shard-local gradients to `reduce_dtype`, takes ONE
    cross-replica `pmean` at that dtype, and casts back to the param
    dtype — the mixed-precision-comms recipe of Micikevicius et al.
    2018: bf16 on the wire, f32 accumulation into master params (the
    optimizer update runs OUTSIDE this section, so it composes with
    ZeRO-1 sharding of the optimizer state).

    Mirrors `_dense_loss_fn` minus the nested workload (its global
    per-batch mask k is rejected at build): SyncBN stat reduction rides
    the axis-named model (`build_ddp_model`), the dropout stream is the
    dense path's split-derivation folded with the shard index (per-shard
    masks — a different stream than the GSPMD path, which is why the
    bf16-vs-f32 parity pin carries a tolerance, not bit equality).

    Returns `(params, stats, images, labels, rng) ->
    (loss, new_stats, logits, grads)` with loss pmean'd and logits left
    batch-sharded."""
    from ..parallel.collectives import build_ddp_model
    from ..parallel.mesh import DATA_AXIS
    from ..utils.compat import shard_map_unchecked
    from jax.sharding import PartitionSpec as P

    workload = cfg.model.head
    model = build_ddp_model(cfg)

    def per_shard(params, batch_stats, images, labels, rng):
        def loss_fn(p, s):
            variables = {"params": p, "batch_stats": s}
            _, drop_rng = jax.random.split(rng)  # same derivation as dense
            drop_rng = jax.random.fold_in(
                drop_rng, jax.lax.axis_index(DATA_AXIS))
            kwargs = dict(train=True, mutable=["batch_stats", "losses"],
                          rngs={"dropout": drop_rng})
            if workload == "arcface":
                logits, mutated = model.apply(variables, images, labels,
                                              **kwargs)
            else:
                logits, mutated = model.apply(variables, images, **kwargs)
            loss = _cross_entropy(logits, labels)
            aux = sum(jax.tree_util.tree_leaves(mutated.get("losses", {})))
            if cfg.model.moe_aux_weight:
                loss = loss + cfg.model.moe_aux_weight * aux
            return loss, (mutated.get("batch_stats", s), logits)

        (loss, (new_stats, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats)
        with jax.named_scope(_EXCHANGE):
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(reduce_dtype), grads)
            # per-shard mean-loss grads, so pmean == grad of the global mean
            grads = jax.lax.pmean(grads, DATA_AXIS)
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype), grads, params)
            loss = jax.lax.pmean(loss, DATA_AXIS)
        return loss, new_stats, logits, grads

    return shard_map_unchecked(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P(DATA_AXIS), P()))


def _scan_microbatches(loss_fn, grad_accum, params, batch_stats, images,
                       labels, rng):
    """K-microbatch accumulation core: reshape the batch to (K, mb, ...)
    and `lax.scan` `loss_fn(params, stats, x, y, r) -> (loss, (stats,
    logits))` over the leading axis, summing per-microbatch MEAN gradients
    into a float32 accumulator (D2/D3: the accumulator never narrows below
    f32 regardless of the wire dtype). Equal microbatches make
    sum-of-means ÷ K exactly the full-batch mean, so the accumulated step
    is arithmetic-identical to the K=1 large-batch step up to summation
    order. BN statistics thread through the carry — each microbatch
    normalizes with the stats the previous one produced, the same
    semantics as running the K microbatches as K separate steps without an
    optimizer update in between. The per-microbatch rng is
    `fold_in(rng, i)`: deterministic, and distinct flip/dropout/mask draws
    per microbatch.

    Returns `(mean_loss, final_stats, logits (B, C), mean_grads)` with
    gradients in float32 — the caller owns the (single, deferred)
    cross-replica reduction and any wire cast."""
    k = int(grad_accum)
    batch = images.shape[0]
    if batch % k:
        raise ValueError(
            f"grad-accum-indivisible: batch {batch} does not split into "
            f"grad_accum={k} equal microbatches")
    mb = batch // k
    xs = images.reshape((k, mb) + images.shape[1:])
    ys = labels.reshape((k, mb) + labels.shape[1:])

    def body(carry, sl):
        stats, gsum, loss_sum = carry
        i, x, y = sl
        r = jax.random.fold_in(rng, i)
        (loss, (new_stats, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, stats, x, y, r)
        gsum = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), gsum, grads)
        return (new_stats, gsum, loss_sum + loss.astype(jnp.float32)), logits

    gsum0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (new_stats, gsum, loss_sum), logits = jax.lax.scan(
        body, (batch_stats, gsum0, jnp.zeros((), jnp.float32)),
        (jnp.arange(k), xs, ys))
    mean_grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
    return (loss_sum / k, new_stats,
            logits.reshape((batch,) + logits.shape[2:]), mean_grads)


def _accum_grad_section(cfg: Config, mesh: Any, grad_accum: int,
                        reduce_dtype: Any):
    """The K-microbatch analogue of `_reduced_grad_section`: each data
    shard scans `grad_accum` microbatches of its batch slice through the
    same fwd/bwd (`_scan_microbatches`) and the cross-replica gradient
    exchange happens ONCE per optimizer step, outside the scan — so the
    reduction payload is the K=1 anchor's, amortized over K microbatches
    (÷K per-microbatch bytes; ÷2K when `reduce_dtype` is bf16). A
    GSPMD-partitioned scan would instead sink the all-reduce INTO the
    while body — one op in HLO text but K executions at runtime — which is
    exactly the dishonesty this explicit section exists to rule out.

    SyncBN stat reductions still ride the axis-named model inside the
    scan body (per-microbatch, per-channel — control-sized next to the
    gradient payload). The nested workload IS supported here (unlike the
    K=1 bf16 section, whose rejection predates this path): the rng enters
    replicated and the microbatch fold is deterministic, so every shard
    draws the same global per-microbatch mask k.

    Returns `(params, stats, images, labels, rng) ->
    (loss, new_stats, logits, grads)`, loss pmean'd, logits
    batch-sharded."""
    from ..parallel.collectives import build_ddp_model
    from ..parallel.mesh import DATA_AXIS
    from ..utils.compat import shard_map_unchecked
    from jax.sharding import PartitionSpec as P

    workload = cfg.model.head
    model = build_ddp_model(cfg)
    if workload == "nested":
        dist = jnp.asarray(gaussian_dist(0.0, cfg.model.nested_std,
                                         feat_dim_for(cfg.model)))
        feat_dim = feat_dim_for(cfg.model)

    def per_shard(params, batch_stats, images, labels, rng):
        def loss_fn(p, s, x, y, r):
            variables = {"params": p, "batch_stats": s}
            mask_rng, drop_rng = jax.random.split(r)  # dense derivation
            drop_rng = jax.random.fold_in(
                drop_rng, jax.lax.axis_index(DATA_AXIS))
            kwargs = dict(train=True, mutable=["batch_stats", "losses"],
                          rngs={"dropout": drop_rng})
            if workload == "arcface":
                logits, mutated = model.apply(variables, x, y, **kwargs)
            elif workload == "nested":
                # mask_rng is replicated (rng enters at P()) and the
                # microbatch fold is shard-independent: one global k per
                # microbatch, as NESTED/train.py:247-250 samples it
                mk = sample_mask_dims(mask_rng, dist)
                mask = prefix_mask(mk, feat_dim)
                logits, mutated = model.apply(variables, x, mask, **kwargs)
            else:
                logits, mutated = model.apply(variables, x, **kwargs)
            loss = _cross_entropy(logits, y)
            aux = sum(jax.tree_util.tree_leaves(mutated.get("losses", {})))
            if cfg.model.moe_aux_weight:
                loss = loss + cfg.model.moe_aux_weight * aux
            return loss, (mutated.get("batch_stats", s), logits)

        loss, new_stats, logits, grads = _scan_microbatches(
            loss_fn, grad_accum, params, batch_stats, images, labels, rng)
        with jax.named_scope(_EXCHANGE):
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(reduce_dtype), grads)
            # THE deferred reduction: one cross-replica mean of the summed
            # per-shard mean grads per optimizer step (pmean of per-shard
            # means == grad of the global mean for equal shards)
            grads = jax.lax.pmean(grads, DATA_AXIS)
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype), grads, params)
            loss = jax.lax.pmean(loss, DATA_AXIS)
        return loss, new_stats, logits, grads

    return shard_map_unchecked(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P(DATA_AXIS), P()))


def _constrain_state(state: TrainState, mesh: Any, zero: bool) -> TrainState:
    """Pin the new state's output shardings to the declared layout
    (params/pipe/model rules, ZeRO data-axis optimizer shards, replicated
    step + BN stats). Without this, GSPMD is free to pick mismatched
    output shardings for the updated state under ZeRO in-shardings, which
    silently breaks input→output buffer aliasing — measured on the dp2
    audit cell: donation coverage 0.47 unconstrained, 1.0 with these
    constraints. Specs are computed from the tracer trees at trace time,
    so they follow the state's actual shapes."""
    return jax.tree_util.tree_map(
        jax.lax.with_sharding_constraint, state,
        state_shardings(state, mesh, zero))


def _build_step(tx, base_rng, loss_fn, metrics_fn, chaos=None, flip=False,
                mesh=None, zero=False, grad_section=None, grad_accum=1):
    """Shared optimizer-update skeleton for every train step: fold_in rng,
    value_and_grad over `loss_fn(params, stats, images, labels, rng) ->
    (loss, (new_stats, aux))`, apply updates, metrics via
    `metrics_fn(loss, aux, labels)`.

    Non-finite guard (AMP-style skip-step): every update is gated on an
    on-device all-finite check of the loss AND the global grad norm. A
    failing step applies the IDENTITY update — params, optimizer state,
    and BN statistics keep their previous values (elementwise select, so
    a passing step is bit-identical to the unguarded update) while the
    step counter still advances (the rng/schedule stream moves on, so a
    restart-free retry of the next batch is not a deterministic replay).
    The `step_ok` flag and `grad_norm` ride the existing metrics fetch —
    no extra host sync; train/sentinel.py applies host-side policy.

    `chaos` nan_loss windows poison the loss AFTER value_and_grad (the
    guard sees NaN, gradients stay untouched), keeping injection
    bit-transparent outside its windows.

    `zero=True` (ZeRO-1) constrains the gradients to the data-sharded
    optimizer layout BEFORE `tx.update` — XLA then materializes each
    shard's gradient slice once (reduce-scatter on TPU) and runs the
    update shard-locally — and pins the new state's output shardings
    (`_constrain_state`) so donation stays whole. With zero=False and no
    grad_section the program is bit-identical to the pre-ZeRO step.

    `grad_section` (from `_reduced_grad_section` or, with accumulation,
    `_accum_grad_section`) replaces the in-jit value_and_grad with an
    explicit shard_map fwd/bwd whose gradient exchange runs once per
    optimizer step at the wire dtype; `loss_fn` is then unused for the
    step but still times the phase probes. `grad_accum > 1` without a
    mesh scans the microbatches locally (`_scan_microbatches`) — no
    collectives, same accumulate-then-update arithmetic. The non-finite
    gate below always inspects the SUMMED gradients at the optimizer
    boundary: one sentinel observation per optimizer step, however many
    microbatches fed it."""
    nan_windows = list(chaos.windows("nan_loss", "step")) if chaos else []

    def step(state: TrainState, images: jnp.ndarray, labels: jnp.ndarray):
        from ..parallel import mesh as meshlib

        rng = jax.random.fold_in(base_rng, state.step)
        # uint8 wire → f32 (+ per-sample device flip); f32 wire untouched.
        # Outside value_and_grad: images carry no parameter gradient.
        # Runs BEFORE any (K, mb, ...) reshape — the uint8 epilogue audit
        # requires raw pixels to flow straight into convert → /255.
        with jax.named_scope(_INPUT):
            images = device_input_epilogue(images, rng, flip=flip)
        if grad_section is not None:
            loss, new_stats, aux, grads = grad_section(
                state.params, state.batch_stats, images, labels, rng)
        elif grad_accum > 1:
            # meshless accumulation: scan microbatches on the one device
            loss, new_stats, aux, grads = _scan_microbatches(
                loss_fn, grad_accum, state.params, state.batch_stats,
                images, labels, rng)
        else:
            (loss, (new_stats, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, state.batch_stats, images, labels, rng
            )
        for lo, hi in nan_windows:
            hit = state.step >= lo
            if hi is not None:
                hit &= state.step <= hi
            loss = jnp.where(hit, jnp.asarray(jnp.nan, loss.dtype), loss)
        if zero:
            # gradient slices land data-sharded (the reduce-scatter half
            # of ZeRO); grads share the params' key paths, so the
            # optimizer sharding rules apply verbatim
            with jax.named_scope(_EXCHANGE):
                grads = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, grads,
                    meshlib.opt_shardings(grads, mesh, zero_data=True))
        with jax.named_scope(_GUARD):
            grad_norm = optax.global_norm(grads)
            step_ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
        with jax.named_scope(_OPT):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)

        def keep(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(step_ok, n, o), new, old)

        with jax.named_scope(_GUARD):
            new_state = state.replace(
                step=state.step + 1,
                params=keep(new_params, state.params),
                batch_stats=keep(new_stats, state.batch_stats),
                opt_state=keep(new_opt, state.opt_state),
            )
        if zero or grad_section is not None:
            with jax.named_scope(_EXCHANGE):
                new_state = _constrain_state(new_state, mesh, zero)
        with jax.named_scope(_METRICS):
            metrics = metrics_fn(loss, aux, labels)
            metrics["step_ok"] = step_ok.astype(jnp.float32)
            metrics["grad_norm"] = grad_norm
        return new_state, metrics

    return jax.jit(step, donate_argnums=0)


def _arcface_sharded_loss(cfg, model, mesh):
    """Partial-FC ArcFace loss/metrics pair: backbone embeddings + class-
    sharded margin weight → `arc_margin_ce_sharded` (loss and top-k counts
    in one shard_map, no (B, C) logits). Same observable contract as the
    dense step, including the dense path's dropout-rng derivation."""
    from ..ops.sharded_head import arc_margin_ce_sharded
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    mc = cfg.model
    batch_axis = DATA_AXIS if mesh.shape[DATA_AXIS] > 1 else None

    def loss_fn(params, batch_stats, images, labels, rng):
        variables = {"params": params, "batch_stats": batch_stats}
        _, drop_rng = jax.random.split(rng)  # same derivation as dense path
        emb, mutated = model.apply(
            variables, images, train=True,
            mutable=["batch_stats", "losses"],
            rngs={"dropout": drop_rng}, method="features")
        with jax.named_scope("loss"):
            loss, t1, t3 = arc_margin_ce_sharded(
                emb, params["margin"]["weight"], labels, mesh, MODEL_AXIS,
                batch_axis=batch_axis, s=mc.arc_s, m=mc.arc_m,
                easy_margin=mc.arc_easy_margin)
        # sown auxiliary penalties (MoE router balance on a ViT backbone)
        # flow into this path too — same contract as the dense step
        aux = sum(jax.tree_util.tree_leaves(mutated.get("losses", {})))
        if cfg.model.moe_aux_weight:
            loss = loss + cfg.model.moe_aux_weight * aux
        return loss, (mutated.get("batch_stats", batch_stats), (t1, t3))

    def metrics_fn(loss, aux, labels):
        t1, t3 = aux
        n = labels.shape[0]
        return {"loss": loss, "top1": t1 / n, "top3": t3 / n}

    return loss_fn, metrics_fn


def make_eval_step(
    cfg: Config, model: Any, mesh: Optional[Any] = None
) -> Callable[..., Dict[str, jnp.ndarray]]:
    """`(state, images, labels, valid) -> {loss_sum, top1, top3, n}` —
    per-batch COUNTS over the rows where valid==1, summed exactly on host
    across batches. This replaces the reference's per-rank-shard metric
    scaled by world_size (BASELINE/main.py:247-249) with the exact global
    reduction; `valid` additionally masks the loader's wrap-padding so the
    metrics are exact for any val-set size.

    With `parallel.arcface_sharded_ce` (and `mesh`), the ArcFace eval runs
    the partial-FC path too: `arc_margin_ce_sharded` with m=0 yields
    exactly the s·cosθ inference scores — no (B, C) logits in eval either."""
    workload = cfg.model.head
    if workload == "arcface" and cfg.parallel.arcface_sharded_ce:
        _require_sharded_ce_mesh(mesh)
        return _make_arcface_sharded_eval(cfg, model, mesh)
    if cfg.model.arch == "decoder_lm" and cfg.model.decoder.diffusion:
        def diffusion_step(state: TrainState, tokens: jnp.ndarray,
                           targets: jnp.ndarray, valid: jnp.ndarray):
            """The training objective's weighted loss over the positions of
            the rows where valid == 1 (`n`), and the top-k counts over their
            masked positions (`n_top`), on the batch's own noise."""
            ce, _, t1, t3, masked, _, _ = _diffusion_sums(
                cfg, model, state.params, tokens, targets, False, valid)
            return {"loss_sum": ce, "top1": t1, "top3": t3,
                    "n": valid.sum() * tokens.shape[1], "n_top": masked}

        return jax.jit(diffusion_step)
    if cfg.model.arch == "decoder_lm":
        def lm_step(state: TrainState, tokens: jnp.ndarray,
                    targets: jnp.ndarray, valid: jnp.ndarray):
            """Counts over every position of the rows where valid == 1."""
            per_token = jnp.repeat(valid, targets.shape[1])
            ce, t1, t3, _ = _lm_sums(cfg, model, state.params, tokens,
                                     targets, False, weights=per_token)
            return {"loss_sum": ce, "top1": t1, "top3": t3,
                    "n": per_token.sum()}

        return jax.jit(lm_step)  # no donation: state live across val batches

    def step(state: TrainState, images: jnp.ndarray, labels: jnp.ndarray,
             valid: jnp.ndarray):
        images = device_input_epilogue(images)  # uint8 wire; eval never flips
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        if workload in ("arcface", "nested"):
            # arcface inference scores are s·cosθ (no margin), arc_main.py eval
            logits = model.apply(variables, images, None, train=False)
        else:
            logits = model.apply(variables, images, train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels)
        return {
            "loss_sum": (ce * valid).sum(),
            "top1": (topk_hits(logits, labels, 1) * valid).sum(),
            "top3": (topk_hits(logits, labels, 3) * valid).sum(),
            "n": valid.sum(),
        }

    # no donation: state is reused by every val batch, and the dead
    # images/labels/valid buffers have no same-shape outputs to alias
    # (module docstring "Donation policy"; audited by cli.analyze)
    return jax.jit(step)


def _make_arcface_sharded_eval(cfg, model, mesh):
    """Partial-FC eval: m=0 in the sharded op gives s·cosθ scores; `valid`
    masks wrap-padding inside the shard_map, so loss/counts stay exact."""
    from ..ops.sharded_head import arc_margin_ce_sharded
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    mc = cfg.model
    batch_axis = DATA_AXIS if mesh.shape[DATA_AXIS] > 1 else None

    def step(state: TrainState, images: jnp.ndarray, labels: jnp.ndarray,
             valid: jnp.ndarray):
        images = device_input_epilogue(images)
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        emb = model.apply(variables, images, train=False, method="features")
        loss_mean, t1, t3 = arc_margin_ce_sharded(
            emb, state.params["margin"]["weight"], labels, mesh, MODEL_AXIS,
            batch_axis=batch_axis, s=mc.arc_s, m=0.0, valid=valid)
        n = valid.sum()
        return {"loss_sum": loss_mean * n, "top1": t1, "top3": t3, "n": n}

    return jax.jit(step)  # no donation: state live across val batches


def make_predict_step(
    cfg: Config, model: Any, batch_stat_mode: bool = False
) -> Callable[[TrainState, jnp.ndarray], jnp.ndarray]:
    """`(state, images) -> (B, C) logits` — used by the PLC correction loop
    to collect f(x) over the train set.

    batch_stat_mode=True normalizes with the prediction batch's own BN
    statistics (discarding the mutation) instead of the running averages —
    matching the reference's practice of harvesting softmax outputs during
    training (PLC/utils.py:269-271). Only safe on shuffled batches: on a
    class-sorted scan each batch is nearly single-class and its statistics
    skew normalization (measured 63% vs 99% argmax-vs-truth on a 97%-val
    model — train/plc_loop.py::_predict_pipeline), which is why the PLC
    correction pass defaults to running averages."""
    workload = cfg.model.head

    def step(state: TrainState, images: jnp.ndarray) -> jnp.ndarray:
        images = device_input_epilogue(images)  # PLC f(x) pass: no flip
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        args = (images, None) if workload in ("arcface", "nested") else (images,)
        if batch_stat_mode:
            logits, _ = model.apply(
                variables, *args, train=True, mutable=["batch_stats"],
                rngs={"dropout": jax.random.PRNGKey(0)},
            )
            return logits
        return model.apply(variables, *args, train=False)

    # no donation: the PLC correction pass scans the whole train set with
    # one state; images are dead per-call but alias nothing (u8 → f32 logits)
    return jax.jit(step)


def make_topk_predict_step(
    cfg: Config, model: Any, k: int, mesh: Optional[Any] = None
) -> Callable[[TrainState, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]:
    """`(state, images) -> (probs (B, k) f32, indices (B, k) i32)` — the
    serving subsystem's predict (serve/engine.py). Same forward as
    `make_predict_step` (uint8 wire via `device_input_epilogue`, static
    dtype dispatch, running BN stats, arcface s·cosθ scores via
    labels=None) but the (B, C) logits never leave the device: softmax +
    top-k run in-jit, so the D2H fetch is k floats + k ints per request
    instead of the full class row. Eval mode has no cross-sample ops, so
    each row depends only on its own input — bucket padding (serve's
    fixed compile shapes) cannot perturb real rows.

    `mesh` turns on data-parallel serving: the (B, k) outputs are pinned
    batch-sharded over 'data' so each serve replica-shard computes and
    keeps only its own rows — the only cross-device traffic left is
    whatever XLA needs for the forward itself (control-sized all-gathers;
    the audit's serve CommsPolicy fences this). Input sharding is left to
    the caller (`make_global_array` on the padded bucket)."""
    workload = cfg.model.head

    def step(state: TrainState, images: jnp.ndarray):
        images = device_input_epilogue(images)  # serving never flips
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        args = (images, None) if workload in ("arcface", "nested") else (images,)
        logits = model.apply(variables, *args, train=False)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        vals, idx = jax.lax.top_k(probs, min(k, probs.shape[-1]))
        return vals, idx.astype(jnp.int32)

    # no donation: serving reuses the state for every micro-batch (until a
    # hot-reload swap); request buffers alias nothing ((B,H,W,3) u8 → (B,k))
    if mesh is not None:
        from ..parallel.mesh import batch_sharding

        out_sh = batch_sharding(mesh)
        return jax.jit(step, out_shardings=(out_sh, out_sh))
    return jax.jit(step)


def make_nested_eval_step(
    cfg: Config, model: Any
) -> Callable[[TrainState, jnp.ndarray, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """All-K truncation sweep for one batch → per-K correct counts (D,).

    The reference runs D separate classifier forwards per batch
    (NESTED/train.py:122-124); here the whole sweep is one blocked cumulative
    matmul on the MXU (ops/nested.py). Counts are summed across batches on
    host; `ops.nested.best_k` then applies the 1e-5·K tiebreak (:143)."""
    feat_dim = feat_dim_for(cfg.model)
    block = 128 if feat_dim % 128 == 0 else feat_dim

    def step(state: TrainState, images: jnp.ndarray, labels: jnp.ndarray,
             valid: jnp.ndarray):
        images = device_input_epilogue(images)
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        feats = model.apply(variables, images, train=False, method="features")
        # NetClassifier kernel is (D, C); the sweep wants (C, D)
        weight = state.params["classifier"]["fc"]["kernel"].T
        t1, t3 = nested_all_k_counts(feats, weight, labels, block=block, mask=valid)
        return {"top1_k": t1, "top3_k": t3, "n": valid.sum()}

    return jax.jit(step)  # no donation: state live across the all-K sweep
