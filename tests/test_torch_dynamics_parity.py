"""Torch-vs-flax TRAINING-DYNAMICS parity (VERDICT r4 next #2).

Forward parity (test_torch_oracle_parity.py) pins the weight converters; this
file pins the *step dynamics* against a torch ground-truth run with identical
init and identical batches — the places silent accuracy drift hides
(SURVEY §7.3 #3):

- BN running-stat updates: torch momentum 0.1 == flax momentum 0.9
  (models/resnet.py), training-mode normalization by batch stats;
- SGD coupling order: torch's ``d_p = g + wd*p; buf = m*buf + d_p;
  p -= lr*buf`` vs our ``chain(add_decayed_weights, sgd(momentum))``
  (train/schedule.py::_group_tx);
- the warmup-vs-decay overlay: per-iteration linear warmup while the
  epoch-indexed decay keeps counting from step 0 (reference
  BASELINE/main.py:170-197 ``WarmUp`` + StepLR at :154; our
  build_schedule overlays rather than shifting);
- NESTED freeze-BN: BN modules eval()'d with weight/bias grads off
  (NESTED/model/model.py:44-55) vs our use_running_average +
  optax.masked(set_to_zero).

The flax side runs the PRODUCTION path end to end: the torch oracle's
state_dict is torch.save'd and loaded through ``cfg.model.pretrained_path``
(create_train_state → load_torch_checkpoint → converter → merge), the step
is ``make_train_step`` over the 8-device CPU mesh with a sharded global
batch, and the optimizer is ``build_optimizer``. The torch side replays the
reference recipe literally.

Two tiers, because cross-backend f32 determinism sets a noise floor:

1. ``test_optimizer_coupling_matches_torch_sgd`` feeds IDENTICAL fixed
   gradients to the real ``build_optimizer`` chain and to ``torch.optim.SGD``
   — elementwise arithmetic only, no reductions, so both sides perform the
   same IEEE ops and any wd-coupling-order, momentum-buffer-init, or
   schedule-indexing difference fails at ~1e-6.
2. The full-model tests run real conv nets, where torch-CPU and XLA-CPU
   reduction orders differ at ~1e-6 per step and training amplifies that
   ~40x/step (measured: losses agree 7e-7 at step 0, 2.5e-3 by step 5 of the six
   this file once ran).
   Their tolerances are therefore SEMANTIC-level (2e-2): they catch a BN
   momentum-convention swap (~9x running-stat error), a wrong lr actually
   applied (warmup/decay overlay), train-vs-eval BN mode mixups, and
   unfrozen freeze-BN — while the subtle couplings are pinned exactly by
   tier 1.

Known, accepted divergence: torch updates running_var with the UNBIASED
batch variance (Bessel n/(n-1)); flax uses the biased one. At the test's
smallest BN reduction (n = 16·32·32 = 16384) that is a 6e-5 relative drift
per step — far inside the tolerances here, and negligible at real batch
sizes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.models.import_torch import (
    convert_resnet_state_dict,
)
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.state import create_train_state
from ddp_classification_pytorch_tpu.train.steps import make_train_step

torch = pytest.importorskip("torch")

from torch_resnet_oracle import make_torch_resnet, randomize_  # noqa: E402

# Four steps are the fewest at which every planted difference is outside
# the tolerances below (read by planting each on the flax side, PR 41): no
# warm-up fails losses, parameters and statistics from 3 steps on, a BN frozen
# on one side only fails all three at once, and a decay that does not fire
# (what counting epochs from the warm-up's end gives: lr 0.01 at step 3, not
# 0.001) fails the parameters at 4, the first step after the warm-up.
N_STEPS = 4
BATCH = 16
CLASSES = 7
SIZE = 64
LR = 0.01
WD = 5e-4
GAMMA = 0.1
WARMUP_ITERS = 3
WARMUP_START = 1e-6
STEPS_PER_EPOCH = 2  # decay fires mid-run: overlay semantics get exercised


def _reference_lr(i: int) -> float:
    """The reference's lr at 0-indexed iteration i: linear warmup
    (BASELINE/main.py:179 ``lr = begin + n_iter*(target-begin)/iter``),
    then StepLR counting epochs from 0 (NOT from warmup's end — the decay
    milestones stay anchored at the true global step, train/schedule.py)."""
    if i < WARMUP_ITERS:
        return WARMUP_START + i * (LR - WARMUP_START) / WARMUP_ITERS
    return LR * GAMMA ** (i // STEPS_PER_EPOCH)


def _batches(seed: int):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(N_STEPS, BATCH, 3, SIZE, SIZE)).astype(np.float32)
    ys = rng.integers(0, CLASSES, size=(N_STEPS, BATCH)).astype(np.int64)
    return xs, ys


def _cfg(pth_path: str, freeze_bn: bool):
    cfg = get_preset("baseline")
    cfg.data.dataset = "synthetic"
    cfg.data.image_size = SIZE
    cfg.data.num_classes = CLASSES
    cfg.data.batch_size = BATCH
    cfg.model.arch = "resnet18"
    cfg.model.variant = "imagenet"  # the oracle/converter stem
    cfg.model.dtype = "float32"
    cfg.model.freeze_bn = freeze_bn
    cfg.model.pretrained = True
    cfg.model.pretrained_path = pth_path
    cfg.optim.optimizer = "sgd"
    cfg.optim.lr = LR
    cfg.optim.momentum = 0.9
    cfg.optim.weight_decay = WD
    cfg.optim.schedule = "step"
    cfg.optim.step_size = 1  # in epochs; STEPS_PER_EPOCH makes it per-2-steps
    cfg.optim.gamma = GAMMA
    cfg.optim.warmup_iters = WARMUP_ITERS
    cfg.optim.warmup_start_lr = WARMUP_START
    return cfg


def _run_flax(cfg, xs, ys):
    mesh = meshlib.make_mesh(meshlib.MeshSpec())  # all devices on 'data'
    model, tx, state = create_train_state(cfg, mesh, STEPS_PER_EPOCH)
    step = make_train_step(cfg, model, tx)
    losses = []
    for i in range(N_STEPS):
        imgs = jnp.asarray(xs[i].transpose(0, 2, 3, 1))
        state, metrics = step(state, imgs, jnp.asarray(ys[i], jnp.int32))
        losses.append(float(metrics["loss"]))
    return losses, state


def _run_torch(sd, xs, ys, freeze_bn: bool):
    tmodel = make_torch_resnet("resnet18", CLASSES)
    tmodel.load_state_dict(sd)
    tmodel.train()
    if freeze_bn:
        # the NESTED recipe verbatim (NESTED/model/model.py:44-55)
        for m in tmodel.modules():
            if isinstance(m, torch.nn.BatchNorm2d):
                m.eval()
                m.weight.requires_grad = False
                m.bias.requires_grad = False
    opt = torch.optim.SGD(tmodel.parameters(), lr=LR, momentum=0.9,
                          weight_decay=WD)
    lossf = torch.nn.CrossEntropyLoss()
    losses = []
    for i in range(N_STEPS):
        opt.param_groups[0]["lr"] = _reference_lr(i)
        opt.zero_grad()
        out = tmodel(torch.from_numpy(xs[i]))
        loss = lossf(out, torch.from_numpy(ys[i]))
        loss.backward()
        opt.step()
        losses.append(float(loss.detach()))
    return losses, tmodel


def _tree_flat(tree):
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v)
        for path, v in jax.tree_util.tree_leaves_with_path(tree)
    }


def _assert_trees_close(flax_tree, torch_tree, rtol, atol, what):
    got = _tree_flat(flax_tree)
    want = _tree_flat(torch_tree)
    assert got.keys() == want.keys(), (what, got.keys() ^ want.keys())
    for k in sorted(got):
        np.testing.assert_allclose(
            got[k], want[k], rtol=rtol, atol=atol,
            err_msg=f"{what}: {k} diverged after {N_STEPS} steps")


def _converted_after(tmodel):
    """Torch's post-training weights, pushed through the SAME converter the
    init crossed — any coupling/momentum/BN drift shows up as a tree diff."""
    return convert_resnet_state_dict(tmodel.state_dict())


@pytest.fixture(scope="module")
def oracle_pth(tmp_path_factory):
    tmodel = make_torch_resnet("resnet18", CLASSES)
    randomize_(tmodel, seed=11)
    path = tmp_path_factory.mktemp("dyn") / "oracle_rn18.pth"
    torch.save(tmodel.state_dict(), str(path))
    return str(path), tmodel.state_dict()


def test_optimizer_coupling_matches_torch_sgd():
    """The production optimizer chain (build_optimizer: warmup-overlaid step
    schedule → add_decayed_weights → momentum trace → -lr) vs torch SGD fed
    the SAME fixed gradients. Pure elementwise arithmetic — both sides run
    the identical IEEE op sequence, so coupling-order / buffer-init /
    schedule-off-by-one bugs fail at near-ulp tolerance."""
    from ddp_classification_pytorch_tpu.train.schedule import build_optimizer

    cfg = _cfg("/dev/null", freeze_bn=False).optim
    tx = build_optimizer(cfg, STEPS_PER_EPOCH)

    rng = np.random.default_rng(7)
    p0 = {"w": rng.normal(size=(5, 3)).astype(np.float32),
          "b": rng.normal(size=(3,)).astype(np.float32)}
    grads = [
        {"w": rng.normal(size=(5, 3)).astype(np.float32),
         "b": rng.normal(size=(3,)).astype(np.float32)}
        for _ in range(N_STEPS)
    ]

    import optax

    fparams = jax.tree_util.tree_map(jnp.asarray, p0)
    opt_state = tx.init(fparams)
    for g in grads:
        updates, opt_state = tx.update(
            jax.tree_util.tree_map(jnp.asarray, g), opt_state, fparams)
        fparams = optax.apply_updates(fparams, updates)

    tparams = {k: torch.nn.Parameter(torch.from_numpy(v.copy()))
               for k, v in p0.items()}
    opt = torch.optim.SGD(tparams.values(), lr=LR, momentum=0.9,
                          weight_decay=WD)
    for i, g in enumerate(grads):
        opt.param_groups[0]["lr"] = _reference_lr(i)
        for k in tparams:
            tparams[k].grad = torch.from_numpy(g[k].copy())
        opt.step()

    for k in p0:
        np.testing.assert_allclose(
            np.asarray(fparams[k]), tparams[k].detach().numpy(),
            rtol=1e-6, atol=1e-7,
            err_msg=f"optimizer coupling diverged on {k!r}")


def test_sgd_bn_warmup_dynamics_match_torch(oracle_pth):
    path, sd = oracle_pth
    xs, ys = _batches(21)
    flax_losses, state = _run_flax(_cfg(path, freeze_bn=False), xs, ys)
    torch_losses, tmodel = _run_torch(sd, xs, ys, freeze_bn=False)

    # per-step loss trajectory: pins training-mode BN normalization + the
    # lr actually applied each iteration (warmup AND the step-2 decay);
    # tolerance is the measured chaos floor x margin (see module docstring)
    np.testing.assert_allclose(flax_losses, torch_losses, rtol=2e-2,
                               err_msg=f"{flax_losses} vs {torch_losses}")
    # the first warmup step happens before any drift can amplify: a wrong
    # warmup start lr or a train/eval BN mixup shows here at f32 precision
    np.testing.assert_allclose(flax_losses[0], torch_losses[0], rtol=1e-4)

    converted = _converted_after(tmodel)
    _assert_trees_close(state.params["backbone"], converted["params"],
                        rtol=2e-2, atol=1e-3, what="params")
    # running stats: the running mean tracks the drifting activations, so
    # its absolute floor is higher (measured 7e-3 after 6 steps, less after 4) — still
    # far below the ~0.5-scale error a 0.1-vs-0.9 momentum mixup produces
    _assert_trees_close(state.batch_stats["backbone"],
                        converted["batch_stats"],
                        rtol=2e-2, atol=2e-2, what="batch_stats")


def test_freeze_bn_dynamics_match_torch(oracle_pth):
    """NESTED's freeze-BN: running stats AND BN scale/bias must stay at
    their init values on both sides while everything else trains."""
    path, sd = oracle_pth
    xs, ys = _batches(22)
    flax_losses, state = _run_flax(_cfg(path, freeze_bn=True), xs, ys)
    torch_losses, tmodel = _run_torch(sd, xs, ys, freeze_bn=True)

    np.testing.assert_allclose(flax_losses, torch_losses, rtol=2e-2)
    np.testing.assert_allclose(flax_losses[0], torch_losses[0], rtol=1e-4)

    init_converted = convert_resnet_state_dict(sd)
    _assert_trees_close(state.batch_stats["backbone"],
                        init_converted["batch_stats"],
                        rtol=0, atol=0, what="frozen running stats (flax)")
    after = _converted_after(tmodel)
    _assert_trees_close(after["batch_stats"],
                        init_converted["batch_stats"],
                        rtol=0, atol=0, what="frozen running stats (torch)")
    _assert_trees_close(state.params["backbone"], after["params"],
                        rtol=2e-2, atol=1e-3, what="params under freeze_bn")
