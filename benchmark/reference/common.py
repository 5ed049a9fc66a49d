"""What the plain references share: seeded weights, the loss, the two
optimizers and the three-step trajectory that `correct` is decided on.

Nothing here imports the program under test, and nothing here takes a
value the program has made. Weights come from `make_params` (one jitted
call from the seed, laid out by each reference's own `param_spec`); the
runner installs the SAME weights into the program, never the other way.

Arithmetic is float32 under `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in bf16 passes). `precision`
selects what the forward pass's matmul/conv operands AND every layer's
output are rounded to (the master weights stay float32):

  float32   the reference proper
  bfloat16  what the configurations state (bf16 compute, f32 params)
  fp8       the control: the step below bf16 that would tempt a later PR
            (fp8 matmuls, activations kept in fp8), as fp8 training does it:
            e4m3 with a per-tensor scale forward, the cotangent rounded to
            e5m2 with a per-tensor scale wherever the forward value was.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)

Params = Dict[str, jax.Array]
# name -> (shape, kind, value): kind is "normal" (value = std) | "const" | "ones" | "zeros"
Spec = Dict[str, Tuple[Tuple[int, ...], str, float]]


def quantizer(precision: str) -> Callable[[jax.Array], jax.Array]:
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        def scaled(x, dtype, top):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
            return (x / scale).astype(dtype).astype(jnp.float32) * scale

        @jax.custom_vjp
        def q(x):
            return scaled(x, jnp.float8_e4m3fn, 448.0)

        q.defvjp(lambda x: (q(x), None),
                 lambda _, g: (scaled(g, jnp.float8_e5m2, 57344.0),))
        return q
    raise ValueError(f"unknown precision {precision!r}")


def make_params(spec: Spec, seed: int) -> Params:
    """Every leaf from the seed, float32. Traceable: jit it once."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, name in enumerate(sorted(spec)):
        shape, kind, std = spec[name]
        if kind == "normal":
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "const":
            out[name] = jnp.full(shape, std, jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            raise ValueError(f"unknown init kind {kind!r} for {name}")
    return out


def normalise(images_u8_or_f32: jax.Array, flip_mask=None) -> jax.Array:
    """uint8 pixels -> (x/255 - mean)/std in float32; float32 input is
    taken as already normalised. `flip_mask` (B,) mirrors those rows
    left-right first — the program's train step does so on the device for
    image-folder input, from a key the runner derives as the program does
    (a departure from "the reference is given the batch as it is": the
    mask is part of the input, not of the model)."""
    x = images_u8_or_f32
    if x.dtype == jnp.uint8:
        x = x.astype(jnp.float32) / 255.0
        x = (x - jnp.asarray(IMAGENET_MEAN, jnp.float32)) / jnp.asarray(
            IMAGENET_STD, jnp.float32)
    if flip_mask is not None:
        x = jnp.where(flip_mask[:, None, None, None], x[:, :, ::-1, :], x)
    return x


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy over the rows."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_and_grad(forward, params: Params, x, y, row_block: int = 0):
    """Mean loss and its gradient. `row_block` > 0 (models whose rows do
    not see each other: no batch norm) walks the batch in blocks of that
    many rows so the float32 activations fit: the mean of equal blocks'
    means is the batch mean, for the loss and for the gradient."""
    def f(p, xb, yb):
        return cross_entropy(forward(p, xb), yb)

    n = x.shape[0]
    if not row_block or row_block >= n:
        return jax.value_and_grad(f)(params, x, y)
    if n % row_block:
        raise ValueError(f"batch {n} is not a multiple of row_block {row_block}")
    k = n // row_block
    xs = x.reshape((k, row_block) + x.shape[1:])
    ys = y.reshape((k, row_block))

    def body(carry, xy):
        loss, grads = jax.value_and_grad(f)(params, *xy)
        return (carry[0] + loss / k,
                jax.tree_util.tree_map(lambda a, g: a + g / k, carry[1], grads)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                    (xs, ys))
    return loss, grads


def leaf_norms(tree: Params) -> Params:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def optimizer_step(opt: dict, params: Params, state: dict, grads: Params,
                   t: int):
    """One update of the configuration's optimizer (`t` = 1 at the first).
    The learning rate is constant over the steps compared (the step decay
    is epochs away) but for the linear warm-up, where the recipe has one.
    sgd: PyTorch/optax momentum, trace = g + m*trace, p -= lr*trace.
    adam: Kingma & Ba with bias correction, eps outside the root."""
    lr = opt["lr"]
    if opt.get("warmup_iters", 0) > 0:
        # linear per-iteration warm-up from warmup_start_lr, counted from 0
        start = opt.get("warmup_start_lr", 1e-6)
        lr = start + (lr - start) * jnp.minimum((t - 1) / opt["warmup_iters"], 1.0)
    if opt["kind"] == "sgd":
        m = opt["momentum"]
        trace = {k: grads[k] + m * state["trace"][k] for k in params}
        new = {k: params[k] - lr * trace[k] for k in params}
        return new, {"trace": trace}
    if opt["kind"] == "adam":
        b1, b2, eps = opt.get("b1", 0.9), opt.get("b2", 0.999), opt.get("eps", 1e-8)
        mu = {k: b1 * state["mu"][k] + (1 - b1) * grads[k] for k in params}
        nu = {k: b2 * state["nu"][k] + (1 - b2) * jnp.square(grads[k])
              for k in params}
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new = {k: params[k] - lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps)
               for k in params}
        return new, {"mu": mu, "nu": nu}
    raise ValueError(f"unknown optimizer {opt['kind']!r}")


def optimizer_init(opt: dict, params: Params) -> dict:
    def zeros():  # distinct buffers: the step donates its state
        return {k: jnp.zeros_like(v) for k, v in params.items()}

    return {"trace": zeros()} if opt["kind"] == "sgd" else {"mu": zeros(), "nu": zeros()}


def make_step(forward, opt: dict, row_block: int = 0):
    """-> jitted `(params, opt_state, images, labels, flip, t) -> (loss,
    gradient, new params, new opt_state)`: one optimizer step of the
    reference on one batch. `flip` is a (B,) bool mask (all False
    where the program does not flip), `t` the step count from 1."""

    def step(params, state, images, labels, flip, t):
        with jax.default_matmul_precision("highest"):
            x = normalise(images, flip)
            loss, grads = loss_and_grad(forward, params, x, labels, row_block)
            new, state = optimizer_step(opt, params, state, grads, t)
        return loss, grads, new, state

    return jax.jit(step, donate_argnums=(1,))


def trajectory(step, opt: dict, params: Params, images, labels, flips=None):
    """Drive `step` over images (S,B,...) / labels (S,B) from `params` ->
    {"loss": [S], "grad0": the first gradient, "grad0_norms": its per-leaf
    norms, "dparam": {leaf: norm of what the S steps changed}}."""
    p, state = params, optimizer_init(opt, params)
    losses, grad0 = [], None
    for s in range(images.shape[0]):
        flip = (jnp.zeros((images.shape[1],), bool) if flips is None
                else jnp.asarray(flips[s]))
        loss, grads, p, state = step(p, state, images[s], labels[s], flip,
                                     jnp.asarray(s + 1, jnp.float32))
        losses.append(loss)
        if s == 0:
            grad0 = grads
        del grads
    dparam = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(p, params)
    return {"loss": losses, "grad0": grad0,
            "grad0_norms": jax.jit(leaf_norms)(grad0), "dparam": dparam}


def difference_gap(got: Params, ref: Params) -> float:
    """Mean over the leaves of |got - ref| (the norm of the DIFFERENCE)
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Norms alone do not see unbiased rounding noise (3 %
    of noise on every element moves a norm by 0.05 %); this does, which is
    what separates a lower precision from the stated one."""
    norms = jax.jit(lambda a, b: (leaf_norms({k: a[k] - b[k] for k in b}),
                                  leaf_norms(b)))(got, ref)
    diff = {k: float(v) for k, v in norms[0].items()}
    size = {k: float(v) for k, v in norms[1].items()}
    med = sorted(size.values())[len(size) // 2]
    gaps = [diff[k] / max(size[k], med, 1e-30) for k in size]
    return sum(gaps) / len(gaps) if all(map(math.isfinite, gaps)) else float("inf")


def mean_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> float:
    """Mean over the leaves of the same per-leaf gap `worst_leaf_gap` takes
    the largest of: steadier from seed to seed than a worst case."""
    med = sorted(float(v) for v in ref.values())[len(ref) // 2]
    gaps = [abs(float(got[k]) - float(r)) / max(float(r), med, 1e-30)
            for k, r in ref.items()]
    return sum(gaps) / len(gaps) if all(map(math.isfinite, gaps)) else float("inf")


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> Tuple[float, str]:
    """Largest |got - ref| over the leaves, each against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero). Compares norms, not the norm of a
    difference."""
    names = sorted(ref)
    if sorted(got) != names:
        raise ValueError("leaf names differ between program and reference")
    med = sorted(float(ref[k]) for k in names)[len(names) // 2]
    worst, where = 0.0, ""
    for k in names:
        r, g = float(ref[k]), float(got[k])
        if not (math.isfinite(r) and math.isfinite(g)):
            return float("inf"), k
        gap = abs(g - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where
