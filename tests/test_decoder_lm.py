"""The token decoder (models/decoder_lm.py, `--model decoder_lm`) against its
plain reference (benchmark/reference/smallthinker.py, imported as it stands:
it takes nothing from the program), the expert share, the sparse dispatch,
the window / grouped-head flash kernels, the layout lists and `--dataset
tokens` through `cli.train`. CPU, toy sizes."""

import functools
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hlo_text import conditionals  # noqa: E402

from benchmark.reference import joyai_llm_flash as ref_sigmoid  # noqa: E402
from benchmark.reference import smallthinker as ref  # noqa: E402
from benchmark.reference.common import make_params  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops.attention import attention  # noqa: E402
from ddp_classification_pytorch_tpu.ops import moe  # noqa: E402
from ddp_classification_pytorch_tpu.ops.moe import slot_bound, sparse_moe  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import _lm_loss  # noqa: E402

# ops/__init__ re-exports a function named like the module
fa = importlib.import_module("ddp_classification_pytorch_tpu.ops.flash_attention")

ARCH = {"vocab_size": 96, "hidden_size": 32, "num_layers": 4, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 8, "expert_width": 16, "num_experts": 8,
        "experts_held": 4, "first_expert": 4, "top_k": 2,
        "rope_layout": [0, 1, 1, 1], "window_layout": [0, 1, 1, 1],
        "window": 8, "rope_theta": 1.5e6, "rms_eps": 1e-6, "seq_len": 32}


def cli_config(arch, *extra):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens",
            "--dtype", "float32", "--optimizer", "adam", "--head_block", "16"]
    for key, value in arch.items():
        argv += [f"--{key}", ",".join(map(str, value))
                 if isinstance(value, list) else str(value)]
    return config_from_args(build_parser().parse_args(argv + list(extra)))


def program_tree(flat):
    """The reference's flat {"a/b": leaf} as the program's nested params."""
    tree = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = name.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return tree


def flat_tree(tree, prefix=""):
    out = {}
    for key, value in tree.items():
        name = f"{prefix}{key}"
        out.update(flat_tree(value, name + "/") if isinstance(value, dict)
                   else {name: value})
    return out


def batch(arch, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, arch["vocab_size"], (rows, arch["seq_len"] + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(ids[:, 1:], jnp.int32)


# (a) ----------------------------------------------------------------------

def test_program_matches_the_plain_reference_loss_and_every_gradient():
    cfg = cli_config(ARCH, "--remat")
    model = build_model(cfg.model, cfg.data.num_classes)
    flat = make_params(ref.param_spec(ARCH), 3)
    tokens, targets = batch(ARCH)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :8], train=False))["params"]
    assert ({k: v.shape for k, v in flat_tree(init).items()}
            == {k: v.shape for k, v in flat.items()})
    loss_fn, _ = _lm_loss(cfg, model)
    (loss, (_, (_, _, load))), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(
        program_tree(flat), {}, tokens, targets, None)
    want, want_grads = jax.jit(jax.value_and_grad(ref.loss_for(ARCH)))(
        flat, tokens, targets)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = flat_tree(grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 2e-4 * scale, name
    # every slot routed to a held expert is counted, none twice
    assert load.shape == (ARCH["num_layers"], ARCH["experts_held"])
    assert 0 < int(load.sum()) <= tokens.size * ARCH["top_k"] * ARCH["num_layers"]


# (b), (c) -----------------------------------------------------------------

def banks(key, experts, c=16, width=8):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (experts, c, width)),
            jax.random.normal(ks[1], (experts, c, width)),
            jax.random.normal(ks[2], (experts, width, c)))


def dense_mixture(u, logits, w, top_k, first=0):
    arch = {"top_k": top_k, "first_expert": first}
    return ref.held_experts(u, logits, *w, arch, lambda x: x)


def test_the_four_shares_add_up_to_the_uncut_layer():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    u, logits = jax.random.normal(ks[0], (64, 16)), jax.random.normal(ks[1], (64, 16))
    w = banks(ks[2], 16)
    whole, load = sparse_moe(u, logits, *w, top_k=3, dtype=jnp.float32)
    assert int(load.sum()) == 64 * 3
    parts = [sparse_moe(u, logits, *(b[4 * s:4 * s + 4] for b in w), top_k=3,
                        first_expert=4 * s, dtype=jnp.float32)
             for s in range(4)]  # router at its full width of 16 in each
    np.testing.assert_allclose(sum(p for p, _ in parts), whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(jnp.concatenate([l for _, l in parts]), load)
    np.testing.assert_allclose(whole, dense_mixture(u, logits, w, 3),
                               rtol=1e-4, atol=1e-4)
    # the same function under a `model` axis of 4: banks sharded, one psum
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4, 1))
    sharded, sharded_load = jax.jit(lambda *a: sparse_moe(
        *a, top_k=3, dtype=jnp.float32, mesh=mesh, axis="model",
        batch_axis="data"))(u, logits, *w)
    np.testing.assert_allclose(sharded, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sharded_load, load)


def zipf_logits(key, n, experts):
    """Tokens drawn Zipf(1.0) from 32 kinds, one router row per kind: the
    same rows come up again and again, so loads are uneven by construction."""
    kinds = jax.random.normal(key, (32, experts))
    p = 1.0 / np.arange(1, 33)
    ids = np.random.default_rng(0).choice(32, size=n, p=p / p.sum())
    return kinds[ids]


@pytest.mark.parametrize("case", ["all_slots_on_one_expert", "an_expert_with_no_token",
                                  "zipf_mix"])
def test_sparse_dispatch_equals_dense_evaluation_and_drops_no_slot(case):
    n, experts, held, first, top_k = 96, 8, 4, 2, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    u, w = jax.random.normal(ks[0], (n, 16)), banks(ks[1], held)
    if case == "all_slots_on_one_expert":   # top-1: every token picks expert 3
        top_k, logits = 1, jnp.zeros((n, experts)).at[:, 3].set(5.0)
    elif case == "an_expert_with_no_token":  # nobody picks held expert 4
        logits = jax.random.normal(ks[2], (n, experts)).at[:, 4].set(-50.0)
    else:
        logits = zipf_logits(ks[2], n, experts)

    def sparse(u, logits, *w):
        return sparse_moe(u, logits, *w, top_k=top_k, first_expert=first,
                          dtype=jnp.float32)

    y, load = sparse(u, logits, *w)
    _, idx = jax.lax.top_k(logits, top_k)
    want_load = [(idx == first + e).sum() for e in range(held)]
    np.testing.assert_array_equal(load, want_load)
    if case == "all_slots_on_one_expert":
        assert load.tolist() == [0, n, 0, 0]
    if case == "an_expert_with_no_token":
        assert load[2] == 0
    np.testing.assert_allclose(y, dense_mixture(u, logits, w, top_k, first),
                               rtol=1e-4, atol=1e-4)
    cot = jax.random.normal(jax.random.PRNGKey(9), y.shape)
    got = jax.grad(lambda *a: (sparse(*a)[0] * cot).sum(), argnums=range(5))(
        u, logits, *w)
    want = jax.grad(lambda u, l, *w: (dense_mixture(u, l, w, top_k, first) * cot).sum(),
                    argnums=range(5))(u, logits, *w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def poisoned_ragged_dot(monkeypatch, seen):
    """`jax.lax.ragged_dot` as the chip's kernels leave it: only the rows of
    the groups are written, forward and in the transposes; the buffer's tail
    is NaN here. Every call that RUNS puts its buffer's rows on `seen`."""
    real = jax.lax.ragged_dot

    def poison(x, gs):
        tail = jnp.arange(x.shape[0])[:, None] >= gs.sum()
        return jnp.where(tail, jnp.nan, x)

    @jax.custom_vjp
    def ragged(x, w, gs):
        jax.debug.callback(lambda: seen.append(x.shape[0]))
        return poison(real(x, w, gs), gs)

    def fwd(x, w, gs):
        return ragged(x, w, gs), (x, w, gs)

    def bwd(res, g):
        x, w, gs = res
        dx, dw = jax.vjp(lambda x, w: real(x, w, gs), x, w)[1](
            jnp.where(jnp.isnan(g), 0.0, g))
        return poison(dx, gs), dw, None

    ragged.defvjp(fwd, bwd)
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        lambda x, w, gs, preferred_element_type=None: ragged(x, w, gs))


def slots_on_held(n, experts, first, loads, key):
    """Router logits (n, experts) that send exactly loads[e] token-slots to
    expert first + e: its first loads[e] tokens choose it, no other does."""
    logits = jax.random.normal(key, (n, experts))
    for e, load in enumerate(loads):
        logits = logits.at[:, first + e].set(
            jnp.where(jnp.arange(n) < load, 9.0, -9.0))
    return logits


@functools.partial(jax.jit, static_argnames=("top_k", "first", "traced_as"))
def sparse_step(u, logits, cot, *w, top_k, first, traced_as):
    """Value, load and the five gradients of `sparse_moe`; `traced_as` names
    what was patched while it was traced (one trace a shape and a name: the
    cases below share them)."""
    def loss(u, logits, *w):
        y, load = sparse_moe(u, logits, *w, top_k=top_k, first_expert=first,
                             dtype=jnp.float32)
        return (y * cot).sum(), load

    return jax.value_and_grad(loss, argnums=range(5), has_aux=True)(u, logits, *w)


SEEN = []   # the rows of every grouped matmul that ran (poisoned_ragged_dot)


@pytest.mark.parametrize("n,top_k,loads,windows", [
    (256, 2, (0, 0), 1), (256, 2, (60, 40), 1), (256, 2, (200, 56), 1),
    (256, 2, (200, 57), 2), (256, 2, (256, 256), 2), (200, 3, (200, 200), 2),
], ids=["no_slot", "under_the_bound", "exactly_at_it", "one_slot_over", "every_slot_here",
        "a_last_window_that_starts_early"])
def test_a_bounded_buffer_takes_what_fits_and_drops_nothing(n, top_k, loads, windows,
                                                            monkeypatch):
    """Holding under a quarter of the experts (2 of 16: 512 slots for a bound
    of 256 sorted rows, or 600 for 384), the counted load picks the branch on
    the device: one window of `bound` rows where it fits, as many as it needs
    where it does not. Values and all five gradients equal the dense
    evaluation and the one-path code (the constant patched until bound = S)
    in either, and every grouped matmul ran on `bound` rows: two a window
    forward, two more where the backward rebuilds it. Rows between the last
    group and a window's end are NaN here and read by nothing."""
    experts, held, first = 16, 2, 5
    bound = slot_bound(top_k * n, held, experts)
    assert bound < top_k * n and windows == max(1, -(-sum(loads) // bound))
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    u, w = jax.random.normal(ks[0], (n, 16)), banks(ks[1], held)
    logits = slots_on_held(n, experts, first, loads, ks[2])
    cot = jax.random.normal(ks[3], u.shape)
    args, kw = (u, logits, cot, *w), dict(top_k=top_k, first=first)

    SEEN.clear()
    poisoned_ragged_dot(monkeypatch, SEEN)
    (got, load), got_grads = sparse_step(*args, **kw, traced_as="poisoned")
    jax.effects_barrier()
    assert load.tolist() == list(loads)
    assert SEEN == [bound] * 4 * windows, SEEN
    monkeypatch.undo()
    monkeypatch.setattr(moe, "SLOT_BOUND_FACTOR", 8)      # 8 x 2/16: no bound
    (full, _), full_grads = sparse_step(*args, **kw, traced_as="one_path")
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda u, l, *w: (dense_mixture(u, l, w, top_k, first) * cot).sum(),
        argnums=range(5)))(u, logits, *w)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    np.testing.assert_allclose(got, full, rtol=1e-6)
    for g, f, r in zip(got_grads, full_grads, want_grads):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(g, f, rtol=1e-5, atol=1e-5)


def test_rows_past_the_last_group_are_never_read(monkeypatch):
    """On the chip the grouped-matmul kernels write only the rows of their
    groups: in the slot buffer's tail (slots of experts held elsewhere) the
    outputs, and the transposes' row cotangents, are whatever the buffer
    held. Here that tail is poisoned with NaN, forward and backward; values
    and gradients still have to equal the dense evaluation."""
    poisoned_ragged_dot(monkeypatch, [])
    n, experts, held, first, top_k = 64, 8, 4, 2, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    u, w = jax.random.normal(ks[0], (n, 16)), banks(ks[1], held)
    logits = jax.random.normal(ks[2], (n, experts))
    cot = jax.random.normal(jax.random.PRNGKey(4), u.shape)

    def sparse(u, logits, *w):
        return sparse_moe(u, logits, *w, top_k=top_k, first_expert=first,
                          dtype=jnp.float32)[0]

    got = jax.value_and_grad(lambda *a: (sparse(*a) * cot).sum(), argnums=range(5))(
        u, logits, *w)
    monkeypatch.undo()
    want = jax.value_and_grad(
        lambda u, l, *w: (dense_mixture(u, l, w, top_k, first) * cot).sum(),
        argnums=range(5))(u, logits, *w)
    assert np.isfinite(float(got[0]))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, r in zip(got[1], want[1]):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", ["softmax_reglu_first_0", "sigmoid_bias_swiglu_first_2"])
def test_the_combines_backward_stays_among_the_sorted_rows(case):
    """Under remat a routing layer's gradient crosses between the slots and
    the sorted rows five times, not six: the dispatch forward, recomputed and
    backward, the combine forward and backward. The recomputed combine gather
    has no reader, and the output's cotangent (N, C) is never written out to
    (k, N, C) (ops/moe.py::_combine). Counted in the program the CPU's
    compiler leaves; values and gradients beside it."""
    n, c, experts, held, top_k = 40, 24, 8, 4, 3      # no two sizes alike
    slots = top_k * n
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    u, logits = jax.random.normal(ks[0], (n, c)), jax.random.normal(ks[1], (n, experts))
    w, cot = banks(ks[2], held, c=c), jax.random.normal(ks[3], (n, c))
    if case == "softmax_reglu_first_0":
        first, kw = 0, {}

        def dense(u, logits, *w):
            return dense_mixture(u, logits, w, top_k, first)
    else:
        first, bias = 2, 0.1 * jax.random.normal(ks[4], (experts,))
        kw = dict(activation="silu", route=dict(scoring="sigmoid", bias=bias, scale=2.5))
        arch = {"top_k": top_k, "first_expert": first, "router_scale": 2.5}

        def dense(u, logits, *w):
            idx, weight = ref_sigmoid.route(logits, bias, arch)
            return ref_sigmoid.held_experts(u, idx, weight, *w, arch, lambda x: x)

    @jax.checkpoint
    def layer(u, logits, *w):
        return u + sparse_moe(u, logits, *w, top_k=top_k, first_expert=first,
                              dtype=jnp.float32, **kw)[0]

    step = jax.jit(jax.value_and_grad(lambda *a: (layer(*a) * cot).sum(),
                                      argnums=range(5)))
    got = step(u, logits, *w)
    want = jax.value_and_grad(lambda u, *a: ((u + dense(u, *a)) * cot).sum(),
                              argnums=range(5))(u, logits, *w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)

    text = step.lower(u, logits, *w).compile().as_text()

    def results(op):   # result dims (ones dropped) and attributes of every `op`
        return [([int(d) for d in dims.split(",") if d not in ("", "1")], rest)
                for dims, rest in re.findall(rf"= \w+\[([\d,]*)\]\S* {op}\((.*)", text)]

    row_gathers = [dims for dims, _ in results("gather") if dims == [slots, c]]
    assert 3 <= len(row_gathers) <= 5, row_gathers
    spread = [dims for dims, rest in results("broadcast")
              if dims == [top_k, n, c] and "dimensions={1,2}" in rest]
    assert not spread, "the output's cotangent is broadcast over the choices"


@pytest.mark.parametrize("held", [2, 4], ids=["an_eighth_held", "a_quarter_held"])
def test_each_branch_keeps_its_residuals_to_itself(held):
    """Under remat a bounded routing layer is two `conditional`s, the
    forward's and the backward's (which rebuilds the taken branch's forward
    inside it); what they hand out is token-space and bank-space only, no
    array of zeros for the branch not taken. Inside either branch, the one
    window and the loop over windows, nothing has S rows but the slot-space
    gather's result. Holding a quarter of the experts the program has no
    `cond` at all. Counted in the program the CPU's compiler leaves."""
    n, c, width, experts, first, top_k = 192, 24, 10, 16, 5, 3   # no two alike
    slots, bound = top_k * n, slot_bound(top_k * n, held, experts)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    u, logits = jax.random.normal(ks[0], (n, c)), jax.random.normal(ks[1], (n, experts))
    w, cot = banks(ks[2], held, c=c, width=width), jax.random.normal(ks[3], (n, c))

    @jax.checkpoint
    def layer(u, logits, *w):
        return u + sparse_moe(u, logits, *w, top_k=top_k, first_expert=first,
                              dtype=jnp.float32)[0]

    lowered = jax.jit(jax.value_and_grad(lambda *a: (layer(*a) * cot).sum(),
                                         argnums=range(5))).lower(u, logits, *w)
    if held == 4:
        assert bound == slots and "stablehlo.case" not in lowered.as_text()
        return
    assert (bound, slots) == (384, 576)
    conds = conditionals(lowered.compile().as_text())
    assert len(conds) == 2, len(conds)
    for results, branches in conds:
        assert f"[{slots}," not in results, results
        assert sorted(" while(" in b for b in branches) == [False, True]
        for branch in branches:
            assert f"[{bound},{2 * width}]" in branch        # the census sees them
            for wide in (width, 2 * width):
                assert f"[{slots},{wide}]" not in branch, wide
            # (S, C) there: the slot-space gather's result and its mask, no more
            made = [op for dims, op in re.findall(r"= \w+\[([\d,]*)\]\S* ([\w\-]+)\(", branch)
                    if [d for d in dims.split(",") if d != "1"] == [str(slots), str(c)]]
            assert 1 <= made.count("gather") <= 2, made
            assert set(made) <= {"gather", "bitcast", "broadcast", "select"}, made


def test_a_model_axis_of_eight_bounds_each_shards_buffer():
    """All 16 experts over a `model` axis of 8: a shard holds 2 of 16, so the
    `shard_map` body takes the bound (the unsharded whole, holding all, has
    none), each shard on its own counted load, the psum outside the `cond`."""
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    u, logits = jax.random.normal(ks[0], (128, 16)), jax.random.normal(ks[1], (128, 16))
    w = banks(ks[2], 16)
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 8, 1))

    def sharded(u, logits, *w):
        return sparse_moe(u, logits, *w, top_k=2, dtype=jnp.float32, mesh=mesh,
                          axis="model")

    assert slot_bound(256, 2, 16) == 128
    assert jax.jit(sharded).lower(u, logits, *w).as_text().count("stablehlo.case") == 1
    got, load = jax.jit(sharded)(u, logits, *w)
    whole, whole_load = sparse_moe(u, logits, *w, top_k=2, dtype=jnp.float32)
    np.testing.assert_allclose(got, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(load, whole_load)


def test_the_decoders_step_is_the_same_step_with_bounded_buffers(monkeypatch):
    """At the model's level (every other model test holds a quarter of its
    experts or more): 2 of 16 held, 512 slots a layer for a bound of 256; loss,
    `moe_load` and every gradient leaf against the same step with the constant
    patched until bound = S."""
    arch = dict(ARCH, num_layers=1, num_experts=16, experts_held=2, first_expert=5,
                seq_len=128)
    cfg = cli_config(arch, "--remat")
    model = build_model(cfg.model, cfg.data.num_classes)
    params = program_tree(make_params(ref.param_spec(arch), 3))
    tokens, targets = batch(arch)
    loss_fn, _ = _lm_loss(cfg, model)

    def step():
        fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        assert ("stablehlo.case" in fn.lower(params, {}, tokens, targets, None).as_text()) \
            == (moe.SLOT_BOUND_FACTOR == 4)
        (loss, (_, (_, _, load))), grads = fn(params, {}, tokens, targets, None)
        return loss, load, flat_tree(grads)

    loss, load, grads = step()
    assert 0 < int(load.sum(axis=1).max()) <= slot_bound(512, 2, 16) == 256
    monkeypatch.setattr(moe, "SLOT_BOUND_FACTOR", 8)
    want_loss, want_load, want = step()
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_array_equal(load, want_load)
    for name, g in want.items():
        np.testing.assert_allclose(grads[name], g, rtol=1e-4, atol=1e-6, err_msg=name)


# (d) ----------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads,window", [
    (7, 1, 200),     # grouped 7:1, band narrower than T: tiles skipped both sides
    (4, 2, 128),     # the band's edge on a tile boundary
    (2, 2, 4096),    # window >= T is plain causal
    (7, 1, None),    # grouped heads, causal
], ids=["gqa7_window200", "gqa2_window128", "window_ge_T", "gqa7_causal"])
def test_flash_kernels_match_the_dense_masked_op(heads, kv_heads, window, monkeypatch):
    monkeypatch.setattr(fa, "_block", lambda t, cap=1024: 128)  # 4 x 4 tiles
    t, d = 512, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, t, heads, d))
    k = jax.random.normal(ks[1], (2, t, kv_heads, d))
    v = jax.random.normal(ks[2], (2, t, kv_heads, d))
    cot = jax.random.normal(ks[3], q.shape)

    def both(fn):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, causal=True, window=window),
                           q, k, v)
        return (out,) + vjp(cot)

    for got, want in zip(both(fa.flash_attention), both(attention)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_dense_op_window_and_groups_against_a_loop():
    t, window = 12, 5
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, t, 4, 8))
    k = jax.random.normal(ks[1], (1, t, 2, 8))
    v = jax.random.normal(ks[2], (1, t, 2, 8))
    out = attention(q, k, v, causal=True, window=window)
    for h in range(4):
        for i in range(t):
            lo = max(0, i - window + 1)
            s = q[0, i, h] @ k[0, lo:i + 1, h // 2].T / np.sqrt(8)
            want = jax.nn.softmax(s) @ v[0, lo:i + 1, h // 2]
            np.testing.assert_allclose(out[0, i, h], want, rtol=1e-4, atol=1e-4)


# (e) ----------------------------------------------------------------------

def last_position(rope, window, tokens):
    arch = dict(ARCH, num_layers=1, rope_layout=[rope], window_layout=[window],
                window=4, seq_len=16)
    cfg = cli_config(arch)
    model = build_model(cfg.model, cfg.data.num_classes)
    params = program_tree(make_params(ref.param_spec(arch), 11))
    hidden, _ = model.apply({"params": params}, tokens, method="hidden")
    return hidden[0, -1]


def test_layer_4k_has_no_rotary_term_and_a_full_mask():
    cfg = cli_config(dict(ARCH, num_layers=52))
    dc = cfg.model.decoder
    assert dc.layout(dc.rope_layout) == dc.layout(dc.window_layout) == (0, 1, 1, 1) * 13
    tokens = jnp.arange(16, dtype=jnp.int32)[None] % 7 + 3
    swapped = tokens.at[0, 0].set(tokens[0, 5]).at[0, 5].set(tokens[0, 0])
    far = tokens.at[0, 0].set(90)   # position 0 is outside a window of 4 at 15
    nope = last_position(0, 0, tokens)
    # no position encoding + full causal mask: the last position sees its
    # prefix as a set, and all of it
    np.testing.assert_allclose(last_position(0, 0, swapped), nope, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(last_position(0, 0, far) - nope).max()) > 1e-4
    # a rotary layer tells the order; a window layer does not see position 0
    assert float(jnp.abs(last_position(1, 0, swapped)
                         - last_position(1, 0, tokens)).max()) > 1e-4
    np.testing.assert_allclose(last_position(1, 1, far), last_position(1, 1, tokens),
                               rtol=1e-5, atol=1e-6)


def test_the_trainer_counts_which_buffer_each_layer_took():
    """`moe_slot_bound_total{layer, path}` from the logged step's `moe_load`
    against the bound the op itself asks: 2 rows of 128 tokens, top-2, 2 of
    16 held: 256 sorted rows for 512 slots."""
    from ddp_classification_pytorch_tpu.models.factory import model_report
    from ddp_classification_pytorch_tpu.obs.registry import Registry

    cfg = cli_config(dict(ARCH, num_experts=16, experts_held=2, seq_len=128),
                     "--batchsize", "2")
    report, obs = model_report(cfg.model), Registry()
    assert report.built(cfg.data.batch_size, obs)["moe_bound"] == "256/512"
    assert (report.bound, report.slots) == (256, 512)
    for load in ([[0, 0], [200, 56], [200, 57], [100, 30]],
                 [[3, 4], [256, 1], [512, 0], [0, 256]]):
        report.logged_step({"moe_load": np.array(load)}, obs)
    text = obs.expose()
    for layer, bounded, full in (("0", 2, 0), ("1", 1, 1), ("2", 0, 2), ("3", 2, 0)):
        assert f'moe_slot_bound_total{{layer="{layer}",path="bounded"}} {bounded}\n' in text
        assert f'moe_slot_bound_total{{layer="{layer}",path="full"}} {full}\n' in text


# (f) ----------------------------------------------------------------------

def test_tokens_dataset_trains_through_cli_train(tmp_path, capsys):
    from ddp_classification_pytorch_tpu.data.tokens import TokenDataset

    t = ARCH["seq_len"]
    ids = (np.arange(8 * (t + 1)) * 7 % 50).astype(np.int32)
    path = tmp_path / "train.bin"
    ids.tofile(path)
    ds = TokenDataset(str(path), t)
    x, y = ds[1]
    assert len(ds) == 8 and x.dtype == np.int32
    np.testing.assert_array_equal(x, ids[t + 1:2 * t + 1])
    np.testing.assert_array_equal(y, ids[t + 2:2 * t + 2])   # shifted by one
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens",
            "--train_dir", str(path), "--batchsize", "8", "--epochs", "2",
            "--optimizer", "adam", "--lr", "0.003", "--adam_b2", "0.95",
            "--platform", "cpu", "--out", str(tmp_path / "run"),
            "--log_every", "1", "--remat", "--head_block", "64"]
    for key, value in ARCH.items():
        argv += [f"--{key}", ",".join(map(str, value))
                 if isinstance(value, list) else str(value)]
    train_main(argv)   # Trainer, ShardedLoader, DevicePrefetcher, _build_step
    with open(tmp_path / "run" / "history.json") as f:
        history = json.load(f)
    losses = history["loss"]                  # one step an epoch: two steps
    assert len(losses) == 2 and all(np.isfinite(losses)) and losses[1] < losses[0]
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    assert 'moe_expert_load_max{layer="3"}' in prom
    assert 'moe_slots_routed_total{held="true"}' in prom
    # 4 of 8 held: the sorted rows are the dropless worst case, every step
    assert 'moe_slot_bound_total{layer="3",path="bounded"} 2' in prom
    setup = next(line for line in capsys.readouterr().out.splitlines()
                 if "[trainer] set-up:" in line)
    assert "moe_bound=512/512" in setup, setup
