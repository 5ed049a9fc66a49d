"""The token decoder (models/decoder_lm.py, `--model decoder_lm`) against its
plain reference (benchmark/reference/smallthinker.py, imported as it stands:
it takes nothing from the program), the expert share, the sparse dispatch,
the window / grouped-head flash kernels, the layout lists and `--dataset
tokens` through `cli.train`. CPU, toy sizes."""

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
from ddp_classification_pytorch_tpu.ops.moe import sparse_moe  # noqa: E402
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


def test_rows_past_the_last_group_are_never_read(monkeypatch):
    """On the chip the grouped-matmul kernels write only the rows of their
    groups: in the slot buffer's tail (slots of experts held elsewhere) the
    outputs, and the transposes' row cotangents, are whatever the buffer
    held. Here that tail is poisoned with NaN, forward and backward; values
    and gradients still have to equal the dense evaluation."""
    real = jax.lax.ragged_dot

    def poison(x, gs):
        tail = jnp.arange(x.shape[0])[:, None] >= gs.sum()
        return jnp.where(tail, jnp.nan, x)

    @jax.custom_vjp
    def ragged(x, w, gs):
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


# (f) ----------------------------------------------------------------------

def test_tokens_dataset_trains_through_cli_train(tmp_path):
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
