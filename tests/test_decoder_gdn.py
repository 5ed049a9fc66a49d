"""The decoder's sixth fixed point (models/decoder_lm.py as Olmo-Hybrid-7B
configures it: Gated DeltaNet — the gated delta rule with ONE decay a head —
in three layers of four, attention with a QK-norm over the whole projection
in the fourth, a block that norms its sub-layers' outputs only, a share of
the heads): the chunked op (ops/gdn.py) against the token-by-token
recurrence, the program against its plain reference
(benchmark/reference/olmo_hybrid_7b.py, imported as it stands: it takes
nothing from the program), the head share tied to the whole layer, the
factory's refusals, the configuration's arithmetic, the readers, and what a
run publishes. CPU, toy sizes."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.flops import olmo_hybrid_7b as flops  # noqa: E402
from benchmark.reference import common, olmo_hybrid_7b as ref  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.models import decoder_lm  # noqa: E402
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops import gdn, kda  # noqa: E402
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib  # noqa: E402
from ddp_classification_pytorch_tpu.train.state import create_train_state  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import _lm_loss, make_train_step  # noqa: E402
from test_decoder_lm import batch, flat_tree, program_tree  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "olmo_hybrid_7b.json")) as f:
    CONF = json.load(f)

# the period gdn, gdn, gdn, attn; rows of two chunks, so that the state crosses one
ARCH = {"vocab_size": 96, "hidden_size": 32, "num_layers": 4, "num_heads": 6,
        "num_kv_heads": 6, "head_dim": 8, "gdn_key_dim": 6, "gdn_value_dim": 12,
        "conv_kernel": 4, "gdn_layout": [1, 1, 1, 0], "dense_width": 48,
        "rms_eps": 1e-6, "heads_held": 0, "seq_len": 128}
KINDS = ["--attention", "gqa", "--qk_norm", "2", "--rope_layout", "0", "--window_layout",
         "0", "--activation", "silu", "--tied_embeddings", "0", "--pre_norm", "0",
         "--sandwich_norm", "1"]


def cli_argv(arch, *extra, dtype="float32"):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens", "--dtype",
            dtype, "--optimizer", "adam", "--head_block", "64",
            "--dense_layers", str(arch["num_layers"]), *KINDS]
    for key, value in arch.items():
        argv += [f"--{key}", ",".join(map(str, value)) if isinstance(value, list)
                 else str(value)]
    return argv + list(extra)


def program(arch, *extra, **kinds):
    cfg = config_from_args(build_parser().parse_args(cli_argv(arch, *extra, **kinds)))
    model = build_model(cfg.model, cfg.data.num_classes)
    return (cfg, model, *_lm_loss(cfg, model))


def seeded(arch=ARCH, seed=3):
    """The reference's seeded leaves, the per-head ones told apart: A_log
    spread as the spec says, dt_bias a different number a head."""
    flat = common.make_params(ref.param_spec(arch), seed)
    for name in flat:
        if name.endswith("gdn_dt_bias"):
            flat[name] = flat[name] + jnp.linspace(-1.0, 1.0, flat[name].shape[0])
        if name.endswith(("q_norm/scale", "k_norm/scale", "gdn_norm/scale")):
            flat[name] = 1.0 + 0.3 * jnp.sin(jnp.arange(flat[name].shape[0], dtype=jnp.float32))
    return flat


# (a) the chunked op against the recurrence -----------------------------------

def op_inputs(heads, t=128, dk=6, dv=12, floor=-3.0, beta=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (2, t, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (2, t, heads, dk)))
    v = jax.random.normal(ks[2], (2, t, heads, dv))
    g = floor * jax.random.uniform(ks[3], (2, t, heads))
    if beta is None:
        beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (2, t, heads)))
    else:
        beta = jnp.full((2, t, heads), beta, jnp.float32)
    return q, k, v, g, beta


def recurrence(*xs):
    with jax.default_matmul_precision("highest"):
        return ref.gdn_recurrence(*xs)


@pytest.mark.parametrize("heads,floor,beta", [
    (3, -3.0, None), (15, -0.5, None), (3, -30.0, None), (3, -0.05, 1.999)],
    ids=["3_heads", "15_heads", "g_down_to_-30", "beta_near_2"])
def test_chunked_op_is_the_token_by_token_recurrence_forward_and_every_gradient(
        heads, floor, beta):
    """d_k != d_v, heads that 8 does not divide, a decay whose 64 tokens are
    exp(-1920), beta at the edge where I - beta k k^T has the eigenvalue -1.
    Tolerance: float32 at the highest matmul precision on both sides; the
    chunk's sums are taken in another order (1e-4 of the largest entry)."""
    xs = op_inputs(heads, floor=floor, beta=beta)
    want = recurrence(*xs)
    got = gdn.gdn_chunked(*xs, dtype=jnp.float32)
    assert got.shape == want.shape == (2, 128, heads, 12) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    scale = float(jnp.abs(want).max())
    assert scale > 0.1 and float(jnp.abs(got - want).max()) < 1e-4 * scale
    probe = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    args = (0, 1, 2, 3, 4)
    want_g = jax.grad(lambda *a: jnp.sum(recurrence(*a) * probe), argnums=args)(*xs)
    got_g = jax.grad(lambda *a: jnp.sum(gdn.gdn_chunked(*a, dtype=jnp.float32) * probe),
                     argnums=args)(*xs)
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - b).max()) < 2e-4 * float(jnp.abs(b).max()), name


def test_an_unbounded_scalar_decay_leaves_float32_only_where_it_is_broadcast_into_kda():
    """Why the scalar decay has an entry of its own: `kda_chunked` splits
    exp(G_t - G_s) between a matmul's operands and counts on g >= -5."""
    q, k, v, g, beta = op_inputs(3, floor=-30.0)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    assert not bool(jnp.isfinite(kda.kda_chunked(q, k, v, wide, beta, dtype=jnp.float32)).all())
    assert bool(jnp.isfinite(gdn.gdn_chunked(q, k, v, g, beta, dtype=jnp.float32)).all())
    # and a gentle one through both is the same number
    q, k, v, g, beta = op_inputs(3, floor=-0.2)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    np.testing.assert_allclose(gdn.gdn_chunked(q, k, v, g, beta, dtype=jnp.float32),
                               kda.kda_chunked(q, k, v, wide, beta, dtype=jnp.float32),
                               atol=2e-5)


@pytest.mark.parametrize("t,ok", [(48, True), (16, True), (100, False), (72, False)])
def test_a_row_is_whole_chunks_or_one_shorter_chunk_as_chunk_of_says(t, ok):
    xs = op_inputs(3, t=t)
    if not ok:
        with pytest.raises(ValueError, match="chunk"):
            gdn.gdn_chunked(*xs, dtype=jnp.float32)
        return
    np.testing.assert_allclose(gdn.gdn_chunked(*xs, dtype=jnp.float32),
                               recurrence(*xs), atol=2e-5)


@pytest.mark.parametrize("heads,group", [(15, 5), (30, 6), (32, 8), (3, 3), (7, 7), (11, 1)])
def test_the_heads_go_the_most_at_a_time_that_divides_them_within_eight(heads, group):
    assert gdn.head_group_of(heads) == group


@pytest.mark.parametrize("sizes,path", [
    ({"gdn_key_dim": 96, "gdn_value_dim": 192, "seq_len": 8192}, "kernel"),   # published
    ({"gdn_key_dim": 128, "gdn_value_dim": 128, "seq_len": 64}, "kernel"),
    ({}, "xla"),                                                  # the tests' widths
    ({"gdn_key_dim": 96, "gdn_value_dim": 192, "seq_len": 48}, "xla"),    # a shorter chunk
    ({"gdn_key_dim": 32, "gdn_value_dim": 192, "seq_len": 8192}, "xla"),
    ({"gdn_layout": [0]}, None),
], ids=str)
def test_the_configured_sizes_decide_what_the_set_up_line_says_of_the_recurrence(
        sizes, path):
    """`gdn_core=` is `ops/gdn.py::takes_kernel` at the configured sizes: no
    flag, environment variable or configuration key chooses."""
    dc = program(dict(ARCH, **sizes))[0].model.decoder
    assert decoder_lm.gdn_core_path(dc) == path


def test_bf16_operands_stay_near_the_recurrence():
    """The matmuls' operands in bf16 (2^-8 a product), accumulation, decays,
    the triangle's inverse and the state float32: 2 % of the largest entry."""
    xs = op_inputs(5, floor=-0.3)
    want = recurrence(*xs)
    got = gdn.gdn_chunked(*xs, dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 0.02 * float(jnp.abs(want).max())


# (b) the program against the plain reference ----------------------------------

def compare_with_reference(arch, *extra, loss_tol=1e-5, grad_tol=3e-4):
    _, model, loss_fn, _ = program(arch, *extra)
    flat = seeded(arch)
    tokens, targets = batch(arch)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :8], train=False))["params"]
    assert ({k: v.shape for k, v in flat_tree(init).items()}
            == {k: v.shape for k, v in flat.items()})
    (loss, _), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        program_tree(flat), {}, tokens, targets, None)
    want, want_grads = jax.jit(jax.value_and_grad(ref.loss_for(arch)))(
        flat, tokens, targets)
    assert abs(float(loss) - float(want)) < loss_tol * abs(float(want))
    got = flat_tree(grads)
    assert set(got) == set(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max())
        assert scale > 0, name
        assert float(jnp.abs(got[name] - g).max()) < grad_tol * scale, name
    return flat


@pytest.mark.parametrize("extra", [(), ("--remat",)], ids=["plain", "remat"])
def test_program_matches_the_plain_reference_loss_and_every_gradient(extra):
    """gdn, gdn, gdn, attn with --pre_norm 0 --sandwich_norm 1 --qk_norm 2,
    every head held. Tolerances: float32 on both sides, the recurrence in
    chunks on one and token by token on the other (3e-4 of a leaf's largest
    gradient entry, 1e-5 of the loss)."""
    flat = compare_with_reference(ARCH, *extra)
    names = {n.split("/", 1)[1] for n in flat if n.startswith("layer")}
    assert "norm_mix_out/scale" in names and "norm_ffn_out/scale" in names
    assert not names & {"norm_in/scale", "norm_post/scale"}      # outputs only
    assert len([n for n in flat if n.startswith("layer0/")]) == 18
    assert len([n for n in flat if n.startswith("layer3/")]) == 11


def test_one_share_of_the_heads_alone_is_the_reference_given_that_share():
    """Heads 3..5 of 6: the leaves are the share's, the QK-norm's mean square
    runs over the held columns, the absent heads add nothing."""
    both = dict(ARCH, num_layers=2, gdn_layout=[1, 0])    # one layer of each kind
    arch = dict(both, heads_held=3)
    flat = compare_with_reference(arch)
    assert flat["layer0/gdn_q/kernel"].shape == (32, 3 * 6)
    assert flat["layer0/gdn_o/kernel"].shape == (3 * 12, 32)
    assert flat["layer0/gdn_a_log"].shape == (3,)
    assert flat["layer1/q_norm/scale"].shape == (3 * 8,)
    assert flat["layer1/o/kernel"].shape == (3 * 8, 32)
    # and it is not the whole model's loss
    whole = seeded(both)
    cut, cut_arch = ref.head_share(whole, both, 3, 3)
    assert cut_arch == arch and {k: v.shape for k, v in cut.items()} \
        == {k: v.shape for k, v in flat.items()}
    tokens, targets = batch(both)
    a = float(jax.jit(ref.loss_for(both))(whole, tokens, targets))
    b = float(jax.jit(ref.loss_for(arch))(cut, tokens, targets))
    assert abs(a - b) > 1e-4


def test_three_steps_of_the_train_step_change_the_parameters_as_the_references_do():
    """The step `cli.train` builds (Adam b2 0.95, the linear warm-up) against
    `common.optimizer_step` on the reference's gradients: the norm of every
    leaf's change over three steps within 2 % (Adam's first steps are lr x
    sign(g), which rounds apart where a gradient entry is all but zero)."""
    arch = dict(ARCH, num_layers=2, gdn_layout=[1, 0], heads_held=3)
    opt = {"kind": "adam", "lr": 0.003, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "warmup_iters": 10, "warmup_start_lr": 1e-6}
    cfg = config_from_args(build_parser().parse_args(cli_argv(
        arch, "--lr", "0.003", "--adam_b2", "0.95", "--warmUpIter", "10", "--batchsize", "2")))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=jax.devices()[:1])
    flat = seeded(arch)
    batches = [batch(arch, seed=s) for s in range(3)]
    with mesh:
        model, tx, state = create_train_state(cfg, mesh, 100)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        # copies: the step donates its state
        state = state.replace(params=jax.tree_util.tree_map(jnp.copy, program_tree(flat)))
        for tokens, targets in batches:
            state, metrics = step(state, tokens, targets)
            assert float(metrics["step_ok"]) == 1.0
    got = flat_tree(jax.device_get(state.params))
    params, opt_state = dict(flat), common.optimizer_init(opt, flat)
    grad = jax.jit(jax.grad(ref.loss_for(arch)))
    for s, (tokens, targets) in enumerate(batches):
        params, opt_state = common.optimizer_step(
            opt, params, opt_state, grad(params, tokens, targets), s + 1)
    for name, before in flat.items():
        want = float(jnp.linalg.norm(params[name] - before))
        have = float(jnp.linalg.norm(got[name] - before))
        assert want > 0 and abs(have - want) < 0.02 * want, (name, have, want)


# (c) the share tied to the whole layer ----------------------------------------

@pytest.mark.parametrize("name,gdn_layer", [("layer0", True), ("layer3", False)],
                         ids=["gdn_layer", "attention_layer"])
def test_two_shares_under_the_mesh_axis_are_the_uncut_references_whole_layer(
        name, gdn_layer):
    """The layer holding all 6 heads, told so (`heads_held` 6), under a
    `model` axis of 2: each shard computes 3 heads' part, one psum completes
    W_o's sum and the whole-width QK-norm's sum of squares — and the result
    is the reference's layer on the uncut leaves. One share alone, no axis and
    no exchange, is the reference given that share (`ref.head_share` cuts the
    leaves: either half of the heads, the program being the same for both), and
    is not the whole layer. With `heads_held` 0 under the same axis the layer
    is replicated: the whole layer again, without a psum."""
    cfg = config_from_args(build_parser().parse_args(cli_argv(dict(ARCH, heads_held=6))))
    dc = cfg.model.decoder
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 2, 1), devices=jax.devices()[:2])
    flat = seeded(ARCH)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 64, ARCH["hidden_size"]))   # one chunk

    def own(leaves):
        return program_tree({k.split("/", 1)[1]: v for k, v in leaves.items()
                             if k.startswith(name + "/")})

    def layer(mesh_, axis, c=dc):
        return decoder_lm.DecoderLayer(c, False, None, jnp.float32, mesh_, axis, 1024,
                                       False, "gdn" if gdn_layer else "attn")

    sharded = jax.jit(lambda p, x_: layer(mesh, "model").apply({"params": p}, x_)[0])
    with jax.default_matmul_precision("highest"):
        want = ref.layer_for(ARCH, lambda y: y)(flat, x, name, gdn_layer)
        np.testing.assert_allclose(sharded(own(flat), x), want, rtol=2e-4, atol=2e-4)
        for first in (0, 3):
            cut, cut_arch = ref.head_share(flat, ARCH, first, 3)
            c = dataclasses.replace(dc, heads_held=3)
            got = jax.jit(lambda p: layer(None, None, c).apply({"params": p}, x)[0])(own(cut))
            np.testing.assert_allclose(
                got, ref.layer_for(cut_arch, lambda y: y)(cut, x, name, gdn_layer),
                rtol=2e-4, atol=2e-4)
            assert float(jnp.abs(got - want).max()) > 1e-2
    # W_o's partial sums; and the sums of q's and of k's squares
    text = sharded.lower(own(flat), x).as_text()
    assert text.count("all_reduce") == (1 if gdn_layer else 3)
    # told of no share (`heads_held` 0) the layer is replicated over the axis
    # as its other leaves are: the same whole layer, and no exchange
    every = dataclasses.replace(dc, heads_held=0)
    replicated = jax.jit(lambda p, x_: layer(mesh, "model", every).apply({"params": p}, x_)[0])
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(replicated(own(flat), x), want, rtol=2e-4, atol=2e-4)
    text = replicated.lower(own(flat), x).as_text()
    assert "all_reduce" not in text and "shard_map" not in text


def test_factory_refuses_what_a_share_of_the_heads_is_not_built_for():
    mla = ("--attention", "mla", "--kv_rank", "16", "--rope_dim", "8", "--qk_norm", "0")
    for extra, match in (
            (("--heads_held", "3", "--gdn_layout", "0", *mla), "mla"),
            (("--heads_held", "3", "--gdn_layout", "0", "--kda_layout", "1,0"), "kda"),
            (("--heads_held", "3", "--gdn_layout", "0", "--conv_layout", "1,0"), "conv"),
            (("--heads_held", "3", "--num_kv_heads", "3"), "whole groups"),
            (("--heads_held", "8"), "whole groups"),
            (("--gdn_key_dim", "0"), "gdn_key_dim"),
            (("--kda_layout", "1,0,0,0"), "same layer"),
            (("--gdn_layout", "2"), "gdn_layout"),
            (("--sandwich_norm", "0"), "no norm at all"),
            (("--qk_norm", "3"), "qk_norm"),
            (("--seq_len", "72"), "chunk")):
        cfg = config_from_args(build_parser().parse_args(cli_argv(ARCH, *extra)))
        with pytest.raises(ValueError, match=match):
            build_model(cfg.model, cfg.data.num_classes)
    # under a mesh axis the held heads divide over it in whole KV groups
    cfg = config_from_args(build_parser().parse_args(cli_argv(dict(ARCH, heads_held=3))))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 2, 1), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="2 shard"):
        build_model(cfg.model, cfg.data.num_classes, mesh=mesh)
    # and the defaults are every earlier decoder's
    dc = decoder_lm.DecoderConfig()
    assert (dc.pre_norm, dc.heads_held, tuple(dc.gdn_layout)) == (1, 0, (0,))
    assert dc.heads == dc.num_heads and dc.kv_heads == dc.num_kv_heads


# (d) the configuration --------------------------------------------------------

def test_analytic_counts_and_the_configurations_own_arithmetic():
    cut, published = CONF["arch"], CONF["published"]

    def count(arch):
        return sum(int(np.prod(s[0])) for s in ref.param_spec(arch).values())

    assert count(cut) == CONF["parameters"] == 766241946
    assert "766,241,946" in CONF["parameters_why"]
    uncut = dict(cut, num_layers=published["num_hidden_layers"], heads_held=0,
                 vocab_size=published["vocab_size"])
    assert count(uncut) == 7430870688 and "7,430,870,688" in published["parameters"]
    # every head and an eighth of the vocabulary: what does not fit
    assert count(dict(cut, heads_held=0)) == 928862196
    catalog = {"model_type": "olmo_hybrid", "hidden_size": 3840, "intermediate_size": 11008,
               "hidden_act": "silu", "max_position_embeddings": 65536,
               "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
               "linear_key_head_dim": 96, "linear_value_head_dim": 192,
               "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
               "rope_parameters": {"rope_theta": None}}
    assert {k: CONF[k] for k in catalog} == catalog
    assert CONF["reduced"] == ["num_hidden_layers", "layer_types", "num_attention_heads",
                               "num_key_value_heads", "linear_num_key_heads",
                               "linear_num_value_heads", "vocab_size"]
    assert [CONF[k] for k in CONF["reduced"]] == [
        4, ["linear_attention"] * 3 + ["full_attention"], 15, 15, 15, 15, 12544]
    assert [published[k] for k in CONF["reduced"]] == [
        32, (["linear_attention"] * 3 + ["full_attention"]) * 8, 30, 30, 30, 30, 100352]
    assert cut["vocab_size"] * 8 == published["vocab_size"]
    assert (cut["hidden_size"], cut["num_heads"], cut["heads_held"], cut["head_dim"],
            cut["gdn_key_dim"], cut["gdn_value_dim"], cut["conv_kernel"],
            cut["dense_width"], cut["gdn_layout"]) == (
        3840, 30, 15, 128, 96, 192, 4, 11008, [1, 1, 1, 0])
    assert cut["head_dim"] * cut["num_heads"] == cut["hidden_size"]
    assert len(CONF["source"]) < 200
    # the step's work
    t = cut["seq_len"]
    assert flops.train_flops_per_image(cut, 0) == 6.0 * flops.forward_macs(cut)
    assert flops.train_flops_per_image(cut, 0) == pytest.approx(36.24e12, rel=1e-3)
    assert flops.score_macs(cut) == 1 * 15 * (128 + 128) * (t * (t + 1) // 2)
    assert flops.attention_flops(cut, 1) == 6.0 * flops.score_macs(cut)
    assert flops.gdn_token_macs(cut) == 44375262 - 30 - 192 - 23040 + 4 * 15 * 384
    assert flops.gdn_core_macs(cut) == 15 * 128 * 4915200
    assert flops.gdn_core_bytes(cut) == 8192 * 15 * ((192 + 384) * 2 + 8)
    bound = flops.gdn_core_bound_s(cut, 1)
    assert bound == 9 * flops.gdn_core_bytes(cut) / 819e9          # the bytes bind
    assert bound > 18 * flops.gdn_core_macs(cut) / 197e12
    assert flops.gdn_core_bound_s(cut, 2) == 2 * bound
    assert flops.gdn_flops(cut, t) == pytest.approx(6.71e12, rel=1e-3)
    # the argv builds the arch
    for conf in (CONF, CONF["rehearse"]):
        dc = config_from_args(build_parser().parse_args(
            conf["argv"] + ["--dataset", "tokens"])).model.decoder
        arch = conf["arch"]
        assert {k: (list(getattr(dc, k)) if isinstance(v, list) else getattr(dc, k))
                for k, v in arch.items()} == arch
        assert (dc.attention, dc.qk_norm, dc.pre_norm, dc.sandwich_norm, dc.dense_layers,
                dc.tied_embeddings, tuple(dc.rope_layout)) == ("gqa", 2, 0, 1, 4, 0, (0,))
        assert kda.chunk_of(dc.seq_len) == kda.CHUNK      # both cross a chunk
        assert (dc.heads, dc.kv_heads) == (arch["heads_held"],) * 2


def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ddp_classification_pytorch_tpu" not in text
    assert "from ddp_classification_pytorch_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


# (e) what a run publishes, and the readers -------------------------------------

def test_the_lowered_step_names_the_three_scopes_and_keeps_the_recurrences_output():
    _, model, loss_fn, _ = program(ARCH, "--remat")
    tokens, targets = batch(ARCH)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    grad = jax.grad(lambda p: loss_fn(p, {}, tokens, targets, None)[0])
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for scope in ("/gdn/", "gdn.in/", "gdn.core/", "gdn.out/", "/attn/", "/ffn/"):
        assert scope in text, scope
    # the name --remat's policy saves, once a Gated DeltaNet layer
    assert str(jax.make_jaxpr(grad)(params)).count("name=gdn_out") >= 3


def test_hybrid_decoder_trains_through_cli_train_and_publishes_what_it_adds(
        tmp_path, capsys):
    arch = dict(ARCH, heads_held=3)
    t = arch["seq_len"]
    ids = (np.arange(8 * (t + 1)) * 7 % 50).astype(np.int32)
    path = tmp_path / "train.bin"
    ids.tofile(path)
    argv = cli_argv(arch, "--train_dir", str(path), "--batchsize", "8", "--epochs",
                    "2", "--lr", "0.003", "--adam_b2", "0.95", "--platform", "cpu",
                    "--out", str(tmp_path / "run"), "--log_every", "1", "--remat")
    train_main(argv)   # Trainer, ShardedLoader, DevicePrefetcher, _build_step
    out = capsys.readouterr().out
    setup = next(ln for ln in out.splitlines() if "[trainer] set-up:" in ln)
    for note in ("gdn_dense=3 gqa_dense=1", "gdn_core=xla", "heads=3/6"):
        assert note in setup, (note, setup)
    with open(tmp_path / "run" / "history.json") as f:
        losses = json.load(f)["loss"]
    assert len(losses) == 2 and all(np.isfinite(losses)) and losses[1] < losses[0]
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    for line in ('decoder_layers_total{ffn="dense",operator="gdn"} 3',
                 'decoder_layers_total{ffn="dense",operator="gqa"} 1',
                 "decoder_heads_held 3"):
        assert line in prom, line
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    for name in ("decoder_heads_held", "`gdn`", "`gdn.in`", "`gdn.core`", "`gdn.out`",
                 "gdn_core=xla", "gdn_core=kernel", "gdn_fwd", "gdn_states", "gdn_bwd",
                 "heads=15/30", "gdn_device_ms", "gdn_core_roofline_pct"):
        assert name in doc, name


def test_the_readers_find_the_scopes_and_say_nothing_where_there_are_none():
    """`gdn_device_ms` = every op anywhere under `gdn` (its three inner
    scopes too), `gdn_core_roofline_pct` = the recurrence's bound over the
    ops under `gdn.core` alone; a program without the scopes (the parent), a
    configuration without the count, or a run without a trace reads None."""
    from benchmark.layers import _scope_members, gdn_core_roofline_pct, gdn_device_ms

    ms = 1_000_000
    lm = "jit(step)/transpose(jvp(DecoderLM.hidden))/jvp(DecoderLM.hidden)/checkpoint/"
    ops = [(lm + "layer0/gdn/layer0._gdn/gdn.in/dot_general", 0, 3 * ms),
           (lm + "layer0/gdn/layer0._gdn/gdn.core/checkpoint/rematted_computation/"
            "while/body/bhcd,bhde->bhce/dot_general", 3 * ms, 20 * ms),
           (lm + "rematted_computation/layer0/gdn/layer0._gdn/gdn.out/dot_general",
            23 * ms, 2 * ms),
           (lm + "layer3/attn/layer3._attention/dot_general", 25 * ms, 5 * ms),
           (lm + "layer1/pre_gdn/mul", 30 * ms, 1 * ms)]     # no whole segment

    def ctx(ops, flops_name="olmo_hybrid_7b"):
        return {_scope_members._KEY: (ops, 1) if ops else None, "batch": 1, "chips": 1,
                "arch": CONF["arch"], "config": {"flops": flops_name},
                "device_kind": "TPU v5 lite"}

    assert gdn_device_ms.read(ctx(ops)) == 25.0
    share = gdn_core_roofline_pct.read(ctx(ops))
    assert share == pytest.approx(100 * flops.gdn_core_bound_s(CONF["arch"], 1) / 20e-3)
    assert 0 < share < 100
    for reader in (gdn_device_ms, gdn_core_roofline_pct):
        assert reader.read(ctx(ops[3:])) is None       # no such scope: the parent
        assert reader.read(ctx(None)) is None          # no trace
    assert gdn_core_roofline_pct.read(ctx(ops, "ling_3_0_flash")) is None
