"""The looped decoder (models/decoder_lm.py as Ouro-2.6B configures it: the
stack walked `loops` times with the same leaves, a norm on each sub-layer's
output, an exit gate, a loss weighted over the passes) against its plain
reference (benchmark/reference/ouro_2_6b.py, imported as it stands: it takes
nothing from the program), the sharing of the weights against an unrolled
walk over copies of them, the exit distribution's edges, the gradient that the
head's `weights` carry, what evaluation reads, and what a run publishes. CPU,
toy sizes."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.flops import ouro_2_6b as flops  # noqa: E402
from benchmark.reference import common, ouro_2_6b as ref  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.models import decoder_lm  # noqa: E402
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops.lm_head import blocked_cross_entropy  # noqa: E402
from ddp_classification_pytorch_tpu.train.state import TrainState  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import (  # noqa: E402
    _lm_loss,
    make_eval_step,
)
from test_decoder_lm import batch, flat_tree, program_tree  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "ouro_2_6b.json")) as f:
    CONF = json.load(f)

ARCH = {"vocab_size": 96, "hidden_size": 32, "num_layers": 2, "num_heads": 4,
        "num_kv_heads": 4, "head_dim": 8, "dense_width": 48, "rope_theta": 1e6,
        "rms_eps": 1e-6, "loops": 3, "exit_beta": 0.05, "seq_len": 32}
KINDS = ["--attention", "gqa", "--rope_pairing", "half", "--activation", "silu",
         "--rope_layout", "1", "--window_layout", "0", "--tied_embeddings", "0"]


def cli_argv(arch, *extra, dtype="float32", sandwich=1):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens", "--dtype",
            dtype, "--optimizer", "adam", "--head_block", "16",
            "--dense_layers", str(arch["num_layers"]),
            "--sandwich_norm", str(sandwich), *KINDS]
    for key, value in arch.items():
        argv += [f"--{key}", str(value)]
    return argv + list(extra)


def program(arch, *extra, **kinds):
    cfg = config_from_args(build_parser().parse_args(cli_argv(arch, *extra, **kinds)))
    model = build_model(cfg.model, cfg.data.num_classes)
    return (cfg, model, *_lm_loss(cfg, model))


def seeded(arch=ARCH, seed=3, gate_bias=None):
    flat = common.make_params(ref.param_spec(arch), seed)
    if gate_bias is not None:
        flat["exit_gate/bias"] = jnp.full((1,), gate_bias, jnp.float32)
    return flat


@functools.lru_cache(maxsize=None)
def step_metrics(gate_bias=None):
    """(loss, the step's metrics) of the program at ARCH on the seeded
    weights, the gate's bias as given."""
    _, _, loss_fn, metrics_fn = program(ARCH)
    tokens, targets = batch(ARCH)
    loss, (_, aux) = jax.jit(loss_fn)(program_tree(seeded(gate_bias=gate_bias)),
                                      {}, tokens, targets, None)
    return float(loss), {k: np.asarray(v) for k, v in
                         metrics_fn(loss, aux, targets).items()}


# (a) the loop off is today's program ----------------------------------------

def test_one_pass_without_the_sandwich_is_the_plain_decoder():
    arch = dict(ARCH, loops=1)
    cfg, model, loss_fn, metrics_fn = program(arch, sandwich=0)
    tokens, targets = batch(arch)
    params = model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    names = set(flat_tree(params))
    assert not [n for n in names if "exit_gate" in n or "_out/" in n], names
    assert len(names) == 1 + 2 * 9 + 2      # the table, 9 leaves a layer, norm, head
    hidden, load = model.apply({"params": params}, tokens, method="hidden")
    assert hidden.shape == (*tokens.shape, arch["hidden_size"]) and load.shape[0] == 0
    loss, (_, aux) = loss_fn(params, {}, tokens, targets, None)
    logits = model.apply({"params": params}, tokens)
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert set(metrics_fn(loss, aux, targets)) == {"loss", "top1", "top3", "moe_load"}
    assert decoder_lm.DecoderConfig().loops == 1    # and it is the default
    assert decoder_lm.DecoderConfig().sandwich_norm == 0


@pytest.mark.parametrize("flag,value", [("--mtp_layers", "1"), ("--dense_layers", "1"),
                                        ("--loops", "0")])
def test_a_looped_stack_is_dense_and_has_no_prediction_module(flag, value):
    extra = ([flag, value, "--expert_width", "16", "--num_experts", "4", "--top_k", "2"]
             if flag != "--loops" else [flag, value])
    with pytest.raises(ValueError, match="--loops"):
        program(ARCH, *extra)


# (b) the same leaves at every pass ------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_looped_gradient_is_the_sum_over_the_copies_of_an_unrolled_walk(remat):
    """R copies of the stack's leaves walked one after the other, the final
    norm between them, give each copy a gradient; the looped model's gradient
    of a shared leaf is their sum."""
    cfg, model, _, _ = program(ARCH, *(["--remat"] if remat else []))
    flat = seeded()
    params = program_tree(flat)
    tokens, _ = batch(ARCH)
    shared = {k: v for k, v in params.items() if k.startswith(("layer", "norm_final"))}
    probe = jax.random.normal(jax.random.PRNGKey(1), (ARCH["loops"], *tokens.shape,
                                                      ARCH["hidden_size"]))

    def looped(stack):
        states, _ = model.apply({"params": {**params, **stack}}, tokens, method="hidden")
        return jnp.sum(states * probe)

    def unrolled(copies):
        x = params["embed"]["embedding"][tokens]
        total = 0.0
        for t, stack in enumerate(copies):
            x, _ = model.apply({"params": {**params, **stack}}, x, None,
                               method=decoder_lm._one_pass)
            total = total + jnp.sum(x * probe[t])
        return total

    got = flat_tree(jax.jit(jax.grad(looped))(shared))
    per_copy = jax.jit(jax.grad(unrolled))([shared] * ARCH["loops"])
    want = flat_tree(jax.tree_util.tree_map(lambda *g: sum(g), *per_copy))
    assert set(got) == set(want) and len(got) == 2 * 11 + 1
    for name, g in want.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 1e-4 * scale, name
    # no copy's gradient alone is the sum: the passes all count
    first = flat_tree(per_copy[0])
    assert float(jnp.abs(first["layer0/q/kernel"] - want["layer0/q/kernel"]).max()) > 0


# (c) the exit distribution --------------------------------------------------

def test_exit_distribution_sums_to_one_and_is_the_references():
    flat = seeded()
    states = jax.random.normal(jax.random.PRNGKey(2), (4, 2, 8, ARCH["hidden_size"]))
    p = decoder_lm.exit_distribution(program_tree(flat)["exit_gate"], states)
    assert p.shape == (4, 2, 8) and float(p.min()) > 0
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, ref.exit_distribution(flat, states), atol=1e-6)
    assert float(p.std()) > 0.05     # the seeded gate spreads the weight


@pytest.mark.parametrize("bias,which", [(-1e4, ARCH["loops"]), (1e4, 1)],
                         ids=["never_exits", "exits_at_once"])
def test_a_saturated_gate_gives_one_pass_its_whole_weight(bias, which):
    loss, m = step_metrics(bias)
    np.testing.assert_allclose(loss, m[f"loss_ut{which}"], rtol=1e-6)   # H = 0
    for t in range(1, ARCH["loops"] + 1):
        assert m[f"exit_p{t}"] == (1.0 if t == which else 0.0)


def test_the_objective_is_the_weighted_passes_less_the_entropy():
    loss, m = step_metrics()
    flat, (tokens, targets) = seeded(), batch(ARCH)
    want, ce, p = jax.jit(ref.loss_parts_for(ARCH))(flat, tokens, targets)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for t in range(ARCH["loops"]):
        np.testing.assert_allclose(m[f"loss_ut{t + 1}"], ce[t], rtol=1e-5)
        np.testing.assert_allclose(m[f"exit_p{t + 1}"], p[t], rtol=1e-5)
    assert abs(sum(m[f"exit_p{t + 1}"] for t in range(ARCH["loops"])) - 1) < 1e-6
    # not the last pass's loss, and not the plain mean of the passes
    assert abs(loss - m[f"loss_ut{ARCH['loops']}"]) > 1e-4


# (d) the weights carry a gradient through the head --------------------------

def test_weights_in_columns_are_so_many_calls_and_carry_a_gradient():
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    h = jax.random.normal(k[0], (64, 16))
    kernel = jax.random.normal(k[1], (16, 40))
    targets = jax.random.randint(k[2], (64,), 0, 40)
    w = jax.random.uniform(k[3], (64, 3))
    both = blocked_cross_entropy(h, kernel, targets, 16, jnp.float32, weights=w)
    for i in range(3):
        one = blocked_cross_entropy(h, kernel, targets, 16, jnp.float32, weights=w[:, i])
        for a, b in zip(both, one):
            np.testing.assert_allclose(a[i], b, rtol=1e-5)
    # d(sum of w x ce)/dw is the row's cross-entropy
    grad = jax.grad(lambda w_: blocked_cross_entropy(
        h, kernel, targets, 16, jnp.float32, weights=w_)[0][0])(w)
    logp = jax.nn.log_softmax(h @ kernel, -1)
    ce = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
    np.testing.assert_allclose(grad[:, 0], ce, rtol=1e-4)
    assert float(jnp.abs(grad[:, 1:]).max()) == 0.0


def test_the_gates_gradient_is_not_zero_and_matches_finite_differences():
    _, _, loss_fn, _ = program(ARCH)
    flat, (tokens, targets) = seeded(), batch(ARCH)

    @jax.jit
    def loss_at(bias, scale):
        f = dict(flat)
        f["exit_gate/bias"] = flat["exit_gate/bias"] + bias
        f["exit_gate/kernel"] = flat["exit_gate/kernel"] * scale
        return loss_fn(program_tree(f), {}, tokens, targets, None)[0]

    for arg, at in ((0, (0.0, 1.0)), (1, (0.0, 1.0))):
        g = float(jax.grad(loss_at, argnums=arg)(*map(jnp.float32, at)))
        eps = 1e-2
        hi = [x + eps * (i == arg) for i, x in enumerate(at)]
        lo = [x - eps * (i == arg) for i, x in enumerate(at)]
        fd = (float(loss_at(*map(jnp.float32, hi)))
              - float(loss_at(*map(jnp.float32, lo)))) / (2 * eps)
        assert abs(g) > 1e-4, (arg, g)
        assert abs(g - fd) < 0.03 * abs(g) + 2e-5, (arg, g, fd)


# (e) the program against the plain reference --------------------------------

@pytest.mark.parametrize("extra", [(), ("--remat",),
                                   ("--remat", "--flash_min_tokens", "0")],
                         ids=["plain", "remat", "remat_flash_kernels"])
def test_program_matches_the_plain_reference_loss_and_every_gradient(extra):
    arch = ARCH
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
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = flat_tree(grads)
    assert set(got) == set(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 2e-4 * scale, name
        assert float(jnp.abs(g).max()) > 0, name     # the gate's two among them


def test_bf16_program_lies_further_from_the_reference_and_fp8_further_still():
    flat, (tokens, targets) = seeded(seed=5), batch(ARCH, seed=1)
    want = float(jax.jit(ref.loss_for(ARCH))(flat, tokens, targets))
    _, _, loss_fn, _ = program(ARCH, dtype="bfloat16")
    got = float(jax.jit(loss_fn)(program_tree(flat), {}, tokens, targets, None)[0])
    bf16 = float(jax.jit(ref.loss_for(ARCH, "bfloat16"))(flat, tokens, targets))
    fp8 = float(jax.jit(ref.loss_for(ARCH, "fp8"))(flat, tokens, targets))
    assert 1e-7 < abs(got - want) / want < 5e-3
    assert abs(bf16 - want) / want < 5e-3
    assert abs(fp8 - want) > 2 * max(abs(got - want), abs(bf16 - want))


# (f) evaluation reads the last pass -----------------------------------------

def test_eval_step_reads_the_last_pass():
    cfg, model, _, _ = program(ARCH)
    _, m = step_metrics()
    tokens, targets = batch(ARCH)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=program_tree(seeded()),
                       batch_stats={}, opt_state=())
    out = make_eval_step(cfg, model)(state, tokens, targets,
                                     jnp.ones((tokens.shape[0],), jnp.float32))
    last = ARCH["loops"]
    np.testing.assert_allclose(out["loss_sum"] / out["n"], m[f"loss_ut{last}"], rtol=1e-5)
    np.testing.assert_allclose(out["top1"] / out["n"], m["top1"], rtol=1e-6)
    np.testing.assert_allclose(out["top3"] / out["n"], m["top3"], rtol=1e-6)
    assert abs(float(out["loss_sum"] / out["n"]) - m["loss_ut1"]) > 1e-4
    logits = model.apply({"params": state.params}, tokens)    # what is served
    hits = jnp.sum(jnp.argmax(logits, -1) == targets)
    assert int(hits) == int(out["top1"])


# (g) what a run publishes ---------------------------------------------------

def test_the_lowered_step_names_loop_and_exit_and_holds_the_stack_once():
    _, model, loss_fn, _ = program(ARCH, "--remat")
    tokens, targets = batch(ARCH)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, tokens, targets, None)[0])) \
        .lower(params).as_text(debug_info=True)
    for scope in ("/loop/while/body", "jvp(exit)/lm_head/", "transpose(jvp(exit))/"):
        assert scope in text, scope
    assert decoder_lm.LOOP_TRACED == "scan"
    # one body for the passes: a layer's nine matmuls stand in the program
    # once forward, once recomputed and twice backward, not once a pass more
    layers = ARCH["num_layers"]
    assert 4 * 9 * layers <= text.count("stablehlo.dot_general") < 4 * 9 * layers + 24


def test_flops_count_every_pass_and_the_files_numbers():
    arch = CONF["arch"]
    assert flops.applications(arch) == 16
    assert flops.train_flops_per_image(arch) == pytest.approx(73.4e12, rel=2e-3)
    assert flops.loop_flops(arch, 1) + flops.exit_head_flops(arch, 1) \
        == flops.train_flops_per_image(arch)
    assert flops.exit_head_flops(arch, 1) == pytest.approx(19.8e12, rel=2e-3)
    assert flops.attention_flops(arch, 1) == pytest.approx(13.2e12, rel=2e-3)
    one = dict(arch, loops=1)
    assert flops.forward_macs(arch) == 4 * flops.forward_macs(one)
    assert CONF["num_hidden_layers"] == arch["num_layers"] == len(CONF["layer_types"])
    assert CONF["total_ut_steps"] == arch["loops"] == 4
    assert (CONF["hidden_size"], CONF["intermediate_size"], CONF["vocab_size"],
            CONF["num_attention_heads"], CONF["num_key_value_heads"], CONF["head_dim"]) \
        == (2048, 5632, 49152, 16, 16, 128)
    spec = ref.param_spec(arch)
    assert sum(int(np.prod(s[0])) for s in spec.values()) == CONF["parameters"] == 406884353


def test_looped_decoder_trains_through_cli_train_and_publishes_what_it_adds(
        tmp_path, capsys):
    t = ARCH["seq_len"]
    ids = (np.arange(8 * (t + 1)) * 7 % 50).astype(np.int32)
    path = tmp_path / "train.bin"
    ids.tofile(path)
    argv = cli_argv(ARCH, "--train_dir", str(path), "--batchsize", "8", "--epochs",
                    "2", "--lr", "0.003", "--adam_b2", "0.95", "--platform", "cpu",
                    "--out", str(tmp_path / "run"), "--log_every", "1", "--remat")
    train_main(argv)   # Trainer, ShardedLoader, DevicePrefetcher, _build_step
    out = capsys.readouterr().out
    setup = next(ln for ln in out.splitlines() if "[trainer] set-up:" in ln)
    assert "gqa_dense=2 loops=3 sandwich=1 passes=scan" in setup
    with open(tmp_path / "run" / "history.json") as f:
        history = json.load(f)
    assert len(history["loss"]) == 2 and history["loss"][1] < history["loss"][0]
    for i in range(2):
        parts = [(history[f"exit_p{t}"][i], history[f"loss_ut{t}"][i]) for t in (1, 2, 3)]
        assert abs(sum(p for p, _ in parts) - 1) < 1e-5
        # the entropy term: the objective lies under the weighted passes by
        # at most beta x log R
        upper = sum(p * ce for p, ce in parts)
        assert upper - 0.05 * np.log(3) - 0.05 < history["loss"][i] < upper + 0.05
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    for name in ("decoder_layer_applications_total 6",
                 'decoder_layers_total{ffn="dense",operator="gqa"} 2',
                 "train_loss_ut1", "train_loss_ut3", "train_exit_p1", "train_exit_p3"):
        assert name in prom, name
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    for name in ("decoder_layer_applications_total", "loss_ut", "exit_p", "`loop`",
                 "`exit`", "passes=scan", "loops="):
        assert name in doc, name
