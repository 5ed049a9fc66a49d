"""The plain SmallThinker reference beside `test_reference.py`'s cases: it
agrees with the program's decoder in float32 on seeded weights (loss and
per-leaf gradient norms, which proves the key-path mapping; `tests/
test_decoder_lm.py` holds every gradient leaf element-wise), a lower
precision lies further from it, it imports nothing from the program, and
the analytic count matches the published "A3B"."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.flops import smallthinker as flops  # noqa: E402
from benchmark.reference import common, smallthinker  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "smallthinker_21b_a3b.json")) as f:
    CONF = json.load(f)
ARCH = CONF["rehearse"]["arch"]


def program_loss(dtype):
    from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args
    from ddp_classification_pytorch_tpu.models.factory import build_model
    from ddp_classification_pytorch_tpu.train.steps import _lm_loss
    from flax.traverse_util import unflatten_dict

    argv = [a for a in CONF["rehearse"]["argv"]]
    argv[argv.index("--dtype") + 1] = dtype
    cfg = config_from_args(build_parser().parse_args(argv + ["--dataset", "tokens"]))
    model = build_model(cfg.model, cfg.data.num_classes)
    loss_fn, _ = _lm_loss(cfg, model)

    def loss(flat, tokens, targets):
        params = unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})
        return loss_fn(params, {}, tokens, targets, None)[0]

    return loss


def batch(rows=2, seed=1):
    ids = np.random.default_rng(seed).integers(
        0, ARCH["vocab_size"], (rows, ARCH["seq_len"] + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(ids[:, 1:], jnp.int32)


def test_reference_agrees_with_the_program_in_float32_and_bf16_lies_further():
    flat = common.make_params(smallthinker.param_spec(ARCH), 5)
    tokens, targets = batch()
    want, want_g = jax.jit(jax.value_and_grad(smallthinker.loss_for(ARCH)))(
        flat, tokens, targets)
    got, got_g = jax.jit(jax.value_and_grad(program_loss("float32")))(
        flat, tokens, targets)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    norms = lambda g: {k: float(v) for k, v in common.leaf_norms(g).items()}  # noqa: E731
    gap, leaf = common.worst_leaf_gap(norms(got_g), norms(want_g))
    assert gap < 1e-4, (gap, leaf)
    # the stated precision (bf16 compute) differs by more than float32 does,
    # the fp8 control by more again
    f32 = common.difference_gap(got_g, want_g)
    lower = {}
    for precision in ("bfloat16", "fp8"):
        g = jax.jit(jax.grad(smallthinker.loss_for(ARCH, precision)))(
            flat, tokens, targets)
        lower[precision] = common.difference_gap(g, want_g)
    assert f32 < 1e-4 < lower["bfloat16"] < lower["fp8"], (f32, lower)


def test_reference_imports_nothing_from_the_program():
    with open(smallthinker.__file__) as f:
        text = f.read()
    assert "ddp_classification_pytorch_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_analytic_count_matches_the_published_a3b():
    cut = CONF["arch"]
    uncut = dict(cut, num_layers=CONF["published"]["num_hidden_layers"],
                 experts_held=CONF["published"]["moe_num_primary_experts"],
                 vocab_size=CONF["published"]["vocab_size"])
    # "21B-A3B": about 3.0 B multiply-accumulates a token outside the score
    # terms (the layers' 2.94 B; the head adds 0.39 B)
    assert abs(flops.token_macs(uncut, with_head=False) / 1e9 - 3.0) < 0.1
    assert abs(flops.token_macs(uncut) / 1e9 - 3.33) < 0.05
    # the cut, a row of 8,192 tokens: 313 M MAC forward a token, 30.7 TFLOP a step
    per_token = flops.forward_macs(cut) / cut["seq_len"]
    assert abs(per_token / 1e6 - 312.6) < 0.5
    assert flops.train_flops_per_image(cut, 224) == 6.0 * flops.forward_macs(cut)
    assert abs(2 * flops.train_flops_per_image(cut) / 1e12 - 30.7) < 0.1
    # band areas, counted exactly
    assert flops.band_area(8, 0) == 36 and flops.band_area(8, 8) == 36
    assert flops.band_area(8, 3) == sum(min(i + 1, 3) for i in range(8))
    # the configuration's own arithmetic
    spec = smallthinker.param_spec(cut)
    assert sum(int(np.prod(s[0])) for s in spec.values()) == CONF["parameters"] == 656529920
    published = {k: v for k, v in CONF.items() if k in (
        "head_dim", "hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
        "num_key_value_heads", "moe_num_active_primary_experts", "sliding_window_size")}
    assert published == {"head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
                         "num_attention_heads": 28, "num_key_value_heads": 4,
                         "moe_num_active_primary_experts": 6, "sliding_window_size": 4096}
    assert (cut["hidden_size"], cut["head_dim"], cut["expert_width"], cut["num_experts"],
            cut["top_k"], cut["window"]) == (2560, 128, 768, 64, 6, 4096)
