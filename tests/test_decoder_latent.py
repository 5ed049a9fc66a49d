"""The decoder's second kind of layer (models/decoder_lm.py as JoyAI-LLM-Flash
configures it: latent attention, a sigmoid bias-corrected router beside a
shared expert, a leading dense layer, a multi-token-prediction module) against
its plain reference (benchmark/reference/joyai_llm_flash.py, imported as it
stands: it takes nothing from the program), the reference against published
modelling code, the expert share, the flash kernels' two-part scores, the
step's counters, and the first decoder's program, which must not have moved.
CPU, toy sizes."""

import functools
import hashlib
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.flops import joyai_llm_flash as flops  # noqa: E402
from benchmark.reference import common, joyai_llm_flash as ref  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops.attention import attention  # noqa: E402
from ddp_classification_pytorch_tpu.ops.moe import sparse_moe  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import _lm_loss  # noqa: E402
from test_decoder_lm import batch, flat_tree, program_tree  # noqa: E402

# ops/__init__ re-exports a function named like the module
fa = importlib.import_module("ddp_classification_pytorch_tpu.ops.flash_attention")

with open(os.path.join(ROOT, "benchmark", "configs", "joyai_llm_flash.json")) as f:
    CONF = json.load(f)

# experts 4-11 of 16 held: a share that starts in the middle of the router
ARCH = {"vocab_size": 96, "hidden_size": 32, "num_layers": 2, "num_heads": 4,
        "head_dim": 16, "rope_dim": 8, "v_head_dim": 16, "q_rank": 24,
        "kv_rank": 16, "dense_layers": 1, "dense_width": 48, "expert_width": 16,
        "num_experts": 16, "experts_held": 8, "first_expert": 4, "top_k": 4,
        "shared_experts": 1, "router_scale": 2.5, "rope_theta": 32e6,
        "rms_eps": 1e-6, "mtp_layers": 1, "mtp_weight": 0.3, "seq_len": 32}
KINDS = ["--attention", "mla", "--rope_pairing", "interleaved", "--activation",
         "silu", "--router", "sigmoid", "--router_tap", "post", "--rope_layout",
         "1", "--window_layout", "0"]


def cli_config(arch, *extra, dtype="float32"):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens", "--dtype",
            dtype, "--optimizer", "adam", "--head_block", "16", *KINDS]
    for key, value in arch.items():
        argv += [f"--{key}", str(value)]
    return config_from_args(build_parser().parse_args(argv + list(extra)))


@functools.lru_cache(maxsize=None)
def reference_grad():
    """The plain reference's loss and gradients at ARCH, compiled ONCE for
    the three tests that hold a program against it."""
    return jax.jit(jax.value_and_grad(ref.loss_for(ARCH)))


def program(arch, *extra, dtype="float32"):
    cfg = cli_config(arch, *extra, dtype=dtype)
    model = build_model(cfg.model, cfg.data.num_classes)
    loss_fn, metrics_fn = _lm_loss(cfg, model)
    return model, loss_fn, metrics_fn


# (a) ----------------------------------------------------------------------

@pytest.mark.parametrize("extra", [(), ("--flash_min_tokens", "0")],
                         ids=["dense_op", "flash_kernels"])
def test_program_matches_the_plain_reference_loss_and_every_gradient(extra):
    model, loss_fn, metrics_fn = program(ARCH, "--remat", *extra)
    flat = common.make_params(ref.param_spec(ARCH), 3)
    assert float(jnp.abs(flat["layer1/router_bias"]).max()) > 0.05  # seeded non-zero
    tokens, targets = batch(ARCH)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :8], train=False))["params"]
    assert ({k: v.shape for k, v in flat_tree(init).items()}
            == {k: v.shape for k, v in flat.items()})
    (loss, (_, aux)), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        program_tree(flat), {}, tokens, targets, None)
    want, want_grads = reference_grad()(flat, tokens, targets)
    # float32 against float32: what is left is the order of the sums (the
    # kernels' tiles, the sorted slots): 1e-5 of the loss, 2e-4 of a leaf's
    # largest entry; a bf16 program lies a hundred times further (below)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = flat_tree(grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 2e-4 * scale, name
    # the bias steers the choice and nothing else: no gradient at all
    for name in got:
        if name.endswith("router_bias"):
            assert float(jnp.abs(got[name]).max()) == 0.0, name
    # the step's two losses are the reference's two
    metrics = metrics_fn(loss, aux, targets)
    main, mtp = jax.jit(ref.loss_parts_for(ARCH))(flat, tokens, targets)
    np.testing.assert_allclose(metrics["loss_main"], main, rtol=1e-5)
    np.testing.assert_allclose(metrics["loss_mtp"], mtp, rtol=1e-5)
    np.testing.assert_allclose(
        metrics["loss"], main + ARCH["mtp_weight"] * mtp, rtol=1e-5)
    # one row of loads a routing layer, the module's last; no slot twice
    load = metrics["moe_load"]
    assert load.shape == (2, ARCH["experts_held"])
    assert 0 < int(load.sum()) <= tokens.size * ARCH["top_k"] * 2


def test_bf16_program_lies_further_from_the_reference_and_fp8_further_still():
    """The tolerances of the float32 test fail a bf16-for-f32 swap (the bf16
    program lies a hundred times further from the reference), and the fp8
    control lies further again: an fp8-for-bf16 swap shows."""
    flat = common.make_params(ref.param_spec(ARCH), 5)
    tokens, targets = batch(ARCH, seed=1)
    want, want_g = reference_grad()(flat, tokens, targets)
    _, loss_fn, _ = program(ARCH, dtype="bfloat16")
    got, g = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {}, tokens, targets, None)[0]))(program_tree(flat))
    bf16 = common.difference_gap(flat_tree(g), want_g)
    fp8 = common.difference_gap(
        jax.jit(jax.grad(ref.loss_for(ARCH, "fp8")))(flat, tokens, targets), want_g)
    assert abs(float(got) - float(want)) > 1e-5 * abs(float(want))
    assert 1e-3 < bf16 < fp8, (bf16, fp8)


# (b) ----------------------------------------------------------------------

def test_reference_forward_matches_the_published_deepseek_v3_code():
    """The reference's main forward (no prediction module: the published
    modelling code has none; all 8 of 8 toy experts held) against
    `transformers`' DeepseekV3 model with the same weights copied in."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.deepseek_v3 import (
            configuration_deepseek_v3 as hf_conf, modeling_deepseek_v3 as hf)
    except Exception as e:  # noqa: BLE001 — whatever stops the import
        pytest.skip(f"transformers' deepseek_v3 cannot be imported: {e}")
    arch = dict(ARCH, num_experts=8, experts_held=8, first_expert=0, mtp_layers=0)
    flat = common.make_params(ref.param_spec(arch), 7)
    tokens, _ = batch(arch, seed=2)
    want = np.asarray(jax.jit(ref.logits_for(arch))(flat, tokens))

    config = hf_conf.DeepseekV3Config(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["dense_width"],
        moe_intermediate_size=arch["expert_width"],
        num_hidden_layers=arch["num_layers"], num_attention_heads=arch["num_heads"],
        num_key_value_heads=arch["num_heads"], n_shared_experts=1,
        n_routed_experts=8, routed_scaling_factor=2.5, kv_lora_rank=arch["kv_rank"],
        q_lora_rank=arch["q_rank"], qk_rope_head_dim=arch["rope_dim"],
        v_head_dim=arch["v_head_dim"], qk_nope_head_dim=arch["head_dim"],
        n_group=1, topk_group=1, num_experts_per_tok=arch["top_k"],
        first_k_dense_replace=1, norm_topk_prob=True, hidden_act="silu",
        max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=32e6,
        rope_scaling=None, rope_interleave=True, attention_bias=False,
        attention_dropout=0.0, tie_word_embeddings=False,
        attn_implementation="eager")
    model = hf.DeepseekV3ForCausalLM(config).to(torch.float32).eval()

    def t(name):  # a (in, out) kernel as torch's (out, in) weight
        return torch.from_numpy(np.asarray(flat[name]).T.copy())

    def v(name):
        return torch.from_numpy(np.asarray(flat[name]).copy())

    state = {"model.embed_tokens.weight": v("embed/embedding"),
             "model.norm.weight": v("norm_final/scale"),
             "lm_head.weight": t("lm_head/kernel")}
    for i in range(arch["num_layers"]):
        a, b = f"model.layers.{i}", f"layer{i}"
        state[f"{a}.input_layernorm.weight"] = v(f"{b}/norm_in/scale")
        state[f"{a}.post_attention_layernorm.weight"] = v(f"{b}/norm_post/scale")
        for theirs, ours in (("q_a_proj", "q_a"), ("q_b_proj", "q_b"),
                             ("kv_a_proj_with_mqa", "kv_a"), ("kv_b_proj", "kv_b"),
                             ("o_proj", "o")):
            state[f"{a}.self_attn.{theirs}.weight"] = t(f"{b}/{ours}/kernel")
        state[f"{a}.self_attn.q_a_layernorm.weight"] = v(f"{b}/q_norm/scale")
        state[f"{a}.self_attn.kv_a_layernorm.weight"] = v(f"{b}/kv_norm/scale")
        if i < arch["dense_layers"]:
            for part in ("gate", "up", "down"):
                state[f"{a}.mlp.{part}_proj.weight"] = t(f"{b}/ffn_{part}/kernel")
            continue
        state[f"{a}.mlp.gate.weight"] = t(f"{b}/router")
        state[f"{a}.mlp.gate.e_score_correction_bias"] = v(f"{b}/router_bias")
        for part in ("gate", "up", "down"):
            state[f"{a}.mlp.shared_experts.{part}_proj.weight"] = t(
                f"{b}/shared_{part}/kernel")
            for e in range(8):
                state[f"{a}.mlp.experts.{e}.{part}_proj.weight"] = torch.from_numpy(
                    np.asarray(flat[f"{b}/w_{part}"][e]).T.copy())
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), \
        (missing, unexpected)
    with torch.no_grad():
        got = model(torch.from_numpy(np.array(tokens)).long()).logits.numpy()
    # float32 on both sides: 2e-4 of the logits' scale (about 1);
    # a wrong rotary pairing, scale or gate reads 1e-2 and more
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# (c) ----------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    n, c, width, experts, held = 64, 16, 8, 16, 4
    u = jax.random.normal(ks[0], (n, c))
    logits = jax.random.normal(ks[1], (n, experts))
    bias = 0.3 * jax.random.normal(ks[2], (experts,))
    w = (jax.random.normal(ks[3], (experts, c, width)),
         jax.random.normal(ks[4], (experts, c, width)),
         jax.random.normal(ks[5], (experts, width, c)))
    route = dict(scoring="sigmoid", bias=bias, scale=2.5)
    arch = {"top_k": 3, "router_scale": 2.5, "first_expert": 0}
    idx, weight = ref.route(logits, bias, arch)
    identity = lambda x: x  # noqa: E731
    uncut = ref.held_experts(u, idx, weight, *w, arch, identity)
    shared = ref.gated_mlp(u, w[0][0], w[1][0], w[2][0], identity)
    whole, load = sparse_moe(u, logits, *w, top_k=3, dtype=jnp.float32,
                             activation="silu", route=route)
    assert int(load.sum()) == n * 3
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=1e-4)
    # every group of `held` experts gives its part at the router's full
    # width; what every chip computes alike, the shared expert, counts once
    parts = [sparse_moe(u, logits, *(b[held * s:held * (s + 1)] for b in w),
                        top_k=3, first_expert=held * s, dtype=jnp.float32,
                        activation="silu", route=route)
             for s in range(experts // held)]
    np.testing.assert_allclose(sum(p for p, _ in parts) + shared, uncut + shared,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(jnp.concatenate([l for _, l in parts]), load)
    # the bias moves the choice (not the weights): without it other slots
    other, _ = sparse_moe(u, logits, *w, top_k=3, dtype=jnp.float32,
                          activation="silu", route=dict(route, bias=None))
    assert float(jnp.abs(other - whole).max()) > 1e-2
    # the same function under a `model` axis of 4: banks sharded, one psum
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(meshlib.MeshSpec(2, 4, 1))
    sharded, sharded_load = jax.jit(lambda *a: sparse_moe(
        *a, top_k=3, dtype=jnp.float32, activation="silu", route=route, mesh=mesh,
        axis="model", batch_axis="data"))(u, logits, *w)
    np.testing.assert_allclose(sharded, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sharded_load, load)


# (d) ----------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads,d,dr,dv,window", [
    (4, 4, 16, 8, 16, None),     # the published shape in small: 24 / 16
    (4, 2, 16, 8, 8, 50),        # grouped KV heads under one rotary head, a band
    (2, 2, 128, 64, 128, None),  # lane-sized: the published 128 + 64 / 128
], ids=["score24_value16", "gqa_window_value8", "lane_sized"])
def test_flash_kernels_two_part_scores_match_the_dense_op(heads, kv_heads, d, dr, dv,
                                                          window, monkeypatch):
    monkeypatch.setattr(fa, "_block", lambda t, cap=1024: 32)  # 4 x 4 tiles
    t = 128
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    args = (jax.random.normal(ks[0], (2, t, heads, d)),
            jax.random.normal(ks[1], (2, t, kv_heads, d)),
            jax.random.normal(ks[2], (2, t, kv_heads, dv)),
            jax.random.normal(ks[3], (2, t, heads, dr)),
            jax.random.normal(ks[4], (2, t, 1, dr)))   # ONE rotary key head
    cot = jax.random.normal(ks[5], (2, t, heads, dv))

    def both(fn):
        out, vjp = jax.vjp(lambda q, k, v, qr, kr: fn(
            q, k, v, causal=True, window=window, q_rope=qr, k_rope=kr), *args)
        return (out,) + vjp(cot)

    got, want = both(fa.flash_attention), both(attention)
    assert got[0].shape == (2, t, heads, dv) and got[5].shape == (2, t, 1, dr)
    # float32 tiles against the float32 (T, T) op: the order of the sums
    tol = 2e-5 if d < 128 else 2e-4
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
    # the dense op joins the parts; written out: one softmax over both terms
    q, k, v, qr, kr = args
    s = (jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, heads // kv_heads, axis=2))
         + jnp.einsum("bqhd,bkd->bhqk", qr, kr[:, :, 0])) / np.sqrt(d + dr)
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (cols <= rows) & ((cols > rows - window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    plain = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, heads // kv_heads, axis=2))
    np.testing.assert_allclose(want[0], plain, rtol=tol, atol=tol)


def test_flash_attention_refuses_parts_that_do_not_go_together():
    q = jnp.zeros((1, 128, 4, 16))
    with pytest.raises(ValueError, match="come together"):
        fa.flash_attention(q, q, q, causal=True, q_rope=q[..., :8])
    with pytest.raises(ValueError, match="do not go with"):
        fa.flash_attention(q, q, q, causal=True, q_rope=q[..., :8],
                           k_rope=jnp.zeros((1, 128, 3, 8)))


# (f) ----------------------------------------------------------------------

@pytest.mark.parametrize("extra,want", [
    ((), "2db75f41754dcf9b660717243bc58cf02cc15a287de6284447b17a837c89910a"),
    (("--flash_min_tokens", "0"),
     "9af5b40da91b482e42c0bada90420ab38d6501347b31ef6ed787cf24cc28f7ca"),
], ids=["dense_op", "flash_kernels"])
def test_the_first_decoders_argv_still_builds_the_program_it_built(extra, want):
    """`st21b_ep4_8k`'s argv (its rehearsal sizes) yields the leaves it
    yielded at the parent of the PR that made the layer a description (PR 32)
    and the lowered step it yielded at PR 34 (`dense_op`) and PR 36
    (`flash_kernels`): sha256 of the parameters' paths,
    shapes and dtypes and of the step's StableHLO text, taken there with this
    same code. PR 34 moved the two step hashes by intent and left the leaves'
    alone: the expert layer's combine became one custom_vjp op whose backward
    is written over the sorted rows (ops/moe.py::_combine), so the program
    text changed in every routing layer. PR 36 moved the `flash_kernels` hash
    by intent and left the `dense_op` hash and the leaves' alone: the flash
    backward became one kernel (ops/flash_attention.py::_dkvq_kernel, one
    `pallas_call` where `flash_dq` and `flash_dkv` stood), so the program
    text changed in every attention layer that reaches the kernels. A change
    of JAX moves all three: take them again from that commit."""
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        conf = json.load(f)
    cfg = config_from_args(build_parser().parse_args(
        conf["rehearse"]["argv"] + ["--dataset", "tokens", "--batchsize", "2",
                                    *extra]))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=jax.devices()[:1])
    with mesh:
        box = {}

        def build():
            box["model"], box["tx"], state = create_train_state(cfg, mesh, 100)
            return state

        state = jax.eval_shape(build)
        step = make_train_step(cfg, box["model"], box["tx"], mesh=mesh)
        tokens = jax.ShapeDtypeStruct((2, cfg.model.decoder.seq_len), jnp.int32)
        text = step.lower(state, tokens, tokens).as_text()
    leaves = [(jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
              for k, v in jax.tree_util.tree_leaves_with_path(state.params)]
    assert len(leaves) == 43
    assert hashlib.sha256(repr(leaves).encode()).hexdigest() == \
        "ab750da4af34fc4d5dbfa60d1ad2b9f324451dcfca0d8ca42a656ec2eef90605"
    assert hashlib.sha256(text.encode()).hexdigest() == want


# (g) ----------------------------------------------------------------------

def test_analytic_counts_match_the_published_48b_a27b():
    cut = CONF["arch"]
    published = CONF["published"]
    uncut = dict(cut, num_layers=published["num_hidden_layers"],
                 experts_held=published["n_routed_experts"],
                 vocab_size=published["vocab_size"])

    def count(arch, keep=lambda name: True):
        return sum(int(np.prod(s[0])) for name, s in ref.param_spec(arch).items()
                   if keep(name))

    # "48B": the 40 layers, embedding and head are 48.94 B parameters; the
    # prediction module (which shares embedding and head) is 1.25 B more
    assert abs(count(uncut, lambda n: not n.startswith("mtp/")) / 1e9 - 48.94) < 0.01
    assert abs(count(uncut) / 1e9 - 50.19) < 0.01
    # "A2.7B": a token meets 2.77 B of them in the 40 layers (embedding,
    # head and the prediction module apart); the 39 routing layers alone
    # are 2.70 B, the head would make it 3.04 B
    layers = flops.layers_token_macs(uncut)
    assert abs(layers / 1e9 - 2.775) < 0.005
    one_dense = flops.attention_token_macs(uncut) + 3 * 2048 * 7168
    assert abs((layers - one_dense) / 1e9 - 2.704) < 0.005
    assert abs((layers + 2048 * 129280) / 1e9 - 3.039) < 0.005
    # the cut: the number in `parameters_why`, and the step's work
    assert count(cut) == CONF["parameters"] == 680441088
    assert "680,441,088" in CONF["parameters_why"]
    assert flops.train_flops_per_image(cut, 224) == 6.0 * flops.forward_macs(cut)
    assert abs(2 * flops.train_flops_per_image(cut) / 1e12 - 55.68) < 0.05
    # scores over 192, weighted sums over 128, the exact triangle, 6 blocks
    t = cut["seq_len"]
    assert flops.score_macs(cut) == 6 * 32 * (192 + 128) * (t * (t + 1) // 2)
    assert flops.attention_flops(cut, 2) == 12.0 * flops.score_macs(cut)
    assert flops.gmm_flops(10.0, cut) == 6.0 * 10 * 3 * 2048 * 768
    assert flops.shared_flops(cut, 16384) == 6.0 * 16384 * 5 * 3 * 2048 * 768
    # the configuration's own arithmetic: every width as published
    catalog = {"hidden_size": 2048, "q_lora_rank": 1536, "kv_lora_rank": 512,
               "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
               "v_head_dim": 128, "intermediate_size": 7168,
               "moe_intermediate_size": 768, "num_attention_heads": 32,
               "num_experts_per_tok": 8, "n_shared_experts": 1,
               "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
               "routed_scaling_factor": 2.5, "rope_theta": 32000000}
    assert {k: CONF[k] for k in catalog} == catalog
    assert CONF["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"], CONF["vocab_size"]) \
        == (5, 16, 16160) == (cut["num_layers"], cut["experts_held"], cut["vocab_size"])
    assert (cut["hidden_size"], cut["q_rank"], cut["kv_rank"], cut["head_dim"],
            cut["rope_dim"], cut["v_head_dim"], cut["dense_width"], cut["expert_width"],
            cut["num_heads"], cut["num_experts"], cut["top_k"]) == (
        2048, 1536, 512, 128, 64, 128, 7168, 768, 32, 256, 8)


def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ddp_classification_pytorch_tpu" not in text
    assert "from ddp_classification_pytorch_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


# counters ------------------------------------------------------------------

def test_latent_decoder_trains_through_cli_train_and_publishes_its_counters(tmp_path):
    t = ARCH["seq_len"]
    ids = (np.arange(8 * (t + 1)) * 7 % 50).astype(np.int32)
    path = tmp_path / "train.bin"
    ids.tofile(path)
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens",
            "--train_dir", str(path), "--batchsize", "8", "--epochs", "2",
            "--optimizer", "adam", "--lr", "0.003", "--adam_b2", "0.95",
            "--platform", "cpu", "--out", str(tmp_path / "run"),
            "--log_every", "1", "--remat", "--head_block", "64", *KINDS]
    for key, value in ARCH.items():
        argv += [f"--{key}", str(value)]
    train_main(argv)   # Trainer, ShardedLoader, DevicePrefetcher, _build_step
    with open(tmp_path / "run" / "history.json") as f:
        history = json.load(f)
    losses = history["loss"]                  # one step an epoch: two steps
    assert len(losses) == 2 and all(np.isfinite(losses)) and losses[1] < losses[0]
    for a, b, c in zip(history["loss"], history["loss_main"], history["loss_mtp"]):
        assert abs(a - (b + ARCH["mtp_weight"] * c)) < 1e-4 * a
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    for name in ("train_loss_main", "train_loss_mtp",
                 'moe_expert_load_max{layer="1"}',
                 'moe_expert_load_mean{layer="mtp"}',
                 'moe_slots_routed_total{held="true"}'):
        assert name in prom, name
    assert 'moe_expert_load_max{layer="0"}' not in prom   # the dense layer routes nothing
