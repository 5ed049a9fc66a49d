"""The decoder's third kind of layer (models/decoder_lm.py as LFM2-8B-A1B
configures it: a gated short convolution in most layers' place of attention,
QK-normed grouped-query heads in the others, a sigmoid bias-corrected router
whose normalisation carries an epsilon, a head tied to the embedding) against
its plain reference (benchmark/reference/lfm2_8b_a1b.py, imported as it
stands: it takes nothing from the program), the reference against published
modelling code, the expert share, the tied table's gradient, the operator's
causality, and the counter that shows the layout a run built. CPU, toy sizes."""

import functools
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

from benchmark.flops import lfm2_8b_a1b as flops  # noqa: E402
from benchmark.reference import common, lfm2_8b_a1b as ref  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.models import decoder_lm  # noqa: E402
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops.moe import route_top_k, sparse_moe  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import _lm_loss  # noqa: E402
from test_decoder_lm import batch, flat_tree, program_tree  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as f:
    CONF = json.load(f)

# experts 2-5 of 8 held: a share that starts in the middle of the router
ARCH = {"vocab_size": 96, "hidden_size": 32, "num_layers": 3, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "conv_layout": [1, 1, 0],
        "conv_kernel": 3, "dense_layers": 1, "dense_width": 48,
        "expert_width": 16, "num_experts": 8, "experts_held": 4,
        "first_expert": 2, "top_k": 3, "router_scale": 1.0, "router_eps": 1e-6,
        "rope_theta": 1e6, "rms_eps": 1e-5, "seq_len": 32}
KINDS = ["--attention", "gqa", "--qk_norm", "1", "--rope_pairing", "half",
         "--activation", "silu", "--router", "sigmoid", "--router_tap", "post",
         "--rope_layout", "1", "--window_layout", "0"]


def cli_argv(arch, *extra, dtype="float32", tied=1):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens", "--dtype",
            dtype, "--optimizer", "adam", "--head_block", "16",
            "--tied_embeddings", str(tied), *KINDS]
    for key, value in arch.items():
        argv += [f"--{key}", ",".join(map(str, value)) if isinstance(value, list)
                 else str(value)]
    return argv + list(extra)


@functools.lru_cache(maxsize=None)
def reference_grad():
    """The plain reference's loss and gradients at ARCH, compiled ONCE for
    the three tests that hold a program against it."""
    return jax.jit(jax.value_and_grad(ref.loss_for(ARCH)))


def program(arch, *extra, **kinds):
    cfg = config_from_args(build_parser().parse_args(cli_argv(arch, *extra, **kinds)))
    model = build_model(cfg.model, cfg.data.num_classes)
    loss_fn, metrics_fn = _lm_loss(cfg, model)
    return model, loss_fn, metrics_fn


# (a) ----------------------------------------------------------------------

@pytest.mark.parametrize("arch,extra", [
    (ARCH, ()),
    (ARCH, ("--flash_min_tokens", "0")),
    # the published 64-wide head: the kernels' blocks are half a lane tile
    (dict(ARCH, hidden_size=64, head_dim=64, num_heads=2, num_kv_heads=1),
     ("--flash_min_tokens", "0")),
], ids=["dense_op", "flash_kernels", "flash_kernels_head64"])
def test_program_matches_the_plain_reference_loss_and_every_gradient(arch, extra):
    model, loss_fn, metrics_fn = program(arch, "--remat", *extra)
    flat = common.make_params(ref.param_spec(arch), 3)
    assert float(jnp.abs(flat["layer1/router_bias"]).max()) > 0.05  # seeded non-zero
    assert "lm_head/kernel" not in flat                             # tied: one table
    tokens, targets = batch(arch)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :8], train=False))["params"]
    assert ({k: v.shape for k, v in flat_tree(init).items()}
            == {k: v.shape for k, v in flat.items()})
    (loss, (_, aux)), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        program_tree(flat), {}, tokens, targets, None)
    want, want_grads = (reference_grad() if arch is ARCH else jax.jit(
        jax.value_and_grad(ref.loss_for(arch))))(flat, tokens, targets)
    # float32 against float32: what is left is the order of the sums (the
    # kernels' tiles, the sorted slots): 1e-5 of the loss, 2e-4 of a leaf's
    # largest entry; a bf16 program lies a hundred times further (below)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = flat_tree(grads)
    assert set(got) == set(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 2e-4 * scale, name
    # the bias steers the choice and nothing else: no gradient at all
    for name in got:
        if name.endswith("router_bias"):
            assert float(jnp.abs(got[name]).max()) == 0.0, name
    # one row of loads a routing layer (the dense layer 0 has none)
    load = metrics_fn(loss, aux, targets)["moe_load"]
    assert load.shape == (2, arch["experts_held"])
    assert 0 < int(load.sum()) <= tokens.size * arch["top_k"] * 2


def test_bf16_program_lies_further_from_the_reference_and_fp8_further_still():
    flat = common.make_params(ref.param_spec(ARCH), 5)
    tokens, targets = batch(ARCH, seed=1)
    want, want_g = reference_grad()(flat, tokens, targets)
    _, loss_fn, _ = program(ARCH, dtype="bfloat16")
    got, g = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {}, tokens, targets, None)[0]))(program_tree(flat))
    bf16 = common.difference_gap(flat_tree(g), want_g)
    fp8 = common.difference_gap(
        jax.jit(jax.grad(ref.loss_for(ARCH, "fp8")))(flat, tokens, targets), want_g)
    # the float32 test's tolerance on a leaf refuses the bf16 program
    assert any(float(jnp.abs(v - want_g[k]).max())
               > 2e-4 * float(jnp.abs(want_g[k]).max()) for k, v in flat_tree(g).items())
    assert np.isfinite(float(got)) and 1e-3 < bf16 < fp8, (bf16, fp8)


# (b) ----------------------------------------------------------------------

def test_the_four_shares_of_8_experts_add_up_to_the_uncut_layer():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    n, c, width, experts, held = 64, 16, 8, 32, 8
    u = jax.random.normal(ks[0], (n, c))
    logits = jax.random.normal(ks[1], (n, experts))
    bias = 0.3 * jax.random.normal(ks[2], (experts,))
    w = (jax.random.normal(ks[3], (experts, c, width)),
         jax.random.normal(ks[4], (experts, c, width)),
         jax.random.normal(ks[5], (experts, width, c)))
    # an epsilon large enough to show: the published 1e-6 is under float32's
    # resolution of a sum near 2
    route = dict(scoring="sigmoid", bias=bias, scale=1.0, eps=0.05)
    arch = {"top_k": 4, "router_scale": 1.0, "router_eps": 0.05, "first_expert": 0}
    idx, weight = ref.route(logits, bias, arch)
    uncut = ref.held_experts(u, idx, weight, *w, arch, lambda x: x)
    whole, load = sparse_moe(u, logits, *w, top_k=4, dtype=jnp.float32,
                             activation="silu", route=route)
    assert int(load.sum()) == n * 4
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=1e-4)
    parts = [sparse_moe(u, logits, *(b[held * s:held * (s + 1)] for b in w),
                        top_k=4, first_expert=held * s, dtype=jnp.float32,
                        activation="silu", route=route)
             for s in range(experts // held)]
    assert len(parts) == 4
    np.testing.assert_allclose(sum(p for p, _ in parts), uncut, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(jnp.concatenate([l for _, l in parts]), load)
    # the epsilon is in the weights: without it they sum to exactly 1
    no_eps, _ = sparse_moe(u, logits, *w, top_k=4, dtype=jnp.float32,
                           activation="silu", route=dict(route, eps=0.0))
    assert float(jnp.abs(no_eps - whole).max()) > 1e-3


def test_router_is_the_published_route_tokens_to_experts():
    """`Lfm2MoeSparseMoeBlock.route_tokens_to_experts` (transformers'
    `modeling_lfm2_moe.py`, which the installed release does not carry yet),
    transcribed line by line with `use_expert_bias`, `norm_topk_prob` and
    `routed_scaling_factor` as the model's config sets them."""
    torch = pytest.importorskip("torch")
    top_k, scaling = 4, 1.0
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(128, 32)).astype(np.float32)
    expert_bias = rng.normal(scale=0.1, size=(32,)).astype(np.float32)

    router_logits = torch.from_numpy(logits)
    routing_weights = router_logits.sigmoid()
    scores_for_routing = routing_weights + torch.from_numpy(expert_bias)
    _, selected_experts = torch.topk(scores_for_routing, k=top_k, dim=-1)
    routing_weights = torch.gather(
        routing_weights, dim=1, index=selected_experts).type_as(router_logits)
    routing_weights = routing_weights / (routing_weights.sum(dim=-1, keepdim=True) + 1e-6)
    routing_weights = routing_weights * scaling

    arch = {"top_k": top_k, "router_scale": scaling, "router_eps": 1e-6}
    for idx, weight in (
            ref.route(jnp.asarray(logits), jnp.asarray(expert_bias), arch),
            route_top_k(jnp.asarray(logits), top_k, scoring="sigmoid",
                        bias=jnp.asarray(expert_bias), scale=scaling, eps=1e-6)):
        np.testing.assert_array_equal(idx, selected_experts.numpy())
        np.testing.assert_allclose(weight, routing_weights.numpy(), rtol=1e-6)


# (c) ----------------------------------------------------------------------

def test_tied_tables_gradient_is_the_lookups_plus_the_heads():
    """The same weights in an untied model whose head is the embedding
    transposed: the tied table's gradient is the sum of the two leaves'."""
    flat = common.make_params(ref.param_spec(ARCH), 11)
    tokens, targets = batch(ARCH, seed=3)

    def grads_of(loss_fn, tree):
        return jax.jit(jax.grad(
            lambda p: loss_fn(p, {}, tokens, targets, None)[0]))(tree)

    _, tied_loss, _ = program(ARCH)
    _, untied_loss, _ = program(ARCH, tied=0)
    tied = grads_of(tied_loss, program_tree(flat))
    assert "lm_head" not in tied
    untied = grads_of(untied_loss, program_tree(
        dict(flat, **{"lm_head/kernel": flat["embed/embedding"].T})))
    lookup, head = untied["embed"]["embedding"], untied["lm_head"]["kernel"].T
    assert float(jnp.abs(lookup).max()) > 0 and float(jnp.abs(head).max()) > 0
    np.testing.assert_allclose(tied["embed"]["embedding"], lookup + head,
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(tied["layer2"]["o"]["kernel"],
                               untied["layer2"]["o"]["kernel"], rtol=1e-5, atol=1e-7)


# (d) ----------------------------------------------------------------------

def test_the_convolution_is_causal_and_is_the_grouped_convolution():
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    t, c, taps = 24, 8, 3
    z = jax.random.normal(ks[0], (2, t, c))
    w = jax.random.normal(ks[1], (taps, c))
    got = ref.short_conv(z, w)
    # torch's Conv1d(C, C, L, groups=C, padding=L-1)[..., :T] in XLA's words
    want = jax.lax.conv_general_dilated(
        z, w[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c,
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a change at position 9 moves no output before it, and none after 11
    moved = ref.short_conv(z.at[:, 9].add(1.0), w) - got
    assert float(jnp.abs(moved[:, :9]).max()) == 0.0
    assert float(jnp.abs(moved[:, 9:12]).min()) > 0.0
    assert float(jnp.abs(moved[:, 12:]).max()) == 0.0
    # the program's operator, through layers of convolutions only: a changed
    # token moves no state before its position
    arch = dict(ARCH, conv_layout=[1], dense_layers=3)
    model, _, _ = program(arch)
    params = program_tree(common.make_params(ref.param_spec(arch), 1))
    tokens, _ = batch(arch)
    hidden = jax.jit(lambda tok: model.apply(
        {"params": params}, tok, train=False, method="hidden")[0])
    moved = hidden(tokens.at[:, 20].set((tokens[:, 20] + 1) % 96)) - hidden(tokens)
    assert float(jnp.abs(moved[:, :20]).max()) == 0.0
    # three layers of two earlier taps each: six positions downstream
    assert float(jnp.abs(moved[:, 20:27]).max()) > 0.0
    assert float(jnp.abs(moved[:, 27:]).max()) == 0.0


# (e) ----------------------------------------------------------------------

def test_reference_forward_matches_the_published_lfm2_code():
    """The reference's convolution layers, QK-normed attention layer, final
    norm and tied logits against `transformers`' Lfm2ForCausalLM with the same
    weights copied in. That model's feed-forward is dense in every layer
    (`lfm2_moe`, the mixture, is not in the installed release), so every layer
    here is a dense one; the router has a test of its own above."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.lfm2 import configuration_lfm2 as hf_conf
        from transformers.models.lfm2 import modeling_lfm2 as hf
    except Exception as e:  # noqa: BLE001 — whatever stops the import
        pytest.skip(f"transformers' lfm2 cannot be imported: {e}")
    arch = dict(ARCH, dense_layers=3, head_dim=8)   # theirs is hidden / heads
    flat = common.make_params(ref.param_spec(arch), 7)
    # scales that are not 1, so that a norm left out or misplaced shows
    for name in flat:
        if name.endswith("/scale"):
            flat[name] = flat[name] + 0.1 * jax.random.normal(
                jax.random.PRNGKey(len(name)), flat[name].shape)
    tokens, _ = batch(arch, seed=2)
    want = np.asarray(jax.jit(ref.logits_for(arch))(flat, tokens))

    config = hf_conf.Lfm2Config(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["dense_width"], num_hidden_layers=arch["num_layers"],
        num_attention_heads=arch["num_heads"],
        num_key_value_heads=arch["num_kv_heads"], max_position_embeddings=64,
        norm_eps=arch["rms_eps"], rope_theta=arch["rope_theta"], conv_bias=False,
        conv_L_cache=arch["conv_kernel"], block_auto_adjust_ff_dim=False,
        layer_types=["conv", "conv", "full_attention"], tie_word_embeddings=True,
        attn_implementation="eager")
    model = hf.Lfm2ForCausalLM(config).to(torch.float32).eval()

    def t(name):  # a (in, out) kernel as torch's (out, in) weight
        return torch.from_numpy(np.asarray(flat[name]).T.copy())

    def v(name):
        return torch.from_numpy(np.asarray(flat[name]).copy())

    state = {"model.embed_tokens.weight": v("embed/embedding"),
             "lm_head.weight": v("embed/embedding"),
             "model.embedding_norm.weight": v("norm_final/scale")}
    for i, (b, conv, _) in enumerate(ref.layer_kinds(arch)):
        a = f"model.layers.{i}"
        state[f"{a}.operator_norm.weight"] = v(f"{b}/norm_in/scale")
        state[f"{a}.ffn_norm.weight"] = v(f"{b}/norm_post/scale")
        for theirs, ours in (("w1", "gate"), ("w3", "up"), ("w2", "down")):
            state[f"{a}.feed_forward.{theirs}.weight"] = t(f"{b}/ffn_{ours}/kernel")
        if conv:
            state[f"{a}.conv.in_proj.weight"] = t(f"{b}/conv_in/kernel")
            state[f"{a}.conv.out_proj.weight"] = t(f"{b}/conv_out/kernel")
            # Conv1d's (C, 1, L) from the taps stored (L, C)
            state[f"{a}.conv.conv.weight"] = t(f"{b}/conv_taps")[:, None, :].contiguous()
            continue
        for theirs, ours in (("q_proj", "q"), ("k_proj", "k"), ("v_proj", "v"),
                             ("out_proj", "o")):
            state[f"{a}.self_attn.{theirs}.weight"] = t(f"{b}/{ours}/kernel")
        state[f"{a}.self_attn.q_layernorm.weight"] = v(f"{b}/q_head_norm/scale")
        state[f"{a}.self_attn.k_layernorm.weight"] = v(f"{b}/k_head_norm/scale")
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("inv_freq" in k or "rotary" in k or "pos_emb" in k
                                  for k in missing), (missing, unexpected)
    with torch.no_grad():
        got = model(torch.from_numpy(np.array(tokens)).long()).logits.numpy()
    # float32 on both sides: 2e-4 of the logits' scale; a wrong chunk order,
    # tap order, rotary pairing or a norm left out reads 1e-2 and more
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# (g) ----------------------------------------------------------------------

def test_analytic_counts_match_the_published_8b_a1b():
    cut = CONF["arch"]
    published = CONF["published"]
    uncut = dict(cut, num_layers=published["num_hidden_layers"],
                 dense_layers=published["num_dense_layers"],
                 conv_layout=[int(k == "conv") for k in published["layer_types"]],
                 experts_held=published["num_experts"],
                 vocab_size=published["vocab_size"])

    def count(arch):
        return sum(int(np.prod(s[0])) for s in ref.param_spec(arch).values())

    # "8.3B": 24 layers (18 convolutions, 6 attentions; 2 dense, 22 routed)
    # and ONE 65,536 x 2,048 table; a second table would make 8.47 B
    assert published["layer_types"].count("conv") == 18
    assert abs(count(uncut) / 1e9 - 8.34) < 0.005
    assert abs((count(uncut) + 65536 * 2048) / 1e9 - 8.47) < 0.005
    # "A1B" (the family's table: A1.5B): a token meets 1.42 B in the layers
    # (18 operators 0.30, 6 attentions 0.06, 2 dense MLPs 0.09, 22 x top-4
    # experts 0.97) and 1.56 B with the head's matmul
    assert abs(flops.layers_token_macs(uncut) / 1e9 - 1.423) < 0.005
    assert abs(flops.token_macs(uncut) / 1e9 - 1.558) < 0.005
    # the cut: the number in `parameters_why`, and the step's work
    assert count(cut) == CONF["parameters"] == 507820288
    assert "507,820,288" in CONF["parameters_why"]
    assert flops.train_flops_per_image(cut, 224) == 6.0 * flops.forward_macs(cut)
    t = cut["seq_len"]
    assert flops.score_macs(cut) == 1 * 32 * (64 + 64) * (t * (t + 1) // 2)
    assert flops.attention_flops(cut, 2) == 12.0 * flops.score_macs(cut)
    assert flops.gmm_flops(10.0, cut) == 6.0 * 10 * 3 * 2048 * 1792
    assert flops.conv_flops(cut, 16384) == 6.0 * 16384 * 4 * (2048 * 6144 + 2048 * 2048)
    assert flops.conv_tap_macs(cut) == 3 * 2048
    # the configuration's own arithmetic: every width as published
    catalog = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
               "intermediate_size": 7168, "max_position_embeddings": 128000,
               "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
               "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts_per_tok": 4, "num_key_value_heads": 8,
               "rope_theta": 1000000, "routed_scaling_factor": 1,
               "use_expert_bias": True}
    assert {k: CONF[k] for k in catalog} == catalog
    assert CONF["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types",
                               "num_experts", "vocab_size"]
    assert (CONF["num_hidden_layers"], CONF["num_dense_layers"], CONF["num_experts"],
            CONF["vocab_size"]) == (5, 1, 8, 16384) == (
        cut["num_layers"], cut["dense_layers"], cut["experts_held"], cut["vocab_size"])
    assert [int(k == "conv") for k in CONF["layer_types"]] == cut["conv_layout"]
    # the cut keeps a whole period of the published pattern after the dense layers
    assert published["layer_types"][3:7] == CONF["layer_types"][1:]
    assert (cut["hidden_size"], cut["num_heads"], cut["num_kv_heads"], cut["head_dim"],
            cut["dense_width"], cut["expert_width"], cut["num_experts"], cut["top_k"],
            cut["conv_kernel"]) == (2048, 32, 8, 64, 7168, 1792, 32, 4, 3)
    # the argv builds the arch
    dc = config_from_args(build_parser().parse_args(
        CONF["argv"] + ["--dataset", "tokens"])).model.decoder
    # the taps: the published length, which is the field's default (the
    # configuration's argv does not name it)
    assert cut["conv_kernel"] == dc.conv_kernel == CONF["conv_L_cache"] == 3
    assert "--conv_kernel" not in CONF["argv"]
    assert {k: (list(getattr(dc, k)) if isinstance(v, list) else getattr(dc, k))
            for k, v in cut.items()} == cut
    assert (dc.qk_norm, dc.tied_embeddings, dc.router, dc.router_tap) == (
        1, 1, "sigmoid", "post")


def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ddp_classification_pytorch_tpu" not in text
    assert "from ddp_classification_pytorch_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_factory_refuses_kinds_that_do_not_go_together():
    for extra, match in ((("--attention", "mla", "--q_rank", "8", "--kv_rank", "8",
                           "--rope_dim", "8"), "qk_norm"),
                         (("--conv_layout", "2"), "conv_layout")):
        cfg = config_from_args(build_parser().parse_args(cli_argv(ARCH, *extra)))
        with pytest.raises(ValueError, match=match):
            build_model(cfg.model, cfg.data.num_classes)


# counters ------------------------------------------------------------------

@pytest.mark.parametrize("extra", [(), ("--flash_min_tokens", "0")],
                         ids=["dense_op", "flash_kernels"])
def test_hybrid_decoder_trains_through_cli_train_and_publishes_its_layout(
        extra, tmp_path, capsys):
    t = ARCH["seq_len"]
    ids = (np.arange(8 * (t + 1)) * 7 % 50).astype(np.int32)
    path = tmp_path / "train.bin"
    ids.tofile(path)
    argv = cli_argv(ARCH, "--train_dir", str(path), "--batchsize", "8", "--epochs",
                    "2", "--lr", "0.003", "--adam_b2", "0.95", "--platform", "cpu",
                    "--out", str(tmp_path / "run"), "--log_every", "1", "--remat",
                    *extra)
    train_main(argv)   # Trainer, ShardedLoader, DevicePrefetcher, _build_step
    with open(tmp_path / "run" / "history.json") as f:
        losses = json.load(f)["loss"]             # one step an epoch: two steps
    assert len(losses) == 2 and all(np.isfinite(losses)) and losses[1] < losses[0]
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    for line in ('decoder_layers_total{ffn="dense",operator="conv"} 1',
                 'decoder_layers_total{ffn="routed",operator="conv"} 1',
                 'decoder_layers_total{ffn="routed",operator="gqa"} 1',
                 'moe_expert_load_max{layer="1"}', 'moe_expert_load_max{layer="2"}'):
        assert line in prom, line
    assert 'moe_expert_load_max{layer="0"}' not in prom   # the dense layer routes nothing
    out = capsys.readouterr().out
    setup = next(line for line in out.splitlines() if "[trainer] set-up:" in line)
    assert "conv_dense=1 conv_routed=1 gqa_routed=1" in setup, setup
    # the attention layer's backward, where it reaches the kernels: the path
    # its shapes choose stands in the set-up line before any step is traced,
    # and the trace counts it (a process total: other tests add to it)
    assert ("flash_backward=fused" in setup) == bool(extra), setup
    if extra:
        assert re.search(r'flash_backward_total\{path="fused"\} [1-9]', prom), prom
