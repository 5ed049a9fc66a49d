"""The train step's device-side names and their reader.

(a) `benchmark/layers/_phases.py`'s phase-and-block function on literal
    `op_name` paths of every form the transforms and the scopes produce;
(b) the same function over every path of the REAL lowered train step of a
    small bottleneck ResNet, a small ViT and a small decoder: each phase is
    there, recomputation only under `--remat`, the blocks match, and next to
    nothing lands in `other`;
(c) the scopes are metadata: with `jax.named_scope` a null context in
    train/steps.py and the two models, the lowered program is byte-equal;
(d) the table from a hand-made list of events, and the trace finder;
and the first cases of the two older scope readers' segment rules.
"""

import contextlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.layers import _phases, _scope_members, _scoped_ops  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
)

RN = "jit(step)/jvp(ClassifierModel)/backbone/"
RN_T = "jit(step)/transpose(jvp(ClassifierModel))/backbone/"
LM = "jit(step)/transpose(jvp(DecoderLM.hidden))/jvp(DecoderLM.hidden)/checkpoint/"


# (a) ----------------------------------------------------------------------

@pytest.mark.parametrize("path,phase,block", [
    (RN + "layer1_block0/Conv_0/conv_general_dilated", "fwd", "layer1.conv"),
    (RN + "layer2_block0/downsample_bn/mul", "fwd", "layer2.bn"),
    (RN_T + "layer3_block5/BatchNorm_2/reduce_sum", "bwd", "layer3.bn"),
    (RN_T + "layer4_block1/bn/jit(relu)/select_n", "bwd", "layer4.bn"),
    (RN + "layer4_block2/residual/jit(relu)/max", "fwd", "layer4.residual"),
    (RN + "conv_stem/conv_general_dilated", "fwd", "stem.conv"),
    (RN + "bn/jit(relu)/max", "fwd", "stem.bn"),
    (RN_T + "pool/select_and_scatter_add", "bwd", "stem.pool"),
    (RN + "head/reduce_sum", "fwd", "head"),
    (RN_T + "head/fc/dot_general", "bwd", "head"),
    ("jit(step)/jvp(loss)/reduce_max", "fwd", "loss"),
    ("jit(step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add", "bwd", "loss"),
    (RN_T + "block3/attn/qkv/dot_general", "bwd", "attn"),
    (RN + "block3/attn/bhqk,bkhd->bqhd/dot_general", "fwd", "attn"),
    (RN + "block11/mlp/mlp_in/dot_general", "fwd", "mlp"),
    (RN_T + "block0/mlp/mul", "bwd", "mlp"),
    (RN + "block0/ln/ln1/rsqrt", "fwd", "ln"),
    (RN_T + "ln/ln_final/mul", "bwd", "ln"),
    (RN + "block7/residual/add", "fwd", "residual"),
    (RN + "patch_embed/patch_embed/conv_general_dilated", "fwd", "patch_embed"),
    ("jit(step)/jvp(DecoderLM.hidden)/layers_1/attn/layers_1._attention/o/dot_general",
     "fwd", "attn"),
    (LM + "rematted_computation/layers_2/moe.experts/mul", "remat", "moe.experts"),
    (LM + "layers_2/moe.combine/gather", "bwd", "moe.combine"),
    (LM + "layers_0/ffn/layers_0._gated_mlp/ffn_up/dot_general", "bwd", "rest"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/lm_head/dot_general",
     "bwd", "lm_head"),
    ("jit(step)/transpose(jvp(lm_head))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "remat", "lm_head"),
    ("jit(step)/jvp(mtp)/lm_head/while/body/closed_call/reduce_max", "fwd", "lm_head"),
    ("jit(step)/step.opt/mul", "opt", "rest"),
    ("jit(step)/step.guard/jit(_where)/select_n", "opt", "rest"),
    ("jit(step)/step.input/convert_element_type", "step.input", "rest"),
    ("jit(step)/shard_map/step.exchange/psum", "step.exchange", "rest"),
    ("jit(step)/step.metrics/jit(take_along_axis)/gather", "step.metrics", "rest"),
    ("jit(step)/jit(_threefry_fold_in)/threefry2x32", "other", "rest"),
])
def test_phase_and_block_of_a_path(path, phase, block):
    assert _phases.classify(path) == (phase, block, True)
    # as the profile writes it: after the category, source lines and shapes
    text = f"loop fusion fusion.12 /root/repo/x.py:93:12\n/root/repo/y.py:17:4\n (f32[256]{{0}}) {path}:"
    assert _phases.classify(text) == (phase, block, True)


def test_joined_paths_the_first_decides_and_a_disagreement_is_told():
    fwd, bwd = RN + "layer1_block0/Conv_0/mul", RN_T + "layer1_block0/Conv_0/mul"
    assert _phases.classify(f"{fwd};{bwd}") == ("fwd", "layer1.conv", False)
    assert _phases.classify(f"{bwd};{bwd[:-3]}add") == ("bwd", "layer1.conv", True)


@pytest.mark.parametrize("text", ["", "reduce_sum", "copy-done f32[256]{0:T(256)S(1)}"])
def test_a_text_without_a_path_has_no_phase_of_its_own(text):
    assert _phases.classify(text) is None


# the two older readers' segment rules --------------------------------------

@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jvp(DecoderLM.hidden)/layers_0/attn/q/dot_general", "attn"),
    # innermost: the prediction module's own attention, router and head
    ("jit(step)/jvp(DecoderLM.hidden)/mtp/mtp/layer/attn/layer._attention/o", "attn"),
    ("jit(step)/jvp(mtp)/lm_head/while/body/dot_general", "lm_head"),
    ("jit(step)/jvp(X)/layers_1/layers_1._router_logits/moe.route/btc,ce->bte", "moe.route"),
    ("jit(step)/transpose(jvp(lm_head))/while/body/add", "lm_head"),
    # `moe.shared`, `conv` and `ffn` are none of SCOPES: the table's `rest`
    ("jit(step)/jvp(X)/layers_1/moe.shared/layers_1._gated_mlp/shared_up/dot_general", None),
    ("jit(step)/jvp(X)/layers_0/conv/conv.mix/mul", None),
    ("jit(step)/jvp(X)/layers_0/ffn/layers_0._gated_mlp/ffn_up/dot_general", None),
    # a whole segment, not a prefix or a suffix of one
    ("jit(step)/jvp(X)/layers_0/attn_norm/mul", None),
    ("jit(step)/jvp(X)/layers_0/pre_attn/mul", None),
])
def test_scoped_ops_puts_an_op_under_its_innermost_scope(path, scope):
    assert _scoped_ops.scope_of(path) == scope


@pytest.mark.parametrize("scope,inherit,want", [
    # outermost counts: the module's attention and its ragged-dot stand under `mtp`
    ("mtp", True, 6.0), ("mtp", False, 4.0), ("moe.shared", False, 8.0),
    ("conv", False, None),  # `conv.mix` is another segment
])
def test_scope_members_counts_every_op_anywhere_under_a_scope(scope, inherit, want):
    ms = 1_000_000
    ops = [("jit(step)/jvp(X)/mtp/mtp/layer/attn/o/dot_general", 0, 4 * ms),
           ("", 4 * ms, 2 * ms),  # a ragged-dot: no path of its own
           ("jit(step)/jvp(X)/layers_1/moe.shared/up/dot_general", 6 * ms, 8 * ms),
           ("", 14 * ms, 1 * ms),
           ("jit(step)/jvp(X)/layers_0/conv.mix/mul", 15 * ms, 1 * ms)]
    ctx = {_scope_members._KEY: (ops, 1)}
    assert _scope_members.scope_ms(ctx, scope, inherit=inherit) == want


# (b) ----------------------------------------------------------------------

def _conf(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)["rehearse"]["argv"]


ARGV = {
    "resnet": ("baseline --model resnet50 --variant imagenet --num_classes 10 "
               "--image_size 32 --crop_size 32 --dtype bfloat16 --input_dtype uint8 "
               "--optimizer sgd --lr 0.001 --momentum 0.9 --dataset cifar10").split(),
    "resnet18": _conf("resnet50_in1k") + ["--dataset", "cifar10"],
    "vit": _conf("vit_b16_in1k") + ["--dataset", "cifar10"],
    "decoder": _conf("joyai_llm_flash") + ["--dataset", "tokens"],
}


def _lower(argv, dp=1, mp=1, batch=8):
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
    from ddp_classification_pytorch_tpu.train.state import create_train_state
    from ddp_classification_pytorch_tpu.train.steps import make_train_step

    cfg = config_from_args(build_parser().parse_args(
        list(argv) + ["--batchsize", str(batch)]))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(dp, mp),
                             devices=jax.devices()[:dp * mp])
    with mesh:
        box = {}

        def build():
            box["model"], box["tx"], state = create_train_state(cfg, mesh, 100)
            return state

        state = jax.eval_shape(build)
        step = make_train_step(cfg, box["model"], box["tx"], mesh=mesh)
        if cfg.model.arch == "decoder_lm":
            x = y = jax.ShapeDtypeStruct((batch, cfg.model.decoder.seq_len), jnp.int32)
        else:
            size = cfg.data.image_size
            x = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.uint8)
            y = jax.ShapeDtypeStruct((batch,), jnp.int32)
        return step.lower(state, x, y)


@pytest.mark.parametrize("model,remat,blocks", [
    ("resnet", False, ("stem.conv", "stem.bn", "stem.pool", "layer1.conv",
                       "layer2.bn", "layer3.residual", "layer4.bn", "head", "loss")),
    ("vit", False, ("patch_embed", "attn", "mlp", "ln", "residual", "head", "loss")),
    ("decoder", True, ("attn", "moe.route", "moe.dispatch", "moe.experts",
                       "moe.combine", "lm_head")),
    ("decoder", False, ("attn", "moe.experts", "lm_head")),
], ids=["resnet", "vit", "decoder_remat", "decoder"])
def test_every_op_of_the_real_step_has_a_phase_and_the_models_ops_a_block(
        model, remat, blocks):
    argv = [a for a in ARGV[model] if remat or a != "--remat"]
    text = _lower(argv).as_text(debug_info=True)
    paths = [p for p in re.findall(r'loc\("([^"]*)"', text) if p.startswith("jit(")]
    assert len(paths) > 300
    found = [_phases.classify(p) for p in paths]
    by_phase = {ph: [f for f in found if f[0] == ph] for ph in _phases.PHASES}
    for phase in ("fwd", "bwd", "opt", "step.metrics"):
        assert by_phase[phase], phase
    assert bool(by_phase["remat"]) == remat
    assert bool(by_phase["step.input"]) == (model != "decoder")  # the uint8 epilogue
    assert len(by_phase["other"]) < 0.02 * len(paths)
    have = {f[1] for f in found}
    assert set(blocks) <= have, set(blocks) - have
    # the model's own ops: next to none without a block
    model_ops = [f for p, f in zip(paths, found)
                 if "ClassifierModel" in p or "jvp(loss)" in p]
    assert sum(f[1] == "rest" for f in model_ops) <= 0.02 * len(model_ops)
    # `bn` takes every phase of a batch norm, `loss` stands outside the model
    if model == "resnet":
        assert {f[0] for f in found if f[1] == "layer3.bn"} == {"fwd", "bwd"}


# (c) ----------------------------------------------------------------------

class _NoScopes:
    """`jax`, but for `named_scope`, whose calls it counts."""

    def __init__(self):
        self.asked = []

    def __getattr__(self, name):
        return getattr(jax, name)

    def named_scope(self, name):
        self.asked.append(name)
        return contextlib.nullcontext()


@pytest.mark.parametrize("model", ["resnet18", "vit"])
@pytest.mark.parametrize("dp,mp", [(1, 1), (4, 1), (4, 2)],
                         ids=["one_device", "data4_zero1", "data4_model2"])
def test_the_scopes_are_metadata_the_lowered_program_is_byte_equal(
        monkeypatch, model, dp, mp):
    from ddp_classification_pytorch_tpu.models import resnet, vit
    from ddp_classification_pytorch_tpu.train import steps

    scoped = _lower(ARGV[model], dp, mp).as_text()
    bare_jax = _NoScopes()
    for module in (steps, resnet, vit):
        monkeypatch.setattr(module, "jax", bare_jax)
    bare = _lower(ARGV[model], dp, mp).as_text()
    assert {"step.opt", "step.guard", "loss", "residual"} <= set(bare_jax.asked)
    assert ("step.exchange" in bare_jax.asked) == (dp > 1)  # ZeRO-1's constraints
    assert bare == scoped


# (d) ----------------------------------------------------------------------

def _events():
    ms = 1_000_000
    fwd, bwd = RN + "layer1_block0/", RN_T + "layer1_block0/"
    rows = [("jit(step)/step.input/convert_element_type", 1),
            (fwd + "Conv_0/conv_general_dilated", 10),
            (fwd + "BatchNorm_0/reduce_sum", 3),
            ("", 1),                                   # inherits fwd, layer1.bn
            ("copy-done bf16[128,56,56,64]{0,3,2,1}", 1),     # no path: the same
            (f"{fwd}bn/jit(relu)/max;{bwd}bn/select_n:", 1),  # disagrees
            ("jit(step)/jvp(loss)/reduce_max:", 1),
            (bwd + "BatchNorm_0/mul", 6),
            (bwd + "Conv_0/conv_general_dilated:", 20),
            (LM + "rematted_computation/layers_0/attn/mul", 4),
            ("jit(step)/step.guard/reduce_sum", 1),
            ("jit(step)/step.opt/mul", 2),
            ("loop fusion steps.py:640 jit(step)/jit(_threefry_fold_in)/threefry2x32:", 5),
            ("", 1),                                          # inherits other
            ("jit(step)/step.metrics/eq", 1)]
    ops, at = [], 0
    for text, dur in rows:
        ops.append((text, at, dur * ms))
        at += (dur + 1) * ms    # a gap after each: the union is the sum
    return ops


def test_the_table_rows_sum_to_the_union_and_an_empty_path_inherits():
    t = _phases.reduce(_events(), steps=2)
    assert t["scoped"] and t["steps"] == 2
    assert t["phase_ms"] == {"step.input": 0.5, "fwd": 8.5, "bwd": 13.0, "remat": 2.0,
                             "opt": 1.5, "other": 3.0, "step.metrics": 0.5}
    assert sum(t["phase_ms"].values()) == t["all_ms"] == 29.0
    assert sum(t["block_ms"].values()) == 29.0
    assert t["ms"][("fwd", "layer1.bn")] == 3.0      # 3 + 1 + 1 inherited + 1 joined
    assert t["block_ms"]["layer1.bn"] == 6.0
    assert t["disagree"] == (1, 0.5)
    assert [ms for _, ms in t["other"]] == [2.5, 0.5] and t["other"][1][0] == ""


def test_the_readers_read_the_table_and_say_so_when_there_are_no_step_scopes(capsys):
    ctx = {"trace": {"steps": 2}, "trace_dir": "unused",
           _scope_members._KEY: (_events(), 2)}
    from benchmark.layers import (bn_device_ms, bwd_device_ms, fwd_device_ms,
                                  opt_device_ms, remat_device_ms, vit_attn_device_ms)
    assert fwd_device_ms.read(ctx) == 8.5 and bwd_device_ms.read(ctx) == 13.0
    assert remat_device_ms.read(ctx) == 2.0 and opt_device_ms.read(ctx) == 1.5
    assert bn_device_ms.read(ctx) == 6.0 and vit_attn_device_ms.read(ctx) == 2.0
    out = capsys.readouterr().out
    assert out.count("device time by phase") == 1 and "within 1 %" in out
    # the parent's program, or an executable from a cache older than the scopes
    bare = [(t, s, d) for t, s, d in _events() if "step." not in t]
    ctx = {"trace": {"steps": 2}, "trace_dir": "unused", _scope_members._KEY: (bare, 2)}
    assert fwd_device_ms.read(ctx) is None and bn_device_ms.read(ctx) is None
    assert "has no step scopes" in capsys.readouterr().out
    # no trace at all: nothing is looked for
    assert fwd_device_ms.read({"trace": None}) is None


def test_the_trace_is_found_where_the_runner_names_none_and_a_stale_one_is_not(tmp_path):
    def trace(cell, age):
        d = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (start + age, start + age))
        return str(tmp_path / cell)

    start = _phases._process_start()
    assert abs(start - os.path.getmtime("/proc/self")) < 3600  # a wall-clock time
    trace("rn50_folder", -60.0)
    assert _phases.find_trace_dir(str(tmp_path), start) is None
    mine = trace("rn50_pool", 30.0)
    trace("vitb16_pool", 10.0)
    assert _phases.find_trace_dir(str(tmp_path), start) == mine
    assert _phases.find_trace_dir(str(tmp_path / "nothing"), start) is None
