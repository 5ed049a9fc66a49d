"""What a model says about itself to the loop that trains it
(`models/factory.py::model_report`; the decoder's: `models/decoder_report.py`).

The text held here was captured from the Trainer of PR 47, which wrote it
itself, in one `cli.train` run of each layout at these sizes, 8 rows a step:
the notes beside `init_state` in the `[trainer] set-up:` line and the static
families of `metrics.prom`. The benchmark's readers and docs/observability.md
read both by name, so a report that spells one differently fails here before
it fails on the chip.
"""

import ast
import os

import pytest
import test_decoder_diffusion as diffusion
import test_decoder_gdn as gated
import test_decoder_hybrid as conv
import test_decoder_kda as kda
import test_decoder_latent as latent
import test_decoder_lm as first
import test_decoder_loop as looped

from ddp_classification_pytorch_tpu.cli.train import (
    build_parser,
    config_from_args,
)
from ddp_classification_pytorch_tpu.models.factory import model_report
from ddp_classification_pytorch_tpu.obs.registry import Registry

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "ddp_classification_pytorch_tpu")
ROWS = 8

APPLICATIONS = (
    "# HELP decoder_layer_applications_total layers a step runs: the layers "
    "built x the passes of the stack (--loops)\n"
    "# TYPE decoder_layer_applications_total counter\n"
    "decoder_layer_applications_total {}\n"
    "# HELP decoder_layers_total layers of the token decoder by token mixer "
    "and feed-forward\n"
    "# TYPE decoder_layers_total counter\n")


def from_argv(argv):
    return lambda: config_from_args(build_parser().parse_args(argv))


# layout -> (its Config, the notes, layers applied a step, the layer counts,
# an epoch's metrics that are `train_<name>` gauges)
CAPTURED = {
    "gqa_experts": (
        lambda: first.cli_config(first.ARCH),
        "gqa_routed=4 moe_bound=512/512", 4,
        'decoder_layers_total{ffn="routed",operator="gqa"} 4\n', []),
    "gqa_bounded": (
        lambda: first.cli_config(dict(first.ARCH, num_experts=16,
                                      experts_held=2, seq_len=128)),
        "gqa_routed=4 moe_bound=1024/2048", 4,
        'decoder_layers_total{ffn="routed",operator="gqa"} 4\n', []),
    "mla_mtp": (
        lambda: latent.cli_config(latent.ARCH),
        "mla_dense=1 mla_routed=1 moe_bound=1024/1024", 2,
        'decoder_layers_total{ffn="dense",operator="mla"} 1\n'
        'decoder_layers_total{ffn="routed",operator="mla"} 1\n',
        ["loss_main", "loss_mtp"]),
    "conv": (
        from_argv(conv.cli_argv(conv.ARCH)),
        "conv_dense=1 conv_routed=1 gqa_routed=1 moe_bound=768/768", 3,
        'decoder_layers_total{ffn="dense",operator="conv"} 1\n'
        'decoder_layers_total{ffn="routed",operator="conv"} 1\n'
        'decoder_layers_total{ffn="routed",operator="gqa"} 1\n', []),
    "conv_flash": (
        from_argv(conv.cli_argv(conv.ARCH, "--flash_min_tokens", "0")),
        "conv_dense=1 conv_routed=1 gqa_routed=1 flash_backward=fused "
        "moe_bound=768/768", 3,
        'decoder_layers_total{ffn="dense",operator="conv"} 1\n'
        'decoder_layers_total{ffn="routed",operator="conv"} 1\n'
        'decoder_layers_total{ffn="routed",operator="gqa"} 1\n', []),
    "kda": (
        from_argv(kda.cli_argv(kda.ARCH)),
        "kda_dense=1 kda_routed=1 mla_routed=1 kda_core=xla kda_prepare=xla "
        "moe_bound=3072/3072", 3,
        'decoder_layers_total{ffn="dense",operator="kda"} 1\n'
        'decoder_layers_total{ffn="routed",operator="kda"} 1\n'
        'decoder_layers_total{ffn="routed",operator="mla"} 1\n', []),
    # (PR 51) Gated DeltaNet's recurrence: plain XLA at the tests' widths, its
    # kernels at the published ones (8,192 x 96 x 192; `ops/gdn.py::takes_kernel`)
    "gdn": (
        from_argv(gated.cli_argv(gated.ARCH)),
        "gdn_dense=3 gqa_dense=1 gdn_core=xla", 4,
        'decoder_layers_total{ffn="dense",operator="gdn"} 3\n'
        'decoder_layers_total{ffn="dense",operator="gqa"} 1\n', []),
    "gdn_published": (
        from_argv(gated.cli_argv(dict(gated.ARCH, gdn_key_dim=96, gdn_value_dim=192,
                                      seq_len=8192))),
        "gdn_dense=3 gqa_dense=1 flash_backward=fused gdn_core=kernel", 4,
        'decoder_layers_total{ffn="dense",operator="gdn"} 3\n'
        'decoder_layers_total{ffn="dense",operator="gqa"} 1\n', []),
    "looped": (
        from_argv(looped.cli_argv(looped.ARCH)),
        "gqa_dense=2 loops=3 sandwich=1 passes=scan", 6,
        'decoder_layers_total{ffn="dense",operator="gqa"} 2\n',
        ["exit_p1", "exit_p2", "exit_p3", "loss_ut1", "loss_ut2", "loss_ut3"]),
    # (PR 53) block diffusion: the objective and the attention layers' mask;
    # a routing layer routes both streams' positions (8 x 2 x 128 x 4)
    "diffusion": (
        from_argv(diffusion.cli_argv(diffusion.ARCH)),
        "gqa_routed=2 objective=block_diffusion block=4 mask_id=95 "
        "attn_mask=block_diffusion moe_bound=8192/8192", 2,
        'decoder_layers_total{ffn="routed",operator="gqa"} 2\n',
        ["loss_level1", "loss_level2", "loss_level3", "loss_level4"]),
    "diffusion_kernels": (
        from_argv(diffusion.cli_argv(dict(diffusion.ARCH, seq_len=8192,
                                          num_experts=64))),
        "gqa_routed=2 objective=block_diffusion block=4 mask_id=95 "
        "attn_mask=block_diffusion flash_backward=fused moe_bound=131072/524288",
        2, 'decoder_layers_total{ffn="routed",operator="gqa"} 2\n',
        ["loss_level1", "loss_level2", "loss_level3", "loss_level4"]),
    "resnet18": (
        from_argv(["baseline", "--dataset", "synthetic", "--model", "resnet18",
                   "--variant", "cifar", "--image_size", "32",
                   "--num_classes", "4", "--dtype", "float32"]),
        "", None, "", []),
}
EPOCH = ["epoch_time", "grad_norm", "loss", "step_ok", "top1", "top3",
         "val_loss", "val_top1", "val_top3"]


@pytest.mark.parametrize("layout", sorted(CAPTURED))
def test_the_report_says_what_the_trainer_of_pr_47_wrote(layout):
    make_cfg, notes, applied, layers, gauges = CAPTURED[layout]
    cfg = make_cfg()
    report, obs = model_report(cfg.model), Registry()
    said = report.built(ROWS, obs)
    # `spans.note` keeps the order: it is the set-up line's
    assert " ".join(f"{k}={v}" for k, v in said.items()) == notes
    assert obs.expose() == (APPLICATIONS.format(applied) + layers
                            if applied else "")
    assert report.epoch_gauges(dict.fromkeys(EPOCH + gauges, 0.0)) == gauges
    report.logged_step({"loss": 0.0}, obs)      # a step that routed nothing
    assert "moe_" not in obs.expose()
    if applied:
        assert report.token_row_length() == cfg.model.decoder.seq_len
        ids = report.init_inputs(cfg.data.image_size)
        assert ids.dtype == "int32" and ids.shape == (2, 8)
    else:
        assert report.init_inputs(32).shape == (2, 32, 32, 3)
        with pytest.raises(ValueError, match="decoder_lm"):
            report.token_row_length()


def test_the_training_loop_does_not_know_the_decoder():
    """`train/loop.py` and `train/state.py` ask `model_report`: they import
    neither the decoder's modules nor the expert layer's, and read no
    `.decoder` of the configuration (the syntax tree, not the text: a comment
    may name the decoder)."""
    for name in ("loop.py", "state.py"):
        path = os.path.join(PACKAGE, "train", name)
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        imported, attributes = set(), set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                module = node.module or ""
                imported.add(module)
                imported.update(f"{module}.{a.name}" for a in node.names)
            elif isinstance(node, ast.Import):
                imported.update(a.name for a in node.names)
            elif isinstance(node, ast.Attribute):
                attributes.add(node.attr)
        for module in imported:
            assert not module.endswith(("ops.moe", "models.decoder_lm",
                                        "models.decoder_report")), (name, module)
            assert module.split(".")[-1] not in (
                "slot_bound", "decoder_lm", "decoder_report", "DecoderReport"
            ), (name, module)
        assert "decoder" not in attributes, name
        for gone in ("_publish_layer_kinds", "_moe_bound", "_publish_moe_load"):
            assert gone not in attributes, (name, gone)
    from ddp_classification_pytorch_tpu.train.loop import Trainer
    assert not [m for m in vars(Trainer) if "moe" in m or "layer_kinds" in m]
