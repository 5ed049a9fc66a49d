"""The token decoders' whole train steps at the published widths of their
benchmark cells, compiled for a described v5e (rules and fixtures:
chip_compile_common.py). A `model_config` PR that adds a decoder adds its
cell as one more CASE of the test below."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from chip_compile_common import (  # noqa: F401
    HBM_BYTES,
    abstract_state,
    device_bytes,
    kernels,
    topo,
)

from hlo_text import conditionals

from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args
from ddp_classification_pytorch_tpu.ops.moe import slot_bound
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.steps import make_train_step

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "benchmark", "configs")


@pytest.mark.parametrize("config,parameters,byte_limit,attention_blocks", [
    # SmallThinker (PR 28): 656 M float32 parameters under Adam, the first
    # configuration whose constraint is memory
    pytest.param("smallthinker_21b_a3b.json", 656529920, 0.9 * HBM_BYTES, 4,
                 id="decoder"),
    # JoyAI-LLM-Flash (PR 32): two losses through one head. 15.8 GB by the
    # compiler's count (state 10.9 GB, temporaries 4.9): over the 0.9 of
    # 16 GB the other steps keep to, so held against what the chip's
    # allocator hands out (`memory_stats()["bytes_limit"]` of a v5e, PR 32's
    # chip runs, which peak at 15.52 GB: PERF.md section 5)
    pytest.param("joyai_llm_flash.json", 680441088, 0.95 * 16_909_336_064, 6,
                 id="latent_decoder"),
    # LFM2-8B-A1B (PR 35): four short-convolution layers (plain XLA) and one
    # attention layer, the head tied to the embedding
    pytest.param("lfm2_8b_a1b.json", 507820288, 0.9 * HBM_BYTES, 1,
                 id="hybrid_decoder"),
    # Ling-3.0-flash (PR 42): six Kimi-delta-attention layers (plain XLA: the
    # chunked recurrence) and one latent-attention layer without a query
    # bottleneck, 1 row of 8,192 tokens. State 13.15 GB: held, as the latent
    # decoder is, against what the chip's allocator hands out
    pytest.param("ling_3_0_flash.json", 822036416, 0.95 * 16_909_336_064, 1,
                 id="delta_decoder"),
    # Ouro-2.6B (PR 45): four dense layers walked four times by ONE scan whose
    # body is the stack (8 kernel calls in the text, 32 layer-kernel runs a
    # step), both 49,152-row tables, 1 row of 8,192 tokens
    pytest.param("ouro_2_6b.json", 406884353, 0.9 * HBM_BYTES, 4,
                 id="looped_decoder"),
    # Olmo-Hybrid-7B (PR 49): three Gated DeltaNet layers (since PR 51 the
    # scalar-decay recurrence on its kernels, 5 of the 15 held heads a grid
    # step) and one attention layer at 15 of 30 heads, a QK-norm over the whole
    # projection, norms on the sub-layers' outputs only, 1 row of 8,192
    # tokens. State 12.26 GB: held against what the chip's allocator hands out
    pytest.param("olmo_hybrid_7b.json", 766241946, 0.95 * 16_909_336_064, 1,
                 id="gated_delta_decoder"),
    # SDAR-30B-A3B-Chat (PR 53): six layers trained by block diffusion, 1 row
    # of 8,192 tokens as two streams (16,384 positions a layer) under the
    # two-stream mask in the flash kernels (compact kv walk), 16 of 128
    # experts held (bounded sorted rows); the batch is (x_0, [x_t ; j])
    pytest.param("sdar_30b_a3b.json", 645623296, 0.95 * 16_909_336_064, 6,
                 id="block_diffusion_decoder"),
])
def test_train_step_fits_one_chip(topo, kernels, config, parameters,
                                  byte_limit, attention_blocks):
    """A benchmark cell's step as `cli.train` builds it (the cell's rows of
    8,192 tokens, --remat, the head in row blocks) lowers, compiles and fits,
    with the compiler's memory count printed."""
    with open(os.path.join(CONFIGS, config)) as f:
        conf = json.load(f)
    cfg = config_from_args(build_parser().parse_args(
        conf["argv"] + ["--dataset", "tokens", "--batchsize",
                        str(conf["batch_per_chip"])]))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        model, tx, state = abstract_state(cfg, mesh)
        # tied: one table
        assert ("lm_head" in state.params) == (not cfg.model.decoder.tied_embeddings)
        assert sum(a.size for a in jax.tree_util.tree_leaves(state.params)) \
            == conf["parameters"] == parameters
        step = make_train_step(cfg, model, tx, mesh=mesh)
        tokens = jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.model.decoder.seq_len), jnp.int32,
            sharding=meshlib.batch_sharding(mesh))
        labels = tokens
        if cfg.model.decoder.diffusion:   # the loader's [x_t ; j] (B, 2, L)
            labels = jax.ShapeDtypeStruct(
                (cfg.data.batch_size, 2, cfg.model.decoder.seq_len), jnp.int32,
                sharding=meshlib.batch_sharding(mesh))
        compiled = step.lower(state, tokens, labels).compile()
    m = compiled.memory_analysis()
    print(f"{config} step by the compiler's count: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB, device total "
          f"{device_bytes(compiled) / 1e9:.2f} GB")
    assert device_bytes(compiled) < byte_limit
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes
    text = compiled.as_text()
    # each attention block is two kernels: forward, the fused backward
    dc = cfg.model.decoder
    assert ("ragged-dot" in text) == (dc.dense_layers < dc.num_layers)
    # holding under a quarter of the router's experts, a routing layer's
    # sorted rows are bounded (ops/moe.py::slot_bound: 4,096 of 65,536 in
    # Ling's cell, 32,768 of 131,072 in JoyAI's): one `conditional` forward
    # and one backward a layer, the ragged-dot kernels in both branches of
    # each (the one window, the walk over windows), and of the worst-case S
    # rows no float32 array and none of the experts' width in either
    slots = cfg.data.batch_size * dc.positions * dc.top_k
    bounded = slot_bound(slots, dc.held, dc.num_experts) < slots
    conds = conditionals(text)
    assert len(conds) == 2 * len(dc.moe_layer_names()) * bounded
    for results, branches in conds:
        assert f"[{slots}," not in results, results
        assert sorted(" while(" in b for b in branches) == [False, True]
        for branch in branches:
            assert "ragged-dot" in branch
            for worst_case in (f"[{slots},{dc.expert_width}]",
                               f"[{slots},{2 * dc.expert_width}]",
                               f"f32[{slots},{dc.hidden_size}]"):
                assert worst_case not in branch, worst_case
    assert text.count("tpu_custom_call") >= 2 * attention_blocks
    assert "flash_dkvq" in text and "flash_dq" not in text
    # a delta layer's recurrence is three more: the forward walk, and in the
    # backward the walk that keeps the chunks' states and the reverse walk;
    # its input side (taps, SiLU, norms, decay) two, forward and backward, and
    # its output side (the gated per-head norm) two
    delta = sum(dc.layout(dc.kda_layout))
    for name in ("kda_fwd", "kda_states", "kda_bwd", "kda_prepare_fwd",
                 "kda_prepare_bwd", "kda_gated_norm_fwd", "kda_gated_norm_bwd"):
        assert (name in text) == bool(delta), name
    assert text.count("tpu_custom_call") >= 2 * attention_blocks + 7 * delta
    # a Gated DeltaNet layer's recurrence is three too (ops/gdn.py), and all
    # it has: under --remat the forward walk runs once, its output saved by name
    gated = sum(dc.layout(dc.gdn_layout))
    for name in ("gdn_fwd", "gdn_states", "gdn_bwd"):
        assert (name in text) == bool(gated), name
    if gated:
        assert text.count("tpu_custom_call") == 2 * attention_blocks + 3 * gated
    if dc.loops > 1:
        # the passes are one loop: the program holds the stack once, not
        # once a pass
        assert text.count("tpu_custom_call") < 2 * attention_blocks * dc.loops
