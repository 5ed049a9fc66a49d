"""Compile for the chip, without the chip.

The TPU's compiler is installed here and compiles for a DESCRIBED v5e:2x2
(`/opt/skills/guides/on-chip-measurement` §2): what interpret mode cannot
show — a block the Mosaic compiler refuses, a program that does not fit the
device, a collective that is not where it should be — fails here at no chip
time. Nothing runs, so these say nothing about results or times.

Rules this file keeps: the topology is described inside a module-scoped
fixture (never at import, in a skipif, in parametrize arguments or in
conftest.py; not autouse), everything built from it is built in fixtures or
tests, the compilation cache is off around the compiles, and all such tests
live in this ONE file (only one process may hold the TPU library).
`_interpret()` sees the CPU here, so the tests steer it with monkeypatch —
not an option of the program.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ddp_classification_pytorch_tpu.analysis.sharding_audit import (
    collective_inventory,
    collective_wire_dtypes,
)
from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.parallel import mesh as meshlib
from ddp_classification_pytorch_tpu.train.state import (
    create_train_state,
    state_shardings,
)
from ddp_classification_pytorch_tpu.train.steps import (
    make_topk_predict_step,
    make_train_step,
)

HBM_BYTES = 16 * 10 ** 9  # one v5e chip (Google Cloud "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels(monkeypatch):
    """Both kernel modules with interpret mode steered off (ops/__init__
    re-exports a function named like the flash module, hence importlib)."""
    pk = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.pallas_kernels")
    fa = importlib.import_module(
        "ddp_classification_pytorch_tpu.ops.flash_attention")
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    return pk, fa


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------------------- kernels --

@pytest.mark.parametrize("rows,channels", [
    (128 * 56 * 56, 64),    # TResNet-M stem end: sub-128 lane dimension
    (128 * 7 * 7, 2048),    # TResNet-M top: widest (tile, c) VMEM blocks
])
def test_fused_bn_leaky_relu_compiles(one_chip, kernels, rows, channels):
    pk, _ = kernels
    x = jax.ShapeDtypeStruct((rows, channels), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one_chip)

    def fwd_and_vjp(x, scale, bias):
        def loss(x, scale, bias):
            y, _, _ = pk.batch_norm_leaky_relu(x, scale, bias)
            return jnp.sum(y.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, scale, bias)

    text = _compiled_text(fwd_and_vjp, x, v, v)
    assert "tpu_custom_call" in text, "the kernel was not compiled by Mosaic"


@pytest.mark.parametrize("shape,causal", [
    ((128, 197, 12, 64), False),   # ViT-B/16: single-block path, D=64 < 128
    ((1, 8192, 12, 64), False),    # long sequence, blocks of 512
    ((1, 8192, 12, 64), True),     # ... with the diagonal-clamped kv index
])
def test_flash_attention_compiles_forward_and_backward(one_chip, kernels,
                                                       shape, causal):
    _, fa = kernels
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd_and_vjp(q, k, v):
        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=causal)
            return jnp.sum(out.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_and_vjp, q, q, q)
    # the forward and the one backward kernel are two Mosaic calls
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")
    assert "flash_dkvq" in text and "flash_dq" not in text


# ------------------------------------------------------------ whole steps --

def _smoke_cfg(batch: int, reduce_dtype: str = "float32"):
    """chip_smoke.py's shapes: ResNet-50, 224 px, 1000 classes, bf16
    compute, uint8 wire."""
    cfg = get_preset("baseline")
    cfg.model.arch = "resnet50"
    cfg.model.dtype = "bfloat16"
    cfg.data.num_classes = 1000
    cfg.data.image_size = 224
    cfg.data.batch_size = batch
    cfg.data.input_dtype = "uint8"
    cfg.parallel.grad_reduce_dtype = reduce_dtype
    return cfg


def _abstract_state(cfg, mesh):
    """(model, tx, TrainState of ShapeDtypeStructs sharded as the trainer
    shards it). A described device holds no array, so the state is traced
    with eval_shape and the repo's own sharding rules are applied to the
    shapes."""
    box = {}

    def build():
        model, tx, state = create_train_state(cfg, mesh, steps_per_epoch=8)
        box["model"], box["tx"] = model, tx
        return state

    shape = jax.eval_shape(build)
    shardings = state_shardings(
        shape, mesh, meshlib.zero_opt_enabled(cfg.parallel.zero_opt, mesh))
    state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, shardings)
    return box["model"], box["tx"], state


def _batch(cfg, mesh):
    sh = meshlib.batch_sharding(mesh)
    b, h = cfg.data.batch_size, cfg.data.image_size
    return (jax.ShapeDtypeStruct((b, h, h, 3), jnp.uint8, sharding=sh),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=sh))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_resnet50_train_step_fits_one_chip(topo):
    cfg = _smoke_cfg(batch=128)
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        model, tx, state = _abstract_state(cfg, mesh)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        compiled = step.lower(state, *_batch(cfg, mesh)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    m = compiled.memory_analysis()
    # donation holds on the chip too: every state byte aliases
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes


def test_resnet50_serve_predict_step_compiles(topo):
    cfg = _smoke_cfg(batch=8)  # the smoke's largest bucket
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        model, _, state = _abstract_state(cfg, mesh)
        predict = make_topk_predict_step(cfg, model, 5, mesh=mesh)
        images, _ = _batch(cfg, mesh)
        compiled = predict.lower(state, images).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_resnet50_dp4_step(topo):
    """`chip_smoke.py --chips 4`'s program: the gradient crosses the chips
    once, at bf16; optimizer state is sharded under ZeRO-1 (a quarter of
    the momentum per device) and the updated parameters are all-gathered."""
    cfg = _smoke_cfg(batch=512, reduce_dtype="bfloat16")
    mesh = meshlib.make_mesh(meshlib.MeshSpec(4, 1), devices=topo.devices)
    with mesh:
        model, tx, state = _abstract_state(cfg, mesh)
        step = make_train_step(cfg, model, tx, mesh=mesh)
        images, labels = _batch(cfg, mesh)
        compiled = step.lower(state, images, labels).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    leaf_bytes = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    param_bytes = leaf_bytes(state.params)
    # the repo's own HLO audit reads the chip's program text as it reads
    # the CPU's (analysis/sharding_audit.py)
    text = compiled.as_text()
    kinds = collective_inventory(text, mesh)["kinds"]
    wire = collective_wire_dtypes(text)
    # bf16 wire: ~2 bytes per parameter all-reduced over the data axis,
    # and no f32 gradient reduction beside it (what is left in f32 is BN
    # statistics and scalars) — an f32 wire would read ~1.0x here
    ar = kinds["all-reduce"]
    assert (0.45 * param_bytes <= ar["axes"]["data"]
            <= 0.55 * param_bytes), (ar, param_bytes)
    n_leaves = len(jax.tree_util.tree_leaves(state.params))
    assert wire["all-reduce"].get("bf16", 0) >= 0.9 * n_leaves, wire
    # ZeRO-1: parameters come back by all-gather after the sharded update
    assert kinds["all-gather"]["bytes"] >= 0.9 * param_bytes, kinds
    assert set(wire["all-gather"]) == {"f32"}, wire
    # ... and each device is handed a quarter of the momentum, not all of it
    replicated = (leaf_bytes(state) + (images.size + labels.size * 4) // 4)
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args < replicated - 0.5 * leaf_bytes(state.opt_state), (
        args, replicated)


# ------------------------------------------------- the token decoder (PR 28) --

@pytest.mark.parametrize("window", [None, 4096], ids=["causal", "window4096"])
def test_flash_attention_grouped_heads_and_window_compile(one_chip, kernels, window):
    """SmallThinker's attention at its published widths: 28 query heads on 4
    KV heads of 128, 8,192 tokens, full causal or a window of 4,096."""
    _, fa = kernels
    q = jax.ShapeDtypeStruct((1, 8192, 28, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16, sharding=one_chip)

    def fwd_and_vjp(q, k, v):
        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True, window=window)
            return jnp.sum(out.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_and_vjp, q, kv, kv)
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")


def test_sparse_experts_compile_to_grouped_matmul_kernels(one_chip):
    """The expert layer at the published widths, one chip's 16 of 64
    experts, 16,384 tokens, top-6: the grouped matmuls lower to the
    compiler's ragged-dot kernels (not to a dense masked product)."""
    from ddp_classification_pytorch_tpu.ops.moe import sparse_moe

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    u, logits = sds((16384, 2560), jnp.bfloat16), sds((16384, 64), jnp.float32)
    bank = sds((16, 2560, 768), jnp.float32)
    down = sds((16, 768, 2560), jnp.float32)

    def fwd_and_vjp(u, logits, *w):
        def loss(u, logits, *w):
            y, _ = sparse_moe(u, logits, *w, top_k=6)
            return jnp.sum(y)
        return jax.value_and_grad(loss, argnums=(0, 2, 3, 4))(u, logits, *w)

    compiled = jax.jit(fwd_and_vjp).lower(u, logits, bank, bank, down).compile()
    assert "ragged-dot" in compiled.as_text()
    # grouped, not dense: far under 16 experts x every slot
    dense = 3 * 2 * 98304 * 3 * 2560 * 768 * 16
    assert compiled.cost_analysis()["flops"] < dense / 8


def test_decoder_train_step_fits_one_chip(topo, kernels):
    """The benchmark's SmallThinker cell as `cli.train` builds it (656 M
    float32 parameters under Adam, 2 rows of 8,192 tokens, --remat, the head
    in row blocks): the first configuration whose constraint is memory."""
    import json
    import os

    from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        conf = json.load(f)
    cfg = config_from_args(build_parser().parse_args(
        conf["argv"] + ["--dataset", "tokens", "--batchsize",
                        str(conf["batch_per_chip"])]))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        model, tx, state = _abstract_state(cfg, mesh)
        assert sum(a.size for a in jax.tree_util.tree_leaves(state.params)) \
            == conf["parameters"] == 656529920
        step = make_train_step(cfg, model, tx, mesh=mesh)
        tokens = jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.model.decoder.seq_len), jnp.int32,
            sharding=meshlib.batch_sharding(mesh))
        compiled = step.lower(state, tokens, tokens).compile()
    assert _device_bytes(compiled) < 0.9 * HBM_BYTES
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes
    text = compiled.as_text()
    # 4 attention layers x (forward, the fused backward) kernels
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= 8
    assert "flash_dkvq" in text and "flash_dq" not in text


# ------------------------------- latent attention, the second decoder (PR 32) --

def test_flash_attention_latent_scores_compile(one_chip, kernels):
    """JoyAI-LLM-Flash's attention at its published widths: 32 heads, scores
    over 128 + 64 (the 64 rotary dims of the key ONE head for all 32), values
    of 128, 8,192 tokens. No pad to 192, no key that repeats the rotary head:
    Mosaic takes the (512, 64) blocks as they are."""
    _, fa = kernels

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    args = (sds(1, 8192, 32, 128), sds(1, 8192, 32, 128), sds(1, 8192, 32, 128),
            sds(1, 8192, 32, 64), sds(1, 8192, 1, 64))

    def fwd_and_vjp(*a):
        def loss(q, k, v, q_rope, k_rope):
            out = fa.flash_attention(q, k, v, causal=True, q_rope=q_rope,
                                     k_rope=k_rope)
            return jnp.sum(out.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*a)

    text = _compiled_text(fwd_and_vjp, *args)
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")


def test_latent_decoder_train_step_fits_one_chip(topo, kernels):
    """The benchmark's JoyAI-LLM-Flash cell as `cli.train` builds it (680 M
    float32 parameters under Adam, 2 rows of 8,192 tokens, --remat, two
    losses through one head in row blocks)."""
    import json
    import os

    from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "joyai_llm_flash.json")) as f:
        conf = json.load(f)
    cfg = config_from_args(build_parser().parse_args(
        conf["argv"] + ["--dataset", "tokens", "--batchsize",
                        str(conf["batch_per_chip"])]))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        model, tx, state = _abstract_state(cfg, mesh)
        assert sum(a.size for a in jax.tree_util.tree_leaves(state.params)) \
            == conf["parameters"] == 680441088
        step = make_train_step(cfg, model, tx, mesh=mesh)
        tokens = jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.model.decoder.seq_len), jnp.int32,
            sharding=meshlib.batch_sharding(mesh))
        compiled = step.lower(state, tokens, tokens).compile()
    # 15.8 GB by the compiler's count (state 10.9 GB, temporaries 4.9): over
    # the 0.9 of 16 GB the other steps keep to, so held against what the
    # chip's allocator hands out (`memory_stats()["bytes_limit"]` of a v5e,
    # PR 32's chip runs, which peak at 15.52 GB: PERF.md section 5)
    assert _device_bytes(compiled) < 0.95 * 16_909_336_064
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes
    text = compiled.as_text()
    # 6 attention blocks x (forward, the fused backward) kernels
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= 12
    assert "flash_dkvq" in text and "flash_dq" not in text


# ------------- the short convolution beside 64-wide heads, the third decoder --

def test_flash_attention_64_wide_heads_compile(one_chip, kernels):
    """LFM2-8B-A1B's attention at its published widths: 32 query heads on 8
    KV heads of 64, 2 rows of 8,192 tokens, full causal. The head is not
    padded to 128: Mosaic takes the (512, 64) blocks of q, k and v as they
    are in both kernels."""
    _, fa = kernels
    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16, sharding=one_chip)

    def fwd_and_vjp(q, k, v):
        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_and_vjp, q, kv, kv)
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")


def test_hybrid_decoder_train_step_fits_one_chip(topo, kernels):
    """The benchmark's LFM2-8B-A1B cell as `cli.train` builds it (508 M
    float32 parameters under Adam, four short-convolution layers and one
    attention layer, 2 rows of 8,192 tokens, --remat, the tied head in row
    blocks), with the compiler's memory count printed."""
    import json
    import os

    from ddp_classification_pytorch_tpu.cli.train import build_parser, config_from_args

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "lfm2_8b_a1b.json")) as f:
        conf = json.load(f)
    cfg = config_from_args(build_parser().parse_args(
        conf["argv"] + ["--dataset", "tokens", "--batchsize",
                        str(conf["batch_per_chip"])]))
    mesh = meshlib.make_mesh(meshlib.MeshSpec(1, 1), devices=topo.devices[:1])
    with mesh:
        model, tx, state = _abstract_state(cfg, mesh)
        assert "lm_head" not in state.params      # tied: one table
        assert sum(a.size for a in jax.tree_util.tree_leaves(state.params)) \
            == conf["parameters"] == 507820288
        step = make_train_step(cfg, model, tx, mesh=mesh)
        tokens = jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.model.decoder.seq_len), jnp.int32,
            sharding=meshlib.batch_sharding(mesh))
        compiled = step.lower(state, tokens, tokens).compile()
    m = compiled.memory_analysis()
    print(f"lfm2_ep4_8k step by the compiler's count: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB, device total "
          f"{_device_bytes(compiled) / 1e9:.2f} GB")
    assert _device_bytes(compiled) < 0.9 * HBM_BYTES
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes
    text = compiled.as_text()
    # one attention block x (forward, the fused backward); the convolution
    # is plain XLA
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= 2
    assert "flash_dkvq" in text and "flash_dq" not in text
