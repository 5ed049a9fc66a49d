"""The kernels at their published shapes, compiled by Mosaic for a described
v5e (rules and fixtures: chip_compile_common.py; the whole steps are in
test_chip_compile_resnet.py and test_chip_compile_decoder.py)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from chip_compile_common import kernels, one_chip, topo  # noqa: F401
from jax.sharding import NamedSharding, PartitionSpec as P


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_text(fa, sharding, shapes, **kw) -> str:
    """The compiled forward and backward of flash attention over bf16
    operands of `shapes`: (q, k, v) or (q, k, v, q_rope, k_rope)."""
    names = ("q", "k", "v", "q_rope", "k_rope")[:len(shapes)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]

    def loss(*a):
        out = fa.flash_attention(*a[:3], **dict(zip(names[3:], a[3:])), **kw)
        return jnp.sum(out.astype(jnp.float32))

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(len(args)))), *args)
    # the forward and the one backward kernel are two Mosaic calls
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")
    return text


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
    text = _flash_text(kernels[1], one_chip, [shape] * 3, causal=causal)
    assert "flash_dkvq" in text and "flash_dq" not in text


@pytest.mark.parametrize("window", [None, 4096], ids=["causal", "window4096"])
def test_flash_attention_grouped_heads_and_window_compile(one_chip, kernels, window):
    """SmallThinker's attention at its published widths: 28 query heads on 4
    KV heads of 128, 8,192 tokens, full causal or a window of 4,096."""
    _flash_text(kernels[1], one_chip,
                [(1, 8192, 28, 128)] + [(1, 8192, 4, 128)] * 2,
                causal=True, window=window)


def test_flash_attention_latent_scores_compile(one_chip, kernels):
    """JoyAI-LLM-Flash's attention at its published widths: 32 heads, scores
    over 128 + 64 (the 64 rotary dims of the key ONE head for all 32), values
    of 128, 8,192 tokens. No pad to 192, no key that repeats the rotary head:
    Mosaic takes the (512, 64) blocks as they are."""
    _flash_text(kernels[1], one_chip,
                [(1, 8192, 32, 128)] * 3 + [(1, 8192, 32, 64), (1, 8192, 1, 64)],
                causal=True)


def test_flash_attention_64_wide_heads_compile(one_chip, kernels):
    """LFM2-8B-A1B's attention at its published widths: 32 query heads on 8
    KV heads of 64, 2 rows of 8,192 tokens, full causal. The head is not
    padded to 128: Mosaic takes the (512, 64) blocks of q, k and v as they
    are in both kernels."""
    _flash_text(kernels[1], one_chip,
                [(2, 8192, 32, 64)] + [(2, 8192, 8, 64)] * 2, causal=True)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "data4_batch512"])
def test_rows_attention_compiles_on_the_projections_layout(topo, kernels,
                                                           chips):
    """ViT-B/16's attention core at `vitb16_pool`'s shapes: 128 images a chip
    of 196 tokens, 12 heads of 64, bf16, read from the qkv projection's
    (B, T, 2304) and written as (B, T, 768). Two Mosaic calls and no
    head-major copy of q, k or v beside them; on the described 2x2 the same
    call at batch 512 inside its shard_map over `data` (a Mosaic call the
    compiler would have to partition is refused)."""
    from ddp_classification_pytorch_tpu.ops import rows_attention as ra
    from ddp_classification_pytorch_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(meshlib.MeshSpec(chips, 1),
                             devices=topo.devices[:chips])
    axes = (meshlib.DATA_AXIS,) if chips > 1 else ()
    qkv = jax.ShapeDtypeStruct(
        (128 * chips, 196, 3 * 12 * 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(meshlib.DATA_AXIS)))
    assert ra.rows_supported(196, 12, 64, 2)

    def loss(qkv):
        out = ra.rows_attention(qkv, 12, mesh=mesh if axes else None,
                                batch_axes=axes)
        return jnp.sum(out.astype(jnp.float32))

    with mesh:
        text = _compiled_text(jax.value_and_grad(loss), qkv)
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")
    assert "attn_rows_fwd" in text and "attn_rows_bwd" in text
    assert "[128,12,64,196]" not in text and "[128,12,196,64]" not in text
    assert "[128,12,196,196]" not in text    # nor the scores


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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("side", ["input", "output"])
def test_kda_sides_compile_forward_and_backward(one_chip, kernels, side, dtype):
    """Ling-3.0-flash's delta layer around its recurrence, at the published
    widths: 32 heads of 128 over 8,192 tokens in (B, T, H·d), blocks of
    (256, 1,024); a head's norm a lane reduction inside one tile. The input
    side reads the 16 rows before a block for its taps and keeps their sums
    in a resident block; the output side's gate rides (B, H, T, 1)."""
    from ddp_classification_pytorch_tpu.ops import kda_gated_norm, kda_prepare

    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert kda_prepare.takes_kernel(8192, 128, 4)
    if side == "input":
        op = kda_prepare.kda_prepare
        args = ([arg((1, 8192, 4096), dtype)] * 4 + [arg((1, 8192, 32), dtype)]
                + [arg((4, 4096))] * 3 + [arg((32,)), arg((4096,))])
    else:
        op = functools.partial(kda_gated_norm.kda_gated_norm, eps=1e-6, dtype=dtype)
        args = [arg((1, 8192, 4096)), arg((1, 8192, 32)), arg((128,))]

    def loss(*a):
        return sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree_util.tree_leaves(op(*a)))

    text = _compiled_text(jax.value_and_grad(loss, argnums=tuple(range(len(args)))), *args)
    assert text.count("tpu_custom_call") == 2, text.count("tpu_custom_call")
    name = {"input": "kda_prepare", "output": "kda_gated_norm"}[side]
    assert f"{name}_fwd" in text and f"{name}_bwd" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_gdn_recurrence_compiles_forward_and_backward(one_chip, kernels, dtype):
    """Olmo-Hybrid-7B's recurrence at the cell's sizes: 15 heads of 96 x 192
    over 8,192 tokens, tokens-minor (B, H, d, T) blocks of 5 heads x 128
    tokens (two chunks, each visited by two grid steps: a traced lane roll, a
    select on the way out), g and beta a head's tokens along the lanes; the
    backward's first walk keeps 0.53 GB of states."""
    from ddp_classification_pytorch_tpu.ops import gdn

    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert gdn.takes_kernel(8192, 96, 192)
    args = ([arg((1, 8192, 15, 96), dtype)] * 2 + [arg((1, 8192, 15, 192), dtype)]
            + [arg((1, 8192, 15))] * 2)

    def loss(*a):
        return jnp.sum(gdn.gdn_chunked(*a, dtype=dtype))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3, text.count("tpu_custom_call")
    assert all(name in text for name in ("gdn_fwd", "gdn_states", "gdn_bwd"))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
