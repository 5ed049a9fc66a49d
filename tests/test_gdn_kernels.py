"""Gated DeltaNet's recurrence on its kernels (ops/gdn.py: `gdn_fwd`, and in
the backward `gdn_states` + `gdn_bwd`; interpret mode here, as the flash and
KDA kernels' tests) at the published head widths, 96 x 192: against the
token-by-token recurrence (benchmark/reference/olmo_hybrid_7b.py, imported as
it stands), against `_chunked` in plain XLA, which shapes take them, and what
their backward keeps. A file of its own: one xdist worker under
`--dist loadfile`."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import olmo_hybrid_7b as ref  # noqa: E402
from ddp_classification_pytorch_tpu.ops import gdn  # noqa: E402
from test_decoder_kda import out_and_grads, worst  # noqa: E402

DK, DV = 96, 192


def inputs(heads, t, seed=0):
    """1 row: unit q (x d_k^-1/2) and k, beta drawn up to 1.99, a tenth of the
    tokens' g down to -30 (the unbounded decay), the rest down to -0.3."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    hard = jax.random.uniform(ks[5], (1, t, heads)) < 0.1
    g = jnp.where(hard, -30.0, -0.3) * jax.random.uniform(ks[3], (1, t, heads))
    return (unit(jax.random.normal(ks[0], (1, t, heads, DK))) * DK ** -0.5,
            unit(jax.random.normal(ks[1], (1, t, heads, DK))),
            jax.random.normal(ks[2], (1, t, heads, DV)), g,
            1.99 * jax.random.uniform(ks[4], (1, t, heads)))


@functools.lru_cache(maxsize=None)
def kernels_at(heads, t, dtype):
    """(inputs, weight, (o, five gradients)) of the kernel path."""
    args = inputs(heads, t, seed=t)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    assert gdn.takes_kernel(t, DK, DV)
    return args, weight, jax.jit(out_and_grads(
        functools.partial(gdn.gdn_chunked, dtype=jnp.dtype(dtype)), weight))(*args)


def recurrence(*xs):
    with jax.default_matmul_precision("highest"):
        return ref.gdn_recurrence(*xs)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,t", [(3, 128), (5, 192), (3, 64)],
                         ids=["3x2chunks", "5x3chunks", "3x1chunk"])
def test_kernels_are_the_token_by_token_recurrence_forward_and_every_gradient(
        heads, t, dtype):
    """Two chunks and three: the state leaves VMEM for no chunk boundary, the
    reverse walk carries dS back over them, a block of two chunks is visited
    twice and the third chunk's block is half a lane tile; one chunk: a block
    is the row; 3 heads and 5 a grid step.
    float32 operands: the order of the sums is what is left; bf16: their
    rounding (2^-8 a product)."""
    args, weight, got = kernels_at(heads, t, dtype)
    want = jax.jit(out_and_grads(recurrence, weight))(*args)
    assert all(bool(jnp.isfinite(x).all()) for x in (got[0], *got[1]))
    assert worst(got, want) < (1e-4 if dtype == "float32" else 2e-2)
    assert got[0].shape == (1, t, heads, DV) and got[0].dtype == jnp.float32
    assert [x.dtype for x in got[1]] == [x.dtype for x in args]
    assert [x.shape for x in got[1]] == [x.shape for x in args]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_and_the_chunks_in_plain_xla_agree(dtype):
    """`_chunked`, the path every other shape takes, is the kernels' second
    oracle: the same arithmetic at the same operand dtypes, so in float32 they
    differ by the order of the sums, in bf16 by roundings of the same size as
    either's distance from the recurrence."""
    args, weight, got = kernels_at(3, 128, dtype)
    xla = jax.jit(out_and_grads(functools.partial(
        gdn._grouped, dtype=jnp.dtype(dtype), core=gdn._chunked), weight))(*args)
    assert worst(got, xla) < (1e-4 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("t,dk,dv,chunk,kernel", [
    (8192, 96, 192, 64, True),    # the published sizes
    (128, 96, 192, 64, True), (64, 128, 128, 64, True), (128, 64, 256, 64, True),
    (128, 6, 12, 64, False),      # the tests' heads
    (128, 32, 192, 64, False), (128, 96, 48, 64, False),
    (128, 72, 200, 64, False),    # not whole sublane tiles
    (48, 96, 192, 64, False),     # one shorter chunk
    (128, 96, 192, 32, False),    # another chunk length: the tests' own
], ids=str)
def test_the_shapes_decide_which_rows_take_the_kernels(t, dk, dv, chunk, kernel):
    assert gdn.takes_kernel(t, dk, dv, chunk) == kernel
    args = [jax.ShapeDtypeStruct((1, t, 3, d), jnp.float32) for d in (dk, dk, dv)] + [
        jax.ShapeDtypeStruct((1, t, 3), jnp.float32)] * 2

    def core(*a):
        with jax.named_scope("gdn.core"):
            return gdn.gdn_chunked(*a, chunk=chunk)

    jaxpr = str(jax.make_jaxpr(core)(*args))
    assert ("name=gdn_fwd" in jaxpr) == kernel == ("scan" not in jaxpr)
    if t <= 128:    # under the scope the readers sum: the jitted forward's call
        text = jax.jit(core).lower(*args).as_text(debug_info=True)
        assert ('gdn.core/jit"' in text and "gdn_fwd/" in text) == kernel


@pytest.mark.parametrize("t", [72, 8200])
def test_a_row_that_is_not_whole_chunks_is_refused_at_the_published_widths_too(t):
    with pytest.raises(ValueError, match="chunk"):
        gdn.takes_kernel(t, DK, DV)
    with pytest.raises(ValueError, match="chunk"):
        gdn.gdn_chunked(*inputs(3, t))


def minor(q, k, v, g, beta):
    """The kernels' own layout: q, k, v (B, H, d, T), the tokens minor."""
    return (*(x.transpose(0, 2, 3, 1) for x in (q, k, v)), g, beta)


def test_the_kernels_backward_keeps_the_ops_inputs_and_nothing_else():
    """What `jax.checkpoint` keeps of `_chunked`: no state, no chunk matrix,
    no solve. At the kernels' own entry (tokens-minor operands)."""
    from jax._src.ad_checkpoint import saved_residuals

    args = minor(*inputs(3, 128))
    assert [x.shape for x in args[:3]] == [(1, 3, DK, 128)] * 2 + [(1, 3, DV, 128)]
    kept = [(a.shape, why) for a, why in saved_residuals(gdn.gdn_tokens_minor, *args)]
    assert kept == [(x.shape, f"from the argument {name}")
                    for name, x in zip(("q", "k", "v", "g", "beta"), args)], kept


def test_the_entry_in_the_kernels_layout_is_the_other_transposed_and_refuses_the_rest():
    """`gdn_chunked` is `gdn_tokens_minor` between two transposes, gradients
    too; the own entry refuses what `takes_kernel` does not take."""
    args, weight, got = kernels_at(3, 128, "float32")
    own = jax.jit(out_and_grads(
        functools.partial(gdn.gdn_tokens_minor, dtype=jnp.float32),
        weight.transpose(0, 2, 3, 1)))(*minor(*args))
    back = [x.transpose(0, 3, 1, 2) for x in (own[0], *own[1][:3])] + list(own[1][3:])
    for a, b in zip(back, (got[0], *got[1])):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    with pytest.raises(ValueError, match="takes_kernel"):
        gdn.gdn_tokens_minor(*minor(*(x[..., :32] if x.ndim == 4 else x
                                      for x in inputs(3, 128))))


@pytest.mark.parametrize("dk,dv,handed", [(96, 192, "float32"), (6, 12, "bfloat16")],
                         ids=["kernels", "xla"])
def test_the_layer_hands_q_k_v_over_in_float32_where_the_kernels_take_them(
        monkeypatch, dk, dv, handed):
    """A bf16 layer (the cells' `--dtype`): at the published head widths q, k, v
    reach the recurrence unrounded (the kernels round at their matmuls'
    operands), at the tests' widths in `dtype`, which is what `_chunked` takes;
    g and beta float32 either way. Read where the traced layer calls the op."""
    import test_decoder_gdn as layers
    from ddp_classification_pytorch_tpu.models import decoder_lm

    dc = layers.program(dict(layers.ARCH, gdn_key_dim=dk, gdn_value_dim=dv),
                        dtype="bfloat16")[0].model.decoder
    layer = decoder_lm.DecoderLayer(dc, False, None, jnp.bfloat16, None, None, 1024,
                                    False, "gdn")
    x = jax.ShapeDtypeStruct((1, 128, dc.hidden_size), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    seen = []

    def spy(q, k, v, g, beta, **kw):
        seen.extend(a.dtype.name for a in (q, k, v, g, beta))
        return jnp.zeros(v.shape, jnp.float32)

    monkeypatch.setattr(gdn, "gdn_chunked", spy)
    jax.eval_shape(layer.apply, params, x)
    assert seen == [handed] * 3 + ["float32"] * 2, seen
    assert gdn.takes_kernel(128, dk, dv) == (handed == "float32")
