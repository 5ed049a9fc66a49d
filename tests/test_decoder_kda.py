"""The decoder's fourth kind of layer (models/decoder_lm.py as Ling-3.0-flash
configures it: Kimi delta attention — a state carried along the row — in
most layers, latent attention without a query bottleneck in the others, a
head-wise output gate on both, a group-limited sigmoid router beside a shared
expert): the chunked op (ops/kda.py) against the token-by-token recurrence,
the program against its plain reference (benchmark/reference/
ling_3_0_flash.py, imported as it stands: it takes nothing from the program),
the group-limited choice against plain sorting, the expert share, the
configuration's arithmetic, and the counters that show the layout a run
built. CPU, toy sizes."""

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

from benchmark.flops import ling_3_0_flash as flops  # noqa: E402
from benchmark.reference import common, ling_3_0_flash as ref  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.models import decoder_lm  # noqa: E402
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops import kda, kda_prepare  # noqa: E402
from ddp_classification_pytorch_tpu.ops.kda import kda_chunked  # noqa: E402
from ddp_classification_pytorch_tpu.ops.kda_gated_norm import kda_gated_norm  # noqa: E402
from ddp_classification_pytorch_tpu.ops.moe import route_top_k, sparse_moe  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import _lm_loss  # noqa: E402
from test_decoder_lm import batch, flat_tree, program_tree  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "ling_3_0_flash.json")) as f:
    CONF = json.load(f)

# two KDA layers (one dense, one routed) and a latent-attention layer; experts
# 4-7 of 16 held: a share that starts at a group's boundary
ARCH = {"vocab_size": 96, "hidden_size": 32, "num_layers": 3, "num_heads": 4,
        "head_dim": 16, "rope_dim": 8, "v_head_dim": 16, "q_rank": 0, "kv_rank": 16,
        "kda_layout": [1, 1, 0], "conv_kernel": 4, "kda_lower_bound": -5.0,
        "out_gate": 1, "dense_layers": 1, "dense_width": 48, "expert_width": 16,
        "num_experts": 16, "experts_held": 4, "first_expert": 4, "top_k": 3,
        "n_group": 4, "topk_group": 2, "shared_experts": 1, "router_scale": 2.5,
        "rope_theta": 6e6, "rms_eps": 1e-6, "seq_len": 128}
KINDS = ["--attention", "mla", "--rope_pairing", "interleaved", "--activation",
         "silu", "--router", "sigmoid", "--router_tap", "post", "--rope_layout", "1",
         "--window_layout", "0", "--num_kv_heads", "4"]


def cli_argv(arch, *extra, dtype="float32"):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens", "--dtype",
            dtype, "--optimizer", "adam", "--head_block", "16", *KINDS]
    for key, value in arch.items():
        if key == "kda_lower_bound":    # the reference's; ops/kda.py's constant
            continue
        argv += [f"--{key}", ",".join(map(str, value)) if isinstance(value, list)
                 else str(value)]
    return argv + list(extra)


def program(arch, *extra, **kinds):
    cfg = config_from_args(build_parser().parse_args(cli_argv(arch, *extra, **kinds)))
    model = build_model(cfg.model, cfg.data.num_classes)
    loss_fn, metrics_fn = _lm_loss(cfg, model)
    return model, loss_fn, metrics_fn


@functools.lru_cache(maxsize=None)
def reference_grad():
    return jax.jit(jax.value_and_grad(ref.loss_for(ARCH)))


def mixer_inputs(t, heads=2, d=8, seed=0, lower=-5.0, shift=-2.0, rows=2):
    """q, k (unit length), v, the log decay g in (lower, 0) and beta."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (rows, t, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, t, heads, d)))
    v = jax.random.normal(ks[2], (rows, t, heads, d))
    g = lower * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (rows, t, heads, d)) + shift)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, t, heads)))
    return q, k, v, g, beta


# (a) the op ---------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def recurrence_at_128():
    """The recurrence's output and gradients on one set of inputs, taken ONCE
    for every chunk length below."""
    args = mixer_inputs(128)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 2, 8))
    want, grads = jax.jit(lambda *a: (
        ref.kda_recurrence(*a),
        jax.grad(lambda *b: jnp.sum(weight * ref.kda_recurrence(*b)),
                 argnums=(0, 1, 2, 3, 4))(*a)))(*args)
    return args, weight, want, grads


@pytest.mark.parametrize("chunk,head_group", [(16, 0), (32, 1), (64, 0), (8, 2)],
                         ids=lambda v: str(v))
def test_chunked_op_is_the_token_by_token_recurrence_forward_and_every_gradient(
        chunk, head_group):
    """T = 128: eight chunks of 16 carry the state over seven boundaries, a
    chunk of 64 holds four sub-chunks, a chunk of 8 is shorter than one; the
    heads all at once or a group at a time. Decays from nearly none
    (exp g = 0.999) to exp(-4.9) a token."""
    args, weight, want, g_want = recurrence_at_128()

    def chunked(*a):
        return kda_chunked(*a, chunk=chunk, dtype=jnp.float32, head_group=head_group)

    got, g_got = jax.jit(lambda *a: (
        chunked(*a), jax.grad(lambda *b: jnp.sum(weight * chunked(*b)),
                              argnums=(0, 1, 2, 3, 4))(*a)))(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, a, b in zip("qkvgb", g_got, g_want):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) < 5e-4 * scale, name
    # the state reaches across chunks: token 40's output moves with token 8's
    # value, and with no later token's
    reach = jax.grad(
        lambda v: jnp.sum(chunked(args[0], args[1], v, *args[3:])[:, 40]))(args[2])
    assert float(jnp.abs(reach[:, 8]).max()) > 0 == float(jnp.abs(reach[:, 41:]).max())


@pytest.mark.parametrize("d", [8, 128], ids=["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_lower_bound_on_every_channel_for_a_whole_chunk_stays_finite(dtype, d):
    """g = -5 everywhere: a chunk's cumulative decay is exp(-320), under
    float32's range; the sub-chunks keep every factor inside it, forward and
    backward, in plain XLA and inside the kernels, and the result is still
    the recurrence's."""
    q, k, v, g, beta = mixer_inputs(64, d=d, seed=1, rows=1)
    assert kda.takes_kernel(64, d, d) == (d == 128)
    g = jnp.full_like(g, -5.0)

    def f(*a):
        return kda_chunked(*a, chunk=64, dtype=dtype)

    out = jax.jit(f)(q, k, v, g, beta)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2, 3, 4)))(
        q, k, v, g, beta)
    assert all(bool(jnp.isfinite(x).all()) for x in (out, *grads))
    assert float(jnp.abs(grads[3]).max()) > 0
    # a sub-chunk's last row stands at exp(-80) against its own column: of a
    # unit k over 128 channels the smallest leave float32's normal range there
    # (2.7e-4 of 4.3e-2, in `_chunked` and in the kernels alike)
    tol = (2e-4 if d == 8 else 5e-4) if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, ref.kda_recurrence(q, k, v, g, beta),
                               rtol=tol, atol=tol)


def test_chunks_that_do_not_tile_the_row_are_refused():
    args = mixer_inputs(48)
    for chunk in (32, 24):      # 48 is not whole chunks of 32; 24 is no multiple of 16
        with pytest.raises(ValueError, match="chunk"):
            kda_chunked(*args, chunk=chunk)


@pytest.mark.parametrize("t,chunk", [(8192, 64), (128, 64), (64, 64), (48, 48),
                                     (32, 32), (16, 16), (8, 8)], ids=str)
def test_a_row_is_cut_into_the_constant_chunk_or_is_one_shorter_chunk(t, chunk):
    assert kda.chunk_of(t) == chunk
    assert (kda.CHUNK, kda.SUB, kda.HEAD_GROUP, kda.LOWER_BOUND) == (64, 16, 8, -5.0)


@pytest.mark.parametrize("t", [72, 96, 24, 8200], ids=str)
def test_a_row_that_is_not_whole_chunks_is_refused(t):
    with pytest.raises(ValueError, match="chunk"):
        kda.chunk_of(t)


@pytest.mark.parametrize("heads,steps", [(32, 4), (16, 2), (8, 0), (4, 0), (12, 3)],
                         ids=str)
def test_the_heads_go_eight_at_a_time_or_the_most_that_divides_them(heads, steps):
    """`HEAD_GROUP` heads a step of `lax.map` (one `scan` of that length
    outside the chunks' own), all at once where they are no more."""
    args = [jax.ShapeDtypeStruct((1, 64, heads, 8), jnp.float32)] * 4 + [
        jax.ShapeDtypeStruct((1, 64, heads), jnp.float32)]
    lengths = re.findall(r"length=(\d+)", str(jax.make_jaxpr(kda_chunked)(*args)))
    # the chunks' own scan (one chunk here), inside the map's where there is one
    assert lengths == ["1"] + [str(steps)] * bool(steps)


# (a') the kernels (interpret mode here, as the flash kernels' tests) ----------

@functools.lru_cache(maxsize=None)
def kernels_at(t, dtype):
    """(inputs, weight, (o, five gradients)) of the kernel path at 128-wide
    heads: 1 row of `t` tokens, 2 heads."""
    args = mixer_inputs(t, d=128, seed=t, rows=1)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    assert kda.takes_kernel(t, 128, 128)
    return args, weight, jax.jit(out_and_grads(
        functools.partial(kda_chunked, dtype=jnp.dtype(dtype)), weight))(*args)


def out_and_grads(f, weight):
    return lambda *a: (f(*a), jax.grad(lambda *b: jnp.sum(weight * f(*b)),
                                       argnums=(0, 1, 2, 3, 4))(*a))


def worst(got, want):
    """The largest gap of (o, gradients), each over its own largest entry."""
    got, want = [got[0], *got[1]], [want[0], *want[1]]
    return max(float(jnp.abs(a - b).max() / jnp.abs(b).max()) for a, b in zip(got, want))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [64, 128, 256])
def test_kernels_are_the_token_by_token_recurrence_forward_and_every_gradient(t, dtype):
    """One chunk, two and four: the state leaves VMEM for no chunk boundary,
    the reverse walk carries dS back over them. float32 operands: the order
    of the sums is what is left; bf16 operands: their rounding."""
    args, weight, got = kernels_at(t, dtype)
    want = jax.jit(out_and_grads(ref.kda_recurrence, weight))(*args)
    assert all(bool(jnp.isfinite(x).all()) for x in (got[0], *got[1]))
    assert worst(got, want) < (5e-5 if dtype == "float32" else 2e-2)
    assert got[0].dtype == jnp.float32 and [x.dtype for x in got[1]] == [
        x.dtype for x in args]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_and_the_chunks_in_plain_xla_agree(dtype):
    """`_chunked`, the path every other shape takes, is the kernels' second
    oracle: the same arithmetic at the same operand dtypes, so in float32 they
    differ by the order of the sums, in bf16 by roundings of the same size as
    either's distance from the recurrence."""
    args, weight, got = kernels_at(128, dtype)
    xla = jax.jit(out_and_grads(functools.partial(
        kda._grouped, dtype=jnp.dtype(dtype)), weight))(*args)
    assert worst(got, xla) < (5e-5 if dtype == "float32" else 2e-2)
    # dg sums differences of products: without the references' own gradient
    # the kernels' lies half again as far from the recurrence's as the chunks'
    # in XLA (1.7 % | 1.2 %), with it nearer (0.8 %)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(weight * ref.kda_recurrence(*a)),
                            argnums=3))(*args)
    assert (float(jnp.linalg.norm(got[1][3] - want))
            <= float(jnp.linalg.norm(xla[1][3] - want)) or dtype == "float32")


@pytest.mark.parametrize("t,dk,dv,chunk,kernel", [
    (8192, 128, 128, 64, True), (64, 128, 128, 64, True), (256, 256, 128, 64, True),
    (128, 8, 8, 64, False),       # the tests' heads
    (128, 16, 16, 64, False),     # the rehearsal arch's
    (128, 64, 128, 64, False), (128, 128, 64, 64, False),
    (48, 128, 128, 64, False),    # one shorter chunk
    (128, 128, 128, 32, False),   # another chunk length: the tests' own
], ids=str)
def test_the_shapes_decide_which_rows_take_the_kernels(t, dk, dv, chunk, kernel):
    assert kda.takes_kernel(t, dk, dv, chunk) == kernel
    args = [jax.ShapeDtypeStruct((1, t, 2, d), jnp.float32) for d in (dk, dk, dv, dk)] + [
        jax.ShapeDtypeStruct((1, t, 2), jnp.float32)]
    text = str(jax.make_jaxpr(functools.partial(kda_chunked, chunk=chunk))(*args))
    assert ("name=kda_fwd" in text) == kernel == ("scan" not in text)


@pytest.mark.parametrize("t", [72, 8200])
def test_a_row_that_is_not_whole_chunks_is_refused_at_128_wide_heads_too(t):
    with pytest.raises(ValueError, match="chunk"):
        kda.takes_kernel(t, 128, 128)
    with pytest.raises(ValueError, match="chunk"):
        kda_chunked(*mixer_inputs(t, d=128, rows=1))


def prepare_inputs(t, heads=2, dtype=jnp.float32, rows=2, seed=0):
    """The projections' outputs x_q, x_k, x_v, x_f (B, T, H · 128) and x_b
    (B, T, H) in `dtype`, the three tap tables, A_log and dt_bias."""
    width = heads * 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    xs = [(1.5 * jax.random.normal(ks[i], (rows, t, width))).astype(dtype)
          for i in range(4)] + [jax.random.normal(ks[4], (rows, t, heads)).astype(dtype)]
    return (*xs, *(0.5 * jax.random.normal(ks[5 + i], (4, width)) for i in range(3)),
            0.5 * jax.random.normal(ks[8], (heads,)),
            jax.random.normal(ks[9], (width,)) - 1.0)


@pytest.mark.parametrize("op", ["recurrence", "prepare"])
def test_the_kernels_backward_keeps_the_ops_inputs_and_nothing_else(op):
    """What `jax.checkpoint` keeps of `_chunked` and of `kda_prepare_xla`: no
    state, no chunk matrix, no tap, no norm."""
    from jax._src.ad_checkpoint import saved_residuals

    if op == "recurrence":     # the kernels' own entry: (B, T, H · d)
        q, k, v, g, beta = mixer_inputs(128, d=128, rows=1)
        args = (*(x.reshape(1, 128, -1) for x in (q, k, v, g)), beta)
        f, names = kda.kda_flat, ("q", "k", "v", "g", "beta")
    else:
        args = prepare_inputs(64)
        f, names = kda_prepare.kda_prepare, ("xq", "xk", "xv", "xf", "xb", "wq", "wk",
                                            "wv", "a_log", "dt_bias")
    kept = [(a.shape, why) for a, why in saved_residuals(f, *args)]
    if op == "prepare":     # β's sigmoid is plain XLA and keeps what it likes
        kept = [x for x in kept if x[0] != args[4].shape]
    assert kept == [(x.shape, f"from the argument {name}")
                    for name, x in zip(names, args) if name != "xb"], kept


# (a'') the input side: taps, SiLU, norms and decay as one op -------------------

@functools.lru_cache(maxsize=None)
def prepare_in_xla(t, dtype):
    """(inputs, weights, (five outputs, ten gradients)) of `kda_prepare_xla`,
    its outputs in the kernels' layout."""
    args = prepare_inputs(t, dtype=jnp.dtype(dtype))
    weights = [jax.random.normal(jax.random.PRNGKey(20 + i), args[min(i, 4)].shape)
               for i in range(5)]

    def xla(*a):
        outs = decoder_lm.kda_prepare_xla(*a, 2, jnp.dtype(dtype))
        return tuple(x.reshape(2, t, -1) for x in outs)

    return args, weights, jax.jit(outs_and_grads(xla, weights))(*args)


def outs_and_grads(f, weights):
    def loss(*a):
        return sum(jnp.sum(w * o.astype(jnp.float32)) for w, o in zip(weights, f(*a)))
    return lambda *a: (f(*a), jax.grad(loss, argnums=tuple(range(10)))(*a))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t,rows", [(64, 32), (128, 64), (256, 128)])
def test_fused_input_side_is_prepare_in_plain_xla_forward_and_every_gradient(
        t, rows, dtype):
    """Two blocks of rows a row: the taps read across the block's boundary
    forward, their transpose backward; two rows of a batch: the second sees
    nothing of the first; two heads: each its own norm. float32 inputs: the
    order of the sums is what is left; bf16: one rounding of an output."""
    args, weights, want = prepare_in_xla(t, dtype)
    assert kda_prepare.takes_kernel(t, 128, 4, rows)
    got = jax.jit(outs_and_grads(
        functools.partial(kda_prepare.kda_prepare, rows=rows), weights))(*args)
    names = ("q", "k", "v", "g", "beta", "xq", "xk", "xv", "xf", "xb", "wq", "wk", "wv",
             "a_log", "dt_bias")
    for name, a, b in zip(names, (*got[0], *got[1]), (*want[0], *want[1])):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), name
        gap = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert gap < (5e-6 if dtype == "float32" else 8e-3), (name, gap)
    # a row's first tokens see zeros before them, not the row before: the
    # second row's outputs do not move with the first row's inputs
    moved = jax.jit(functools.partial(kda_prepare.kda_prepare, rows=rows))(
        *(x.at[0].set(0) for x in args[:5]), *args[5:])
    for a, b in zip(moved[:3], got[0][:3]):
        np.testing.assert_array_equal(a[1], b[1])
        assert float(jnp.abs(a[0].astype(jnp.float32)).max()) == 0.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t,rows", [(64, 32), (256, 128)])
def test_fused_output_side_is_the_gated_norm_in_plain_xla_forward_and_every_gradient(
        t, rows, dtype):
    """The per-head RMSNorm and its gate over (B, T, H · d) against `RMSNorm`'s
    arithmetic over (B, T, H, d): two blocks of rows, two rows, two heads."""
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    o = 2.0 * jax.random.normal(ks[0], (2, t, 256))
    gate = jax.nn.sigmoid(jax.random.normal(ks[1], (2, t, 2)))
    scale = 1.0 + 0.3 * jax.random.normal(ks[2], (128,))
    weight = jax.random.normal(ks[3], (2, t, 256))

    def xla(o, gate, scale):
        x = o.reshape(2, t, 2, 128)
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6) * scale
        return (y * gate[..., None]).astype(dtype).reshape(2, t, -1)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *b: jnp.sum(weight * f(*b).astype(jnp.float32)), argnums=(0, 1, 2))(*a))
        )(o, gate, scale)

    got = both(functools.partial(kda_gated_norm, eps=1e-6, dtype=jnp.dtype(dtype),
                                 rows=rows))
    want = both(xla)
    for name, a, b in zip(("y", "o", "gate", "scale"), (got[0], *got[1]),
                          (want[0], *want[1])):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) < 2e-6, name


@pytest.mark.parametrize("arch,path", [
    ({"head_dim": 128, "seq_len": 8192}, "kernel"),
    ({"head_dim": 128, "seq_len": 64}, "kernel"),        # one shorter block
    ({"head_dim": 128, "seq_len": 512}, "kernel"),
    ({"head_dim": 128, "seq_len": 320}, "xla"),          # whole chunks, not whole blocks
    ({"head_dim": 128, "seq_len": 48}, "xla"),           # the recurrence in `_grouped`
    ({"head_dim": 64, "seq_len": 8192}, "xla"),
    ({"head_dim": 256, "seq_len": 8192}, "xla"),         # a head over two lane tiles
    ({"head_dim": 16, "seq_len": 128}, "xla"),           # the rehearsal arch's
    ({"head_dim": 128, "seq_len": 8192, "conv_kernel": 3}, "xla"),
    ({"head_dim": 128, "seq_len": 8192, "kda_layout": [0]}, None),
], ids=str)
def test_the_shapes_decide_whether_the_input_side_takes_its_kernels(arch, path):
    dc = config_from_args(build_parser().parse_args(
        cli_argv(dict(ARCH, v_head_dim=arch["head_dim"], **arch)))).model.decoder
    assert decoder_lm.kda_prepare_path(dc) == path
    core = decoder_lm.kda_core_path(dc)
    assert (core is None) == (path is None) and (path != "kernel" or core == "kernel")


def test_under_remat_a_layer_walks_the_row_once_forward_and_twice_backward():
    """`--remat` keeps `kda_out` by name (DecoderLM.setup), so the layer's
    recomputed forward does not hold the forward kernel again (PR 42: 65 ms a
    step): the gradient's program has `kda_fwd` once (the forward pass),
    `kda_states` once and `kda_bwd` once; the input side's forward kernel
    twice (the pass and its recomputation: q, k, v, g are what the backward's
    walks read) and its backward once, and the output side's the same. No
    (B, T, H, d) value stands anywhere in the layer, forward or backward."""
    arch = dict(ARCH, num_layers=1, dense_layers=1, kda_layout=[1], num_heads=2,
                head_dim=128, seq_len=64)
    _, loss_fn, _ = program(arch, "--remat", "--num_kv_heads", "2")
    params = jax.eval_shape(lambda: program_tree(
        common.make_params(ref.param_spec(arch), 0)))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x, y: loss_fn(p, {}, x, y, None)[0]))(params, tokens, tokens))
    kernels = (r"name=(kda_prepare_fwd|kda_prepare_bwd|kda_fwd|kda_states|kda_bwd|"
               r"kda_gated_norm_fwd|kda_gated_norm_bwd)\b")
    assert re.findall(kernels, text) == [
        "kda_prepare_fwd", "kda_fwd", "kda_gated_norm_fwd",          # the pass
        "kda_prepare_fwd", "kda_gated_norm_fwd",                     # again, but for o
        "kda_gated_norm_bwd", "kda_states", "kda_bwd", "kda_prepare_bwd"]
    # the layer through, forward and backward: the projections make
    # (B, T, H · d), W_o reads it, and every op between keeps it
    assert "[1,64,256]" in text and "[1,64,2,128]" not in text


# (b) the program against the reference ---------------------------------------

def test_program_matches_the_plain_reference_loss_and_every_gradient():
    # the latent layer through the flash kernels
    model, loss_fn, metrics_fn = program(ARCH, "--remat", "--flash_min_tokens", "0")
    flat = common.make_params(ref.param_spec(ARCH), 3)
    assert float(jnp.abs(flat["layer1/router_bias"]).max()) > 0.05  # seeded non-zero
    assert float(flat["layer0/kda_dt_bias"][0]) == ref.DT_BIAS
    assert "layer2/q_a/kernel" not in flat and "layer2/q/kernel" in flat  # no bottleneck
    tokens, targets = batch(ARCH)
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :8], train=False))["params"]
    assert ({k: v.shape for k, v in flat_tree(init).items()}
            == {k: v.shape for k, v in flat.items()})
    (loss, (_, aux)), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        program_tree(flat), {}, tokens, targets, None)
    want, want_grads = reference_grad()(flat, tokens, targets)
    # float32 against float32: the order of the sums is what is left (the
    # chunks, the kernels' tiles, the sorted slots)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = flat_tree(grads)
    assert set(got) == set(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 2e-4 * scale, name
    for name in got:
        if name.endswith("router_bias"):
            assert float(jnp.abs(got[name]).max()) == 0.0, name
    load = metrics_fn(loss, aux, targets)["moe_load"]
    assert load.shape == (2, ARCH["experts_held"])


def test_program_on_the_kernel_path_matches_the_plain_reference_too():
    """One dense delta layer at 128-wide heads: the input side's fused op, the
    recurrence's kernels and the output side's fused op hand (B, T, H · d) to
    each other (`kda_prepare=kernel`), and loss and every gradient are the
    float32 reference's, which knows nothing of layouts."""
    arch = dict(ARCH, num_layers=1, dense_layers=1, kda_layout=[1], num_heads=2,
                head_dim=128, seq_len=64)
    model, loss_fn, _ = program(arch, "--remat", "--num_kv_heads", "2")
    assert decoder_lm.kda_prepare_path(model.cfg) == "kernel"
    flat = common.make_params(ref.param_spec(arch), 7)
    # the norm's scale and the taps are ones and small at seeded weights: move them
    flat = {k: (v + 0.3 * jax.random.normal(jax.random.PRNGKey(len(k)), v.shape)
                if k.endswith(("kda_norm/scale", "kda_a_log")) else v)
            for k, v in flat.items()}
    tokens, targets = batch(arch)
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
    assert any(float(jnp.abs(v - want_g[k]).max())
               > 2e-4 * float(jnp.abs(want_g[k]).max()) for k, v in flat_tree(g).items())
    assert np.isfinite(float(got)) and 1e-3 < bf16 < fp8, (bf16, fp8)


def test_the_state_is_causal_and_reaches_past_every_window():
    """Layers of KDA only: a changed token moves no state before its
    position, and still moves the row's last position 43 tokens on (the
    taps alone would reach 3 x 3 = 9)."""
    arch = dict(ARCH, kda_layout=[1], dense_layers=3)
    model, _, _ = program(arch)
    params = program_tree(common.make_params(ref.param_spec(arch), 1))
    tokens, _ = batch(arch)
    hidden = jax.jit(lambda tok: model.apply(
        {"params": params}, tok, train=False, method="hidden")[0])
    moved = hidden(tokens.at[:, 20].set((tokens[:, 20] + 1) % 96)) - hidden(tokens)
    assert float(jnp.abs(moved[:, :20]).max()) == 0.0
    assert float(jnp.abs(moved[:, 20]).max()) > 0.0
    assert float(jnp.abs(moved[:, -1]).max()) > 0.0


# (c) the router ----------------------------------------------------------------

def test_group_limited_choice_is_the_sorting_one_and_one_group_is_todays():
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    logits = jax.random.normal(ks[0], (256, 64))
    bias = 0.3 * jax.random.normal(ks[1], (64,))
    arch = {"top_k": 6, "router_scale": 2.5, "n_group": 8, "topk_group": 3}
    want_idx, want_w = ref.route(logits, bias, arch)
    idx, w = jax.jit(lambda l, b: route_top_k(
        l, 6, scoring="sigmoid", bias=b, scale=2.5, n_group=8, topk_group=3))(logits, bias)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    # every choice lies in at most 3 of the 8 groups of 8, and the limit binds
    assert int((jnp.sort(idx // 8, axis=-1)[:, 1:] != jnp.sort(idx // 8, axis=-1)[:, :-1]
                ).sum(axis=-1).max()) <= 2
    free_idx, free_w = route_top_k(logits, 6, scoring="sigmoid", bias=bias, scale=2.5)
    assert bool((free_idx != idx).any())
    # n_group = 1 is the program it was: the same ids, the same weights, the
    # same jaxpr as a call that does not name the groups
    one_idx, one_w = route_top_k(logits, 6, scoring="sigmoid", bias=bias, scale=2.5,
                                 n_group=1, topk_group=1)
    np.testing.assert_array_equal(one_idx, free_idx)
    np.testing.assert_array_equal(one_w, free_w)
    np.testing.assert_array_equal(
        free_idx, jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 6)[1])

    def text(**groups):
        return str(jax.make_jaxpr(lambda l, b: route_top_k(
            l, 6, scoring="sigmoid", bias=b, scale=2.5, **groups))(logits, bias))

    assert text() == text(n_group=1, topk_group=1) != text(n_group=8, topk_group=3)
    # the choice has no gradient of its own: the bias's is exactly zero, and
    # the logits' flows through the chosen scores alone
    d_bias = jax.grad(lambda b: route_top_k(logits, 6, scoring="sigmoid", bias=b,
                                            n_group=8, topk_group=3)[1].sum())(bias)
    assert float(jnp.abs(d_bias).max()) == 0.0


def test_the_four_shares_of_16_experts_in_2_groups_add_up_to_the_uncut_layer():
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    n, c, width, experts, held = 32, 16, 8, 16, 4
    u = jax.random.normal(ks[0], (n, c))
    logits = jax.random.normal(ks[1], (n, experts))
    bias = 0.3 * jax.random.normal(ks[2], (experts,))
    w = (jax.random.normal(ks[3], (experts, c, width)),
         jax.random.normal(ks[4], (experts, c, width)),
         jax.random.normal(ks[5], (experts, width, c)))
    shared = (jax.random.normal(ks[6], (c, width)), jax.random.normal(ks[7], (c, width)),
              jax.random.normal(ks[8], (width, c)))
    route = dict(scoring="sigmoid", bias=bias, scale=2.5, n_group=2, topk_group=1)
    arch = {"top_k": 4, "router_scale": 2.5, "first_expert": 0, "n_group": 2,
            "topk_group": 1}
    idx, weight = ref.route(logits, bias, arch)
    assert int((idx // 8 != idx[:, :1] // 8).sum()) == 0      # one group a token
    once = ref.gated_mlp(u, *shared, lambda x: x)
    uncut = ref.held_experts(u, idx, weight, *w, arch, lambda x: x) + once
    parts = [sparse_moe(u, logits, *(b[held * s:held * (s + 1)] for b in w),
                        top_k=4, first_expert=held * s, dtype=jnp.float32,
                        activation="silu", route=route)
             for s in range(experts // held)]
    assert len(parts) == 4
    # the shares' expert parts, and what every chip computes alike counted once
    np.testing.assert_allclose(sum(p for p, _ in parts) + once, uncut,
                               rtol=1e-4, atol=1e-4)
    assert int(sum(l.sum() for _, l in parts)) == n * 4


# (d) the configuration -----------------------------------------------------------

def test_analytic_counts_and_the_configurations_own_arithmetic():
    cut, published = CONF["arch"], CONF["published"]

    def count(arch):
        return sum(int(np.prod(s[0])) for s in ref.param_spec(arch).values())

    assert count(cut) == CONF["parameters"] == 822036416
    assert "822,036,416" in CONF["parameters_why"]
    # the published model: 42 layers in groups of five KDA and one MLA, two
    # dense; "125B-A5.5B"
    uncut = dict(cut, num_layers=published["num_hidden_layers"],
                 dense_layers=published["first_k_dense_replace"],
                 kda_layout=[1, 1, 1, 1, 1, 0], experts_held=published["num_experts"],
                 vocab_size=published["vocab_size"])
    assert flops.kda_layout(uncut).count(1) == 35
    assert abs(count(uncut) / 1e9 - 124.05) < 0.005      # "about 125B"
    assert "124.05 B" in published["parameters"]
    # a token meets 4.34 B in the layers, 4.74 B with the head's matmul
    assert abs(flops.layers_token_macs(uncut) / 1e9 - 4.336) < 0.005
    assert abs(flops.token_macs(uncut) / 1e9 - 4.739) < 0.005
    # the cut keeps the published widths and a whole group after the dense layer
    catalog = {"hidden_size": 2560, "intermediate_size": 6144, "moe_intermediate_size": 768,
               "moe_shared_expert_intermediate_size": 768, "num_attention_heads": 32,
               "num_key_value_heads": 32, "head_dim": 128, "kv_lora_rank": 512,
               "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
               "v_head_dim": 128, "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
               "routed_scaling_factor": 2.5, "short_conv_kernel_size": 4,
               "kda_lower_bound": -5, "layer_group_size": 6, "rope_theta": 6000000,
               "num_shared_experts": 1, "mtp_loss_scaling_factor": 0,
               "model_type": "bailing_hybrid"}
    assert {k: CONF[k] for k in catalog} == catalog
    assert CONF["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "num_experts",
                               "vocab_size", "num_nextn_predict_layers"]
    assert (CONF["num_hidden_layers"], CONF["first_k_dense_replace"], CONF["num_experts"],
            CONF["vocab_size"], CONF["num_nextn_predict_layers"]) == (7, 1, 8, 19648, 0)
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (42, 512, 157184)
    assert cut["vocab_size"] * 8 == published["vocab_size"]
    assert cut["kda_layout"] == [1, 1, 1, 1, 1, 1, 0]
    assert (cut["hidden_size"], cut["num_heads"], cut["head_dim"], cut["rope_dim"],
            cut["v_head_dim"], cut["kv_rank"], cut["q_rank"], cut["dense_width"],
            cut["expert_width"], cut["num_experts"], cut["top_k"], cut["n_group"],
            cut["topk_group"], cut["conv_kernel"]) == (
        2560, 32, 128, 64, 128, 512, 0, 6144, 768, 512, 8, 8, 4, 4)
    # the step's work
    t = cut["seq_len"]
    assert flops.train_flops_per_image(cut, 0) == 6.0 * flops.forward_macs(cut)
    assert flops.score_macs(cut) == 1 * 32 * (128 + 64 + 128) * (t * (t + 1) // 2)
    assert flops.attention_flops(cut, 1) == 6.0 * flops.score_macs(cut)
    assert flops.gmm_flops(10.0, cut) == 6.0 * 10 * 3 * 2560 * 768
    assert flops.kda_core_macs(cut) == 32 * 128 * 4456448
    # the recurrence's bound is its bytes: 6 layers x 3 x 8,192 x 32 x 1,540 B
    assert flops.kda_core_bytes(cut) == 8192 * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    bound = flops.kda_core_bound_s(cut, 1)
    assert bound == 18 * flops.kda_core_bytes(cut) / 819e9
    assert bound > 36 * flops.kda_core_macs(cut) / 197e12
    assert flops.kda_core_bound_s(cut, 2) == 2 * bound
    # the argv builds the arch
    for conf in (CONF, CONF["rehearse"]):
        dc = config_from_args(build_parser().parse_args(
            conf["argv"] + ["--dataset", "tokens"])).model.decoder
        arch = conf["arch"]
        assert arch["kda_lower_bound"] == kda.LOWER_BOUND   # the reference's key
        assert {k: (list(getattr(dc, k)) if isinstance(v, list) else getattr(dc, k))
                for k, v in arch.items() if k != "kda_lower_bound"} \
            == {k: v for k, v in arch.items() if k != "kda_lower_bound"}
        assert (dc.attention, dc.router, dc.router_tap, dc.mtp_layers,
                dc.tied_embeddings) == ("mla", "sigmoid", "post", 0, 0)
        assert kda.chunk_of(dc.seq_len) == kda.CHUNK      # both cross a chunk


def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ddp_classification_pytorch_tpu" not in text
    assert "from ddp_classification_pytorch_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_factory_refuses_kinds_that_do_not_go_together():
    for extra, match in ((("--conv_layout", "1,0,0"), "same layer"),
                         (("--kda_layout", "2"), "kda_layout"),
                         (("--seq_len", "72"), "chunk"),
                         (("--n_group", "3"), "n_group"),
                         (("--n_group", "8", "--topk_group", "1"), "n_group"),
                         (("--kv_rank", "0"), "kv_rank")):
        cfg = config_from_args(build_parser().parse_args(cli_argv(ARCH, *extra)))
        with pytest.raises(ValueError, match=match):
            build_model(cfg.model, cfg.data.num_classes)


# counters ------------------------------------------------------------------

def test_delta_decoder_trains_through_cli_train_and_publishes_its_layout(
        tmp_path, capsys):
    t = ARCH["seq_len"]
    ids = (np.arange(8 * (t + 1)) * 7 % 50).astype(np.int32)
    path = tmp_path / "train.bin"
    ids.tofile(path)
    argv = cli_argv(ARCH, "--train_dir", str(path), "--batchsize", "8", "--epochs",
                    "2", "--lr", "0.003", "--adam_b2", "0.95", "--platform", "cpu",
                    "--out", str(tmp_path / "run"), "--log_every", "1", "--remat")
    train_main(argv)   # Trainer, ShardedLoader, DevicePrefetcher, _build_step
    with open(tmp_path / "run" / "history.json") as f:
        losses = json.load(f)["loss"]             # one step an epoch: two steps
    assert len(losses) == 2 and all(np.isfinite(losses)) and losses[1] < losses[0]
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    for line in ('decoder_layers_total{ffn="dense",operator="kda"} 1',
                 'decoder_layers_total{ffn="routed",operator="kda"} 1',
                 'decoder_layers_total{ffn="routed",operator="mla"} 1',
                 'moe_expert_load_max{layer="1"}', 'moe_expert_load_max{layer="2"}'):
        assert line in prom, line
    out = capsys.readouterr().out
    setup = next(line for line in out.splitlines() if "[trainer] set-up:" in line)
    assert "kda_dense=1 kda_routed=1 mla_routed=1" in setup, setup
    assert "kda_core=xla" in setup, setup      # 16-wide heads: no kernel
    assert "kda_prepare=xla" in setup, setup


# the two readers ---------------------------------------------------------------

def test_the_readers_find_the_scopes_and_say_nothing_where_there_are_none():
    """`kda_device_ms` = every op anywhere under `kda` (its three inner
    scopes too), `kda_core_roofline_pct` = the recurrence's bound over the
    ops under `kda.core` alone; a program without the scopes (the parent), a
    configuration without the count, or a run without a trace reads None."""
    from benchmark.layers import _scope_members, kda_core_roofline_pct, kda_device_ms

    ms = 1_000_000
    lm = "jit(step)/transpose(jvp(DecoderLM.hidden))/jvp(DecoderLM.hidden)/checkpoint/"
    ops = [(lm + "layer0/kda/layer0._kda/kda.in/kda_q/dot_general", 0, 3 * ms),
           (lm + "layer0/kda/layer0._kda/kda.core/checkpoint/rematted_computation/"
            "while/body/bhcd,bhde->bhce/dot_general", 3 * ms, 20 * ms),
           (lm + "rematted_computation/layer0/kda/layer0._kda/kda.out/kda_o/dot_general",
            23 * ms, 2 * ms),
           (lm + "layer6/attn/layer6._attention/o/dot_general", 25 * ms, 5 * ms),
           (lm + "layer1/pre_kda/mul", 30 * ms, 1 * ms)]     # no whole segment

    def ctx(ops, flops_name="ling_3_0_flash"):
        return {_scope_members._KEY: (ops, 1) if ops else None, "batch": 1, "chips": 1,
                "arch": CONF["arch"], "config": {"flops": flops_name},
                "device_kind": "TPU v5 lite"}

    assert kda_device_ms.read(ctx(ops)) == 25.0
    share = kda_core_roofline_pct.read(ctx(ops))
    assert share == pytest.approx(100 * flops.kda_core_bound_s(CONF["arch"], 1) / 20e-3)
    assert 0 < share < 100
    for reader in (kda_device_ms, kda_core_roofline_pct):
        assert reader.read(ctx(ops[3:])) is None       # no such scope: the parent
        assert reader.read(ctx(None)) is None          # no trace
    assert kda_core_roofline_pct.read(ctx(ops, "lfm2_8b_a1b")) is None
