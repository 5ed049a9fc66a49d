"""The decoder trained by diffusion over blocks (models/decoder_lm.py as
SDAR-30B-A3B-Chat configures it, `--objective block_diffusion`) against its
plain reference (benchmark/reference/sdar_30b_a3b.py, imported as it stands:
it takes nothing from the program), both against the per-block definition of
what the two-stream pass computes, the chips' expert shares against the uncut
layer, the loader's noise, the factory's refusals, evaluation, and what a run
publishes. CPU, toy sizes."""

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

from benchmark.flops import sdar_30b_a3b as flops  # noqa: E402
from benchmark.reference import common, sdar_30b_a3b as ref  # noqa: E402
from ddp_classification_pytorch_tpu.cli.train import (  # noqa: E402
    build_parser,
    config_from_args,
    main as train_main,
)
from ddp_classification_pytorch_tpu.data.diffusion import (  # noqa: E402
    LEVELS,
    NoisedTokens,
    level_of,
)
from ddp_classification_pytorch_tpu.models import decoder_lm  # noqa: E402
from ddp_classification_pytorch_tpu.models.factory import build_model  # noqa: E402
from ddp_classification_pytorch_tpu.ops.moe import sparse_moe  # noqa: E402
from ddp_classification_pytorch_tpu.train.state import TrainState  # noqa: E402
from ddp_classification_pytorch_tpu.train.steps import (  # noqa: E402
    _lm_loss,
    make_eval_step,
)
from test_decoder_lm import flat_tree, program_tree  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b.json")) as f:
    CONF = json.load(f)

# SDAR's shape at toy sizes: grouped heads with a QK-norm and rotary
# everywhere, a softmax router over 16 experts of which 4 are held, top-4
ARCH = {"vocab_size": 96, "hidden_size": 64, "num_layers": 2, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "expert_width": 32, "num_experts": 16,
        "experts_held": 4, "first_expert": 4, "top_k": 4, "rope_theta": 1e6,
        "rms_eps": 1e-6, "seq_len": 128, "diffusion_block": 4,
        "diffusion_eps": 1e-3, "mask_id": 95}
KINDS = ["--attention", "gqa", "--qk_norm", "1", "--rope_pairing", "half",
         "--rope_layout", "1", "--window_layout", "0", "--activation", "silu",
         "--router", "softmax", "--router_tap", "post",
         "--objective", "block_diffusion"]


def cli_argv(arch, *extra, dtype="float32"):
    argv = ["baseline", "--model", "decoder_lm", "--dataset", "tokens", "--dtype",
            dtype, "--optimizer", "adam", "--head_block", "64", *KINDS]
    for key, value in arch.items():
        argv += [f"--{key}", str(value)]
    return argv + list(extra)


def program(arch, *extra):
    cfg = config_from_args(build_parser().parse_args(cli_argv(arch, *extra)))
    model = build_model(cfg.model, cfg.data.num_classes)
    return cfg, model


def batch(arch, rows=2, seed=0):
    """(x_0 (rows, L), [x_t ; j] (rows, 2, L)) as the loader stacks the
    dataset's items."""
    ids = np.random.default_rng(seed).integers(
        0, arch["vocab_size"] - 1, (rows, arch["seq_len"] + 1)).astype(np.int32)
    ds = NoisedTokens([(row[:-1], row[1:]) for row in ids], arch["diffusion_block"],
                      arch["mask_id"], arch["diffusion_eps"], seed=seed)
    ds.rows = _Rows(ds.rows)
    items = [ds[i] for i in range(rows)]
    return (jnp.asarray(np.stack([x for x, _ in items])),
            jnp.asarray(np.stack([y for _, y in items])))


class _Rows(list):
    """A list of (row, shifted row) under the loader's dataset contract."""

    def __getitem__(self, i, rng=None):
        return list.__getitem__(self, i)


# (a) ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def reference_on_the_seeded_batch():
    """(weights, batch, the reference's loss and gradients): both attention
    paths of the program are held to the same one."""
    flat = common.make_params(ref.param_spec(ARCH), 3)
    clean, noised = batch(ARCH)
    return flat, clean, noised, jax.jit(jax.value_and_grad(ref.loss_for(ARCH)))(
        flat, clean, noised)


@pytest.mark.parametrize("path,extra", [("dense_op", ()),
                                        ("flash_kernels", ("--flash_min_tokens", "0"))])
def test_program_matches_the_plain_reference_loss_and_every_gradient(path, extra):
    """Loss and every leaf's gradient on seeded weights, the two-stream
    attention through the (2L, 2L) op and through the kernels (interpret
    mode: two streams of one 128-token tile)."""
    cfg, model = program(ARCH, "--remat", *extra)
    flat, clean, noised, (want, want_grads) = reference_on_the_seeded_batch()
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), clean[:, :4], train=False))["params"]
    assert ({k: v.shape for k, v in flat_tree(init).items()}
            == {k: v.shape for k, v in flat.items()})
    loss_fn, metrics_fn = _lm_loss(cfg, model)
    (loss, (_, aux)), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        program_tree(flat), {}, clean, noised, None)
    np.testing.assert_allclose(loss, want, rtol=2e-5)
    got = flat_tree(grads)
    for name, w in want_grads.items():
        np.testing.assert_allclose(got[name], w, rtol=2e-3, atol=2e-5, err_msg=name)
    # the step's metrics: the masked count is the batch's, the bands' losses
    # weighted by their positions give the loss, the loads count 2L positions
    m = metrics_fn(loss, aux, noised)
    masked = np.asarray(noised[:, 0] == ARCH["mask_id"])
    assert int(m["masked_tokens"]) == masked.sum()
    band = np.minimum((np.asarray(noised[:, 1]) - 1) * 4 // LEVELS, 3)
    share = np.bincount(band.reshape(-1), minlength=4) / band.size
    np.testing.assert_allclose(
        sum(share[i] * float(m[f"loss_level{i + 1}"]) for i in range(4)), loss,
        rtol=1e-5)
    assert m["moe_load"].shape == (2, ARCH["experts_held"])
    assert 0 < float(m["moe_load"].sum()) <= 2 * clean.size * 2 * ARCH["top_k"]


# (b) ----------------------------------------------------------------------

def test_the_two_stream_pass_is_the_per_block_definition():
    """For each block b a plain block-causal forward over [x_0[blocks < b] ;
    x_t[block b]] (ONE stream, positions 0.., nothing of the two-stream mask)
    gives the same logits at block b as the two-stream pass: in the
    reference, and in the program against it."""
    arch = dict(ARCH, seq_len=16)
    length, blk = arch["seq_len"], arch["diffusion_block"]
    flat = common.make_params(ref.param_spec(arch), 5)
    clean, noised = batch(arch, rows=1, seed=4)
    both = jnp.concatenate([clean, noised[:, 0]], axis=1)
    two = ref.head_logits(arch, flat, jax.jit(ref.states_for(arch))(flat, both))[:, length:]

    def block_causal(rows, cols, _, block):
        return rows // block >= cols // block

    for b in range(length // blk):
        row = jnp.concatenate([clean[:, :b * blk], noised[:, 0, b * blk:(b + 1) * blk]],
                              axis=1)
        one = ref.states_for(arch, rule=block_causal,
                             positions=jnp.arange(row.shape[1]))
        logits = ref.head_logits(arch, flat, jax.jit(one)(flat, row))
        np.testing.assert_allclose(logits[:, -blk:], two[:, b * blk:(b + 1) * blk],
                                   rtol=1e-4, atol=1e-5, err_msg=f"block {b}")
    _, model = program(arch)
    params = program_tree(flat)
    h, _ = model.apply({"params": params}, clean, train=False, method="hidden",
                       targets=noised)
    assert h.shape == (1, length, arch["hidden_size"])     # the noised stream's
    np.testing.assert_allclose(h @ flat["lm_head/kernel"], two, rtol=1e-4, atol=1e-5)
    # served (no noise given): the row's own blocks, which is the same pass
    # with x_t = x_0
    served = model.apply({"params": params}, clean, train=False)
    again, _ = model.apply({"params": params}, clean, train=False, method="hidden",
                           targets=jnp.stack([clean, noised[:, 1]], axis=1))
    np.testing.assert_allclose(served, again @ flat["lm_head/kernel"], rtol=1e-5,
                               atol=1e-6)


# (d) ----------------------------------------------------------------------

def test_eight_chips_expert_shares_add_up_to_the_uncut_layer():
    """Two of sixteen experts a chip, eight chips: what the program's expert
    layer gives for each share (`first_expert` 0, 2, .. 14) adds up to the
    reference's layer with every expert held, and each share is the
    reference's own for that chip."""
    arch = dict(ARCH, experts_held=16, first_expert=0)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    n, c, w = 64, arch["hidden_size"], arch["expert_width"]
    u = jax.random.normal(ks[0], (n, c))
    logits = jax.random.normal(ks[1], (n, 16))
    banks = [jax.random.normal(k, s) * 0.2 for k, s in
             zip(ks[2:], ((16, c, w), (16, c, w), (16, w, c)))]
    whole = ref.held_experts(u, logits, *banks, arch, lambda x: x)
    total = jnp.zeros_like(whole)
    for chip in range(8):
        share = [bank[2 * chip:2 * chip + 2] for bank in banks]
        y, load = sparse_moe(u, logits, *share, top_k=arch["top_k"],
                             first_expert=2 * chip, dtype=jnp.float32,
                             activation="silu")
        mine = ref.held_experts(u, logits, *share,
                                dict(arch, experts_held=2, first_expert=2 * chip),
                                lambda x: x)
        np.testing.assert_allclose(y, mine, rtol=1e-4, atol=1e-5)
        total = total + y
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


# (e) ----------------------------------------------------------------------

def _rows(n=6, length=4096, vocab=500, seed=0):
    ids = np.random.default_rng(seed).integers(0, vocab - 1, (n, length + 1))
    return _Rows((r[:-1].astype(np.int32), r[1:].astype(np.int32)) for r in ids)


@pytest.mark.parametrize("block", [4, 32])
def test_noised_rows_differ_from_the_clean_ones_by_mask_ids_alone(block):
    ds = NoisedTokens(_rows(), block, mask_id=499, eps=1e-3, seed=7)
    x0, (xt, level) = ds[2]
    assert x0.dtype == xt.dtype == level.dtype == np.int32
    assert x0.shape == xt.shape == level.shape == (4096,)
    np.testing.assert_array_equal(x0, ds.rows[2][0])
    assert set(np.unique(xt[xt != x0])) == {499}
    # one level a block, on the grid, and the masked share tracks t
    by_block = level.reshape(-1, block)
    assert (by_block == by_block[:, :1]).all()
    assert level.min() >= 1 and level.max() <= LEVELS
    t = level_of(level.astype(np.float64), 1e-3)
    assert abs((xt == 499).mean() - t.mean()) < 0.03
    low, high = t < 0.25, t > 0.75
    assert (xt == 499)[low].mean() < 0.2 < 0.8 < (xt == 499)[high].mean()


def test_the_same_seed_gives_the_same_batch_with_the_loaders_rng_or_without():
    from ddp_classification_pytorch_tpu.data.loader import ShardedLoader

    def first_batches(seed, keyed=False):
        ds = NoisedTokens(_rows(n=8, length=64), 4, 499, 1e-3, seed, keyed=keyed)
        loader = ShardedLoader(ds, 4, shuffle=True, seed=seed, num_workers=2)
        try:
            return [(x.copy(), y.copy()) for x, y in loader]
        finally:
            loader.close()

    a, b, c = first_batches(3), first_batches(3), first_batches(4)
    assert a[0][0].shape == (4, 64) and a[0][1].shape == (4, 2, 64)
    assert a[0][1].dtype == np.int32
    for (x, y), (x2, y2) in zip(a, b):
        np.testing.assert_array_equal(x, x2)
        np.testing.assert_array_equal(y, y2)
    assert any((y != y2).any() for (_, y), (_, y2) in zip(a, c))
    # no generator given (a bare index), or a keyed set (validation): the
    # draw is (seed, sample)'s whatever the loader hands over
    ds = NoisedTokens(_rows(n=8, length=64), 4, 499, 1e-3, seed=3)
    np.testing.assert_array_equal(ds[5][1], ds[5][1])
    keyed = NoisedTokens(ds.rows, 4, 499, 1e-3, seed=3, keyed=True)
    np.testing.assert_array_equal(
        keyed.__getitem__(5, np.random.default_rng(99))[1], ds[5][1])
    assert (ds.__getitem__(5, np.random.default_rng(99))[1] != ds[5][1]).any()
    with pytest.raises(ValueError, match="whole blocks"):
        NoisedTokens(_rows(n=1, length=30), 4, 499, 1e-3, 0)[0]


# (f) ----------------------------------------------------------------------

@pytest.mark.parametrize("extra,why", [
    (("--dense_layers", "2", "--dense_width", "48", "--sandwich_norm", "1",
      "--loops", "2"), "--loops 2"),
    (("--mtp_layers", "1"), "--mtp_layers 1"),
    (("--window_layout", "0,1", "--window", "16"), "a window layer"),
    (("--conv_layout", "1,0"), "conv"),
    (("--kda_layout", "0,1"), "kda"),
    (("--gdn_layout", "1,0", "--gdn_key_dim", "8", "--gdn_value_dim", "16"), "gdn"),
    (("--diffusion_block", "6"), "whole blocks"),
    (("--mask_id", "96"), "an id of the vocabulary held"),
    (("--objective", "denoise"), "one of"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_factory_refuses_what_has_no_two_stream_form(extra, why):
    with pytest.raises(ValueError, match=re.escape(why)):
        program(ARCH, *extra)


def test_the_mask_id_defaults_to_the_last_row_of_the_vocabulary_held():
    arch = {k: v for k, v in ARCH.items() if k != "mask_id"}
    cfg, _ = program(arch)
    assert cfg.model.decoder.mask_token == 95 and cfg.model.decoder.positions == 256
    cfg = config_from_args(build_parser().parse_args(
        [a for a in cli_argv(arch) if a not in ("--objective", "block_diffusion")]))
    assert cfg.model.decoder.objective == "next_token"
    assert cfg.model.decoder.positions == 128


# evaluation, and what a run publishes --------------------------------------

def test_evaluation_reads_the_objectives_loss_and_the_masked_positions():
    cfg, model = program(ARCH)
    flat = common.make_params(ref.param_spec(ARCH), 3)
    clean, noised = batch(ARCH, rows=3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=program_tree(flat),
                       batch_stats={}, opt_state=None)
    valid = jnp.asarray([1.0, 1.0, 0.0])
    out = make_eval_step(cfg, model)(state, clean, noised, valid)
    want = ref.loss_for(ARCH)(flat, clean[:2], noised[:2])
    np.testing.assert_allclose(out["loss_sum"] / out["n"], want, rtol=2e-5)
    assert float(out["n"]) == 2 * ARCH["seq_len"]
    assert float(out["n_top"]) == float((noised[:2, 0] == ARCH["mask_id"]).sum())
    assert 0 <= float(out["top1"]) <= float(out["top3"]) <= float(out["n_top"])


def test_cli_run_trains_and_publishes_the_objective(tmp_path, capsys):
    """The normal path end to end (Trainer, loader, noise, evaluation,
    checkpoint; the (2L, 2L) op: the kernels' counters are held in
    test_flash_diffusion.py and the set-up line's kernel notes in
    test_model_report.py), the set-up line's fields and the families of
    `metrics.prom`."""
    file = tmp_path / "tok.bin"
    (np.arange(129 * 24) * 7 % 90).astype(np.int32).tofile(file)
    out = tmp_path / "run"
    rc = train_main(cli_argv(ARCH, "--train_dir", str(file), "--batchsize", "8",
                             "--epochs", "1", "--lr", "0.003", "--remat",
                             "--platform", "cpu",
                             "--num_workers", "0", "--log_every", "1",
                             "--out", str(out)))
    assert rc in (0, None)
    log = capsys.readouterr().out
    assert ("gqa_routed=2 objective=block_diffusion block=4 mask_id=95 "
            "attn_mask=block_diffusion moe_bound=8192/8192") in log, log
    history = json.loads((out / "history.json").read_text())
    assert {"loss", "loss_level1", "loss_level4", "masked_tokens", "val_loss",
            "val_top1"} <= set(history)
    assert history["loss"][0] < 5.0 and os.path.exists(out / "ckpt_best.msgpack")
    prom = (out / "metrics.prom").read_text()
    for line in (r"diffusion_masked_tokens_total [1-9]",
                 r"diffusion_positions_total [1-9]",
                 r"train_loss_level1 \d", r"train_loss_level4 \d",
                 r'span_count_total\{span="input.noise"\} [1-9]'):
        assert re.search(line, prom), (line, prom)


def test_the_cells_step_counts_its_live_pairs():
    """benchmark/flops/sdar_30b_a3b.py: attention over the LIVE pairs of the
    two-stream mask (L^2 + L B of the (2L)^2, counted here from the mask
    itself at a small size), the projections and experts over 2L positions,
    the head over L rows: the two-stream attention is 58 % of the step."""
    arch = dict(ARCH, seq_len=64, diffusion_block=4)
    mask = np.asarray(ref.seen(np.arange(128)[:, None], np.arange(128)[None, :], 64, 4))
    assert flops.live_pairs(arch) == mask.sum()
    full = CONF["arch"]
    assert flops.live_pairs(full) == 8192 * 8192 + 8192 * 4
    per_row = flops.train_flops_per_image(full, 0)
    assert flops.attention_flops(full, 1) == 6.0 * 2 * 6 * 32 * 128 * flops.live_pairs(full)
    assert 0.5 < flops.attention_flops(full, 1) / per_row < 0.65
    assert flops.gmm_flops(1.0, full) == 6.0 * 3 * 2048 * 768
