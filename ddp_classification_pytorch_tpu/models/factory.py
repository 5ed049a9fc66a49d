"""Backbone/model construction from ModelConfig — replaces the per-silo model
build blocks (BASELINE/main.py:134-144, ARCFACE/arc_main.py:223-234,
CDR/main.py:330-338, NESTED/train.py:345-349)."""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..config import ModelConfig
from . import resnet as _resnet
from . import vit as _vit
from .tresnet import tresnet_m
from .vgg import vgg19_bn
from .heads import ArcEmbedding, ArcMarginHead, NetClassifier

_RESNETS = {
    "resnet18": _resnet.resnet18,
    "resnet34": _resnet.resnet34,
    "resnet50": _resnet.resnet50,
    "resnet101": _resnet.resnet101,
    "resnet152": _resnet.resnet152,
}


def feat_dim_for(cfg: ModelConfig) -> int:
    if cfg.feat_dim:
        return cfg.feat_dim
    if cfg.arch in _resnet.FEAT_DIMS:
        return _resnet.FEAT_DIMS[cfg.arch]
    if cfg.arch == "vgg19_bn":
        return 4096
    if cfg.arch in ("tresnet_m", "timm"):
        return 2048
    if cfg.arch in _vit.FEAT_DIMS:
        return _vit.FEAT_DIMS[cfg.arch]
    raise ValueError(f"unknown arch {cfg.arch}")


def build_backbone(cfg: ModelConfig, num_classes: int = 0,
                   axis_name: Optional[str] = None,
                   mesh: Optional[Any] = None) -> nn.Module:
    """Backbone emitting features (num_classes=0) or logits.

    `mesh` (when its 'model' axis is >1) switches the ViT family to
    sequence-parallel ring attention with tokens sharded over that axis, and
    on any mesh of more than one device tells its attention to wrap its
    Pallas call in a shard_map over the batch (models/vit.py::
    attention_path); the CNN zoos ignore it (their parallelism is
    batch/class sharding)."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.moe_experts and cfg.arch not in _vit.VIT_CONFIGS:
        raise ValueError(
            f"moe_experts requires a ViT arch (transformer FFN to split); "
            f"got {cfg.arch!r}")
    if cfg.arch in _RESNETS:
        return _RESNETS[cfg.arch](
            num_classes=num_classes, variant=cfg.variant, dtype=dtype,
            axis_name=axis_name, freeze_bn=cfg.freeze_bn, remat=cfg.remat,
        )
    if cfg.arch == "vgg19_bn":
        return vgg19_bn(num_classes=num_classes, dtype=dtype,
                        axis_name=axis_name, dropout=cfg.dropout or 0.5)
    if cfg.arch in ("tresnet_m", "timm"):
        # reference `--model timm` → tresnet_m_miil_in21k (BASELINE/main.py:141-144)
        return tresnet_m(num_classes=num_classes, dtype=dtype)
    if cfg.arch in _vit.VIT_CONFIGS:
        # lazy: parallel/__init__ imports this module (collectives → factory)
        from ..parallel.mesh import MODEL_AXIS

        mp = mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1
        # the model axis serves ONE role per config: EP when MoE is on,
        # ring-SP otherwise
        moe_axis = MODEL_AXIS if (cfg.moe_experts > 0 and mp > 1) else None
        seq = MODEL_AXIS if (mp > 1 and not cfg.moe_experts) else None
        return _vit.build_vit(
            cfg.arch, num_classes=num_classes, dtype=dtype,
            dropout=cfg.dropout,
            mesh=mesh if mesh is not None and mesh.size > 1 else None,
            seq_axis=seq, remat=cfg.remat, use_flash=cfg.flash_attention,
            moe_experts=cfg.moe_experts, moe_top_k=cfg.moe_top_k,
            moe_axis=moe_axis, flash_min_tokens=cfg.flash_min_tokens,
            ln_bf16=cfg.ln_bf16,
        )
    raise ValueError(f"unknown arch {cfg.arch!r}")


def build_decoder_lm(cfg: ModelConfig, num_classes: int,
                     mesh: Optional[Any] = None) -> nn.Module:
    """The token decoder (models/decoder_lm.py): a per-token classifier over
    the vocabulary, so `num_classes` IS its vocabulary (the CLI keeps the two
    equal). A `model` mesh axis > 1 shards the expert banks over it."""
    from ..parallel.mesh import MODEL_AXIS
    from .decoder_lm import DecoderLM

    dc = cfg.decoder
    if cfg.head != "fc":
        raise ValueError(f"decoder_lm trains with head='fc', got {cfg.head!r}")
    if num_classes != dc.vocab_size:
        raise ValueError(
            f"decoder_lm classifies over its vocabulary: num_classes "
            f"{num_classes} != vocab_size {dc.vocab_size}")
    if dc.num_heads % dc.num_kv_heads:
        raise ValueError(f"{dc.num_heads} query heads do not divide over "
                         f"{dc.num_kv_heads} KV heads")
    if dc.first_expert + dc.held > dc.num_experts:
        raise ValueError(
            f"experts {dc.first_expert}..{dc.first_expert + dc.held - 1} are "
            f"not among the router's {dc.num_experts}")
    kinds = {"attention": ("gqa", "mla"), "rope_pairing": ("half", "interleaved"),
             "activation": ("relu", "silu"), "router": ("softmax", "sigmoid"),
             "router_tap": ("pre", "post"),
             "objective": ("next_token", "block_diffusion")}
    for key, allowed in kinds.items():
        if getattr(dc, key) not in allowed:
            raise ValueError(f"decoder {key}={getattr(dc, key)!r}: one of {allowed}")
    if dc.attention == "mla" and not (dc.kv_rank and dc.rope_dim):
        raise ValueError("latent attention needs --kv_rank and --rope_dim "
                         "(--q_rank 0: queries without a bottleneck)")
    if not 0 <= dc.dense_layers <= dc.num_layers or (dc.dense_layers
                                                     and not dc.dense_width):
        raise ValueError(f"{dc.dense_layers} dense layers of width "
                         f"{dc.dense_width} in a depth of {dc.num_layers}")
    if dc.qk_norm and dc.attention != "gqa":
        raise ValueError("--qk_norm norms grouped-query heads; latent "
                         "attention norms its two latents already")
    if dc.qk_norm not in (0, 1, 2):
        raise ValueError(f"--qk_norm {dc.qk_norm}: 0 = none, 1 = every head, "
                         "2 = the whole projection")
    if not dc.pre_norm and not dc.sandwich_norm:
        raise ValueError("--pre_norm 0 without --sandwich_norm 1 leaves a "
                         "sub-layer no norm at all")
    mixers = ("conv_layout", "kda_layout", "gdn_layout")
    for key in mixers:
        if any(v not in (0, 1) for v in getattr(dc, key)):
            raise ValueError(f"{key} {tuple(getattr(dc, key))} is 0/1 per layer")
    if any(sum(marks) > 1 for marks in zip(*(dc.layout(getattr(dc, key))
                                             for key in mixers))):
        raise ValueError(f"{', '.join(mixers)}: two of them mark the same layer")
    operators = {op for op, _ in dc.layer_kinds()}
    if operators & {"kda", "gdn"}:
        from ..ops.kda import chunk_of

        chunk_of(dc.seq_len)   # refuses a row that is not whole chunks
    if "gdn" in operators and not (dc.gdn_key_dim > 0 and dc.gdn_value_dim > 0):
        raise ValueError("Gated DeltaNet layers need --gdn_key_dim and "
                         "--gdn_value_dim")
    mp = mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1
    if dc.heads_held:
        # ROADMAP R13 (a) stands open for the three below: their projections
        # are not cut by heads here (a latent's up-projection, KDA's fused
        # input side and kernels, a convolution without heads)
        refused = operators & {"mla", "kda", "conv"}
        if refused:
            raise ValueError(
                f"--heads_held {dc.heads_held}: a share of the heads is built "
                f"for 'gqa' and Gated DeltaNet layers, not for {sorted(refused)}")
        group = dc.num_heads // dc.num_kv_heads
        if (dc.heads_held > dc.num_heads or dc.heads_held % mp
                or (dc.heads_held // mp) % group):
            raise ValueError(
                f"{dc.heads_held} of {dc.num_heads} heads over {mp} shard(s): a "
                f"share is whole groups of {group} query head(s) on one KV "
                f"head, the same number a shard")
    if dc.n_group > 1 and (dc.router != "sigmoid" or dc.num_experts % dc.n_group
                           or not 1 <= dc.topk_group <= dc.n_group
                           or dc.top_k > dc.topk_group * (dc.num_experts // dc.n_group)):
        raise ValueError(
            f"n_group {dc.n_group} / topk_group {dc.topk_group}: a sigmoid "
            f"router's {dc.num_experts} experts in equal groups, the kept "
            f"groups holding at least top_k {dc.top_k}")
    if dc.mtp_layers not in (0, 1):
        raise ValueError("multi-token prediction is built at depth 0 or 1, "
                         f"got mtp_layers={dc.mtp_layers}")
    if dc.loops < 1 or (dc.loops > 1 and (dc.mtp_layers
                                          or dc.dense_layers != dc.num_layers)):
        raise ValueError(
            f"--loops {dc.loops}: the stack runs 1 or more times, and a "
            "looped stack is built of dense layers (--dense_layers = "
            "--num_layers) without a prediction module")
    if dc.diffusion:
        # the two-stream pass is written for attention under its own mask:
        # the other mixers' causal taps and carried states, a window's band,
        # a second pass and a shifted second loss have no two-stream form here
        refused = sorted(operators & {"conv", "kda", "gdn"})
        windowed = any(w and op == dc.attention for w, (op, _) in
                       zip(dc.layout(dc.window_layout), dc.layer_kinds()))
        why = (f"the {refused} mixers" if refused else
               "a window layer (--window_layout)" if windowed else
               f"--loops {dc.loops}" if dc.loops > 1 else
               f"--mtp_layers {dc.mtp_layers}" if dc.mtp_layers else "")
        if why:
            raise ValueError(
                f"--objective block_diffusion trains attention layers under "
                f"the two-stream mask in one pass; it is not built for {why}")
        if (dc.diffusion_block < 1 or dc.seq_len % dc.diffusion_block
                or not 0 <= dc.mask_token < dc.vocab_size
                or not 0.0 <= dc.diffusion_eps < 1.0):
            raise ValueError(
                f"--objective block_diffusion: rows of {dc.seq_len} tokens in "
                f"blocks of {dc.diffusion_block}, mask id {dc.mask_token} of "
                f"{dc.vocab_size}, eps {dc.diffusion_eps}: whole blocks, an id "
                "of the vocabulary held, 0 <= eps < 1")
    return DecoderLM(dc, dtype=jnp.dtype(cfg.dtype), remat=cfg.remat,
                     mesh=mesh if mp > 1 else None,
                     expert_axis=MODEL_AXIS if mp > 1 else None,
                     flash_min_tokens=cfg.flash_min_tokens)


class ClassifierModel(nn.Module):
    """backbone → logits (BASELINE/CDR shape)."""

    backbone: nn.Module

    def __call__(self, x: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        return self.backbone(x, train=train)


class ArcFaceModel(nn.Module):
    """backbone → embedding → margin head (ARCFACE shape). Call with labels
    for training logits; labels=None gives s·cosθ scores."""

    backbone: nn.Module
    embedding: ArcEmbedding
    margin: ArcMarginHead

    def __call__(self, x, labels=None, train: bool = True):
        feat = self.backbone(x, train=train)
        emb = self.embedding(feat)
        return self.margin(emb, labels)

    def features(self, x, train: bool = True):
        """Embedding only — the class-sharded CE path (ops/sharded_head.py)
        consumes embeddings + the raw margin weight, skipping the (B, C)
        logits the margin head would build."""
        return self.embedding(self.backbone(x, train=train))


class NestedModel(nn.Module):
    """NetFeat + NetClassifier with a feature mask slot (NESTED shape,
    model/model.py:12-76). `mask=None` → unmasked logits."""

    backbone: nn.Module
    classifier: NetClassifier

    def __call__(self, x, mask=None, train: bool = True):
        feat = self.backbone(x, train=train)
        if mask is not None:
            feat = feat * mask
        return self.classifier(feat)

    def features(self, x, train: bool = False):
        return self.backbone(x, train=train)


def build_model(cfg: ModelConfig, num_classes: int,
                axis_name: Optional[str] = None,
                mesh: Optional[Any] = None,
                pipeline_microbatches: int = 0) -> Any:
    if pipeline_microbatches > 0:
        from ..parallel.mesh import MODEL_AXIS, PIPE_AXIS
        from .pipeline_vit import GPipeArcFaceViT, GPipeViT

        if cfg.arch not in _vit.VIT_CONFIGS:
            raise ValueError(
                f"pipeline parallelism (--pp_microbatches) requires a ViT "
                f"arch with a homogeneous block stack; got {cfg.arch!r}")
        if mesh is None:
            raise ValueError("pipeline parallelism requires a device mesh")
        if cfg.dropout:
            raise ValueError(
                "pipeline parallelism does not support dropout (the tick "
                "loop carries no per-tick rng); set --dropout 0")
        if cfg.moe_experts:
            raise ValueError(
                "pipeline parallelism and moe_experts both claim the model "
                "axis — one role per config (drop --pp_microbatches or "
                "--moe_experts)")
        # a dedicated 'pipe' axis (3-axis mesh, --pp_stages) hosts the
        # stage ring so the 'model' axis stays free for class-dim TP;
        # legacy 2-axis meshes keep the one-role-per-config 'model' ring
        pipe_axis = (PIPE_AXIS if dict(mesh.shape).get(PIPE_AXIS, 1) > 1
                     else MODEL_AXIS)
        if cfg.head == "arcface":
            return GPipeArcFaceViT(
                cfg.arch, num_classes, mesh, pipeline_microbatches,
                dtype=jnp.dtype(cfg.dtype), axis_name=pipe_axis,
                remat=cfg.remat,
                embed_dims=(512, cfg.arc_embed_dim),
                s=cfg.arc_s, m=cfg.arc_m, easy_margin=cfg.arc_easy_margin,
                log_softmax_quirk=cfg.arc_log_softmax_quirk,
                ln_bf16=cfg.ln_bf16)
        if cfg.head != "fc":
            raise ValueError(
                f"pipeline parallelism supports head='fc' or 'arcface' "
                f"(got {cfg.head!r})")
        return GPipeViT(
            cfg.arch, num_classes, mesh, pipeline_microbatches,
            dtype=jnp.dtype(cfg.dtype), axis_name=pipe_axis, remat=cfg.remat,
            ln_bf16=cfg.ln_bf16)
    if cfg.arch == "decoder_lm":
        return build_decoder_lm(cfg, num_classes, mesh)
    if cfg.head == "fc":
        return ClassifierModel(build_backbone(cfg, num_classes, axis_name, mesh))
    if cfg.head == "arcface":
        return ArcFaceModel(
            backbone=build_backbone(cfg, 0, axis_name, mesh),
            embedding=ArcEmbedding(dims=(512, cfg.arc_embed_dim),
                                   log_softmax_quirk=cfg.arc_log_softmax_quirk),
            margin=ArcMarginHead(
                num_classes=num_classes, in_features=cfg.arc_embed_dim,
                s=cfg.arc_s, m=cfg.arc_m, easy_margin=cfg.arc_easy_margin,
            ),
        )
    if cfg.head == "nested":
        return NestedModel(
            backbone=build_backbone(cfg, 0, axis_name, mesh),
            classifier=NetClassifier(num_classes),
        )
    raise ValueError(f"unknown head {cfg.head!r}")


class ModelReport:
    """What a model says about itself to the loop that trains it
    (`model_report`). An image model: the images it is initialised on, and
    nothing else — no token rows, no notes, no counters, no gauges."""

    def init_inputs(self, image_size: int) -> Any:
        return jnp.zeros((2, image_size, image_size, 3), jnp.float32)

    def token_row_length(self) -> int:
        raise ValueError("dataset 'tokens' feeds a model that reads token "
                         "rows (--model decoder_lm)")

    def datasets(self, train_ds, val_ds, seed: int):
        """What the loaders read: the datasets as built, or wrapped where
        the model's objective makes its inputs in the loader."""
        return train_ds, val_ds

    def built(self, rows: int, registry, image_size: int = 0) -> dict:
        """Notes for the set-up line on what was built for steps of `rows`
        rows (of `image_size` pixels a side, where the model reads images);
        its static counters go into `registry`."""
        return {}

    def logged_step(self, metrics, registry) -> None:
        """What a logged step's metrics show, into `registry`."""

    def epoch_gauges(self, metrics) -> list:
        """Names of an epoch's mean metrics published as `train_<name>`."""
        return []


class ViTReport(ModelReport):
    """A ViT also says which attention core its blocks take at the step's
    shapes: what `vit_attention_total{path}` counts once the step is traced
    (models/vit.py::attention_path, the rule `MHA` itself dispatches on)."""

    def __init__(self, cfg: ModelConfig, mesh: Optional[Any],
                 pipelined: bool):
        # a pipelined stack builds its blocks bare, inside its own shard_map
        # (models/pipeline_vit.py): no mesh, no streaming kernels
        self.vit = build_backbone(cfg, mesh=None if pipelined else mesh)
        self.use_flash = self.vit.use_flash and not pipelined

    def built(self, rows: int, registry, image_size: int = 0) -> dict:
        v = self.vit
        path, _ = _vit.attention_path(
            rows, (image_size // v.patch) ** 2, v.heads, v.dim // v.heads,
            v.dtype, v.mesh, v.seq_axis, self.use_flash, v.flash_min_tokens)
        return {"vit_attention": path}


def model_report(cfg: ModelConfig, mesh: Optional[Any] = None,
                 pipeline_microbatches: int = 0) -> ModelReport:
    """The report of the model `build_model` builds from the same
    arguments."""
    if cfg.arch == "decoder_lm":
        from .decoder_report import DecoderReport

        return DecoderReport(cfg)
    if cfg.arch in _vit.VIT_CONFIGS:
        return ViTReport(cfg, mesh, pipeline_microbatches > 0)
    return ModelReport()
