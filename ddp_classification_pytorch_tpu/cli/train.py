"""Unified CLI — replaces the reference's four launch stacks.

Reference (SURVEY L6): `torch.distributed.launch --nproc_per_node=N main.py
--world_size=N --local_rank …` per silo (BASELINE/train.sh:1,
ARCFACE/arc_train.sh:1, CDR/train.sh:1-4, NESTED/train.sh:1-7). On TPU there
is no process-per-device launcher: ONE process per host sees all local chips,
and `jax.distributed.initialize()` is the only multi-host setup. So
`--nproc_per_node/--world_size/--local_rank` cease to exist by design — the
`--device` branch the north star asks for is the `--platform` flag here.

Every behavior-affecting reference flag maps to a field of the Config tree:

    python -m ddp_classification_pytorch_tpu.cli.train baseline \
        --folder /data/food --batchsize 16 --model resnet50 --lr 0.001
    python -m ddp_classification_pytorch_tpu.cli.train arcface  --optimizer adam
    python -m ddp_classification_pytorch_tpu.cli.train cdr      --noise_rate 0.2
    python -m ddp_classification_pytorch_tpu.cli.train nested   --nested 100 \
        --warmUpIter 10000 --freeze-bn
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Sequence

from ..config import Config, get_preset


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ddp_classification_pytorch_tpu.cli.train",
        description="TPU-native classification training (all reference workloads)",
    )
    p.add_argument("workload", choices=["baseline", "arcface", "cdr", "nested", "plc"],
                   help="which reference silo's recipe to run")

    d = p.add_argument_group("data")
    d.add_argument("--folder", "-f", default="", help="dataset root containing "
                   "train/val dirs (reference --folder, BASELINE/main.py:27)")
    d.add_argument("--train_dir", default="", help="explicit train dir (overrides --folder)")
    d.add_argument("--val_dir", default="", help="explicit val dir (overrides --folder)")
    d.add_argument("--dataset", default="",
                   help="imagefolder | synthetic | plc | cifar10 | cifar100 | "
                        "tokens (--train_dir names a flat file of int32 "
                        "ids, cut into rows of --seq_len + 1)")
    d.add_argument("--synthetic_size", type=int, default=0,
                   help="train-set size for --dataset synthetic (default "
                        "512); drills shrink it so multi-process restart "
                        "cycles stay control-path-bound, not compute-bound")
    d.add_argument("--batchsize", "-b", type=int, default=0,
                   help="PER-HOST batch size; the global batch is "
                   "batchsize × num_hosts (cf. reference per-GPU batch, "
                   "BASELINE/main.py:29)")
    d.add_argument("--num_classes", type=int, default=0)
    d.add_argument("--imgs_per_class", type=int, default=0,
                   help="per-class cap (500 baseline / 400 arcface)")
    d.add_argument("--num_workers", type=int, default=0, help="host loader threads")
    d.add_argument("--device_prefetch", type=int, default=-1,
                   help="device batches staged ahead of the step loop by a "
                        "background H2D stager thread (default 2; each "
                        "staged batch holds device memory; 0 = synchronous "
                        "assembly inside the step loop)")
    d.add_argument("--h2d-overlap", dest="h2d_overlap", action="store_true",
                   help="double-buffered H2D dispatch: fetch host batch N+1 "
                        "on a separate thread while batch N's "
                        "make_global_array transfer is in flight (one-slot "
                        "in-flight budget; ignored at --device_prefetch 0)")
    d.add_argument("--image_size", type=int, default=0)
    d.add_argument("--crop_size", type=int, default=0,
                   help="train-crop / resize-short side (default 256, the "
                        "reference's RandomResizedCrop(256); set ~= "
                        "--image_size for small-image folders)")
    d.add_argument("--transform", default="",
                   help="transform preset for imagefolder data: baseline | "
                        "cdr | cifar | clothing1m (default: workload preset; "
                        "'cifar' = pad-4 random crop + flip at --image_size, "
                        "for small-image folders)")
    d.add_argument("--input_dtype", default="", choices=["", "uint8", "float32"],
                   help="H2D wire format (default uint8): 'uint8' ships raw "
                        "pixels at ¼ the bytes and fuses normalization + the "
                        "train flip into the jitted step; 'float32' is the "
                        "legacy host-normalize path, numerically exact to "
                        "the pre-uint8 framework")

    m = p.add_argument_group("model")
    m.add_argument("--model", "--arch", dest="model", default="",
                   help="resnet18/34/50/101/152 | vgg19_bn | tresnet_m | "
                        "vit_t16/s16/b16 (reference --model + extensions) | "
                        "decoder_lm (a token decoder: next-token training "
                        "as per-position classification; sizes in the "
                        "'decoder' group: per layer grouped-query or latent "
                        "attention, a gated short convolution, Kimi delta "
                        "attention or Gated DeltaNet, a share of the heads, "
                        "dense / routed / shared feed-forward, "
                        "softmax or sigmoid router with or without a group "
                        "limit, a multi-token-prediction module, a tied or "
                        "untied head, the stack run --loops times with an "
                        "exit gate, --objective next_token or "
                        "block_diffusion; defaults = the published "
                        "SmallThinker-21BA3B-Instruct)")
    m.add_argument("--flash_attention", action="store_true",
                   help="ViT: Pallas streaming attention kernel for the "
                        "unsharded path")
    m.add_argument("--flash_min_tokens", type=int, default=-1,
                   help="auto-pick floor: below this token count "
                        "--flash_attention does not take the streaming "
                        "kernels (a ViT's row then takes the whole-row "
                        "kernel pair where it fits, else the dense op; "
                        "default 1024; 0 = the streaming kernels always)")
    m.add_argument("--ln_bf16", action="store_true",
                   help="ViT: LayerNorms in bf16 instead of f32 (bandwidth "
                        "experiment; no chip reading yet, ROADMAP S4)")
    m.add_argument("--variant", default="", help="imagenet | cifar stem")
    m.add_argument("--pretrained", action="store_true",
                   help="load converted torchvision weights")
    m.add_argument("--pretrained_path", default="",
                   help=".pth/.pt torch checkpoint to import (torchvision "
                   "state_dict or NESTED {'feat','cls'} format)")
    m.add_argument("--dtype", default="", help="bfloat16 | float32 compute dtype")
    m.add_argument("--dropout", type=float, default=-1.0)
    m.add_argument("--remat", action="store_true",
                   help="rematerialize residual blocks (trade FLOPs for HBM; "
                   "enables larger global batches)")

    o = p.add_argument_group("optimization")
    o.add_argument("--optimizer", default="", help="sgd | adam (arc_main.py:34-43)")
    o.add_argument("--lr", type=float, default=0.0)
    o.add_argument("--momentum", type=float, default=-1.0)
    o.add_argument("--weight_decay", type=float, default=-1.0)
    o.add_argument("--epochs", type=int, default=0)
    o.add_argument("--lrSchedule", type=int, nargs="*", default=None,
                   help="multistep milestones (NESTED/train.py:472)")
    o.add_argument("--warmUpIter", type=int, default=-1,
                   help="linear warmup iterations (NESTED/train.py:466)")
    o.add_argument("--adam_b2", type=float, default=-1.0,
                   help="Adam's second-moment decay (default 0.999)")

    dec = p.add_argument_group(
        "decoder", "sizes of --model decoder_lm (config.DecoderConfig); "
        "--num_classes follows --vocab_size")
    # in config.DecoderConfig's own order, which says what each value means
    for flag, kind in (("vocab_size", int), ("hidden_size", int),
                       ("num_layers", int), ("num_heads", int),
                       ("num_kv_heads", int), ("head_dim", int),
                       ("expert_width", int), ("num_experts", int),
                       ("experts_held", int), ("first_expert", int),
                       ("top_k", int), ("window", int), ("rope_theta", float),
                       ("rms_eps", float), ("seq_len", int),
                       ("head_block", int),
                       ("attention", str), ("q_rank", int), ("kv_rank", int),
                       ("rope_dim", int), ("v_head_dim", int),
                       ("rope_pairing", str), ("qk_norm", int),
                       ("out_gate", int), ("gdn_key_dim", int),
                       ("gdn_value_dim", int), ("conv_kernel", int),
                       ("heads_held", int), ("dense_layers", int),
                       ("dense_width", int),
                       ("activation", str), ("router", str),
                       ("router_scale", float), ("router_eps", float),
                       ("n_group", int), ("topk_group", int),
                       ("router_tap", str), ("shared_experts", int),
                       ("mtp_layers", int), ("mtp_weight", float),
                       ("tied_embeddings", int), ("loops", int),
                       ("sandwich_norm", int), ("pre_norm", int),
                       ("exit_beta", float), ("objective", str),
                       ("diffusion_block", int), ("diffusion_eps", float),
                       ("mask_id", int)):
        dec.add_argument(f"--{flag}", type=kind, default=None)
    for flag in ("rope_layout", "window_layout", "conv_layout", "kda_layout",
                 "gdn_layout"):
        dec.add_argument(f"--{flag}", default=None,
                         help="comma-separated 0/1 per layer, repeated to "
                              "the depth (e.g. 0,1,1,1); conv_layout: 1 = the "
                              "gated short convolution in attention's place; "
                              "kda_layout: 1 = Kimi delta attention there; "
                              "gdn_layout: 1 = Gated DeltaNet there")

    a = p.add_argument_group("arcface")
    a.add_argument("--arc_s", type=float, default=-1.0)
    a.add_argument("--arc_m", type=float, default=-1.0)
    a.add_argument("--head_lr", type=float, default=-1.0,
                   help="lr for the margin-head param group (reference's "
                        "optimizer group 2, arc_main.py:248-253); unset = "
                        "inherit --lr")
    a.add_argument("--head_weight_decay", type=float, default=-1.0,
                   help="weight decay for the margin-head param group; "
                        "unset = inherit --weight_decay")
    a.add_argument("--easy_margin", dest="easy_margin", default=None,
                   action="store_true")

    c = p.add_argument_group("cdr")
    c.add_argument("--noise_rate", type=float, default=-1.0, help="CDR/main.py:37")
    c.add_argument("--num_gradual", type=int, default=-1, help="CDR/main.py:41")
    c.add_argument("--live_clip_schedule", action="store_true",
                   help="use the reference's INTENDED gradual clip schedule "
                   "instead of its actual dead-code constant (CDR/main.py:222-227)")

    n = p.add_argument_group("nested")
    n.add_argument("--nested", type=float, default=-1.0,
                   help="Gaussian σ over feature dims (NESTED/train.py:512-530)")
    n.add_argument("--freeze-bn", dest="freeze_bn", default=None, action="store_true")
    n.add_argument("--no-freeze-bn", dest="freeze_bn", action="store_false",
                   help="train BN normally (the preset's freeze-BN mirrors "
                        "NESTED/train.py:529, which assumes a pretrained "
                        "backbone; from-scratch runs want live BN)")
    n.add_argument("--resumePth", default="", help="NESTED/train.py:481")

    pl = p.add_argument_group("plc")
    pl.add_argument("--correction", default="", choices=["", "lrt", "prob"],
                    help="label-correction method (PLC/utils.py:291,321)")
    pl.add_argument("--delta", type=float, default=-1.0, help="initial θ threshold")
    pl.add_argument("--delta_increment", type=float, default=-1.0, help="β step")
    pl.add_argument("--thd", type=float, default=-1.0, help="prob-correction confidence")
    pl.add_argument("--plc_warmup_epochs", type=int, default=-1)
    pl.add_argument("--plc_max_flip_frac", type=float, default=-1.0,
                    help="cap the label fraction one correction pass may "
                         "flip, keeping the most-confident flips; guards "
                         "against self-confirming collapse on an immature "
                         "model (1.0 = uncapped reference semantics)")
    pl.add_argument("--plc_batch_stat_predictions", action="store_true",
                    help="harvest correction f(x) with each batch's own BN "
                         "statistics (the reference's during-training "
                         "flavor, PLC/utils.py:269-271); UNSAFE on the "
                         "default class-sorted scan — measured 63%% vs 99%% "
                         "prediction accuracy vs the running-stat default")

    r = p.add_argument_group("run")
    r.add_argument("--seed", type=int, default=-1)
    r.add_argument("--out", default="", help="output dir (records + checkpoints)")
    r.add_argument("--resume", default="", help="checkpoint path to resume from")
    r.add_argument("--auto_resume", action="store_true",
                   help="resume from the latest checkpoint in --out if any "
                        "(preemption recovery; see scripts/supervise.sh)")
    r.add_argument("--tensorboard", action="store_true",
                   help="write TensorBoard event files to <out>/tb "
                        "(dependency-free writer, utils/tensorboard.py)")
    r.add_argument("--log_every", type=int, default=0)
    r.add_argument("--save_best_only", action="store_true")
    r.add_argument("--keep_checkpoints", type=int, default=0,
                   help="prune per-epoch checkpoints beyond the newest N "
                        "(0 = keep all; ckpt_best is always kept)")
    r.add_argument("--profile_steps", type=int, default=0,
                   help="capture a jax.profiler trace of N train steps")
    r.add_argument("--debug_nans", action="store_true",
                   help="enable jax_debug_nans (fail fast on NaN)")
    r.add_argument("--hang_timeout_s", type=float, default=0.0,
                   help="mid-run hang watchdog: exit 7 when no host-observed "
                        "progress lands for this many seconds, so "
                        "supervise.sh + --auto_resume can recover (0 = off; "
                        "set WELL above the slowest compile — 900+ on "
                        "the TPU, more for TResNet)")
    r.add_argument("--max_bad_steps", type=int, default=-1,
                   help="non-finite step sentinel: every train step skips "
                        "its update (identity) when loss/grad-norm go "
                        "NaN/Inf; after N CONSECUTIVE skips exit 8 "
                        "('diverged' — deterministic, supervise.sh does "
                        "not restart it). Default 25; 0 = skip forever, "
                        "never exit")
    r.add_argument("--strict_compile", action="store_true",
                   help="make a steady-state recompile fatal (rc 2 at the "
                        "epoch boundary): after the first eval'd epoch a "
                        "compile sentinel treats any further XLA compile as "
                        "a signature drift; default logs it warn-only "
                        "(analysis/compile_sentinel.py)")
    r.add_argument("--fault_spec", default="",
                   help="deterministic fault injection (utils/chaos.py), "
                        "e.g. 'nan_loss@step=7..9,ckpt_io@epoch=1,"
                        "loader_io@batch=3,sigterm@step=20'; "
                        "CHAOS_FAULT_SPEC env overrides; see "
                        "scripts/chaos_drill.sh")
    r.add_argument("--grad_accum", type=int, default=0,
                   help="microbatch accumulation factor")
    r.add_argument("--platform", default="", choices=["", "tpu", "cpu"],
                   help="force a JAX platform (the north star's --device branch); "
                   "default: whatever jax finds (TPU when present)")

    par = p.add_argument_group("parallelism")
    par.add_argument("--dp", type=int, default=0,
                     help="data-parallel mesh axis size (0 = all devices)")
    par.add_argument("--mp", type=int, default=0,
                     help="model-parallel axis (class-dim sharding of wide "
                          "heads; ring-attention seq sharding for ViT; "
                          "pipeline stages with --pp_microbatches)")
    par.add_argument("--pp_microbatches", type=int, default=0,
                     help="enable GPipe pipelining of the ViT block stack "
                          "over the model axis with N microbatches")
    par.add_argument("--pp_stages", type=int, default=0,
                     help="give the pipeline its OWN mesh axis with N "
                          "stages (3-axis dp×tp×pp mesh), composing with "
                          "--mp class-dim TP; devices = dp×mp×N")
    par.add_argument("--dcn_slices", type=int, default=0,
                     help="multi-slice pods: two-tier mesh with DP across "
                          "N DCN-connected slices, model axis on ICI")
    par.add_argument("--moe_experts", type=int, default=0,
                     help="ViT: dropless split-FFN mixture-of-experts with "
                          "N experts per block; with --mp > 1 the experts "
                          "shard over the model axis (expert parallelism)")
    par.add_argument("--moe_top_k", type=int, default=2,
                     help="router top-k for --moe_experts")
    par.add_argument("--moe_aux_weight", type=float, default=None,
                     help="router load-balance penalty weight "
                          "(default 0.01; 0 disables)")
    par.add_argument("--sharded_ce", action="store_true",
                     help="arcface: partial-FC loss — class-sharded "
                          "softmax-CE over the model axis, no (B, C) "
                          "logits (needs --mp > 1, classes divisible)")
    par.add_argument("--zero_opt", default="",
                     choices=["", "auto", "on", "off"],
                     help="ZeRO-1: partition optimizer state over the data "
                          "axis (reduce-scatter grads, shard-local update, "
                          "all-gather params); 'auto' (the default) enables "
                          "it whenever the data axis spans >1 device")
    par.add_argument("--grad_reduce_dtype", default="",
                     choices=["", "float32", "bfloat16"],
                     help="wire dtype of the cross-replica gradient "
                          "reduction; bfloat16 halves the payload, master "
                          "params/momentum stay f32 (torch-AMP-style)")
    par.add_argument("--multihost", action="store_true",
                     help="call jax.distributed.initialize() (TPU pods)")

    compat = p.add_argument_group("reference-CLI compatibility (ignored)")
    compat.add_argument("--world_size", type=int, default=None,
                        help="ignored: TPU meshes derive their size from the "
                        "hardware; parallelism is --dp/--mp")
    compat.add_argument("--local_rank", type=int, default=None,
                        help="ignored: no per-device processes on TPU; one "
                        "process per host sees all local chips")
    compat.add_argument("--gpu", default=None,
                        help="ignored: device selection is the backend's "
                        "(CDR/main.py:51, NESTED/train.py:473 pass it; "
                        "scripted reference invocations must not break)")
    return p


def config_from_args(args: argparse.Namespace) -> Config:
    cfg = get_preset(args.workload)

    if args.folder:
        cfg.data.train_dir = f"{args.folder}/train"
        cfg.data.val_dir = f"{args.folder}/val"
    if args.train_dir:
        cfg.data.train_dir = args.train_dir
    if args.val_dir:
        cfg.data.val_dir = args.val_dir
    if args.dataset:
        cfg.data.dataset = args.dataset
        if args.dataset in ("cifar10", "cifar100"):
            # CIFAR facts override the preset's ImageNet-ish defaults unless
            # the user explicitly passes the flags
            if not args.num_classes:
                cfg.data.num_classes = 10 if args.dataset == "cifar10" else 100
            if not args.image_size:
                cfg.data.image_size = 32
            if not args.variant:
                cfg.model.variant = "cifar"
    if args.synthetic_size:
        cfg.data.synthetic_size = args.synthetic_size
    if args.batchsize:
        cfg.data.batch_size = args.batchsize
    if args.num_classes:
        cfg.data.num_classes = args.num_classes
    if args.imgs_per_class:
        cfg.data.imgs_per_class = args.imgs_per_class
    if args.num_workers:
        cfg.data.num_workers = args.num_workers
    if args.device_prefetch >= 0:
        cfg.data.device_prefetch = args.device_prefetch
    if args.h2d_overlap:
        cfg.data.h2d_overlap = True
    if args.image_size:
        cfg.data.image_size = args.image_size
    if args.crop_size:
        cfg.data.train_crop_size = args.crop_size
    if args.transform:
        cfg.data.transform = args.transform
    if args.input_dtype:
        cfg.data.input_dtype = args.input_dtype

    if args.model:
        cfg.model.arch = args.model
    if args.flash_attention:
        cfg.model.flash_attention = True
    if args.ln_bf16:
        cfg.model.ln_bf16 = True
    if args.flash_min_tokens >= 0:
        cfg.model.flash_min_tokens = args.flash_min_tokens
    if args.variant:
        cfg.model.variant = args.variant
    if args.pretrained:
        cfg.model.pretrained = True
    if args.pretrained_path:
        cfg.model.pretrained = True
        cfg.model.pretrained_path = args.pretrained_path
    if args.dtype:
        cfg.model.dtype = args.dtype
    if args.dropout >= 0:
        cfg.model.dropout = args.dropout
    if args.remat:
        cfg.model.remat = True
    if args.arc_s >= 0:
        cfg.model.arc_s = args.arc_s
    if args.arc_m >= 0:
        cfg.model.arc_m = args.arc_m
    if args.easy_margin is not None:
        cfg.model.arc_easy_margin = args.easy_margin
    if args.nested >= 0:
        cfg.model.nested_std = args.nested
    if args.freeze_bn is not None:
        cfg.model.freeze_bn = args.freeze_bn

    if args.optimizer:
        cfg.optim.optimizer = args.optimizer
    if args.lr:
        cfg.optim.lr = args.lr
    if args.momentum >= 0:
        cfg.optim.momentum = args.momentum
    if args.weight_decay >= 0:
        cfg.optim.weight_decay = args.weight_decay
    if args.head_lr >= 0:
        cfg.optim.head_lr = args.head_lr
    if args.head_weight_decay >= 0:
        cfg.optim.head_weight_decay = args.head_weight_decay
    if args.lrSchedule is not None:
        cfg.optim.schedule = "multistep"
        cfg.optim.milestones = tuple(args.lrSchedule)
    if args.warmUpIter >= 0:
        cfg.optim.warmup_iters = args.warmUpIter
    if args.adam_b2 >= 0:
        cfg.optim.adam_b2 = args.adam_b2
    dc = cfg.model.decoder
    for f in dataclasses.fields(dc):
        value = getattr(args, f.name, None)
        if value is not None:
            setattr(dc, f.name, tuple(int(x) for x in value.split(","))
                    if f.name.endswith("_layout") else value)
    if cfg.model.arch == "decoder_lm":
        if args.num_classes and args.num_classes != dc.vocab_size:
            raise ValueError(
                f"--num_classes {args.num_classes} != --vocab_size "
                f"{dc.vocab_size}: the decoder classifies over its vocabulary")
        cfg.data.num_classes = dc.vocab_size
    if args.noise_rate >= 0:
        cfg.optim.noise_rate = args.noise_rate
    if args.num_gradual >= 0:
        cfg.optim.num_gradual = args.num_gradual
    if args.live_clip_schedule:
        cfg.optim.cdr_dead_schedule = False

    if args.epochs:
        cfg.run.epochs = args.epochs
    if args.seed >= 0:
        cfg.run.seed = args.seed
    if args.out:
        cfg.run.out_dir = args.out
    if args.resume or args.resumePth:
        cfg.run.resume = args.resume or args.resumePth
    if args.auto_resume:
        cfg.run.auto_resume = True
    if args.tensorboard:
        cfg.run.tensorboard = True
    if args.log_every:
        cfg.run.log_every = args.log_every
    if args.save_best_only:
        cfg.run.save_best_only = True
    if args.keep_checkpoints:
        cfg.run.keep_checkpoints = args.keep_checkpoints
    if args.profile_steps:
        cfg.run.profile_steps = args.profile_steps
    if args.debug_nans:
        cfg.run.debug_nans = True
    if args.hang_timeout_s:
        cfg.run.hang_timeout_s = args.hang_timeout_s
    if args.max_bad_steps >= 0:
        cfg.run.max_bad_steps = args.max_bad_steps
    if args.strict_compile:
        cfg.run.strict_compile = True
    if args.fault_spec:
        cfg.run.fault_spec = args.fault_spec
    if args.grad_accum:
        cfg.parallel.grad_accum = args.grad_accum

    if args.correction:
        cfg.plc.correction = args.correction
    if args.delta >= 0:
        cfg.plc.current_delta = args.delta
    if args.delta_increment >= 0:
        cfg.plc.delta_increment = args.delta_increment
    if args.thd >= 0:
        cfg.plc.thd = args.thd
    if args.plc_warmup_epochs >= 0:
        cfg.plc.warmup_epochs = args.plc_warmup_epochs
    if args.plc_max_flip_frac >= 0:
        cfg.plc.max_flip_frac = args.plc_max_flip_frac
    if args.plc_batch_stat_predictions:
        cfg.plc.batch_stat_predictions = True

    if args.dp:
        cfg.parallel.data_axis = args.dp
    if args.mp:
        cfg.parallel.model_axis = args.mp
    if args.pp_microbatches:
        cfg.parallel.pipeline_microbatches = args.pp_microbatches
    if args.pp_stages:
        if not args.pp_microbatches:
            raise ValueError("--pp_stages requires --pp_microbatches")
        cfg.parallel.pipeline_stages = args.pp_stages
    if args.dcn_slices:
        cfg.parallel.dcn_slices = args.dcn_slices
    if args.sharded_ce:
        cfg.parallel.arcface_sharded_ce = True
    if args.zero_opt:
        cfg.parallel.zero_opt = args.zero_opt
    if args.grad_reduce_dtype:
        cfg.parallel.grad_reduce_dtype = args.grad_reduce_dtype
    if args.moe_aux_weight is not None and args.moe_aux_weight < 0:
        raise ValueError(
            f"--moe_aux_weight must be >= 0, got {args.moe_aux_weight}")
    if args.moe_experts:
        cfg.model.moe_experts = args.moe_experts
        cfg.model.moe_top_k = args.moe_top_k
        if args.moe_aux_weight is not None:
            cfg.model.moe_aux_weight = args.moe_aux_weight
    return cfg


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    try:
        # cheap config errors surface before any backend use, and exit 2 — the same
        # code argparse uses for usage errors — so supervisors can tell a
        # deterministic config failure (rc 2: restarting replays the bug)
        # from an unhandled runtime exception (bare rc 1: transient
        # XlaRuntimeError, OOM, dataloader IO — retryable with backoff
        # under supervise.sh)
        cfg = config_from_args(args)
    except ValueError as e:
        import sys

        print(f"[trainer] config error: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    if args.multihost:
        # bounded-retry rendezvous (parallel/fleet.py): restarted hosts
        # miss each other's window under uncoordinated supervise.sh
        # backoffs, so initialize retries with a deterministic schedule
        # keyed off the shared $OUT/generation file; terminal failure is
        # rc 6 (outage-shaped — supervise.sh backs off OUTAGE_BACKOFF_S
        # and tries again instead of giving up fast)
        from ..parallel.fleet import (FleetConfigError, PodInconsistent,
                                      PodUnviable, RendezvousFailed,
                                      initialize_with_retry)
        from ..parallel.mesh import MeshSpec

        # the configured mesh gates elastic viability: a survivor world
        # whose device count cannot cover it is rc 10, not a
        # construction-time crash after rendezvous
        spec = MeshSpec(cfg.parallel.data_axis, cfg.parallel.model_axis,
                        max(cfg.parallel.pipeline_stages, 1))
        try:
            initialize_with_retry(out_dir=cfg.run.out_dir, mesh_spec=spec)
        except FleetConfigError as e:
            import sys

            # malformed FLEET_* launch env: deterministic, so the same
            # rc 2 as every other config error — supervise.sh must stop,
            # not replay the bad env MAX_RESTARTS times
            print(f"[trainer] config error: {e}", file=sys.stderr)
            raise SystemExit(FleetConfigError.exit_code) from None
        except PodUnviable as e:
            import sys

            # rc 10 = "pod-unviable": the survivor set is too small (or
            # does not divide into the mesh) — outage-shaped for the
            # supervisor, since dead peers may come back
            print(f"[trainer] pod-unviable: {e}", file=sys.stderr)
            raise SystemExit(PodUnviable.exit_code) from None
        except RendezvousFailed as e:
            import sys

            print(f"[trainer] {e}", file=sys.stderr)
            raise SystemExit(RendezvousFailed.exit_code) from None
        except PodInconsistent as e:
            import sys

            # the post-rendezvous membership digest agreement failed:
            # split-brain world views — same rc 9 as a split-brain resume
            print(f"[trainer] pod-inconsistent: {e}", file=sys.stderr)
            raise SystemExit(PodInconsistent.exit_code) from None
    if (args.world_size is not None or args.local_rank is not None
            or args.gpu is not None):
        print("[compat] --world_size/--local_rank/--gpu are ignored on TPU: "
              "one process per host, batch shards over the device mesh")
    from ..utils.cache import compile_stats_line, enable_persistent_cache

    enable_persistent_cache()

    from ..train.loop import Trainer
    from ..train.plc_loop import PLCTrainer
    from ..utils.seeding import set_seed

    set_seed(cfg.run.seed)
    if cfg.run.debug_nans:
        jax.config.update("jax_debug_nans", True)
    from ..parallel.fleet import PodAbort, PodInconsistent, PodReform

    trainer_cls = PLCTrainer if cfg.workload == "plc" else Trainer
    try:
        trainer = trainer_cls(cfg)
    except PodInconsistent as e:
        import sys

        # rc 9 = "pod-inconsistent": the resume digest agreement failed —
        # at least one host restored different bytes than host 0's
        # broadcast choice. Loud and immediate instead of a silent
        # split-brain resume; usually shared-filesystem staleness, so
        # supervise.sh retries it with a runtime backoff.
        print(f"[trainer] pod-inconsistent: {e}", file=sys.stderr)
        raise SystemExit(PodInconsistent.exit_code) from None
    except ValueError as e:
        import sys
        import traceback

        # construction-time ValueErrors are config-shaped and deterministic
        # (MeshSpec.resolve "mesh does not cover N devices" when an axis
        # doesn't divide the device count, build_model's pipeline arch/head
        # rejections, make_hybrid_mesh's dcn+pp rejection, a bad dataset or
        # checkpoint path) — map them to the same rc 2 as config_from_args
        # so supervise.sh doesn't replay the bug MAX_RESTARTS times with
        # backoff (ADVICE r4). Keep the traceback: unlike the pre-parse
        # errors above, construction spans mesh/model/data code and the
        # message alone may not locate the source.
        traceback.print_exc(file=sys.stderr)
        print(f"[trainer] config error: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    from ..analysis.compile_sentinel import SteadyStateRecompile
    from ..train.sentinel import SentinelDiverged

    try:
        trainer.run()
    except SteadyStateRecompile as e:
        import sys

        # --strict_compile tripped: a steady-state XLA compile means some
        # aval/signature drifted mid-run — deterministic (the same run
        # replays the same cache miss), so rc 2: supervisors must not
        # restart it. The sentinel already logged the offending signature.
        print(f"[trainer] steady-state recompile: {e}", file=sys.stderr)
        raise SystemExit(SteadyStateRecompile.exit_code) from None
    except SentinelDiverged as e:
        import sys

        # rc 8 = "diverged": max_bad_steps consecutive non-finite steps.
        # Deterministic — the same weights replay the same divergence — so
        # supervise.sh stops instead of burning the retry budget on it.
        print(f"[trainer] diverged: {e}", file=sys.stderr)
        raise SystemExit(SentinelDiverged.exit_code) from None
    except PodAbort as e:
        import sys

        # coordinated pod stop: some host's abort intent (sentinel rc 8,
        # deferred SIGTERM 143, …) propagated through the epoch-boundary
        # exchange — every host exits with the SAME code, so the
        # supervisors classify one failure, not N different ones
        print(f"[trainer] {e}", file=sys.stderr)
        raise SystemExit(e.code) from None
    except PodReform as e:
        import sys

        # rc 11 = "pod-reform": the epoch-boundary exchange observed a
        # membership change (lost member's lease expired, or a recovered
        # host's fresh lease) — every host exits together and the
        # supervisors respawn them into the re-formed world fast
        print(f"[trainer] pod-reform: {e}", file=sys.stderr)
        raise SystemExit(PodReform.exit_code) from None
    from ..utils.logging import device_memory_line, host0_print

    host0_print(f"[trainer] {compile_stats_line()}")
    host0_print(f"[trainer] {device_memory_line()}")


if __name__ == "__main__":
    main()
