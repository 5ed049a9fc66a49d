"""Config tree for all workloads.

The reference scatters configuration across four argparse blocks and hardcoded
constants (BASELINE/main.py:25-32,84-87; ARCFACE/arc_main.py:34-43;
CDR/main.py:32-57; NESTED/train.py:458-486). Here every knob is a typed field
on one dataclass tree, with per-workload presets that reproduce the reference
defaults exactly.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence


@dataclass
class DataConfig:
    """Dataset + input-pipeline options.

    Reference semantics carried over: per-class image caps (500 for BASELINE
    BASELINE/main.py:98,107; 400 for ARCFACE arc_main.py:190; CDR additionally
    keeps only the first 100 class dirs, CDR/main.py:73-81), ImageNet
    normalization constants, and epoch-seeded reshuffle equal to
    `DistributedSampler.set_epoch` (BASELINE/main.py:269).
    """

    train_dir: str = ""
    val_dir: str = ""
    dataset: str = "imagefolder"  # imagefolder | synthetic | plc
    image_size: int = 224
    train_crop_size: int = 256  # reference RandomResizedCrop(256), BASELINE/main.py:61
    num_classes: int = 2173  # BASELINE/main.py:85
    imgs_per_class: int = 500  # BASELINE/main.py:98
    max_classes: int = 0  # 0 = all; CDR uses 100 (CDR/main.py:73)
    batch_size: int = 16  # per-process global batch is batch_size * num_hosts
    num_workers: int = 4  # BASELINE/main.py:130-131
    prefetch: int = 2
    # device-side prefetch depth (data/device_prefetch.py): a background
    # stager thread keeps this many fully-formed, globally-sharded device
    # batches staged ahead of the step loop, so batch assembly + H2D
    # transfer overlap device compute instead of serializing with it. Each
    # staged batch holds device memory (~depth extra batches of HBM).
    # 0 = synchronous assembly inside the step loop (the pre-prefetch path).
    device_prefetch: int = 2
    # double-buffered H2D dispatch (data/device_prefetch.py overlap mode):
    # host-batch fetch and the make_global_array H2D transfer pipeline on
    # two threads, so batch N+1's fetch overlaps batch N's in-flight
    # transfer (one-slot in-flight budget). Ignored at device_prefetch=0,
    # which stays bit-for-bit synchronous.
    h2d_overlap: bool = False
    synthetic_size: int = 0  # for dataset == "synthetic"
    # H2D wire format (data/transforms.py, train/steps.py). "uint8"
    # (default): transforms emit raw uint8 HWC pixels — ¼ the host→device
    # bytes of normalized float32 — and the jitted step normalizes
    # `(x/255−μ)/σ` (plus the train-time horizontal flip, rng threaded from
    # the step key) as a device-side epilogue XLA fuses into the first
    # conv's input read. "float32": the legacy host-normalize path,
    # numerically exact to the pre-uint8 framework — the fallback when
    # bitwise reproduction of an old run matters. The two match to float
    # tolerance on identical crops (quantization is pre-normalize in both).
    input_dtype: str = "uint8"
    # transform preset: baseline | cdr | cifar | clothing1m (SURVEY C15)
    transform: str = "baseline"
    # use the native C++ dataplane (libjpeg decode + fused transform) for
    # supported presets; auto-falls back to the Python/PIL path
    native_loader: bool = True


@dataclass
class DecoderConfig:
    """Sizes and kinds of the token decoder (`--model decoder_lm`,
    models/decoder_lm.py) — the one place they live; the CLI fills it.
    Defaults are the published SmallThinker-21BA3B-Instruct config.json
    (PowerInfer, arXiv:2507.20984). The layer is described by data: the
    token mixer per layer, one of four kinds (`conv_layout`: the gated short
    convolution of LFM2, LiquidAI's `lfm2` / `lfm2_moe`; `kda_layout`: Kimi
    delta attention, a recurrence over the row, arXiv:2510.26692;
    `gdn_layout`: Gated DeltaNet, the same recurrence with one decay a head,
    arXiv:2412.06464; else the configured attention), the attention kind,
    its QK-norm and its output gate, where the block's norms stand
    (`pre_norm`, `sandwich_norm`), the feed-forward kind per layer (`dense_layers` leading dense ones,
    then routed experts with or without a shared expert), the router's
    scoring, group limit and tap, the activation, the rotary pairing, a head
    of its own or tied to the embedding, and a multi-token-prediction module
    after the last layer. `loops` > 1 runs the whole stack that many times on
    its own output with the same weights (a looped language model,
    arXiv:2510.25741: `sandwich_norm`, an exit gate, a loss weighted over the
    passes). `objective` says how it is trained: next-token prediction, or
    diffusion over blocks of `diffusion_block` tokens (arXiv:2503.09573: a
    clean and a noised stream in one pass, models/decoder_lm.py). Seven
    published models are its fixed points: SmallThinker's, the
    DeepSeek-V3 layer as JoyAI-LLM-Flash configures it, LFM2-8B-A1B's,
    Ling-3.0-flash's (inclusionAI, `bailing_hybrid`), Ouro-2.6B (ByteDance,
    `ouro`), Olmo-Hybrid-7B's (allenai, `olmo_hybrid`) and SDAR-30B-A3B-Chat
    (JetLM, `sdar_moe`, arXiv:2510.06303: trained by block diffusion).

    A deployment that spreads a layer's experts, its heads and the
    vocabulary's rows over several chips gives each chip its share:
    `experts_held` experts starting at `first_expert` (the router keeps its
    full width `num_experts` and its `top_k`), `heads_held` of the `num_heads`
    heads of every "gqa" and Gated DeltaNet layer, and `vocab_size`
    rows of the vocabulary (embedding, head, loss and token ids are over the
    slice)."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768          # moe_ffn_hidden_size (ReGLU)
    num_experts: int = 64            # router width
    experts_held: int = 0            # 0 = all of them
    first_expert: int = 0
    top_k: int = 6
    # per-layer 0/1 lists, repeated to num_layers: rotary embedding or none
    # (NoPE), sliding window or full causal
    rope_layout: Sequence[int] = (0, 1, 1, 1)
    window_layout: Sequence[int] = (0, 1, 1, 1)
    window: int = 4096
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    seq_len: int = 8192              # tokens per row (the file holds T + 1)
    # rows of (B·T) the head and its loss take at a time, so that float32
    # logits over the vocabulary never stand whole (ops/lm_head.py)
    head_block: int = 2048
    # attention: "gqa" = grouped-query heads of `head_dim`, rotary over the
    # whole head; "mla" = latent attention (DeepSeek-V2/V3): queries through
    # a rank-`q_rank` bottleneck (0: straight from the normed input, no
    # bottleneck), keys and values from a rank-`kv_rank`
    # latent, scores over `head_dim` dims without position + `rope_dim`
    # rotary dims whose key part is ONE head shared by every query head,
    # values of `v_head_dim`
    attention: str = "gqa"
    q_rank: int = 0
    kv_rank: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0              # 0 = head_dim
    rope_pairing: str = "half"       # "half": i with i + D/2 | "interleaved": 2i with 2i + 1
    # "gqa" queries and keys normed before the rotary embedding: 1 = an
    # RMSNorm on every head (one scale of head_dim for all heads); 2 = ONE
    # RMSNorm over the whole projection, all heads' dims together (Olmo's)
    qk_norm: int = 0
    out_gate: int = 0                # 1: each attention head's output times sigmoid(h w_g), one gate a head, before W_o
    # the token mixer, two 0/1 lists repeated to the depth like the two
    # above; a layer neither marks runs the attention above.
    # conv_layout 1 = the gated short convolution (LFM2: [B | C | X] = h W_in,
    # a causal depthwise convolution of `conv_kernel` taps over B * X, gated
    # by C, then W_out) stands where attention stands.
    # kda_layout 1 = Kimi delta attention (models/decoder_lm.py, ops/kda.py):
    # q, k, v through a causal depthwise convolution of `conv_kernel` taps
    # and SiLU, q and k L2-normed, `num_heads` states of head_dim x head_dim
    # carried along the row by the gated delta rule with a per-channel decay
    # whose log lies in (-5, 0), in chunks of 64 tokens, 8 heads at a time
    # (ops/kda.py's LOWER_BOUND, CHUNK, HEAD_GROUP: constants until a second
    # published value exists); a gated per-head RMSNorm on the output
    # gdn_layout 1 = Gated DeltaNet (models/decoder_lm.py, ops/gdn.py): q, k,
    # v through taps and SiLU as above, q and k L2-normed, `num_heads` states
    # of gdn_key_dim x gdn_value_dim carried by the gated delta rule with ONE
    # decay a head and token, its log -exp(A_log) softplus(. + dt_bias)
    # unbounded, beta in (0, 2); a per-head RMSNorm on the output gated by
    # SiLU of a full-width projection
    conv_layout: Sequence[int] = (0,)
    kda_layout: Sequence[int] = (0,)
    gdn_layout: Sequence[int] = (0,)
    gdn_key_dim: int = 0             # of a Gated DeltaNet head's q and k (Olmo-Hybrid: 96)
    gdn_value_dim: int = 0           # of its v and o (Olmo-Hybrid: 192)
    conv_kernel: int = 3             # taps (LFM2 conv_L_cache 3; Ling short_conv_kernel_size 4; Olmo-Hybrid linear_conv_kernel_dim 4)
    # the chip's share of the heads of every "gqa" and Gated DeltaNet layer:
    # `heads_held` of `num_heads` in each projection, tap and per-head leaf
    # and in W_o's rows; what the absent heads would add to the mixer's
    # output is left out. Which of the layer's heads they are is the
    # deployment's to say: no arithmetic here reads it (unlike `first_expert`,
    # which offsets the router's indices).
    # 0 = all of them and no share: the mixers replicated over any mesh axis.
    # > 0 under a `model` mesh axis > 1: the held heads are split evenly over
    # it and one psum completes W_o's sum (and a whole-width QK-norm's)
    heads_held: int = 0
    # feed-forward: the first `dense_layers` layers are one gated MLP of
    # `dense_width`; the others route over the experts
    dense_layers: int = 0
    dense_width: int = 0
    activation: str = "relu"         # the gate's: "relu" (ReGLU) | "silu" (SwiGLU)
    # router: "softmax" = softmax over the top-k logits; "sigmoid" = sigmoid
    # scores, top-k of score + a bias only selection reads, weights = the
    # chosen scores renormalised and times `router_scale`
    router: str = "softmax"
    router_scale: float = 1.0
    router_eps: float = 0.0          # "sigmoid": added to the chosen scores' sum (LFM2: 1e-6)
    # "sigmoid" with n_group > 1 (DeepSeek-V3's group-limited choice): the
    # experts stand in `n_group` groups, a group's score is the sum of its
    # two largest score + bias, and the top-k is taken inside the
    # `topk_group` best groups only
    n_group: int = 1
    topk_group: int = 1
    router_tap: str = "pre"          # reads the layer's normed input: "pre" attention | "post"
    shared_experts: int = 0          # experts every token takes (width x this many)
    # multi-token prediction (DeepSeek-V3 eq. 21-25): 0 or 1 extra layer that
    # predicts the token after next through the shared embedding and head
    mtp_layers: int = 0
    mtp_weight: float = 0.3
    # 1: the head is the embedding transposed (no `lm_head` leaf; the
    # table's gradient sums the lookup's scatter-add and the head's matmul)
    tied_embeddings: int = 0
    # a looped model (Ouro, `total_ut_steps`): the stack of `num_layers`
    # layers runs `loops` times with the same weights, the final norm closes
    # every pass and its output is what the next pass starts from; the head
    # and a one-unit exit gate (leaf `exit_gate`) read every pass, and the
    # training loss is the passes' cross-entropies weighted by the gate's
    # exit distribution p, less `exit_beta` x the entropy of p
    # (train/steps.py::_lm_loss). Evaluation and the step's top-k counts read
    # the last pass. Dense layers only, no prediction module
    loops: int = 1
    # 1: a second RMSNorm on each sub-layer's OUTPUT before the residual add
    # (`norm_mix_out`, `norm_ffn_out`), beside the one on its input
    sandwich_norm: int = 0
    # 0: no RMSNorm on a sub-layer's INPUT (`norm_in`, `norm_post` absent);
    # with sandwich_norm 1 that is Olmo's reordered block, x + RMSNorm(f(x))
    pre_norm: int = 1
    exit_beta: float = 0.05          # read only where loops > 1
    # how the decoder is trained. "next_token": every position classifies the
    # token after it under a causal mask. "block_diffusion" (BD3-LM,
    # arXiv:2503.09573; SDAR): the row x_0 is cut into blocks of
    # `diffusion_block` tokens, each block noised at its own level t (every
    # token of it replaced by `mask_id` with probability t: data/diffusion.py
    # makes x_t and the levels in the loader, t = diffusion_eps +
    # (1 - diffusion_eps) j / 65,536, j uniform on 1..65,536), and ONE pass
    # over [x_0 ; x_t] under the two-stream mask predicts every masked token
    # from its own noised block and the clean blocks before it; the loss is
    # the masked positions' cross-entropy weighted 1 / t, over all positions
    # (train/steps.py::_diffusion_sums). Attention layers only, one pass, no
    # window, no prediction module (models/factory.py refuses the rest)
    objective: str = "next_token"
    diffusion_block: int = 4
    diffusion_eps: float = 1e-3
    mask_id: int = -1                # -1 = the last row of the vocabulary held

    @property
    def mask_token(self) -> int:
        return self.mask_id if self.mask_id >= 0 else self.vocab_size - 1

    @property
    def diffusion(self) -> bool:
        return self.objective == "block_diffusion"

    @property
    def positions(self) -> int:
        """Positions of a row that go through every layer: its tokens, or
        under block diffusion the clean and the noised stream."""
        return self.seq_len * (2 if self.diffusion else 1)

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def heads(self) -> int:
        """Query heads a mixer computes here."""
        return self.heads_held or self.num_heads

    @property
    def kv_heads(self) -> int:
        """KV heads of the held query heads (whole groups: models/factory.py)."""
        return self.num_kv_heads * self.heads // self.num_heads

    def layout(self, which: Sequence[int]) -> tuple:
        """A layout list repeated to the depth."""
        return tuple(int(which[i % len(which)]) for i in range(self.num_layers))

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    def moe_layer_names(self) -> tuple:
        """What each row of the step's `moe_load` is: the layers that route,
        then the prediction module's."""
        return (tuple(str(i) for i in range(self.dense_layers, self.num_layers))
                + ("mtp",) * self.mtp_layers)

    def layer_kinds(self) -> tuple:
        """(operator, ffn) of each of the `num_layers` layers: operator
        "conv", "kda", "gdn" or the attention kind, ffn "dense" | "routed"."""
        return tuple(("conv" if conv else "kda" if kda else "gdn" if gdn
                      else self.attention,
                      "dense" if i < self.dense_layers else "routed")
                     for i, (conv, kda, gdn) in enumerate(zip(
                         self.layout(self.conv_layout), self.layout(self.kda_layout),
                         self.layout(self.gdn_layout))))


@dataclass
class ModelConfig:
    """Backbone + head selection.

    arch covers the reference zoo: torchvision-style ImageNet ResNets
    (NESTED/model/imagenet_resnet.py), CIFAR ResNets
    (NESTED/model/cifar_resnet.py), VGG19-BN (NESTED/model/vgg.py) — plus the
    framework's transformer extension (vit_t16/vit_s16/vit_b16, models/vit.py)
    whose token axis ring-shards over the mesh 'model' axis (long-context
    sequence parallelism; the reference has no attention, SURVEY §2.2).
    """

    arch: str = "resnet50"
    variant: str = "imagenet"  # imagenet | cifar
    pretrained: bool = False  # load converted torchvision weights at init
    # path to a torch .pth/.pt checkpoint (torchvision state_dict, a
    # {'state_dict': ...} wrapper, or the reference's NESTED {'feat','cls'}
    # format). Zero-egress environments supply the file; no URL download.
    pretrained_path: str = ""
    feat_dim: int = 0  # 0 = arch default (512 r18/34, 2048 r50+)
    head: str = "fc"  # fc | arcface | nested
    # ArcFace (ARCFACE/arc_main.py:234: s=30, m=0.5, easy_margin=True)
    arc_s: float = 30.0
    arc_m: float = 0.5
    arc_easy_margin: bool = True
    arc_embed_dim: int = 256  # arc_main.py:223-231: 2048->512->256 embedding
    # reference quirk: arc_main.py:230 appends LogSoftmax to the EMBEDDING
    # (almost certainly a bug — features are re-normalized in the margin
    # product); off by default, flag preserves bug-compat training
    arc_log_softmax_quirk: bool = False
    # Nested dropout (NESTED/train.py:512-530: nested=100 i.e. sigma of the
    # Gaussian over feature dims; freeze_bn=True)
    nested_std: float = 100.0
    freeze_bn: bool = False
    dropout: float = 0.0
    dtype: str = "bfloat16"  # compute dtype; params and BN stats stay f32
    remat: bool = False  # per-block rematerialization (activation-memory lever)
    # ViT family: dropless split-FFN mixture-of-experts in every block
    # (ops/moe.py); >0 enables it. Experts shard over the mesh `model` axis
    # (expert parallelism) — the axis serves one role per config, so this
    # excludes ring-SP/PP for the same run.
    moe_experts: int = 0
    moe_top_k: int = 2
    # Switch-style router load-balance penalty weight (ops/moe.py::
    # load_balance_loss, sown per block, summed into the training loss)
    moe_aux_weight: float = 0.01
    # ViT family: use the Pallas streaming flash-attention kernels for the
    # unsharded attention path (ops/flash_attention.py) from
    # `flash_min_tokens` tokens on; the ring-sharded path consumes each
    # visiting KV shard with them too. Without it a ViT's row takes the
    # whole-row kernel pair where it fits VMEM (ops/rows_attention.py; chosen
    # by shape, models/vit.py::attention_path), else the dense op
    flash_attention: bool = False
    # Auto-pick floor for the unsharded path: below this token count,
    # --flash_attention does not take the streaming kernels (a ViT's row then
    # takes the whole-row pair or the dense op, the decoder's the dense op).
    # Read on the chip at ViT-B/16's 196 tokens, batch 128 (PR 52, one traced
    # run each, `vit_attn_device_ms` of `step_device_ms`): the dense op 117.7
    # of 171.0 ms, the streaming kernels forced (`--flash_min_tokens 0`) 95.6
    # of 149.9, the whole-row pair 41.3 of 96.7: at a row that fits VMEM the
    # streaming kernels are never the ones to ask for. Where the floor
    # belongs between 1,100 tokens (the pair's bound) and 8,192 (the
    # decoder's rows, streamed) has no reading (ROADMAP S7).
    # 0 = always use the streaming kernels. The ring path ignores this floor:
    # there the kernel's job is keeping the per-shard score tile
    # unmaterialized, which matters at any length.
    flash_min_tokens: int = 1024
    # ViT only: run the LayerNorms in the compute dtype (bf16) instead of
    # f32 — a bandwidth experiment for the HBM-bound ViT step (VERDICT r3
    # #5; no chip reading exists, ROADMAP S4). Off = the standard
    # f32-LN recipe every convergence record uses.
    ln_bf16: bool = False
    # arch == "decoder_lm": every size of the token decoder
    decoder: DecoderConfig = field(default_factory=DecoderConfig)


@dataclass
class OptimConfig:
    """Optimizer + LR schedule.

    Reference recipes: SGD(momentum=0.9) lr 1e-3 + StepLR(10, 0.1)
    (BASELINE/main.py:86,153-154); Adam-or-SGD switch (arc_main.py:248-253);
    MultiStepLR([10,20]) (CDR/main.py:340) / ([20,30,40,120])
    (NESTED/train.py:472); linear iteration warmup (BASELINE/main.py:170-197,
    NESTED/train.py:276-327).
    """

    optimizer: str = "sgd"  # sgd | adam
    lr: float = 1e-3
    momentum: float = 0.9
    adam_b2: float = 0.999  # optax's default; language-model recipes use 0.95
    weight_decay: float = 0.0
    # Per-group hyperparameters for the head param group (ArcFace margin
    # head — the reference builds ONE optimizer over TWO param groups,
    # arc_main.py:248-253; its recipes use identical hyperparams per group,
    # so None = inherit lr/weight_decay and the optimizer reduces to a
    # single transform over the joint tree). Set to diverge the groups.
    head_lr: Optional[float] = None
    head_weight_decay: Optional[float] = None
    schedule: str = "step"  # step | multistep | constant
    step_size: int = 10
    gamma: float = 0.1
    milestones: Sequence[int] = field(default_factory=lambda: (10, 20))
    warmup_iters: int = 0
    warmup_start_lr: float = 1e-6  # BASELINE/main.py:175
    grad_transform: str = "none"  # none | cdr
    # CDR (CDR/main.py:37,54): keep top (1-noise_rate) of grad mass
    noise_rate: float = 0.2
    num_gradual: int = 10
    # Reference quirk (CDR/main.py:222-227): the gradual clip schedule is dead
    # code, overwritten with the constant. True reproduces the reference.
    cdr_dead_schedule: bool = True


@dataclass
class ParallelConfig:
    """Mesh layout. The reference supports DP only (SURVEY §2.2); we add a
    `model` axis so wide class-dim heads (ArcFace, 2173→10⁶ identities) can be
    tensor-sharded — the vision analogue of sequence parallelism."""

    data_axis: int = 0  # 0 = all devices on data axis
    model_axis: int = 1
    # microbatching / grad accumulation (capability headroom; reference: none)
    grad_accum: int = 1
    # >0 enables GPipe pipeline parallelism for the ViT family: the block
    # stack shards into stages and this many microbatches stream through
    # them (ops/pipeline.py). With pipeline_stages=0 the stages live on the
    # model axis (one role per config: class-TP | ring-attention SP | PP).
    pipeline_microbatches: int = 0
    # >1 gives the pipeline its OWN mesh axis ('pipe', parallel/mesh.py)
    # with this many stages, composing dp×tp×pp in one program: blocks
    # stage-shard over 'pipe' while the model axis keeps class-dim TP
    # (e.g. an arcface head via arcface_sharded_ce). Device count must
    # equal data_axis × model_axis × pipeline_stages.
    pipeline_stages: int = 0
    # multi-slice deployments: number of DCN-connected slices. >0 builds a
    # two-tier mesh (parallel/mesh.py::make_hybrid_mesh) — DP spans slices
    # (one DCN allreduce/step), model axis stays inside a slice on ICI.
    dcn_slices: int = 0
    # partial-FC-style ArcFace loss: compute softmax-CE with the class dim
    # sharded over the model axis (ops/sharded_head.py) — no (B, C) logits
    # anywhere. The scale path for 10⁵-10⁶-identity heads; requires
    # model_axis > 1 and num_classes divisible by it.
    arcface_sharded_ce: bool = False
    # ZeRO-1 optimizer-state partitioning (Rajbhandari et al. 2020): shard
    # each optimizer-state leaf over the data axis so XLA compiles
    # reduce-scatter -> shard-local update -> param all-gather instead of
    # replicated all-reduce + N identical updates. "auto" = on when the
    # data axis spans >1 device, off otherwise; "on"/"off" force it. The
    # update arithmetic is unchanged (each shard computes exactly the
    # slice of the replicated update it owns), so checkpoints and parity
    # pins are bit-compatible with the replicated layout.
    zero_opt: str = "auto"  # auto | on | off
    # Wire dtype for the cross-replica gradient reduction. "bfloat16"
    # casts grads to bf16 before the reduction and back to the param
    # dtype after, halving the all-reduce payload; the optimizer update
    # still accumulates into f32 master params. Rides a shard_map grad
    # section, so it composes with zero_opt but not with pipeline stages
    # or arcface_sharded_ce (rejected at step build).
    grad_reduce_dtype: str = "float32"  # float32 | bfloat16


@dataclass
class PLCConfig:
    """Progressive-label-correction loop (PLC silo — the reference left it
    '// TODO' with no training entry point, SURVEY §1; here it is a first-class
    workload wiring `ops.labelnoise` corrections into the train loop via
    `FolderDataset.update_corrupted_label` semantics, PLC/FolderDataset.py:80-82)."""

    correction: str = "lrt"  # lrt | prob
    current_delta: float = 0.3  # PLC/utils.py:291 θ
    delta_increment: float = 0.1  # β
    thd: float = 0.1  # prob_correction confidence threshold (:321)
    warmup_epochs: int = 2  # epochs of plain training before correction starts
    # collect f(x) with the prediction batch's own BN stats (as the reference
    # harvests softmax during training, utils.py:269-271) vs running averages.
    # Default False: the ordered correction scan is class-sorted, so each
    # prediction batch is nearly single-class and batch statistics skew its
    # normalization — measured 63% vs 99% argmax-vs-truth on a 97%-val model
    # (train/plc_loop.py::_predict_pipeline); True reproduces the reference's
    # harvest-during-training flavor and is only safe on shuffled batches
    batch_stat_predictions: bool = False
    # synthetic-noise injection for experiments (utils.py:149-220); -1 = off
    noise_type: int = -1
    noise_factor: float = 1.2
    # Safety valve over the reference behavior: cap the fraction of labels a
    # single correction pass may flip, keeping the most-confident flips
    # (largest prediction-vs-label disagreement). Correction on an immature
    # model self-confirms: observed live, a warmup-5 run flipped 17% of
    # labels in one pass and collapsed the label set onto 3 classes (noise
    # 19% -> 82%). 1.0 = uncapped reference semantics.
    max_flip_frac: float = 1.0


@dataclass
class RunConfig:
    """Loop + IO. Epochs/ckpt/record semantics per BASELINE/main.py:258-317."""

    epochs: int = 100  # NUM_EPOCH, BASELINE/main.py:87
    seed: int = 999  # set_seed(999), BASELINE/main.py:43-50
    log_every: int = 20  # BASELINE/main.py:284
    eval_every: int = 1
    eval_first: bool = False  # initial Test before training (NESTED:413-414)
    out_dir: str = "./runs/default"
    save_every_epoch: bool = True  # BASELINE/main.py:308-310
    save_best_only: bool = False  # NESTED netBest.pth policy, train.py:154-161
    async_checkpoint: bool = True  # background serialize+write (SURVEY §5)
    keep_checkpoints: int = 0  # prune epoch ckpts beyond N (0 = keep all)
    resume: str = ""  # NESTED --resumePth, train.py:372-378
    # preemption recovery (SURVEY §5 failure-detection row): pick up the
    # latest checkpoint in out_dir automatically — the restart command is
    # then identical to the start command (scripts/supervise.sh relies on it)
    auto_resume: bool = False
    write_records: bool = True  # output.txt / history.json (SURVEY C23)
    # TensorBoard event files at <out_dir>/tb (utils/tensorboard.py, no deps).
    # The reference only ever carried commented-out tensorboardX imports
    # (BASELINE/main.py:41-42,311)
    tensorboard: bool = False
    # observability (SURVEY §5 tracing/race-detection rows — the reference has
    # ad-hoc wall-clock timers only)
    profile_steps: int = 0  # >0: capture a jax.profiler trace of steps [10, 10+N)
    profile_dir: str = ""   # default: <out_dir>/profile
    debug_nans: bool = False  # jax_debug_nans for fail-fast numeric debugging
    # mid-run hang detection (a device sync that never returns raises no
    # exception, and a hang never exits, so supervise.sh alone cannot
    # recover it). >0 arms a
    # heartbeat watchdog: if no host-observed progress (log-line sync,
    # epoch-end sync, eval sync, final-drain start) lands for this many
    # seconds, the process exits
    # loudly (code 7) so supervise.sh + auto_resume can take over. Set WELL
    # above the slowest legitimate gap — the first compile can take minutes
    # (TResNet); 0 disables.
    hang_timeout_s: float = 0.0
    # Non-finite step sentinel (train/sentinel.py): every jitted train step
    # skips its update (identity) when loss/grad-norm go non-finite; after
    # this many CONSECUTIVE skips the run exits rc 8 ("diverged") — a
    # deterministic failure supervise.sh must NOT hot-loop restart. The
    # streak is evaluated at the log_every sync cadence, so detection lands
    # within one log window of the threshold. 0 = skip forever, never exit.
    max_bad_steps: int = 25
    # Deterministic fault injection (utils/chaos.py), e.g.
    # "nan_loss@step=7,ckpt_io@epoch=1,loader_io@batch=3,sigterm@step=20".
    # CHAOS_FAULT_SPEC env overrides; empty = every hook is inert and the
    # train step compiles to exactly the uninjected program.
    fault_spec: str = ""
    # Compile sentinel (analysis/compile_sentinel.py): the trainer arms a
    # recompile guard once the first eval'd epoch completes (all steady-state
    # programs compiled); any later compile is logged with the offending
    # function + aval signature. False = warn-only; True = deterministic
    # rc 2 at the epoch boundary (a steady-state recompile replays on
    # restart, so supervisors must not retry it).
    strict_compile: bool = False


def dp_round_up_buckets(buckets: Sequence[int], dp: int) -> tuple:
    """Round each bucket UP to the next dp multiple and dedup (ascending):
    the compile-count bound survives data-parallel serving — at most
    len(buckets) padded shapes, each evenly shardable over 'data'. Called
    by `ServeConfig.resolve_buckets` (auto-buckets)."""
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    return tuple(sorted({((int(b) + dp - 1) // dp) * dp for b in buckets}))


@dataclass
class ServeConfig:
    """Inference serving (serve/ subsystem, cli/serve.py).

    The engine assembles micro-batches from a bounded request queue under a
    deadline and pads them to a small fixed set of bucket sizes, so the
    jitted predict compiles at most len(buckets) programs — the classic
    adaptive-batching trade (Clipper-style): `batch_timeout_ms` bounds the
    latency a lone request pays waiting for company, `max_batch` bounds how
    much throughput a full queue can amortize into one device dispatch.
    """

    max_batch: int = 8  # largest micro-batch the batcher assembles
    # deadline from the FIRST queued request until a partial batch flushes;
    # 0 = never wait (every collect takes whatever is queued right now)
    batch_timeout_ms: float = 5.0
    queue_depth: int = 64  # bounded intake; submits beyond it are rejected
    # padded batch shapes (ascending). () = powers of two up to max_batch.
    # Each bucket is one compiled program; requests pad to the smallest
    # bucket that fits the collected batch. Under a >1-device serve mesh
    # every bucket must be divisible by the data-parallel width (each
    # padded batch shards evenly over 'data'); auto-buckets round up.
    buckets: Sequence[int] = ()
    # devices on the serve mesh's data axis (0 = all visible devices);
    # per-replica throughput scales with it — the predict runs dp-sharded
    # over the mesh, batches arrive as data-sharded global arrays
    serve_devices: int = 0
    # AOT executable sidecar (serve/aot.py): "auto" = <run dir>/aot next
    # to the served checkpoint, "off" = disable, else an explicit dir. A
    # joining replica deserializes the warmed bucket executables instead
    # of compiling them — zero steady-state compiles on a warm boot.
    aot_cache: str = "auto"
    topk: int = 5  # classes returned per request
    checkpoint: str = ""  # explicit checkpoint to serve (verified; rc 2 if corrupt)
    watch_dir: str = ""  # run dir to poll for checkpoint hot-reload
    reload_poll_s: float = 5.0  # hot-reload poll cadence
    port: int = 0  # >0: stdlib http front-end on this port (serve/http.py)
    log_every_s: float = 10.0  # metrics console line cadence
    # Compile sentinel: warmup() arms a recompile guard after prepaying the
    # bucket programs; a steady-state compile (a shape leaking past the
    # bucket padding) is counted + logged. False = warn-only; True = the
    # engine stops intake and cli.serve exits rc 2 (deterministic).
    strict_compile: bool = False
    # --- serve-fleet control plane (serve/fleet.py) ---
    # shared fleet run dir ("" = fleet off, lone-replica mode). Replicas
    # sharing it heartbeat via $FLEET_DIR/serve_fleet/lease.r<id> and
    # serialize hot reloads through the single drain token (rolling wave).
    fleet_dir: str = ""
    fleet_replica: int = 0  # this replica's id in the shared fleet dir
    fleet_ttl_s: float = 15.0  # lease/token freshness horizon (mtime vs now)
    # admission control above the engine queue: 0 = off (engine bound only);
    # >0 = shed when measured wait (depth / observed service rate) exceeds
    # this deadline (fair-share shed at 1x, any-tenant shed at 2x)
    admission_deadline_ms: float = 0.0
    # per-tenant weighted fair shares, "name:weight,name:weight"
    # ("" = single 'default' tenant at weight 1)
    admission_tenants: str = ""

    def validate_fleet(self) -> None:
        """Config-shaped fleet/admission validation (ValueError = rc 2)."""
        if self.fleet_replica < 0:
            raise ValueError(
                f"serve.fleet_replica must be >= 0, got {self.fleet_replica}")
        if self.fleet_ttl_s <= 0:
            raise ValueError(
                f"serve.fleet_ttl_s must be > 0, got {self.fleet_ttl_s}")
        if self.admission_deadline_ms < 0:
            raise ValueError(
                f"serve.admission_deadline_ms must be >= 0, "
                f"got {self.admission_deadline_ms}")
        from .serve.fleet import parse_tenants

        parse_tenants(self.admission_tenants)

    def resolve_buckets(self, dp: int = 1) -> tuple:
        """Validated ascending bucket tuple (ValueError = config-shaped,
        the serve CLI maps it to the deterministic rc 2).

        `dp` is the serve mesh's data-parallel width: every padded batch
        shards its leading axis over 'data', so each bucket must be a
        dp multiple or the global array cannot be assembled. Explicit
        buckets that violate this are rejected (the operator asked for
        shapes that cannot run); auto-buckets round UP to the next dp
        multiple — padding overhead, never a dropped request."""
        if self.max_batch < 1:
            raise ValueError(f"serve.max_batch must be >= 1, got {self.max_batch}")
        if self.batch_timeout_ms < 0:
            raise ValueError(
                f"serve.batch_timeout_ms must be >= 0, got {self.batch_timeout_ms}")
        if self.queue_depth < 1:
            raise ValueError(f"serve.queue_depth must be >= 1, got {self.queue_depth}")
        if self.topk < 1:
            raise ValueError(f"serve.topk must be >= 1, got {self.topk}")
        if dp < 1:
            raise ValueError(f"serve data-parallel width must be >= 1, got {dp}")
        if self.buckets:
            buckets = tuple(int(b) for b in self.buckets)
            bad = [b for b in buckets if b % dp]
            if bad:
                raise ValueError(
                    f"serve.buckets {bad} not divisible by the serve mesh's "
                    f"data-parallel width dp={dp} — every padded batch shards "
                    "its leading axis over 'data', so each bucket must be a "
                    f"multiple of {dp} (error: serve-bucket-dp-indivisible)")
        else:
            buckets, b = [], 1
            while b < self.max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_batch)
            buckets = dp_round_up_buckets(buckets, dp)
        if any(b < 1 for b in buckets) or list(buckets) != sorted(set(buckets)):
            raise ValueError(
                f"serve.buckets must be positive and strictly ascending, "
                f"got {buckets}")
        if self.max_batch > buckets[-1]:
            raise ValueError(
                f"serve.max_batch={self.max_batch} exceeds the largest bucket "
                f"{buckets[-1]} — a full batch would have no padded shape to "
                "run at")
        return buckets


@dataclass
class Config:
    workload: str = "baseline"  # baseline | arcface | cdr | nested | plc
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    run: RunConfig = field(default_factory=RunConfig)
    plc: PLCConfig = field(default_factory=PLCConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)


def baseline_preset() -> Config:
    """BASELINE/main.py defaults: ResNet-50, CE, batch 16/proc, SGD 1e-3,
    StepLR(10,0.1), 100 epochs, 2173 classes, ≤500 imgs/class."""
    return Config(workload="baseline")


def arcface_preset() -> Config:
    """ARCFACE/arc_main.py: ResNet-50 → 256-d embedding + ArcMarginProduct
    (s=30, m=0.5, easy_margin=True at :234), batch 32, Adam 1e-3."""
    cfg = Config(workload="arcface")
    cfg.data.batch_size = 32
    cfg.data.imgs_per_class = 400  # arc_main.py:190
    cfg.model.head = "arcface"
    cfg.optim.optimizer = "adam"
    return cfg


def cdr_preset() -> Config:
    """CDR/main.py: ResNet-50, batch 128, SGD 0.1, MultiStepLR([10,20]),
    selective-gradient step, first 100 classes."""
    cfg = Config(workload="cdr")
    cfg.data.batch_size = 128
    cfg.data.max_classes = 100
    cfg.data.num_classes = 100
    cfg.data.transform = "cdr"
    cfg.optim.lr = 0.1
    cfg.optim.schedule = "multistep"
    cfg.optim.milestones = (10, 20)
    cfg.optim.grad_transform = "cdr"
    cfg.run.epochs = 30  # CDR/main.py:54 n_epoch default
    return cfg


def nested_preset() -> Config:
    """NESTED/train.py: ResNet-50 feat + bias-free linear cls, batch 128,
    10k-iter warmup → lr 1e-2, MultiStepLR([20,30,40,120]), nested σ=100,
    freeze-BN (main() hardcodes nested=100, freeze_bn=True at :527,529)."""
    cfg = Config(workload="nested")
    cfg.data.batch_size = 128
    cfg.model.head = "nested"
    cfg.model.nested_std = 100.0
    cfg.model.freeze_bn = True
    cfg.optim.lr = 1e-2
    cfg.optim.schedule = "multistep"
    cfg.optim.milestones = (20, 30, 40, 120)
    cfg.optim.warmup_iters = 10000
    cfg.run.epochs = 50
    cfg.run.save_best_only = True
    cfg.run.eval_first = True  # initial Test before training (train.py:413-414)
    return cfg


def plc_preset() -> Config:
    """PLC correction training on Clothing1M-scale data: ResNet-50, batch 128,
    LRT correction after 2 warmup epochs. The reference shipped the dataset +
    algorithms but no trainer (README.md:12 'PLC // TODO'); recipe constants
    follow the PLC paper defaults encoded in utils.py:291-360."""
    cfg = Config(workload="plc")
    cfg.data.batch_size = 128
    cfg.data.num_classes = 14  # Clothing1M
    cfg.optim.lr = 0.01
    cfg.optim.schedule = "multistep"
    cfg.optim.milestones = (10, 20)
    cfg.run.epochs = 30
    return cfg


PRESETS = {
    "baseline": baseline_preset,
    "arcface": arcface_preset,
    "cdr": cdr_preset,
    "nested": nested_preset,
    "plc": plc_preset,
}


def get_preset(name: str) -> Config:
    try:
        return PRESETS[name]()
    except KeyError:
        raise ValueError(f"unknown workload {name!r}; one of {sorted(PRESETS)}")
