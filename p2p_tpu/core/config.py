"""Configuration system.

The reference configures everything through 21 argparse flags plus a pile of
hardcoded constants (SURVEY.md §5.6: dataset root, quantizer bits, loss
weights 10/10/1, Num_D=3 ...). Here every knob is an explicit dataclass
field, and the five BASELINE.json target configs are checked in as named
presets retrievable via :func:`get_preset`.

Reference flag parity (train.py:133-157) is kept by ``Config.from_flags`` in
``p2p_tpu.cli.train``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

from p2p_tpu.core.mesh import MeshSpec


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # Generator family: "expand" (reference ExpandNetwork transform-net,
    # networks.py:447), "unet" (classic pix2pix U-Net), "pix2pixhd"
    # (coarse-to-fine global+local), "resnet" (9-block ResnetGenerator,
    # the commented alternative at networks.py:168).
    # "spade" is the SPADE / GauGAN generator (models/spade.py): no
    # encoder, driven by a label map at every block.
    # "vqgan" is the whole VQGAN autoencoder (models/vqgan.py): encoder,
    # learned codebook, decoder, trained under ONE loss in the G slot;
    # ngf is its base width ``ch``, the vq_* fields below its sizes.
    # "swinir" is the SwinIR super-resolution transformer (models/swinir.py):
    # ngf is its embedding width, n_blocks its groups (RSTB), ``scale`` below
    # its upsampler's factor; what SwinIR fixes are constants of the module.
    # "lama" is the LaMa inpainting generator of fast Fourier convolutions
    # (models/ffc.py): ngf its stem width, n_blocks its residual FFC blocks,
    # ffc_ratio below the global share of their channels; its input is the
    # masked image and the mask as a fourth channel (input_nc 4), which is
    # what tells the loader, the step and cli.infer that inputs carry masks
    # (models/registry.input_mask_channel).
    generator: str = "expand"
    input_nc: int = 3
    # Label-map conditioning (0 = the input is an image). With
    # label_classes > 0 the loader ships uint8 (H, W, 2) maps — class id,
    # instance-edge bit — and the steps one-hot them ON DEVICE
    # (utils/images.ingest_input) into label_classes (+1 with label_edge)
    # channels, which is what G and D's conditioning half then see;
    # input_nc states that channel count.
    label_classes: int = 0
    label_edge: bool = False
    output_nc: int = 3
    # Target extent over input extent (super-resolution: 4 = a 64x64 input
    # for a 256x256 target). ``DataConfig.image_size`` / ``image_width`` state
    # the TARGET's extent; the loader, the dummy batches (utils/images.
    # wire_spec), cli.infer, the serving buckets and the evaluation read the
    # input's from ``Config.input_hw``. 1 for every image-to-image preset.
    scale: int = 1
    ngf: int = 32            # reference ExpandNetwork base width (networks.py:460)
    ndf: int = 64            # discriminator base width (networks.py:708)
    n_blocks: int = 9        # residual blocks in expand/resnet G (networks.py:472)
    # Discriminator: multiscale PatchGAN (networks.py:716). num_D=3,
    # n_layers=3, spectral norm on inner convs, intermediate features kept
    # for the feature-matching loss.
    num_D: int = 3
    n_layers_D: int = 3
    use_spectral_norm: bool = True
    get_interm_feat: bool = True
    # Compression pre-filter (networks.py:201) + quantizer bits
    # (hardcoded 3 at train.py:297).
    use_compression_net: bool = True
    quant_bits: int = 3
    # Straight-through estimator through the quantizer. The reference has
    # none (SURVEY Q2) so its net_c never learns; True implements the
    # *intended* behavior, False is bug-compatible.
    quant_ste: bool = True
    # "batch" | "instance" | "pallas_instance"
    norm: str = "batch"
    # Discriminator-side normalization on the inner PatchGAN convs:
    # "none" (reference parity — networks.py:716 has no D norms) |
    # "instance" | "pallas_instance" (the pix2pixHD paper's D layout;
    # stateless/affine-free, so the param tree — and therefore
    # checkpoints — are identical either way). With "pallas_instance"
    # the conv epilogue (norm + LeakyReLU) is ONE fused Pallas pass
    # (ops/pallas/norm_act.py).
    norm_d: str = "none"
    # U-Net decoder dropout (the pix2pix noise source); for "swinir" its
    # stochastic depth. The train step threads a per-step ``dropout`` rng
    # when this is on.
    use_dropout: bool = False
    init_type: str = "normal"   # normal | xavier | kaiming | orthogonal
    init_gain: float = 0.02
    # int8 QAT path (ops/int8.py): run the MXU-dominant inner convs of G
    # and D as s8×s8→s32 MXU convolutions (forward + both backward
    # contractions) with dynamic symmetric scales. The 3/6-channel stems
    # and the image-producing heads stay bf16 (HBM-bound + quality
    # critical). v5e: 2× MXU peak vs bf16. Applies to all discriminator
    # families (spectral norm composes: the power iteration tracks the
    # true f32 weight, only w/σ is quantized) and — via int8_generator —
    # to the "unet" encoder and the ResNet-trunk families
    # (resnet / pix2pixhd / pix2pixhd_global k3-s1 blocks).
    int8: bool = False
    # Extend int8 to the generator too. Off by default: measured on v5e,
    # the U-Net's bf16 convs already run near MXU peak fused with their
    # norms/activations, and the int8 wgrad's slice materialization at
    # 128²+ spatial costs more than the MXU gain — int8 pays on the
    # discriminator (wide stride-1/2 convs at ≤65² spatial), where all
    # three contractions hit the doubled int8 MXU rate.
    int8_generator: bool = False
    # With int8_generator: also switch the U-Net decoder deconvs to the
    # quantized subpixel form (QuantSubpixelDeconv). Measured a net loss
    # on v5e (interleave + large-spatial wgrad slices); kept reachable
    # for other chips/shapes.
    int8_decoder: bool = False
    # Delayed (stored-scale) activation quantization: per-layer amax
    # carried in a 'quant' collection threaded through TrainState (like
    # batch_stats), so the forward quantize no longer serializes on an
    # absmax reduction — one HBM pass instead of two per quantized
    # activation (ops/int8.py int8_conv_ds). Measured +3% on the bs=128
    # headline (1632→1681 img/s); a no-op at bs=1 (185.8 vs 186.2 —
    # that shape is kernel-launch-latency-bound, not absmax-bound,
    # correcting round 2's hypothesis). Transient clipping after an
    # activation spike decays in one step (decaying-max update).
    int8_delayed: bool = False
    # ISSUE 14 coverage knobs (one per remaining --int8-diff site family;
    # every site is REACHABLE on the int8 path, and the default carries
    # the measured-rejected verdict where there is one):
    # 3/6-channel input stems (U-Net down0, the PatchGAN stage-0 conv,
    # net_c's k5 RGB conv) on the int8 path. Off by default: the stems
    # are HBM-bound (the MXU gains nothing on a 3-wide contraction) —
    # the round-2..5 doctrine — but the knob keeps the form measurable
    # per chip/shape (even the facades_int8_full row keeps it off).
    int8_stem: bool = False
    # Discriminator logits head on the int8 path: the kn2row-eligible
    # 512→1 head runs the s8×s8→s32 tap-decomposition dot
    # (ops/int8.py int8_kn2row_conv — fwd and wgrad on the int8 MXU,
    # the tiny-contraction dgrad stays bf16 per the per-form dispatch
    # table); a non-kn2row head falls back to QuantConv. The U-Net
    # IMAGE head stays bf16 always (quality + HBM critical — the dated
    # in-source waiver at models/unet.py documents the verdict).
    int8_head: bool = False
    # CompressionNetwork (net_c) convs on the int8 path. Its output is
    # already crushed to `quant_bits` (3) by the pipeline quantizer, so
    # int8 QAT noise inside the pre-filter is far below the signal the
    # net is trained to survive; amax state joins the 'quant' collection
    # as quant_c (train step, PP, frozen-scale eval/serving, elastic
    # reshard_amax all thread it).
    int8_compression: bool = False
    # Quantize-fused conv epilogues (ops/pallas/norm_act.py
    # norm_act_quant): with norm_d="pallas_instance" + int8_delayed the
    # discriminator's inner-conv epilogue [instance norm + LeakyReLU +
    # clip/round quantize + amax measurement] runs as ONE streaming
    # Pallas pass, so the newly quantized conv does not pay a separate
    # full-size read+write for the clip/round; the consumer conv takes
    # the prequantized activation (int8_conv_pq). Requires int8 +
    # int8_delayed + a stateless instance-family norm_d.
    int8_fused_epilogue: bool = False
    # Keep the mathematically-dead conv biases in front of mean-
    # subtracting norms (round-2 checkpoint param layout). Default False:
    # those biases are exactly cancelled by the norm in forward AND
    # receive identically-zero gradients (the norm backward emits
    # zero-channel-mean cotangents), yet computing those zero gradients
    # re-read full-size cotangents (~3 ms/step at bs=128/256²).
    legacy_layout: bool = False
    # Feed D the UNCONCATENATED (a, b) conditional pair (the split-stem
    # form, models/patchgan._SplitStemConv): no materialized 6-channel
    # full-res pair tensors, conv(a, W_a) CSE-shared across the fake/real
    # branches. MEASURED shape-dependent: loses at 256²/bs128 (1661 vs
    # 1701 — the concat was already fused into the stem's window gather)
    # but the pair tensors at 1024×512 run at 26 GB/s in the round-4
    # profile, so the HD preset flips it on (round-5 ledger).
    split_d_pairs: bool = False
    # False: D sees the image ALONE (an unconditional PatchGAN, the VQGAN
    # lineage's ``disc_conditional: False``); True pairs it with the
    # conditioning input, as every pix2pix-family preset does.
    d_conditional: bool = True
    # "patch" (the multiscale PatchGAN, models/patchgan.py) | "unet" (the
    # Real-ESRGAN lineage's U-Net with per-pixel logits at the image's
    # extent, models/unet_d.py; ndf its width; spectrally normalised, one
    # scale, unconditional: use_spectral_norm, num_D 1, d_conditional False).
    discriminator: str = "patch"
    # zero padding of D's five k4 convolutions: 2 is the reference's
    # ceil(3/2) (networks.py:716), 1 the pix2pix / VQGAN PatchGAN's.
    d_padding: int = 2
    # generator="vqgan" (models/vqgan.py; the authors' ddconfig names):
    # channel multipliers a level (one stride-2 downsampling between two
    # levels), residual blocks a level (the decoder has one more), the
    # codebook's size and its width, which is the latent's too.
    vq_ch_mult: Tuple[int, ...] = (1, 1, 2, 2, 4)
    vq_res_blocks: int = 2
    vq_codes: int = 16384
    vq_embed_dim: int = 256
    # generator="lama": the share of a residual block's channels on the
    # GLOBAL branch, the one a spectral transform (rfft2, 1x1 convolution,
    # irfft2) mixes over the whole extent; the released models use 0.75.
    ffc_ratio: float = 0.75


@dataclasses.dataclass(frozen=True)
class LossConfig:
    # lsgan | vanilla | hinge | nonsaturating (softplus of the logits: D
    # minimises softplus(-D(x)) + softplus(D(G)); G softplus(-D(G)))
    gan_mode: str = "lsgan"
    # Reduce the per-scale GAN losses of a multiscale D by their MEAN (the
    # SPADE lineage) instead of the reference's SUM (networks.py:808-850).
    gan_scale_mean: bool = False
    # weight of the GAN term in G's loss (1 everywhere but the ESRGAN
    # lineage, whose option files give 0.1)
    gan_weight: float = 1.0
    lambda_feat: float = 10.0        # train.py:351
    # "l1": the reference's weighted L1 sum over D's taps (train.py:344-351)
    # | "mse": the LaMa lineage's MEAN over the taps of the mean squared
    # difference, times lambda_feat.
    feat_mode: str = "l1"
    # > 0: the R1 gradient penalty on D's real call (Mescheder et al. 2018,
    # as the LaMa lineage's NonSaturatingWithR1 has it): D's loss gains
    # gp_coef x the batch mean of |grad_x sum D(x)|^2, x the real image in
    # [0, 1] units; the first second-order term of the step.
    gp_coef: float = 0.0
    lambda_vgg: float = 10.0         # train.py:377
    lambda_tv: float = 1.0           # train.py:378
    lambda_l1: float = 0.0           # reference --lamb=10 but L1 is dead (Q3)
    # Gram-matrix style loss — the reference's commented-out experiment
    # (train.py:370-382), live behind this weight.
    lambda_style: float = 0.0
    # Feed [-1,1] images to VGG un-normalized, as the reference does
    # (networks.py:26 — no ImageNet mean/std). Changes loss scale; keep
    # faithful by default.
    vgg_imagenet_norm: bool = False
    # "relu" (the reference's five post-ReLU taps relu1_1 .. relu5_1 with
    # weights 1/32 .. 1) | "preact" (the ESRGAN lineage's: conv1_2, conv2_2,
    # conv3_4, conv4_4, conv5_4 BEFORE the ReLU, weights 0.1, 0.1, 1, 1, 1).
    vgg_taps: str = "relu"
    # Sobel edge L1 between fake and real — the reference's commented-out
    # edge experiment (train.py:307,313,362-363; sobelLayer at
    # networks.py:852). Dead there (0 here) but live behind this weight.
    lambda_sobel: float = 0.0
    # The reference's commented warmup schedule (train.py:445-448):
    # effective sobel weight ramps linearly to lambda_sobel over this
    # many epochs (``100/20*epoch`` shape); 0 = constant weight.
    sobel_warmup_epochs: int = 0
    # Mean angular error (degrees) between fake and real per-pixel color
    # vectors — the reference's commented-out experiment
    # (train.py:355-360; angular_loss at networks.py:870). 0 = off.
    lambda_angular: float = 0.0
    # LPIPS (losses/lpips.py: VGG16 taps relu1_2 .. relu5_3, unit-
    # normalised over channels, squared difference, a learned non-negative
    # 1x1 head a tap, spatial mean): the VQGAN lineage's perceptual term.
    lambda_lpips: float = 0.0
    # The LaMa lineage's high-receptive-field perceptual term (losses/
    # perceptual.hrf_loss): the sum over the four stages of a frozen DILATED
    # ResNet50 (models/resnet_dilated.py) of the mean squared difference of
    # its features, ImageNet-normalised inputs.
    lambda_hrf: float = 0.0
    # > 0: the VQGAN lineage's adaptive adversarial weight. The GAN term
    # of G's loss is scaled by this times lambda = |grad_W nll| /
    # (|grad_W g| + 1e-4), clipped to [0, 1e4] and held constant, W the
    # generator's last kernel, nll the terms that reach the image
    # directly and g the term that reaches it through D (train/step.py).
    adaptive_gan_weight: float = 0.0


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    lr: float = 2e-4                 # train.py:241-243
    # D's own learning rate (TTUR); None = ``lr``, the reference's one
    # rate for every net. The schedule's shape is shared.
    lr_d: Optional[float] = None
    beta1: float = 0.5
    beta2: float = 0.999
    lr_policy: str = "lambda"        # lambda | step | plateau | cosine (networks.py:104) | constant
    niter: int = 100                 # epochs at constant lr
    niter_decay: int = 100           # epochs of linear decay to 0
    lr_decay_iters: int = 50         # step policy period
    # Fix Q1: the reference's optimizer_c holds net_d's params so net_c
    # never trains. True wires C's optimizer to C (intended behavior).
    train_compression_net: bool = True
    # Global-norm gradient clipping (0 = off, reference parity). The guard
    # for per-sample-norm backward blowups on degenerate (near-constant)
    # images — see train/state.py:make_optimizers.
    grad_clip: float = 0.0
    # Storage dtype for BOTH Adam moments (None = f32, reference parity;
    # "bfloat16" halves the optimizer state's HBM footprint AND per-step
    # traffic — the bs=1 facades budget is parameter/moment-traffic-bound).
    # Params stay f32 masters; the moment math runs
    # in f32 and only the STORED moments round (train/state.py
    # scale_by_adam_lp).
    moment_dtype: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class DataConfig:
    root: str = "dataset"
    dataset: str = "facades"
    direction: str = "b2a"           # train.py:139
    image_size: int = 256
    image_width: Optional[int] = None  # None → square
    batch_size: int = 1              # train.py:143
    test_batch_size: int = 1
    threads: int = 4
    # Paired augmentation (the reference's commented-out resize-286 +
    # random-crop-256 + flip, dataset.py:28-46) on the train split.
    augment: bool = False
    # Video clips for vid2vid-style configs
    n_frames: int = 1
    # uint8 input pipeline: the decode memo stores raw bytes (4× less host
    # RAM than f32), H2D ships uint8 (4× less PCIe), and the train/eval
    # steps normalize ON DEVICE — (f32(u8) − 127.5)·(1/127.5), the one
    # canonical FMA-proof expression (utils/images.ingest), bit-exact with
    # the host normalize — so this is a pure transport optimization.
    uint8_pipeline: bool = True


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    mesh: MeshSpec = MeshSpec(data=-1, spatial=1, time=1)
    # Tensor parallelism (mesh.model > 1): smallest channel count the
    # Megatron pair rule shards (parallel/rules.py make_tp_rules). 512
    # keeps the narrow layers replicated where a psum would cost more
    # than the shard saves; tests/dryruns lower it so tiny models shard.
    tp_min_ch: int = 512
    # With mesh.fsdp > 1: extend the ZeRO state sharding from the
    # optimizer moments + EMA (always sharded over the fsdp axis —
    # parallel/rules.py make_fsdp_rules) to the params themselves
    # (ZeRO-3-ish). Off by default: the param all-gather then sits on
    # every forward's critical path, which only pays once params
    # themselves blow the HBM budget; moments+EMA are ~2/3 of the state
    # bytes (memory_budget.json) and shard free of that trade.
    fsdp_params: bool = False
    # Sync batch-norm statistics across the data axis (pmean). At bs=1 per
    # device this is the only way BatchNorm matches reference semantics.
    sync_batchnorm: bool = True
    # Remat the generator blocks to trade FLOPs/recompute for HBM:
    # False = off; True/"full" = classic full remat (min memory, recomputes
    # block convs); "conv" = save conv outputs + norm stats, recompute only
    # elementwise chains (policy remat — no extra MXU work).
    remat: Union[bool, str] = False
    # Latency-hiding GPipe schedule (parallel/pp.py gpipe_trunk overlap=):
    # the stage→stage ppermute is issued on the PREVIOUS tick's output, so
    # the transfer runs concurrently with this tick's block compute
    # (double-buffered hand-off). Costs S-1 extra fill/drain ticks —
    # pays when the ICI hop is a meaningful fraction of stage compute
    # (transfer_time/stage_time > (S-1)/(M+S-1)); off by default pending
    # an on-chip win at the driver's mesh shapes.
    pp_overlap: bool = False


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    nepoch: int = 200
    epoch_count: int = 1             # resume start epoch (train.py:137)
    epoch_save: int = 20             # --epochsave
    seed: int = 123                  # train.py:166
    log_every: int = 50
    checkpoint_dir: str = "checkpoint"
    result_dir: str = "result"
    eval_every_epoch: bool = True
    mixed_precision: bool = True
    # >1: run this many train steps per dispatch via lax.scan
    # (build_multi_train_step) — amortizes host dispatch overhead;
    # leftover steps use the single-step path.
    scan_steps: int = 1
    # VFID (Fréchet distance over pooled VGG19 taps) during eval — the
    # north-star quality metric; needs lambda_vgg>0 or a VGG asset loaded.
    eval_fid: bool = False
    # Historical-fake pool fed to D's fake branch (reference ImagePool,
    # instantiated size 0 = passthrough at train.py:248). pool_size > 0
    # enables a DEVICE-side ring buffer in TrainState (utils.pool.
    # device_pool_query) holding (real_a ‖ fake_b) pairs.
    pool_size: int = 0
    # Persistent XLA compilation cache directory (core/cache.py): compiled
    # programs are reused across PROCESSES, so restarts/preemptions pay
    # XLA compile only on the first run ever. None = off. The serving
    # engine (p2p_tpu.serve) has its own knob with the same plumbing.
    compilation_cache_dir: Optional[str] = None
    # Elastic relaunch (docs/RESILIENCE.md "Elastic relaunch"): on resume,
    # reconcile the checkpoint's recorded topology (process count, mesh
    # axis sizes, global batch, dtype policy) against the current one and
    # RESHARD compatible deltas — a preemptible-fleet relaunch may land on
    # a different slice size. False = the strict pre-elastic contract:
    # any topology delta aborts with a diagnostic instead of resharding.
    elastic: bool = True
    # Opt-in dtype-policy migration on resume (resilience/reshape.py):
    # a mixed_precision/moment_dtype delta performs an explicit, LOGGED
    # cast (moments per the MOMENT_MIGRATION policy table, integrity
    # manifest regenerated post-cast) instead of aborting. False = the
    # safe default: dtype deltas abort with the flag named.
    cast_on_restore: bool = False
    # After a TP-width amax migration (tp_amax_recalibrate), hold the
    # remapped int8 scales FROZEN for this many dispatches — the paranoid
    # path's warmup before the decaying-max update resumes. 0 = off.
    recalibrate_steps: int = 0
    # jax_debug_nans: first NaN-producing primitive raises with location.
    debug_nans: bool = False
    # The reference's commented "masking" experiment (train.py:324-334):
    # dump mask.png = bitwise_and(uint8(fake_b), uint8(real_a)) next to
    # the eval sample images. Pure visualization — it feeds no loss in
    # the reference either.
    save_masks: bool = False


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Self-healing training (p2p_tpu.resilience.health): divergence
    sentinel -> recovery ladder -> last-good rollback, plus the EMA
    generator. ``enabled`` default True: the sentinel consumes metrics the
    loop already computes (one delayed small D2H per dispatch) and the
    in-jit skip guard folds into the existing update-scale multiply —
    measured-in-band on the healthy path (pre-round reading)."""

    enabled: bool = True
    # Sentinel: robust z-score over the last `window` HEALTHY steps per
    # watched loss (G/D/C + grad norms when tapped); a step is a SPIKE
    # when |z| > spike_zscore, DIVERGED when any watched value is
    # non-finite. The EWMA (alpha) smooths the reference level the
    # z-score recenters on.
    window: int = 32
    spike_zscore: float = 6.0
    ewma_alpha: float = 0.1
    # Ladder rung 2: scale the (G/D/C) LR by cooldown_factor for
    # cooldown_steps observed steps, then restore.
    cooldown_steps: int = 20
    cooldown_factor: float = 0.1
    # Ladder rung 3: rollbacks to the last-good checkpoint before the run
    # gives up with DIVERGED_EXIT_CODE (76).
    max_rollbacks: int = 3
    # A healthy streak this long resets the ladder to rung 0.
    reset_after: int = 16
    # EMA generator params (ProGAN-lineage stabilization): None = off
    # (TrainState.ema_g stays None — old checkpoints restore bit-for-bit);
    # 0.0 = EMA tracks params exactly (the parity-pin mode); 0.999 = the
    # classic smoothing. Eval and serving use the EMA weights when present.
    ema_decay: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class DebugConfig:
    """Numerical/telemetry debug taps (p2p_tpu.obs; all off by default —
    the happy path pays nothing)."""

    # Host-side post-dispatch guard over the step metrics (core/debug.
    # check_finite): emits a kind="nonfinite" record into the metrics
    # stream, then raises. Fetches the metrics every dispatch — a fence;
    # debugging flag, not a production default.
    check_finite: bool = False
    # In-jit NaN/Inf sentinel over the step metrics via jax.debug.callback
    # (obs/taps.py): async device→host counts, NO fence on the happy path.
    # Cheap enough to leave on in production when chasing instabilities.
    nan_sentinel: bool = False
    # Add grad_norm_g / grad_norm_d global-norm scalars to the step metrics
    # (they ride the metrics fetch the loop already pays for).
    grad_norms: bool = False


@dataclasses.dataclass(frozen=True)
class Config:
    name: str = "default"
    model: ModelConfig = ModelConfig()
    loss: LossConfig = LossConfig()
    optim: OptimConfig = OptimConfig()
    data: DataConfig = DataConfig()
    parallel: ParallelConfig = ParallelConfig()
    train: TrainConfig = TrainConfig()
    debug: DebugConfig = DebugConfig()
    health: HealthConfig = HealthConfig()

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)

    @property
    def image_hw(self) -> Tuple[int, int]:
        h = self.data.image_size
        w = self.data.image_width or h
        return h, w

    @property
    def input_hw(self) -> Tuple[int, int]:
        """The input's extent: the target's over ``model.scale``."""
        h, w = self.image_hw
        s = self.model.scale
        if h % s or w % s:
            raise ValueError(f"image extent {h}x{w} does not divide by "
                             f"model.scale {s}")
        return h // s, w // s


# ----------------------------------------------------------------------------
# The five BASELINE.json target configs, checked in as presets.
# ----------------------------------------------------------------------------

_PRESETS = {}


def _register(cfg: Config) -> Config:
    _PRESETS[cfg.name] = cfg
    return cfg


# 1. facades 256×256 pix2pix (U-Net G + 70×70 PatchGAN D, bs=1)
_register(
    Config(
        name="facades",
        model=ModelConfig(generator="unet", ngf=64, num_D=1, n_layers_D=3,
                          use_spectral_norm=False, use_compression_net=False,
                          use_dropout=True),
        loss=LossConfig(lambda_feat=0.0, lambda_vgg=0.0, lambda_tv=0.0,
                        lambda_l1=100.0),
        data=DataConfig(dataset="facades", image_size=256, batch_size=1),
        parallel=ParallelConfig(mesh=MeshSpec(data=1)),
    )
)

# facades on the int8 QAT MXU path (ops/int8.py): identical architecture
# and losses; the DISCRIMINATOR's inner convs run s8×s8→s32 on the MXU
# (2× peak on v5e) with DELAYED (stored-scale) activation quantization —
# the round-3 headline path, trained to quality over 40 epochs on real
# photos (metrics_facades_int8_decay.jsonl). The generator stays bf16
# (int8_generator measured slower at this shape), stems/heads bf16.
_register(
    Config(
        name="facades_int8",
        model=ModelConfig(generator="unet", ngf=64, num_D=1, n_layers_D=3,
                          use_spectral_norm=False, use_compression_net=False,
                          use_dropout=True, int8=True, int8_delayed=True),
        loss=LossConfig(lambda_feat=0.0, lambda_vgg=0.0, lambda_tv=0.0,
                        lambda_l1=100.0),
        data=DataConfig(dataset="facades", image_size=256, batch_size=1),
        # bf16-stored Adam moments (round-5 ledger): bs=1 204→228 img/s
        # (the parameter/moment-traffic-bound path), ≥neutral at bs=128
        # (1716.0); quality pinned by metrics_mom16_q.jsonl (e9 peak
        # 22.6 PSNR on the 10-epoch decayed real256 protocol) and the
        # optax-trajectory unit test.
        optim=OptimConfig(moment_dtype="bfloat16"),
        parallel=ParallelConfig(mesh=MeshSpec(data=1)),
    )
)

# Reference-faithful config: ExpandNetwork + CompressionNetwork + multiscale D
# with the exact loss surface of /root/reference/train.py.
_register(
    Config(
        name="reference",
        model=ModelConfig(generator="expand"),
        loss=LossConfig(),
        data=DataConfig(dataset="facades", image_size=256, batch_size=1),
        parallel=ParallelConfig(mesh=MeshSpec(data=1)),
    )
)

# 2. edges2shoes 256×256, bs=64 data-parallel
_register(
    Config(
        name="edges2shoes_dp",
        model=ModelConfig(generator="unet", ngf=64, num_D=1, n_layers_D=3,
                          use_spectral_norm=False, use_compression_net=False,
                          use_dropout=True),
        loss=LossConfig(lambda_feat=0.0, lambda_vgg=0.0, lambda_tv=0.0,
                        lambda_l1=100.0),
        data=DataConfig(dataset="edges2shoes", image_size=256, batch_size=64),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1)),
    )
)

# 3. Cityscapes labels→photo 512×256 (GSPMD spatial shard)
_register(
    Config(
        name="cityscapes_spatial",
        model=ModelConfig(generator="resnet", ngf=64, norm="instance",
                          use_compression_net=False),
        loss=LossConfig(lambda_l1=0.0),
        data=DataConfig(dataset="cityscapes", image_size=256, image_width=512,
                        batch_size=4),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1, spatial=2)),
    )
)

# 4. pix2pixHD multi-scale G/D (Pallas InstanceNorm + conv). The default
#    extent 1024×512 is what one chip holds; the paper's 2048×1024 is
#    ``--image_size 1024 --image_width 2048 --mesh data=2,spatial=2
#    --batch_size 2`` on a four-chip host (PERF.md section 4)
_register(
    Config(
        name="pix2pixhd",
        # split_d_pairs: at 1024×512 the materialized 6-ch pair tensors
        # run at 26 GB/s (round-4 profile); the split-stem form measures
        # 8.76 vs 8.65 img/s (round-5 ledger). With the _NearestUp2Conv
        # subpixel dispatch (+7.5%) the preset is 8.05 → 8.76 overall.
        model=ModelConfig(generator="pix2pixhd", ngf=64, norm="pallas_instance",
                          num_D=3, n_layers_D=3, use_compression_net=False,
                          split_d_pairs=True),
        loss=LossConfig(lambda_feat=10.0, lambda_vgg=10.0, lambda_tv=0.0),
        data=DataConfig(dataset="cityscapes_hd", image_size=512,
                        image_width=1024, batch_size=1),
        # remat off: 1024×512 bs=1 fits single-chip HBM and full remat
        # costs 20% (README perf table); switch to remat="conv" (keep conv
        # outputs, recompute elementwise) on tighter-memory meshes.
        parallel=ParallelConfig(mesh=MeshSpec(data=-1, spatial=2)),
    )
)

# 5. vid2vid 8-frame temporal discriminator (sequence-parallel over ICI)
_register(
    Config(
        name="vid2vid_temporal",
        model=ModelConfig(generator="unet", ngf=64, norm="instance",
                          use_compression_net=False),
        loss=LossConfig(lambda_feat=10.0, lambda_vgg=0.0, lambda_tv=0.0),
        data=DataConfig(dataset="vid2vid", image_size=256, batch_size=1,
                        n_frames=8),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1, time=4)),
    )
)


# 6. SPADE / GauGAN label->photo at Cityscapes' 512x256 (Park et al. 2019,
#    arXiv:1903.07291 sec. 3 + app. A; sizes of github.com/NVlabs/SPADE
#    SPADEGenerator 'normal', cityscapes options). m = one-hot of 35
#    classes + 1 instance-edge channel, 36 channels at 256x512; nf = 64.
#      SPADE_C(x, m) = BN0(x) * (1 + gamma) + beta, BN0 affine-free batch
#        norm (eps 1e-5; batch statistics in training, running in eval),
#        a = relu(conv3x3(resize_nearest(m, size(x)), 36 -> 128)),
#        gamma, beta = conv3x3(a, 128 -> C) each; bias, zero pad 1, no SN.
#      ResBlk(fin, fout), fmid = min(fin, fout):
#        dx = conv3x3_sn(lrelu(SPADE_fin(x, m)), fin -> fmid)
#        dx = conv3x3_sn(lrelu(SPADE_fmid(dx, m)), fmid -> fout)
#        xs = x, or conv1x1_sn_nobias(SPADE_fin(x, m)) where fin != fout
#        out = xs + dx      (LeakyReLU 0.2 as the authors' code runs it;
#        the paper's figure draws ReLU)
#      G: conv3x3(resize_nearest(m, 8x16), 36 -> 1024); ResBlk(1024,1024);
#        up; 2 x ResBlk(1024,1024); up; ResBlk(1024,512); up;
#        ResBlk(512,256); up; ResBlk(256,128); up; ResBlk(128,64);
#        tanh(conv3x3(lrelu(x), 64 -> 3)); every up is nearest x2.
#      D: 2 scales of C64(s2)-C128(s2)-C256(s2)-C512(s1)-1, k4 pad 2,
#        LeakyReLU 0.2, spectral norm + affine-free instance norm on the
#        three inner convs, on concat(m, image) = 39 channels.
#      Losses: hinge, averaged over the scales; feature matching 10 / num_D;
#        VGG19 relu1_1..5_1 L1 x 10. Adam(0, 0.9): G 1e-4, D 4e-4 (TTUR).
#    Spectral norm (one power iteration a forward, u in the state) sits on
#    the three convs of every ResBlk and D's inner convs, nowhere else;
#    init xavier-normal with gain 0.02.
#    Departures: this Trainer's step (D and G losses from ONE generator
#    forward, G seeing the D of the step's start; the authors update G, then
#    run G again for D) and its 0.5 on D's loss (train/step.py).
_register(
    Config(
        name="spade_cityscapes",
        model=ModelConfig(generator="spade", ngf=64, input_nc=36,
                          label_classes=35, label_edge=True, norm="batch",
                          num_D=2, n_layers_D=3, norm_d="instance",
                          use_compression_net=False, init_type="xavier",
                          split_d_pairs=True),
        loss=LossConfig(gan_mode="hinge", gan_scale_mean=True,
                        lambda_feat=10.0, lambda_vgg=10.0, lambda_tv=0.0),
        optim=OptimConfig(lr=1e-4, lr_d=4e-4, beta1=0.0, beta2=0.9),
        data=DataConfig(dataset="cityscapes", image_size=256,
                        image_width=512, batch_size=1),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1)),
    )
)


# 7. VQGAN, the released ImageNet f16 model with 16384 codes (Esser,
#    Rombach, Ommer 2021, arXiv:2012.09841 sec. 3.1; sizes of
#    github.com/CompVis/taming-transformers vqgan_imagenet_f16_16384
#    model.yaml). GN = GroupNorm(32, eps 1e-6, affine), sw(x) = x *
#    sigmoid(x), every k3 convolution pads 1 with zeros and has a bias.
#      Res(cin, cout)(x) = s(x) + conv3(sw(GN(conv3(sw(GN(x)))))), s the
#        identity or conv1x1 where cin != cout.
#      Attn(c)(x) = x + proj(softmax(q k^T c^-0.5) v), q, k, v, proj 1x1
#        convolutions of GN(x), one head over the H*W positions.
#      Down = conv3 stride 2 on a zero pad below and right; Up =
#        conv3(nearest x2).
#      Encoder, ch 128, ch_mult (1,1,2,2,4), 2 Res a level (+ Attn at
#        extent 16), Down between levels; mid Res Attn Res;
#        conv3(sw(GN), 512 -> 256). Decoder the mirror with 3 Res a level.
#      Quantizer: z = conv1x1(enc); k = argmin_j |z - e_j|^2 over 16384
#        codes of width 256; forward z + sg(e_k - z); L_q = mean((sg(e_k)
#        - z)^2) + 0.25 mean((e_k - sg(z))^2) (the code's legacy form:
#        beta sits on the CODEBOOK term, not on the commitment term as
#        the paper writes it); conv1x1 after it.
#      D: C64(s2) - C128(s2, BN) - C256(s1, BN) - 1, k4 pad 1, LeakyReLU
#        0.2, on the image alone. Losses: nll = L1 + LPIPS; G: nll + 0.75
#        * lambda * (-mean D(r)) + L_q, lambda the adaptive weight; D: 0.5
#        * (hinge real + hinge fake). Adam(0.5, 0.9), lr 4.5e-6 x batch,
#        constant.
#    The whole autoencoder is the G slot (one loss, one optimizer); input
#    = target. Departures: this Trainer's step (D's fake comes from the
#    same generator forward as G's loss; the authors run the autoencoder
#    again after its update), convolution biases start at zero (torch
#    draws them uniform), VGG16 and the LPIPS heads are seeded.
_register(
    Config(
        name="vqgan_imagenet_f16",
        model=ModelConfig(generator="vqgan", ngf=128, norm="group_swish",
                          ndf=64, num_D=1, n_layers_D=2, norm_d="batch",
                          use_spectral_norm=False, get_interm_feat=False,
                          use_compression_net=False, d_conditional=False,
                          d_padding=1),
        loss=LossConfig(gan_mode="hinge", lambda_feat=0.0, lambda_vgg=0.0,
                        lambda_tv=0.0, lambda_l1=1.0, lambda_lpips=1.0,
                        adaptive_gan_weight=0.75),
        # 4.5e-6 x 12: the authors' base rate times configs/
        # imagenet_vqgan.yaml's batch on one device (cli.train scales
        # nothing: give --lr with another batch)
        optim=OptimConfig(lr=5.4e-5, beta1=0.5, beta2=0.9,
                          lr_policy="constant"),
        data=DataConfig(dataset="imagenet", image_size=256, batch_size=12),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1)),
    )
)


# 8. SwinIR-M, real-world x4 super-resolution with its GAN objective (Liang
#    et al. 2021, arXiv:2108.10257 sec. 3, 4.1; sizes of github.com/
#    JingyunLiang/SwinIR 003_realSR_BSRGAN_DFO_s64w8_SwinIR-M_x4_GAN and
#    cszn/KAIR train_swinir_sr_realworld_x4_gan.json). The equations are
#    models/swinir.py's docstring (G: 6 groups of 6 Swin layers, width 180,
#    6 heads of 30 over 8x8 windows, 'nearest+conv' x4) and models/
#    unet_d.py's (D: UNetDiscriminatorSN, 64 features, per-pixel logits).
#      L_G = 1 * mean|y - r| + 1 * sum_l w_l mean|phi_l(y) - phi_l(r)| + 0.1
#        * BCE(D(y), 1) on images in [0, 1] (lambda_l1 0.5 on this system's
#        [-1, 1]); phi_l VGG19's conv1_2 .. conv5_4 before the ReLU on
#        ImageNet-normalised inputs, w = (0.1, 0.1, 1, 1, 1).
#      L_D = BCE(D(r), 1) + BCE(D(sg(y)), 0). Adam(0.9, 0.999) at 1e-4,
#        constant; EMA of G at 0.999.
#    Departures: this Trainer's step (D's fake comes from the same generator
#    forward as G's loss) and its 0.5 on D's loss (train/step.py); no
#    PSNR-pretrained start; the LQ side is whatever the dataset holds (the
#    authors degrade on the host with BSRGAN's random pipeline).
_register(
    Config(
        name="swinir_realsr_x4",
        model=ModelConfig(generator="swinir", ngf=180, n_blocks=6, scale=4,
                          norm="layer", ndf=64, discriminator="unet",
                          num_D=1, use_spectral_norm=True,
                          get_interm_feat=False, use_compression_net=False,
                          d_conditional=False, use_dropout=True),
        loss=LossConfig(gan_mode="vanilla", gan_weight=0.1, lambda_feat=0.0,
                        lambda_vgg=1.0, lambda_tv=0.0, lambda_l1=0.5,
                        vgg_imagenet_norm=True, vgg_taps="preact"),
        optim=OptimConfig(lr=1e-4, beta1=0.9, beta2=0.999,
                          lr_policy="constant"),
        data=DataConfig(dataset="realsr", image_size=256, batch_size=4),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1)),
        health=HealthConfig(ema_decay=0.999),
    )
)


# 9. Big LaMa, large-mask inpainting on fast Fourier convolutions (Suvorov et
#    al., WACV 2022, arXiv:2109.07161 sec. 2.1-2.4, 3; sizes of github.com/
#    advimman/lama configs/training/big-lama.yaml + generator/
#    ffc_resnet_075.yaml, as recalled). The layer equations are models/
#    ffc.py's docstring: a k7 stem 4 -> 64, three stride-2 k3 convolutions to
#    512, 18 residual blocks of two FFCs at 128 local / 384 global channels
#    (the Fourier unit on 192), three transposed convolutions back, k7 head,
#    sigmoid. x = image, m = mask (1 = missing), input = [x * (1 - m), m].
#      D: C64(s2) - C128(s2,BN) - C256(s2,BN) - C512(s2,BN) - C512(s1,BN) -
#        1, k4 pad 2, LeakyReLU 0.2, on the image alone; every layer's
#        output a feature for the matching term.
#      L_D = softplus(-D(x)) + gp_coef * mean_n |grad_x sum D(x)|^2 +
#        softplus(D(y)) * m' + softplus(-D(y)) * (1 - m'), m' the mask
#        resized (nearest) to the logits: the known pixels of a generated
#        image count as real (mask_as_fake_target).
#      L_G = 10 * mean softplus(-D(y)) + 10 * mean(|y - x| * (1 - m)) + 100
#        * mean_layers mse(D_l(y), D_l(x)) + 30 * sum_stages mse(phi_s(y),
#        phi_s(x)), phi the dilated ResNet50, images in [0, 1] (lambda_l1 5
#        on this system's [-1, 1]). Adam(0.9, 0.999): G 1e-3, D 1e-4.
#    Departures: this Trainer's step (D's fake is G's own forward, G sees the
#    D of the step's start, D's BatchNorm statistics advance twice a step)
#    and its 0.5 on D's whole loss; D sees images in [-1, 1] and the penalty
#    is scaled to [0, 1] units; the perceptual trunk's weights are seeded.
_register(
    Config(
        name="big_lama",
        model=ModelConfig(generator="lama", ngf=64, n_blocks=18, input_nc=4,
                          norm="batch", ffc_ratio=0.75, ndf=64, num_D=1,
                          n_layers_D=4, norm_d="batch",
                          use_spectral_norm=False, get_interm_feat=True,
                          use_compression_net=False, d_conditional=False),
        loss=LossConfig(gan_mode="nonsaturating", gan_weight=10.0,
                        gp_coef=0.001, lambda_feat=100.0, feat_mode="mse",
                        lambda_vgg=0.0, lambda_tv=0.0, lambda_l1=5.0,
                        lambda_hrf=30.0),
        optim=OptimConfig(lr=1e-3, lr_d=1e-4, beta1=0.9, beta2=0.999,
                          lr_policy="constant"),
        data=DataConfig(dataset="places", image_size=256, batch_size=16),
        parallel=ParallelConfig(mesh=MeshSpec(data=-1)),
    )
)


def get_preset(name: str) -> Config:
    try:
        return _PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown preset {name!r}; have {sorted(_PRESETS)}") from None


def int8_full_coverage(cfg: Config) -> Config:
    """The ONE definition of "full-model delayed int8" (ISSUE 14): every
    coverage knob the --int8-diff worklist drained, on top of ``cfg``.

    Shared by the lint CLI (the ``train_step[facades_int8_full]`` traced
    program the coverage worklist audits) and the ``facades_int8_full``
    preset, so the statically audited program and the trained one can
    never drift apart. Deliberately NOT
    flipped: ``int8_stem`` (HBM-bound 3/6-ch stems — the measured-rejected
    verdict carried by dated in-source waivers) and the U-Net image head
    (quality + HBM critical, no knob)."""
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(
            cfg.model,
            int8=True,
            int8_delayed=True,
            int8_generator=True,
            int8_decoder=True,
            int8_head=True,
            use_compression_net=True,
            int8_compression=True,
        ),
    )


# The full-coverage int8 config as a FIRST-CLASS preset (ISSUE 15): a
# plain --preset row. Same override set the lint CLI traces as
# train_step[facades_int8_full], so the static and trained programs
# cannot drift.
_register(int8_full_coverage(_PRESETS["facades_int8"]).replace(
    name="facades_int8_full"))


def list_presets():
    return sorted(_PRESETS)
