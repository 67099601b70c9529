"""Model factories — the TPU-native counterpart of the reference's
``define_C`` / ``define_G`` / ``define_D`` (networks.py:157,164,708).

Factories build flax modules from :class:`p2p_tpu.core.config.ModelConfig`
and expose :func:`init_variables`, which re-draws weights per the configured
init type (normal/xavier/kaiming/orthogonal — networks.py:128-150 semantics:
conv/linear kernels re-initialized, BatchNorm γ~N(1,0.02), biases zero).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import freeze, unfreeze

from p2p_tpu.core.config import ModelConfig
from p2p_tpu.models.compression import CompressionNetwork
from p2p_tpu.models.expand import ExpandNetwork
from p2p_tpu.models.patchgan import MultiscaleDiscriminator


def define_C(cfg: ModelConfig, dtype=None) -> nn.Module:
    return CompressionNetwork(
        int8=cfg.int8 and cfg.int8_compression,
        int8_delayed=cfg.int8_delayed,
        dtype=dtype,
    )


def define_G(cfg: ModelConfig, dtype=None, remat=False) -> nn.Module:
    int8_g = cfg.int8 and cfg.int8_generator
    delayed = cfg.int8_delayed
    if cfg.generator == "expand":
        return ExpandNetwork(
            ngf=cfg.ngf,
            n_blocks=cfg.n_blocks,
            out_channels=cfg.output_nc,
            norm=cfg.norm,
            remat=remat,
            int8=int8_g,
            int8_delayed=delayed,
            legacy_layout=cfg.legacy_layout,
            dtype=dtype,
        )
    if cfg.generator == "unet":
        from p2p_tpu.models.unet import UNetGenerator

        return UNetGenerator(
            ngf=cfg.ngf, out_channels=cfg.output_nc, norm=cfg.norm,
            use_dropout=cfg.use_dropout, int8=int8_g,
            int8_decoder=cfg.int8_decoder,
            int8_delayed=delayed,
            int8_stem=cfg.int8_stem,
            legacy_layout=cfg.legacy_layout,
            dtype=dtype,
        )
    if cfg.generator == "resnet":
        from p2p_tpu.models.resnet_gen import ResnetGenerator

        return ResnetGenerator(
            ngf=cfg.ngf,
            n_blocks=cfg.n_blocks,
            out_channels=cfg.output_nc,
            norm=cfg.norm,
            remat=remat,
            int8=int8_g,
            int8_delayed=delayed,
            legacy_layout=cfg.legacy_layout,
            dtype=dtype,
        )
    if cfg.generator == "pix2pixhd":
        from p2p_tpu.models.pix2pixhd import Pix2PixHDGenerator

        return Pix2PixHDGenerator(
            ngf=cfg.ngf, out_channels=cfg.output_nc,
            n_blocks_global=cfg.n_blocks, norm=cfg.norm,
            remat=remat, int8=int8_g, int8_delayed=delayed,
            legacy_layout=cfg.legacy_layout, dtype=dtype,
        )
    if cfg.generator == "pix2pixhd_global":
        # phase 1 of the coarse-to-fine schedule: G1 alone at half res
        from p2p_tpu.models.pix2pixhd import GlobalGenerator

        return GlobalGenerator(
            ngf=cfg.ngf, out_channels=cfg.output_nc, n_blocks=cfg.n_blocks,
            norm=cfg.norm, remat=remat, int8=int8_g, int8_delayed=delayed,
            legacy_layout=cfg.legacy_layout, dtype=dtype,
        )
    if cfg.generator == "spade":
        from p2p_tpu.models.spade import SPADEGenerator

        return SPADEGenerator(nf=cfg.ngf, out_channels=cfg.output_nc,
                              dtype=dtype)
    if cfg.generator == "vqgan":
        from p2p_tpu.models.vqgan import VQGAN

        return VQGAN(ch=cfg.ngf, ch_mult=tuple(cfg.vq_ch_mult),
                     res_blocks=cfg.vq_res_blocks, codes=cfg.vq_codes,
                     embed_dim=cfg.vq_embed_dim,
                     out_channels=cfg.output_nc, dtype=dtype)
    if cfg.generator == "swinir":
        from p2p_tpu.models.swinir import SwinIR

        return SwinIR(embed=cfg.ngf, groups=cfg.n_blocks,
                      out_channels=cfg.output_nc, scale=cfg.scale,
                      dtype=dtype)
    if cfg.generator == "lama":
        from p2p_tpu.models.ffc import LamaGenerator

        return LamaGenerator(ngf=cfg.ngf, n_blocks=cfg.n_blocks,
                             ratio=cfg.ffc_ratio,
                             out_channels=cfg.output_nc, dtype=dtype)
    raise ValueError(f"unknown generator {cfg.generator!r}")


def input_mask_channel(cfg: ModelConfig) -> Optional[int]:
    """The channel of the configured generator's INPUT that is a mask (1 =
    a pixel to be filled, the other channels the image with those pixels
    blanked), or None for a generator whose input is an image or a label
    map. Where there is one the loader draws the masks and builds the
    input from the target (``data/pipeline.py``), the train step weighs
    its losses by it, and ``cli.infer`` reads masks beside its images."""
    if cfg.generator == "lama":
        from p2p_tpu.models.ffc import MASK_CHANNEL

        return MASK_CHANNEL
    return None


class GeneratorSide(NamedTuple):
    """What a generator with a learned quantizer hands the train step
    beside its image (``train/step.py`` reads these and names no model):
    ``collection`` is the variable collection its training forward fills,
    ``read`` turns that collection into ``codebook_loss`` (a scalar the
    step adds to G's loss and pulls back through), ``indices`` and
    ``last_input`` (the input of the last convolution), ``last_kernel``
    is that convolution's kernel's path in ``params_g`` (a k3 convolution
    on a zero pad of 1: ``train/step.adaptive_gan_weight``) and ``usage``
    maps the indices to (distinct codes, perplexity)."""

    collection: str
    read: Callable[[Any], Dict[str, jax.Array]]
    last_kernel: Tuple[str, ...]
    usage: Callable[[jax.Array], Tuple[jax.Array, jax.Array]]


def generator_side(cfg: ModelConfig) -> Optional[GeneratorSide]:
    """The configured generator's :class:`GeneratorSide`, or None for one
    that maps a tensor to a tensor and nothing else."""
    if cfg.generator == "vqgan":
        from p2p_tpu.models import vqgan

        return GeneratorSide("vq", vqgan.side_outputs, vqgan.LAST_KERNEL,
                             functools.partial(vqgan.code_usage,
                                               codes=cfg.vq_codes))
    return None


def input_extent_multiple(cfg: ModelConfig) -> int:
    """What the configured generator needs its INPUT's height and width to
    be multiples of beyond what its preset's own extent shows (a window
    transformer: its window); 1 where an input of another extent is not
    served (``cli.infer`` pads up to it)."""
    if cfg.generator == "swinir":
        from p2p_tpu.models.swinir import WINDOW

        return WINDOW
    if cfg.generator == "lama":
        from p2p_tpu.models.ffc import EXTENT_MULTIPLE

        return EXTENT_MULTIPLE
    return 1


def generator_gauges(cfg: ModelConfig, h: int, w: int) -> Dict[str, float]:
    """What the configured generator does for one ``h`` x ``w`` image, from
    its shapes, as gauges the Trainer sets when it builds the step; empty
    for a generator that states none."""
    if cfg.generator == "spade":
        from p2p_tpu.models.spade import spade_arithmetic

        return spade_arithmetic(cfg.ngf, cfg.input_nc, h, w)
    if cfg.generator == "vqgan":
        from p2p_tpu.models.vgg import vgg_gflop_per_image
        from p2p_tpu.models.vqgan import vqgan_arithmetic

        out = vqgan_arithmetic(cfg.ngf, tuple(cfg.vq_ch_mult),
                               cfg.vq_res_blocks, cfg.vq_codes,
                               cfg.vq_embed_dim, h, w)
        # LPIPS: one VGG16 forward (the step runs two and one backward)
        out["vqgan_lpips_gflop_per_image"] = vgg_gflop_per_image(
            "vgg16", h, w)
        return out
    if cfg.generator == "swinir":
        from p2p_tpu.models.swinir import swinir_arithmetic
        from p2p_tpu.models.vgg import vgg_gflop_per_image

        # h x w is the TARGET's extent; the body runs on the input's
        out = swinir_arithmetic(cfg.ngf, cfg.n_blocks, h // cfg.scale,
                                w // cfg.scale)
        # one VGG19 forward through conv5_4 (the step runs two and one
        # backward)
        out["swinir_vgg_gflop_per_image"] = vgg_gflop_per_image(
            "vgg19_preact", h, w)
        return out
    if cfg.generator == "lama":
        from p2p_tpu.models.ffc import ffc_arithmetic
        from p2p_tpu.models.resnet_dilated import (
            resnet50_dilated_gflop_per_image,
        )

        out = ffc_arithmetic(cfg.ngf, cfg.n_blocks, cfg.ffc_ratio, h, w)
        # one forward of the dilated ResNet50 (the step runs two and one
        # backward)
        out["lama_hrf_gflop_per_image"] = resnet50_dilated_gflop_per_image(
            h, w)
        return out
    return {}


def generator_trace_gauges(cfg: ModelConfig) -> Dict[str, float]:
    """What the configured generator chose by itself the LAST time this
    process traced it (a kernel in XLA's place), as gauges the Trainer
    sets right after a dispatch of its own step traced; empty for a
    generator that chooses nothing."""
    if cfg.generator == "swinir":
        from p2p_tpu.ops.pallas.window_attention import kernel_sites

        sites = kernel_sites()
        return {
            "swinir_attn_kernel_layers": float(sites["layers"]),
            "swinir_attn_kernel_windows_per_block": float(
                sites["windows_per_block"])}
    return {}


def define_D(cfg: ModelConfig, dtype=None) -> nn.Module:
    if cfg.discriminator == "unet":
        from p2p_tpu.models.unet_d import UNetDiscriminatorSN

        if not cfg.use_spectral_norm or cfg.num_D != 1 or cfg.d_conditional:
            raise ValueError(
                "discriminator 'unet' is spectrally normalised, has one "
                "scale and sees the image alone: set use_spectral_norm, "
                "num_D 1 and d_conditional False")
        return UNetDiscriminatorSN(ndf=cfg.ndf, dtype=dtype)
    if cfg.discriminator != "patch":
        raise ValueError(f"unknown discriminator {cfg.discriminator!r}")
    return MultiscaleDiscriminator(
        ndf=cfg.ndf,
        n_layers=cfg.n_layers_D,
        num_D=cfg.num_D,
        use_spectral_norm=cfg.use_spectral_norm,
        get_interm_feat=cfg.get_interm_feat,
        int8=cfg.int8,
        int8_delayed=cfg.int8_delayed,
        int8_stem=cfg.int8_stem,
        int8_head=cfg.int8_head,
        int8_fused_epilogue=cfg.int8_fused_epilogue,
        norm=cfg.norm_d,
        padding=cfg.d_padding,
        dtype=dtype,
    )


# ---------------------------------------------------------------- init types

def _kernel_initializer(init_type: str, gain: float):
    if init_type == "normal":
        return nn.initializers.normal(stddev=gain)
    if init_type == "xavier":
        # init.xavier_normal_(w, gain): std = gain * sqrt(2 / (fan_in +
        # fan_out)) (networks.py:133)
        return nn.initializers.variance_scaling(gain ** 2, "fan_avg",
                                                "normal")
    if init_type == "kaiming":
        return nn.initializers.kaiming_normal()
    if init_type == "orthogonal":
        return nn.initializers.orthogonal(scale=gain)
    raise ValueError(f"unknown init type {init_type!r}")


def apply_init_type(
    params: Dict[str, Any], rng: jax.Array, init_type: str = "normal",
    gain: float = 0.02
) -> Dict[str, Any]:
    """Re-draw conv/linear kernels per the configured initializer.

    Leaves biases, norm affines (already γ~N(1,0.02)/β=0 at init), PReLU
    alphas and spectral-norm state untouched.
    """
    init_fn = _kernel_initializer(init_type, gain)
    flat = jax.tree_util.tree_flatten_with_path(unfreeze(params))[0]
    treedef = jax.tree_util.tree_structure(unfreeze(params))
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        last = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if last == "kernel" and getattr(leaf, "ndim", 0) >= 2:
            sub = jax.random.fold_in(rng, i)
            leaves.append(init_fn(sub, leaf.shape, leaf.dtype))
        else:
            leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_variables(module: nn.Module, rng: jax.Array, sample_input,
                   init_type: str = "normal", gain: float = 0.02, **kwargs):
    """init() + configured weight re-draw; returns the full variable dict."""
    variables = unfreeze(module.init(rng, sample_input, **kwargs))
    if init_type != "normal":  # modules already default to normal(0.02)
        variables["params"] = apply_init_type(
            variables["params"], jax.random.fold_in(rng, 7), init_type, gain
        )
    return variables
