"""PatchGAN discriminators (pix2pixHD-style multiscale).

Behavior parity with /root/reference/networks.py:716-806, num_D=3,
n_layers=3, spectral norm on the inner convs, intermediate features
returned for the feature-matching loss.

A single NLayerDiscriminator with n_layers=3 has 5 stages (model0..model4):
  0: conv(in→ndf,   k4, s2, pad2) + LeakyReLU(0.2)
  1: SN conv(ndf→2ndf,  k4, s2, pad2) + LeakyReLU     [spectral norm]
  2: SN conv(2ndf→4ndf, k4, s2, pad2) + LeakyReLU     [spectral norm]
  3: SN conv(4ndf→8ndf, k4, s1, pad2) + LeakyReLU     [spectral norm]
  4: conv(8ndf→1, k4, s1, pad2)
(channel growth capped at 512; pad = ceil(3/2) = 2 exactly as the
reference's ``padw``.)

Multiscale: num_D independent discriminators; scale i sees the input
downsampled i times by AvgPool(3, s2, pad1, count_include_pad=False).
Output ordering matches the reference: result[0] is the FINEST scale
(applied to the un-downsampled input) — networks.py:749.

Each forward returns ``[[act_0..act_4] per scale]``. The 70×70-PatchGAN of
classic pix2pix is the num_D=1, no-SN, no-interm-feat corner of this module.
"""

from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.ops.activations import leaky_relu_y
from p2p_tpu.ops.conv import KN2RowConv, normal_init, save_conv_out
from p2p_tpu.ops.norm import make_norm_act
from p2p_tpu.ops.spectral_norm import SpectralConv


def avg_pool_downsample(x: jax.Array) -> jax.Array:
    """AvgPool2d(3, stride=2, padding=1, count_include_pad=False) in NHWC."""
    ones = jnp.ones(x.shape[1:3] + (1,), x.dtype)[None]
    sum_ = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 3, 3, 1), (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)]
    )
    cnt = jax.lax.reduce_window(
        ones, 0.0, jax.lax.add, (1, 3, 3, 1), (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)]
    )
    return sum_ / cnt


class _SplitStemConv(nn.Module):
    """The conditional-D stem conv applied to an UNCONCATENATED (a, b)
    pair: ``conv(concat(a,b), W) == conv(a, W[:,:,:ca]) + conv(b, W[:,:,ca:])``
    by linearity of convolution in the input channels.

    Why: the reference concatenates (input ‖ output) before D
    (train.py:308,315) and so did round 3 — materializing two 6-channel
    NHWC pairs per step (~100 MB each at 256²/bs128) that the stem
    immediately re-reads, and computing the conditioning half
    ``conv(real_a, W_a)`` twice (fake and real branches — XLA CSE dedupes
    the identical subexpression once the halves are separate ops). The
    fake branch's input cotangent also becomes per-half, so the dead
    ``real_a`` dgrad disappears structurally instead of being sliced off
    after computation (train/step.py round-3 ``[..., in_c:]``).

    Param tree matches the concat path exactly (``Conv_0/{kernel,bias}``
    with the full 6-channel HWIO kernel) — checkpoints interchange, and
    init still runs the concat path.
    """

    features: int
    stride: int
    padding: int = 2
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, a, b):
        c = a.shape[-1] + b.shape[-1]
        kernel = self.param("kernel", normal_init(),
                            (4, 4, c, self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        dt = self.dtype or jnp.float32
        ca = a.shape[-1]
        pad = [(self.padding, self.padding)] * 2

        def cv(inp, kk):
            dn = jax.lax.conv_dimension_numbers(
                inp.shape, kk.shape, ("NHWC", "HWIO", "NHWC"))
            return jax.lax.conv_general_dilated(
                inp.astype(dt), kk.astype(dt),
                (self.stride, self.stride), pad, dimension_numbers=dn,
            )

        y = cv(a, kernel[:, :, :ca]) + cv(b, kernel[:, :, ca:])
        return save_conv_out(y + bias.astype(y.dtype))


class _PlainConv(nn.Module):
    features: int
    stride: int
    padding: int = 2
    # int8 QAT MXU path (ops/int8.py) — set by NLayerDiscriminator on
    # its wide inner convs (and, under int8_stem/int8_head, the stem
    # and logits head).
    int8: bool = False
    int8_delayed: bool = False
    # quantize-fused input epilogue threading (ops/int8.py QuantConv)
    epilogue: Optional[Callable] = None
    epilogue_tap: bool = False
    # False in front of a BatchNorm (the VQGAN lineage's D: the norm's
    # own shift stands in); the plain bf16 convolution only
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        if isinstance(x, (tuple, list)):
            # unconcatenated conditional pair — the split-stem path
            # (param tree identical to the concat path: Conv_0 holds the
            # full 6-channel kernel). Stays bf16 even under int8_stem:
            # the split form exists precisely because the halves are
            # HBM-bound image reads.
            a, b = x
            return _SplitStemConv(
                self.features, stride=self.stride, padding=self.padding,
                dtype=self.dtype, name="Conv_0",
            )(a, b)
        if self.stride == 1 and self.features * 16 <= x.shape[-1]:
            # thin head (e.g. 512→1): kn2row matmul decomposition — the
            # MXU conv runs at 3-6 TF/s with one live output lane; this
            # form is one full-rate HBM pass over x (ops/conv.py). With
            # int8 the tap dot runs s8×s8→s32 (int8_kn2row_conv).
            return KN2RowConv(self.features, kernel_size=4,
                              padding=self.padding, int8=self.int8,
                              int8_delayed=self.int8_delayed,
                              dtype=self.dtype, name="Conv_0")(x)
        if self.int8:
            from p2p_tpu.ops.int8 import QuantConv

            return QuantConv(
                self.features, kernel_size=4, strides=self.stride,
                padding=self.padding, dtype=self.dtype,
                kernel_init=normal_init(), name="Conv_0",
                delayed=self.int8_delayed,
                epilogue=self.epilogue, epilogue_tap=self.epilogue_tap,
            )(x)
        # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 measured-rejected: only the 6-ch stage-0 stem reaches this line under delayed-int8 (inner convs take the int8 branch above, the head the kn2row branch); the 6-wide contraction leaves the MXU idle in any dtype — HBM-bound, the rounds 2-5 stems-stay-bf16 doctrine. ModelConfig.int8_stem keeps the form measurable per chip.
        return save_conv_out(nn.Conv(
            self.features,
            kernel_size=(4, 4),
            strides=(self.stride, self.stride),
            padding=self.padding,
            use_bias=self.use_bias,
            dtype=self.dtype,
            kernel_init=normal_init(),
        )(x))


class NLayerDiscriminator(nn.Module):
    ndf: int = 64
    n_layers: int = 3
    use_spectral_norm: bool = True
    use_sigmoid: bool = False
    get_interm_feat: bool = True
    # int8 QAT path for the wide inner convs (stages 1..n_layers); by
    # default the 6-ch stem and the 1-ch head stay bf16. Composes with
    # spectral norm: the power iteration tracks the true f32 weight and
    # only the normalized w/σ is quantized (SpectralConv.int8).
    int8: bool = False
    int8_delayed: bool = False
    # ISSUE 14 coverage knobs (core/config.py ModelConfig docs):
    # int8_stem quantizes the stage-0 conv (concat form only — the
    # split-pair stem stays bf16 by design); int8_head runs the logits
    # head on the int8 kn2row path; int8_fused_epilogue fuses each inner
    # conv's input epilogue [norm+LeakyReLU+quantize+amax] into one
    # streaming pass (needs int8_delayed + an instance-family norm).
    int8_stem: bool = False
    int8_head: bool = False
    int8_fused_epilogue: bool = False
    # Normalization on the inner (stage 1..n_layers) convs — the pix2pixHD
    # paper's D carries InstanceNorm there; this repo's reference lineage
    # (networks.py:716) has none, so "none" is the parity default.
    # "instance"/"pallas_instance" norms are affine-free → the param tree
    # is IDENTICAL either way (checkpoints interchange); with
    # "pallas_instance" the whole conv epilogue (norm + LeakyReLU) runs as
    # ONE fused Pallas pass (ops/pallas/norm_act.py) — the D-side leaky
    # variant of the generator's fused chains.
    # "batch" (the VQGAN lineage's D): BatchNorm with affine after the
    # inner convolutions, which then carry no bias; always the batch's own
    # moments (D only ever runs inside the train step), its running
    # statistics threaded by the step as ``TrainState.batch_stats_d``.
    norm: str = "none"
    # zero padding of the five k4 convolutions (ModelConfig.d_padding)
    padding: int = 2
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x) -> List[jax.Array]:
        if self.norm not in ("none", "instance", "pallas_instance", "batch"):
            raise ValueError(
                f"discriminator norm must be none, instance, "
                f"pallas_instance (stateless) or batch (its statistics "
                f"threaded as TrainState.batch_stats_d), got {self.norm!r}")
        if self.norm == "batch" and (self.use_spectral_norm or self.int8):
            raise ValueError(
                "discriminator norm 'batch' goes with plain convolutions: "
                "no spectral norm, no int8")
        fused_q = (self.int8 and self.int8_delayed
                   and self.int8_fused_epilogue)
        if fused_q and self.norm not in ("instance", "pallas_instance"):
            raise ValueError(
                "int8_fused_epilogue needs a stateless instance-family "
                f"discriminator norm (norm_d), got {self.norm!r}")
        feats = []
        nf = self.ndf
        na = (make_norm_act(self.norm, dtype=self.dtype)
              if self.norm != "none" else None)
        y = _PlainConv(nf, stride=2, padding=self.padding,
                       int8=self.int8 and self.int8_stem,
                       int8_delayed=self.int8_delayed,
                       dtype=self.dtype)(x)
        y = leaky_relu_y(y, 0.2)
        feats.append(y)

        def inner_conv(y, features, stride, ep=None, tap=False):
            if self.use_spectral_norm:
                return SpectralConv(
                    features, kernel_size=4, stride=stride,
                    padding=self.padding,
                    int8=self.int8, int8_delayed=self.int8_delayed,
                    epilogue=ep, epilogue_tap=tap, dtype=self.dtype
                )(y)
            return _PlainConv(features, stride=stride, padding=self.padding,
                              int8=self.int8,
                              int8_delayed=self.int8_delayed,
                              epilogue=ep, epilogue_tap=tap,
                              use_bias=self.norm != "batch",
                              dtype=self.dtype)(y)

        def inner(y, features, stride):
            y = inner_conv(y, features, stride)
            if na is not None:
                return na(y, act="leaky", slope=0.2)
            return leaky_relu_y(y, 0.2)

        widths = []
        for _ in range(1, self.n_layers):
            nf = min(nf * 2, 512)
            widths.append((nf, 2))
        nf = min(nf * 2, 512)
        widths.append((nf, 1))

        if not fused_q:
            for features, stride in widths:
                y = inner(y, features, stride)
                feats.append(y)
        else:
            # quantize-fused epilogues: each inner conv after the first
            # consumes the PREVIOUS conv's raw output through its fused
            # [norm + LeakyReLU + clip/round + amax] input epilogue
            # (ops/pallas/norm_act.py) — the float activation between
            # inner stages is never materialized. Feature-matching taps
            # become the dequantized surrogate sx·q: exactly the values
            # the downstream conv contracts (QAT-faithful taps). Module
            # construction order is identical to the unfused branch, so
            # flax auto-naming — and the whole param/quant tree — is
            # unchanged; only the LAST inner epilogue stays unfused (the
            # logits head quantizes its own input).
            ep = (lambda y_, sx: na(y_, act="leaky", slope=0.2,
                                    quant_scale=sx))
            raw = None
            for features, stride in widths:
                if raw is None:
                    raw = inner_conv(y, features, stride)
                else:
                    raw, tap = inner_conv(raw, features, stride, ep=ep,
                                          tap=True)
                    feats.append(tap)
            y = na(raw, act="leaky", slope=0.2)
            feats.append(y)

        y = _PlainConv(1, stride=1, padding=self.padding,
                       int8=self.int8 and self.int8_head,
                       int8_delayed=self.int8_delayed,
                       dtype=self.dtype)(y)
        if self.use_sigmoid:
            y = nn.sigmoid(y)
        feats.append(y)

        if self.get_interm_feat:
            return feats
        return [feats[-1]]


class MultiscaleDiscriminator(nn.Module):
    ndf: int = 64
    n_layers: int = 3
    num_D: int = 3
    use_spectral_norm: bool = True
    use_sigmoid: bool = False
    get_interm_feat: bool = True
    int8: bool = False
    int8_delayed: bool = False
    int8_stem: bool = False
    int8_head: bool = False
    int8_fused_epilogue: bool = False
    norm: str = "none"
    padding: int = 2
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x) -> List[List[jax.Array]]:
        results = []
        current = x
        for i in range(self.num_D):
            # Finest-first result ordering; submodule index num_D-1-i keeps
            # parameter naming aligned with the reference's scale{i} layout.
            d = NLayerDiscriminator(
                ndf=self.ndf,
                n_layers=self.n_layers,
                use_spectral_norm=self.use_spectral_norm,
                use_sigmoid=self.use_sigmoid,
                get_interm_feat=self.get_interm_feat,
                int8=self.int8,
                int8_delayed=self.int8_delayed,
                int8_stem=self.int8_stem,
                int8_head=self.int8_head,
                int8_fused_epilogue=self.int8_fused_epilogue,
                norm=self.norm,
                padding=self.padding,
                dtype=self.dtype,
                name=f"scale{self.num_D - 1 - i}",
            )
            results.append(d(current))
            if i != self.num_D - 1:
                # unconcatenated (a, b) pairs downsample elementwise —
                # AvgPool is channelwise, so pooling the halves equals
                # pooling the concat
                if isinstance(current, (tuple, list)):
                    current = tuple(avg_pool_downsample(t) for t in current)
                else:
                    current = avg_pool_downsample(current)
        return results
