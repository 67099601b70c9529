"""U-Net generator — classic pix2pix (the BASELINE facades/edges2shoes
configs; the reference's BASELINE.json mislabels its ExpandNetwork a
"U-Net", see SURVEY §0 — this is the real one).

Architecture follows the pix2pix U-Net-256: ``num_downs`` stride-2 encoder
convs (k4) with LeakyReLU(0.2), channel growth ngf→8·ngf (capped), skip
connections at every resolution, decoder mirrors with norm+ReLU, tanh head.
Innermost and outermost levels carry no norm, as in the original.

Decoder upsampling is ConvTranspose k4 s2 (the torch pix2pix parameter
layout): the fastest form measured on v5e despite XLA's reverse-heavy
transposed-conv backward. The conv k2s1 + depth-to-space and the
nearest-resize + conv k3 forms it was measured against lost and are gone.

TPU-first deviation from the torch lineage (semantics, not translation):
- Dropout (the pix2pix noise source, 0.5 on the three innermost decoder
  levels) is off by default; when ``use_dropout`` is set the caller passes
  an ``rngs={'dropout': ...}`` to apply().
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.ops.conv import normal_init, save_conv_out
from p2p_tpu.ops.activations import leaky_relu_y, relu_y, tanh_y
from p2p_tpu.ops.norm import make_norm


class UNetGenerator(nn.Module):
    ngf: int = 64
    out_channels: int = 3
    num_downs: int = 8         # 256x256 → 1x1 bottleneck
    norm: str = "batch"
    use_dropout: bool = False
    # int8 QAT MXU path (ops/int8.py) for the encoder convs (all except
    # the 3-ch stem down0). int8_decoder additionally switches the
    # decoder deconvs (except the image head up0) to the quantized
    # subpixel form — measured a net loss on v5e, kept as an option.
    int8: bool = False
    int8_decoder: bool = False
    int8_delayed: bool = False
    # Extend int8 to the k4-s2 RGB stem (down0). Default off — the
    # measured-rejected verdict: the 3-wide contraction leaves the MXU
    # idle either way (the stem is HBM-bound; see the dated waiver at
    # the down_conv site) — but the knob keeps the form measurable per
    # chip/shape (the facades_int8_full preset does not flip it).
    int8_stem: bool = False
    # Keep the (mathematically dead) conv biases in front of norm layers.
    # A per-channel bias immediately followed by a mean-subtracting norm
    # (BatchNorm OR InstanceNorm) is exactly cancelled in the forward
    # (mean absorbs it), and the norm backward emits zero-channel-mean
    # cotangents so the bias gradient is identically ~0 — yet computing
    # it re-reads the full cotangent (profiled ~3 ms/step of reduce_sum
    # kernels at bs=128/256²). Default: drop those biases (exact same
    # function, same training dynamics — they initialize at 0 and never
    # move). True restores the round-2 checkpoint param layout.
    legacy_layout: bool = False
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        mk = make_norm(self.norm, train=train, dtype=self.dtype)
        # Shapes are static under jit: clamp the depth to the factor-of-2
        # content of H and W so every decoder upsample exactly mirrors its
        # encoder level (96 = 2^5·3 → 5 levels, 3px bottleneck).
        def pow2_levels(n: int) -> int:
            k = 0
            while n % 2 == 0 and n > 1:
                n //= 2
                k += 1
            return k

        num_downs = min(self.num_downs, pow2_levels(x.shape[1]),
                        pow2_levels(x.shape[2]))

        normed = self.norm != "none" and not self.legacy_layout

        def down_conv(y, features, name, int8=False, norm_after=False,
                      stem=False):
            bias = not norm_after
            if stem and self.int8 and self.int8_stem:
                int8 = True
            if int8:
                from p2p_tpu.ops.int8 import QuantConv

                return QuantConv(
                    features, kernel_size=4, strides=2, padding=1,
                    use_bias=bias, dtype=self.dtype,
                    kernel_init=normal_init(), name=name,
                    delayed=self.int8_delayed,
                )(y)
            # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 measured-rejected: only the 3-ch stem (down0) reaches this line under delayed-int8 (encoder i>0 takes the QuantConv branch above); its k4·3-wide contraction leaves the MXU idle in ANY dtype — the conv is HBM-bound, int8 buys nothing and costs the quantize pass (rounds 2-5 doctrine). ModelConfig.int8_stem keeps the form measurable per chip.
            return save_conv_out(nn.Conv(
                features, kernel_size=(4, 4), strides=(2, 2), padding=1,
                use_bias=bias, dtype=self.dtype, kernel_init=normal_init(),
                name=name,
            )(y))

        # ---- encoder ----------------------------------------------------
        feats = [min(self.ngf * (2 ** i), self.ngf * 8)
                 for i in range(num_downs)]
        skips = []
        y = x
        for i, f in enumerate(feats):
            if i > 0:
                y = leaky_relu_y(y, 0.2)
            y = down_conv(y, f, name=f"down{i}",
                          int8=self.int8 and i > 0,
                          norm_after=normed and 0 < i < num_downs - 1,
                          stem=i == 0)
            # no norm on the outermost and innermost encoder convs
            if 0 < i < num_downs - 1:
                y = mk()(y)
            skips.append(y)

        # ---- decoder ----------------------------------------------------
        for i in reversed(range(num_downs)):
            f = self.out_channels if i == 0 else feats[i - 1]
            y = relu_y(y)
            if self.int8 and self.int8_decoder and i > 0:
                # conv-k2s1 subpixel form: the ConvTranspose family
                # member whose int8 lowering wins in all three
                # contractions (see ops/int8.py). Off by default:
                # measured on v5e the interleave + large-spatial
                # wgrad slices cost more than the MXU gain.
                from p2p_tpu.ops.int8 import QuantSubpixelDeconv

                # bias kept: after the shifted interleave it is a per-
                # PHASE (2×2-periodic) offset, which a norm's global mean
                # only partially absorbs — not dead, unlike plain convs
                y = QuantSubpixelDeconv(
                    f, dtype=self.dtype, delayed=self.int8_delayed,
                    kernel_init=normal_init(), name=f"up{i}",
                )(y)
            else:
                # bias dropped when a norm follows (i>0): the norm's
                # mean subtraction cancels it exactly (see legacy_layout)
                # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 measured-rejected: under delayed-int8 with int8_decoder only the IMAGE head (up0) reaches this line (i>0 takes QuantSubpixelDeconv above); the tanh-facing head is quality-critical AND HBM-bound (3 live output lanes) — it stays bf16 by doctrine, deliberately without a knob (ops/int8.py module docstring).
                y = save_conv_out(nn.ConvTranspose(
                    f, kernel_size=(4, 4), strides=(2, 2),
                    padding="SAME", use_bias=not (normed and i > 0),
                    dtype=self.dtype,
                    kernel_init=normal_init(), name=f"up{i}",
                )(y))
            if i > 0:
                y = mk()(y)
                # dropout on the three decoder levels after the innermost
                if self.use_dropout and num_downs - 4 <= i < num_downs - 1:
                    y = nn.Dropout(0.5, deterministic=not train)(y)
                y = jnp.concatenate([y, skips[i - 1]], axis=-1)
        return tanh_y(y)
