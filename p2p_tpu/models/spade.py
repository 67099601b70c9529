"""SPADE / GauGAN generator (Park, Liu, Wang, Zhu, "Semantic Image
Synthesis with Spatially-Adaptive Normalization", CVPR 2019,
arXiv:1903.07291, section 3 and appendix A; sizes of the authors' code,
``SPADEGenerator`` with ``num_upsampling_layers = normal``, no VAE encoder).

The generator has no encoder: it starts from the conditioning map ``m``
(one-hot classes + an instance-edge channel) resized to 1/32 of the
output and is driven by ``m`` at every block through
:class:`p2p_tpu.ops.norm.SPADE`:

    x = conv3x3(resize_nearest(m, H/32 x W/32), M -> 16 nf)
    head_0 = ResBlk(16nf, 16nf); up; G_middle_0, G_middle_1 = ResBlk(16nf,
    16nf); up; up_0 = ResBlk(16nf, 8nf); up; up_1 = ResBlk(8nf, 4nf); up;
    up_2 = ResBlk(4nf, 2nf); up; up_3 = ResBlk(2nf, nf);
    tanh(conv3x3(lrelu_0.2(x), nf -> 3))            every up nearest x2

    ResBlk(fin, fout), fmid = min(fin, fout):
      dx  = conv3x3_sn(lrelu_0.2(SPADE_fin(x, m)), fin -> fmid)
      dx  = conv3x3_sn(lrelu_0.2(SPADE_fmid(dx, m)), fmid -> fout)
      xs  = x if fin == fout else conv1x1_sn_nobias(SPADE_fin(x, m))
      out = xs + dx

LeakyReLU 0.2 as the authors' code runs it (the paper's figure draws
ReLU). Spectral norm (``ops/spectral_norm.SpectralConv``: one power
iteration a forward, ``u`` in the ``spectral`` collection, which the train
state threads as ``spectral_g``) sits on the three convolutions of every
ResBlk and nowhere else. All convolutions pad with zeros.

House layout (``ModelConfig.legacy_layout``'s rule): a bias whose conv
feeds nothing but mean-subtracting norms is cancelled in the forward and
has an identically zero gradient, so it is not there: the first conv, every
``conv_0``, and ``conv_1`` of every block but the last (a per-channel
constant rides the identity shortcuts only as far as the next learned
one, whose both paths start with BN0). The authors' modules carry them;
Adam would walk them at +-lr a step on rounding noise. ``up_3/conv_1``
(into the image head) and every convolution inside SPADE keep theirs.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.ops.activations import leaky_relu_y, tanh_y
from p2p_tpu.ops.conv import ConvLayer, upsample_nearest
from p2p_tpu.ops.norm import SPADE
from p2p_tpu.ops.spectral_norm import SpectralConv

#: the generator's ResBlks in order, by name, with (fin, fout) in units of
#: nf and whether a nearest x2 upsample FOLLOWS the block
BLOCKS = (("head_0", 16, 16, True), ("G_middle_0", 16, 16, False),
          ("G_middle_1", 16, 16, True), ("up_0", 16, 8, True),
          ("up_1", 8, 4, True), ("up_2", 4, 2, True), ("up_3", 2, 1, False))
#: the output extent over the extent the generator starts from
DOWN = 32


class SPADEResnetBlock(nn.Module):
    fin: int
    fout: int
    hidden: int = 128
    # conv_1's bias: live only where the block's output meets something
    # other than a BN0 (the last block, into the image head)
    out_bias: bool = False
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, m, train: bool = True):
        fmid = min(self.fin, self.fout)
        spade = lambda name: SPADE(  # noqa: E731
            self.hidden, train=train, dtype=self.dtype, name=name)
        sn = lambda f, k, name, bias=True: SpectralConv(  # noqa: E731
            f, kernel_size=k, padding=k // 2, use_bias=bias,
            dtype=self.dtype, name=name)
        xs = x
        if self.fin != self.fout:
            xs = sn(self.fout, 1, "conv_s", bias=False)(
                spade("norm_s")(x, m))
        dx = sn(fmid, 3, "conv_0", bias=False)(
            leaky_relu_y(spade("norm_0")(x, m), 0.2))
        dx = sn(self.fout, 3, "conv_1", bias=self.out_bias)(
            leaky_relu_y(spade("norm_1")(dx, m), 0.2))
        return xs + dx


class SPADEGenerator(nn.Module):
    nf: int = 64
    out_channels: int = 3
    hidden: int = 128
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, m, train: bool = True):
        if m.shape[1] % DOWN or m.shape[2] % DOWN:
            raise ValueError(f"SPADEGenerator needs H and W divisible by "
                             f"{DOWN}, got {m.shape}")
        if self.dtype is not None:
            m = m.astype(self.dtype)
        x = ConvLayer(16 * self.nf, kernel_size=3, pad_mode="zero",
                      use_bias=False, dtype=self.dtype,
                      name="fc")(m[:, ::DOWN, ::DOWN])
        for name, fin, fout, up in BLOCKS:
            x = SPADEResnetBlock(fin * self.nf, fout * self.nf, self.hidden,
                                 out_bias=name == BLOCKS[-1][0],
                                 dtype=self.dtype, name=name)(x, m, train)
            if up:
                x = upsample_nearest(x, 2)
        x = ConvLayer(self.out_channels, kernel_size=3, pad_mode="zero",
                      dtype=self.dtype, name="conv_img")(
                          leaky_relu_y(x, 0.2))
        return tanh_y(x)


def spade_arithmetic(nf: int, label_nc: int, h: int, w: int,
                     hidden: int = 128) -> Dict[str, float]:
    """The generator's forward arithmetic for one ``h`` x ``w`` image from
    its shapes (2 x multiply-adds of the convolutions): the number of
    SPADE sites, the GFLOP of their modulation convolutions (shared,
    gamma, beta) and of the whole generator."""
    conv = lambda px, k, ci, co: 2.0 * px * k * k * ci * co  # noqa: E731
    px = (h // DOWN) * (w // DOWN)
    total = conv(px, 3, label_nc, 16 * nf)
    sites, mod = 0, 0.0
    for _, fin, fout, up in BLOCKS:
        fin, fout = fin * nf, fout * nf
        fmid = min(fin, fout)
        normed = [fin, fmid] + ([fin] if fin != fout else [])
        for c in normed:
            sites += 1
            mod += conv(px, 3, label_nc, hidden) + 2 * conv(px, 3, hidden, c)
        total += conv(px, 3, fin, fmid) + conv(px, 3, fmid, fout)
        if fin != fout:
            total += conv(px, 1, fin, fout)
        if up:
            px *= 4
    total += conv(px, 3, nf, 3) + mod
    return {"spade_sites": float(sites),
            "spade_modulation_gflop_per_image": mod / 1e9,
            "generator_gflop_per_image": total / 1e9}
