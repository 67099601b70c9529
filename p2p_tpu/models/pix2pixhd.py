"""pix2pixHD coarse-to-fine generator (BASELINE configs[3]: 1024×512 on
one chip; the paper's 2048×1024 on data=2 × spatial=2, PERF.md section 4).

Global generator G1 (a deeper ResnetGenerator: 4 stride-2 downsamples, 9
blocks, channels capped at 1024) learns at half resolution; a local enhancer
G2 wraps it at full resolution: the input is avg-pool-downsampled for G1,
G1's pre-output features are added into G2's half-res features, 3 residual
blocks and one upsample produce the full-res image. The reference has no HD
path (the capability comes from BASELINE.json, not /root/reference) —
architecture follows the pix2pixHD paper's G, re-expressed with this
framework's reflection-padded resize-conv layers.

Width convention matches the torch lineage: ``ngf`` names the GLOBAL
generator width (paper: 64); the enhancer runs at ``ngf//2``.

TPU-first: InstanceNorm here is the Pallas-fused kernel when the preset
says so (norm='pallas_instance'). The trunk honors ``ParallelConfig.remat``
(off by default — 1024×512 bs=1 fits single-chip HBM and full remat costs
20%; 'conv' keeps conv outputs and recomputes only elementwise chains for
tighter-memory meshes).
"""

from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.models.patchgan import avg_pool_downsample
from p2p_tpu.models.resnet_gen import ResnetBlock, ResnetGenerator
from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer, remat_wrap
from p2p_tpu.ops.norm import make_norm_act
from p2p_tpu.ops.activations import tanh_y


def GlobalGenerator(
    ngf: int = 64,
    out_channels: int = 3,
    n_blocks: int = 9,
    norm: str = "instance",
    return_features: bool = False,
    remat: Union[bool, str] = False,
    int8: bool = False,
    int8_delayed: bool = False,
    legacy_layout: bool = False,
    dtype=None,
    name: Optional[str] = None,
) -> ResnetGenerator:
    """G1: the ResnetGenerator configured as pix2pixHD's global net
    (4 downsamples, channel cap 1024)."""
    return ResnetGenerator(
        ngf=ngf, n_blocks=n_blocks, out_channels=out_channels,
        n_downsampling=4, norm=norm, max_features=1024,
        return_features=return_features, remat=remat, int8=int8,
        int8_delayed=int8_delayed, legacy_layout=legacy_layout, dtype=dtype,
        name=name,
    )


class Pix2PixHDGenerator(nn.Module):
    """G2∘G1: one local enhancer around the global generator."""

    ngf: int = 64              # global width; the enhancer runs at ngf//2
    out_channels: int = 3
    n_blocks_global: int = 9
    n_blocks_local: int = 3
    norm: str = "instance"
    remat: Union[bool, str] = False
    # int8 MXU path for the G1 trunk + local enhancer ResnetBlocks
    int8: bool = False
    int8_delayed: bool = False
    # see UNetGenerator.legacy_layout: conv biases before mean-subtracting
    # norms are exactly dead; default drops them (True = round-2 layout)
    legacy_layout: bool = False
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        # fused conv epilogues for norm='pallas_instance' (ops/norm.py
        # make_norm_act — the same seam the ResNet family uses)
        na = make_norm_act(self.norm, train=train, dtype=self.dtype)
        ub = self.legacy_layout or self.norm == "none"
        ngf_local = self.ngf // 2

        # G1 on the avg-pooled half-res input, pre-output features
        x_half = avg_pool_downsample(x)
        g1_feats = GlobalGenerator(
            ngf=self.ngf, n_blocks=self.n_blocks_global, norm=self.norm,
            return_features=True, remat=self.remat, int8=self.int8, int8_delayed=self.int8_delayed,
            legacy_layout=self.legacy_layout, dtype=self.dtype, name="global",
        )(x_half, train)

        # G2 front end on the full-res input, down to half res
        y = ConvLayer(ngf_local, kernel_size=7, use_bias=ub,
                      dtype=self.dtype)(x)
        y = na(y, act="relu")
        y = ConvLayer(self.ngf, kernel_size=3, stride=2, use_bias=ub,
                      dtype=self.dtype)(y)
        y = na(y, act="relu")

        # fuse + local trunk
        y = y + g1_feats
        block_cls = remat_wrap(ResnetBlock, self.remat)
        for i in range(self.n_blocks_local):
            # explicit name: remat wrapping must not change param paths
            y = block_cls(self.ngf, norm=self.norm, int8=self.int8, int8_delayed=self.int8_delayed,
                          legacy_layout=self.legacy_layout, dtype=self.dtype,
                          name=f"ResnetBlock_{i}")(y, train)

        y = UpsampleConvLayer(ngf_local, kernel_size=3, upsample=2,
                              use_bias=ub, dtype=self.dtype)(y)
        y = na(y, act="relu")
        y = ConvLayer(self.out_channels, kernel_size=7, dtype=self.dtype)(y)
        return tanh_y(y)
