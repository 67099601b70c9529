"""A frozen DILATED ResNet50 trunk: the perceptual network of the LaMa
lineage's high-receptive-field loss (``ResNetPL``; losses/perceptual.
hrf_loss).

The ADE20k segmentation encoder ``resnet50dilated`` of github.com/CSAILVision/
semantic-segmentation-pytorch as the LaMa authors load it (as recalled): a
deep stem (conv3 stride 2 3 -> 64, conv3 64 -> 64, conv3 64 -> 128, each
with BatchNorm and ReLU; max pool 3 stride 2 pad 1), then four stages of
bottleneck blocks (3, 4, 6, 3; planes 64, 128, 256, 512; outputs 256, 512,
1024, 2048), the stride on a block's k3 convolution. ``dilate_scale`` 8:
stages 3 and 4 keep stage 2's extent; their stride-2 convolutions run at
stride 1 (the k3 one with half the stage's dilation), every other k3
convolution of stage 3 with dilation 2, of stage 4 with dilation 4
(``_nostride_dilate``). A 256x256 image leaves the stages at 64x64x256,
32x32x512, 32x32x1024 and 32x32x2048; all four are returned.

BatchNorm is FROZEN: each is the per-channel affine of its stored
statistics, so rows do not couple and nothing is threaded. No convolution
has a bias.

Weights: no asset in this repo, so the tree is fixed-seed random
(:func:`load_resnet50_dilated_params`), as VGG19's is: He-normal kernels,
identity statistics, the last BatchNorm of a block at scale 0.25 so that 16
residual sums stay within a few units. A valid distance for timing and
tests, not the published one; speed does not depend on the values.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from p2p_tpu.ops.activations import relu_y
from p2p_tpu.ops.conv import save_conv_out

#: (blocks, planes, stride, dilation of the stage as ``_nostride_dilate``
#: is handed it; 1 = the stage keeps its stride)
STAGES = ((3, 64, 1, 1), (4, 128, 2, 1), (6, 256, 2, 2), (3, 512, 2, 4))
EXPANSION = 4
BN_EPS = 1e-5

_IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
_IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def block_geometry(stride: int, dilate: int, first: bool):
    """(stride, dilation) of a block's k3 convolution (its shortcut
    convolution, where it has one, takes the same stride), after
    ``_nostride_dilate``: in a dilated stage the first block's stride goes
    and its k3 convolution takes half the stage's dilation, every other
    one the whole."""
    if dilate == 1:
        return (stride if first else 1), 1
    return 1, (dilate // 2 if first else dilate)


class _FrozenBN(nn.Module):
    """BatchNorm in evaluation as one affine: ``x * a + b`` with ``a =
    scale * rsqrt(var + eps)``, ``b = bias - mean * a``."""

    scale_init: float = 1.0

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        const = nn.initializers.constant
        scale = self.param("scale", const(self.scale_init), (c,), jnp.float32)
        bias = self.param("bias", const(0.0), (c,), jnp.float32)
        mean = self.param("mean", const(0.0), (c,), jnp.float32)
        var = self.param("var", const(1.0), (c,), jnp.float32)
        a = scale * jax.lax.rsqrt(var + BN_EPS)
        b = bias - mean * a
        return x * a.astype(x.dtype) + b.astype(x.dtype)


def _conv(features: int, kernel: int, stride: int, dilation: int, dtype,
          name: str):
    pad = dilation * (kernel // 2)
    return nn.Conv(features, (kernel, kernel), strides=(stride, stride),
                   padding=((pad, pad), (pad, pad)),
                   kernel_dilation=(dilation, dilation), use_bias=False,
                   dtype=dtype, kernel_init=nn.initializers.he_normal(),
                   name=name)


class _Bottleneck(nn.Module):
    planes: int
    stride: int
    dilation: int
    shortcut_stride: Optional[int]   # None: the identity
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        dt = self.dtype
        y = relu_y(_FrozenBN(name="bn1")(save_conv_out(
            _conv(self.planes, 1, 1, 1, dt, "conv1")(x))))
        y = relu_y(_FrozenBN(name="bn2")(save_conv_out(
            _conv(self.planes, 3, self.stride, self.dilation, dt,
                  "conv2")(y))))
        y = _FrozenBN(scale_init=0.25, name="bn3")(save_conv_out(
            _conv(self.planes * EXPANSION, 1, 1, 1, dt, "conv3")(y)))
        if self.shortcut_stride is not None:
            x = _FrozenBN(name="downsample_bn")(save_conv_out(
                _conv(self.planes * EXPANSION, 1, self.shortcut_stride, 1,
                      dt, "downsample")(x)))
        return relu_y(y + x)


class ResNet50Dilated(nn.Module):
    """The four stages' outputs for ``x`` in [-1, 1] (mapped to [0, 1]
    and ImageNet-normalised here). ``store_dtype``: the dtype activations
    are kept in (the loss sets bfloat16 for bfloat16 images); None is
    float32."""

    store_dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x) -> List[jax.Array]:
        dt = self.store_dtype
        x = ((x.astype(jnp.float32) + 1.0) * 0.5 - _IMAGENET_MEAN
             ) / _IMAGENET_STD
        y = x if dt is None else x.astype(dt)
        for i, (features, stride) in enumerate(((64, 2), (64, 1), (128, 1))):
            y = relu_y(_FrozenBN(name=f"stem_bn{i + 1}")(save_conv_out(
                _conv(features, 3, stride, 1, dt, f"stem_conv{i + 1}")(y))))
        y = nn.max_pool(y, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        outs = []
        width = y.shape[-1]
        for s, (blocks, planes, stride, dilate) in enumerate(STAGES):
            for b in range(blocks):
                k3_stride, k3_dilation = block_geometry(
                    stride, dilate, b == 0)
                shortcut = (k3_stride if b == 0
                            and (stride != 1 or width != planes * EXPANSION)
                            else None)
                y = _Bottleneck(planes, k3_stride, k3_dilation, shortcut,
                                dtype=dt, name=f"layer{s + 1}_{b}")(y)
                width = planes * EXPANSION
            outs.append(y)
        return outs


def load_resnet50_dilated_params(seed: int = 50):
    """The frozen tree :func:`p2p_tpu.losses.perceptual.hrf_loss` reads:
    fixed-seed random (module docstring)."""
    dummy = jnp.zeros((1, 32, 32, 3), jnp.float32)
    return jax.jit(ResNet50Dilated().init)(jax.random.key(seed),
                                           dummy)["params"]


def resnet50_dilated_gflop_per_image(h: int, w: int) -> float:
    """One forward of the trunk on one ``h`` x ``w`` image, 2 x
    multiply-adds, in GFLOP."""
    macs, c = 0.0, 3
    h, w = h // 2, w // 2
    for features in (64, 64, 128):
        macs += 9.0 * c * features * h * w
        c = features
    h, w = h // 2, w // 2
    for blocks, planes, stride, dilate in STAGES:
        for b in range(blocks):
            s, _ = block_geometry(stride, dilate, b == 0)
            out = planes * EXPANSION
            macs += c * planes * h * w            # conv1, before the stride
            h, w = h // s, w // s
            macs += (9.0 * planes * planes + planes * out) * h * w
            if b == 0:
                macs += c * out * h * w           # the shortcut
            c = out
    return 2.0 * macs / 1e9
