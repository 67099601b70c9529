"""The U-Net discriminator of Real-ESRGAN (Wang et al. 2021,
arXiv:2107.10833; ``UNetDiscriminatorSN``, which the SwinIR authors train
their real-world models against: github.com/cszn/KAIR ``models/
network_discriminator.py::Discriminator_UNet``).

``SN`` = spectral norm, one power iteration a training forward
(:class:`p2p_tpu.ops.spectral_norm.SpectralConv`, its vectors in the
``spectral`` collection the train step threads); ``lrelu_0.2`` after every
convolution but the last; no normalisation; one scale; the image alone. The
module takes images in this system's [-1, 1] and reads them, as the
authors', in [0, 1]:

    x0 = conv3(y01, 3 -> F)
    x1 = SN conv4_s2(x0, F -> 2F, no bias);  x2 = SN conv4_s2(x1, 2F -> 4F,
        no bias);  x3 = SN conv4_s2(x2, 4F -> 8F, no bias)
    x4 = SN conv3(bilinear_x2(x3), 8F -> 4F, no bias) + x2
    x5 = SN conv3(bilinear_x2(x4), 4F -> 2F, no bias) + x1
    x6 = SN conv3(bilinear_x2(x5), 2F -> F, no bias) + x0
    out = conv3(lrelu(SN conv3(lrelu(SN conv3(x6, F -> F, no bias)), F -> F,
        no bias)), F -> 1)

(the skips add AFTER the activation, as the authors' code does); bilinear
with ``align_corners=False``. Returns ``[[logits]]``, a ``[N, H, W, 1]`` map
in the nested-list form the GAN losses read (one scale, its last feature).
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.ops.activations import leaky_relu_y
from p2p_tpu.ops.conv import ConvLayer
from p2p_tpu.ops.spectral_norm import SpectralConv

#: torch's default Conv2d kernel init: uniform(+-1/sqrt(fan_in))
_KERNEL_INIT = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in",
                                                "uniform")


def bilinear_up2(x: jax.Array) -> jax.Array:
    """``F.interpolate(scale_factor=2, mode="bilinear",
    align_corners=False)`` in NHWC."""
    n, h, w, c = x.shape
    return jax.image.resize(x, (n, 2 * h, 2 * w, c), "bilinear")


class UNetDiscriminatorSN(nn.Module):
    ndf: int = 64
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x) -> List[List[jax.Array]]:
        f, dt = self.ndf, self.dtype

        def sn(y, features, k, stride, name):
            return leaky_relu_y(SpectralConv(
                features, kernel_size=k, stride=stride, padding=1,
                use_bias=False, dtype=dt, kernel_init=_KERNEL_INIT,
                name=name)(y), 0.2)

        plain = lambda features, name: ConvLayer(  # noqa: E731
            features, kernel_size=3, pad_mode="zero", dtype=dt,
            kernel_init=_KERNEL_INIT, name=name)
        x01 = (x.astype(jnp.float32) + 1.0) * 0.5
        x0 = leaky_relu_y(plain(f, "conv0")(
            x01.astype(dt) if dt is not None else x01), 0.2)
        x1 = sn(x0, 2 * f, 4, 2, "conv1")
        x2 = sn(x1, 4 * f, 4, 2, "conv2")
        x3 = sn(x2, 8 * f, 4, 2, "conv3")
        x4 = sn(bilinear_up2(x3), 4 * f, 3, 1, "conv4") + x2
        x5 = sn(bilinear_up2(x4), 2 * f, 3, 1, "conv5") + x1
        x6 = sn(bilinear_up2(x5), f, 3, 1, "conv6") + x0
        out = sn(sn(x6, f, 3, 1, "conv7"), f, 3, 1, "conv8")
        return [[plain(1, "conv9")(out)]]
