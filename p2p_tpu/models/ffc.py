"""The LaMa inpainting generator: a ResNet of fast Fourier convolutions.

Suvorov et al., "Resolution-robust Large Mask Inpainting with Fourier
Convolutions" (WACV 2022, arXiv:2109.07161, sections 2.1 and 3), at the
layout of github.com/advimman/lama ``FFCResNetGenerator`` under
``ffc_resnet_075.yaml`` (as recalled); fast Fourier convolutions are Chi,
Jiang, Mu (NeurIPS 2020). NHWC; BN = BatchNorm (eps 1e-5, the batch's own
biased moments in training), every FFC convolution has no bias and pads
by reflection.

A tensor inside the network is a PAIR ``(x_l, x_g)`` of a local and a
global branch; ``ratio`` is the global branch's share of the channels.

  FFC(x_l, x_g):  y_l = conv_k(x_l; l2l) + conv_k(x_g; g2l)
                  y_g = conv_k(x_l; l2g) + S(x_g)
  S(x_g) = conv1x1(h + F(h); C_g/2 -> C_g),  h = ReLU(BN(conv1x1(x_g;
    C_g -> C_g/2)))                      (the spectral transform; no LFU)
  F(h): Z = rfft2(h) over H and W, orthonormal, ``[H, W/2+1]`` complex;
    real and imaginary parts interleaved as 2 x C_g/2 channels; conv1x1
    (no bias), BN, ReLU; back to complex; irfft2 to ``[H, W]``,
    orthonormal                          (the Fourier unit)
  FFC_BN_ACT = FFC, then BN and ReLU on each branch of its own.
  Block(x) = x + FFC_BN_ACT(FFC_BN_ACT(x)), k3, on both branches.

  G(x): x in [0, 1], 4 channels (the masked image, the mask);
    reflect pad 3, conv k7 4 -> ngf, BN, ReLU (all local);
    three conv k3 stride 2 (reflect pad 1), BN, ReLU: ngf -> 8 ngf, the
    last one's output split ``(1 - ratio, ratio)`` into the pair;
    ``n_blocks`` Blocks; concatenate the pair;
    three ConvTranspose(k3, stride 2, pad 1, output pad 1), BN, ReLU: 8 ngf
    -> ngf; reflect pad 3, conv k7 ngf -> 3 (bias); sigmoid.

The layers with ratio 0 on both sides (the stem and the first two
downsamplings) are plain convolutions, as the source's FFC degenerates to
its ``convl2l`` there; the third downsampling has a local input alone and
writes both branches (``l2l``, ``l2g``). The source's transposed
convolutions carry a bias that the BatchNorm behind them cancels (its
gradient is identically zero); it is left out, as everywhere in this
system (``ModelConfig.legacy_layout``).

In this system images travel in [-1, 1]: the module maps its input to the
authors' [0, 1] at its first layer (the mask channel's -1 / 1 become 0 /
1, a missing pixel's -1 the authors' 0) and its sigmoid back at its last.
In training it returns the predicted image; in evaluation the COMPOSITE
``m * prediction + (1 - m) * input`` (the known pixels are the input's).

The transforms are real matrix products with constant DFT matrices on the
NHWC tensor (:func:`rfft2`, :func:`irfft2`; channels stay on the lanes, no
complex tensor is built and nothing of an FFT library runs): a 32-point
transform is a ``[32, 32]`` matrix, nothing beside the convolutions, where
XLA's ``fft`` expanded into thousands of small ops and layout changes
(PERF.md section 6, PR 42). One path for every extent. Between them the
spectrum is ``[N, H, W/2+1, C, 2]``, (real, imaginary) an axis of its own
through the unit's 1x1 convolution, BatchNorm and ReLU: the STORED
parameters are those of the ``2 C`` interleaved channels above (channel
``2i`` the real part of channel ``i``, ``2i + 1`` its imaginary; the
kernel ``[1, 1, 2C, 2C]``, BatchNorm's vectors of ``2C``), read as
``[C, 2]``, so no pass brings the pairs side by side on the lanes.

Precision: convolutions in the compute dtype; the two transforms are
float32 products at ``Precision.HIGHEST`` (float64 where a test runs the
module in it) with their orthonormal scale folded into a matrix, the 1x1
convolution between them reads real / imaginary channels in the compute
dtype; BatchNorm's moments are float32 (ops/norm.py).

``jax.named_scope``s: ``ffc_local`` (the k3 convolutions of a block's
FFCs and their pads), ``ffc_spectral`` (the spectral transform) and,
inside it, ``ffc_fft`` (both transforms with their casts).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from p2p_tpu.ops.activations import relu_y
from p2p_tpu.ops.conv import ConvLayer, reflect_pad_2d, save_conv_out
from p2p_tpu.ops.norm import BatchNorm

#: stride-2 convolutions before (and transposed ones after) the blocks
N_DOWN = 3
#: what the input's height and width must be multiples of
EXTENT_MULTIPLE = 2 ** N_DOWN
#: the input channel that holds the mask (1 = missing)
MASK_CHANNEL = 3

#: torch's default Conv2d draw, uniform(+-1/sqrt(fan_in)), which the
#: source leaves in place
torch_default_init = nn.initializers.variance_scaling(
    1.0 / 3.0, "fan_in", "uniform")


def split_channels(features: int, ratio: float) -> Tuple[int, int]:
    """(local, global) channels of ``features`` at ``ratio``: the
    source's ``int(features * ratio)`` global, the rest local."""
    g = int(features * ratio)
    return features - g, g


def _conv(features: int, kernel: int, dtype, name: str) -> nn.Conv:
    """A VALID convolution without bias on an input padded already."""
    return nn.Conv(features, (kernel, kernel), padding="VALID",
                   use_bias=False, dtype=dtype,
                   kernel_init=torch_default_init, name=name)


@functools.lru_cache(maxsize=32)
def dft_matrices(h: int, w: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The real DFT matrices of an ``h`` x ``w`` extent, float64, with
    ``p`` / ``q`` = 0 the real and 1 the imaginary part:

    ``along_w[p, l, w] = (cos, -sin)(2 pi l w / W)`` for the ``W/2 + 1``
    columns ``l`` of a real input's spectrum; ``along_h[q, k, h, p]`` the
    block matrix ``[[C, S], [-S, C]]`` of ``exp(-2 pi i k h / H)`` on a
    (real, imaginary) pair, whose transpose is the inverse's; ``weight[l]``
    how often column ``l`` stands in the full spectrum (its mirror ``W - l``
    is its conjugate: 2, but for column 0 and an even ``W``'s Nyquist
    column)."""
    cols = w // 2 + 1
    aw = 2.0 * np.pi * np.outer(np.arange(cols), np.arange(w)) / w
    ah = 2.0 * np.pi * np.outer(np.arange(h), np.arange(h)) / h
    along_w = np.stack([np.cos(aw), -np.sin(aw)])
    along_h = np.empty((2, h, h, 2))
    along_h[0, :, :, 0] = along_h[1, :, :, 1] = np.cos(ah)
    along_h[0, :, :, 1] = np.sin(ah)
    along_h[1, :, :, 0] = -np.sin(ah)
    weight = np.full((cols,), 2.0)
    weight[0] = 1.0
    if w % 2 == 0:
        weight[-1] = 1.0
    return along_w, along_h, weight


def _product(spec: str, matrix: np.ndarray, x):
    """``einsum(spec, matrix, x)`` with a constant matrix in ``x``'s own
    float32 (float64) at ``Precision.HIGHEST``."""
    return jnp.einsum(spec, jnp.asarray(matrix, x.dtype), x,
                      precision=jax.lax.Precision.HIGHEST)


def rfft2(x):
    """``jnp.fft.rfft2(x, axes=(1, 2), norm="ortho")`` of a real
    ``[n, h, w, c]`` as ``[n, h, w/2+1, c, 2]`` (real, imaginary): real
    to complex along W, then complex to complex along H over the (h, real
    / imaginary) pairs, ``c`` minor in both products."""
    _, h, w, _ = x.shape
    along_w, along_h, _ = dft_matrices(h, w)
    return _product("qkhp,nhplc->nklcq", along_h / np.sqrt(h * w),
                    _product("plw,nhwc->nhplc", along_w, x))


def irfft2(z, w: int):
    """``jnp.fft.irfft2(.., s=(h, w), axes=(1, 2), norm="ortho")`` of
    ``[n, h, w/2+1, c, 2]`` (real, imaginary; Hermitian or not) as the
    real ``[n, h, w, c]``: the inverse along H, then complex to real
    along W, where the imaginary parts of column 0 and of the Nyquist
    column meet a sine that is zero, as the library drops them."""
    h = z.shape[1]
    along_w, along_h, weight = dft_matrices(h, w)
    return _product("plw,nhplc->nhwc",
                    along_w * weight[None, :, None] / np.sqrt(h * w),
                    _product("qkhp,nklcq->nhplc", along_h, z))


class _PairConv(nn.Module):
    """The unit's 1x1 convolution (no bias) on ``[N, H, W, C, 2]``, the
    (real, imaginary) pairs an axis of their own: the kernel is stored as
    the ``[1, 1, 2C, 2C]`` of a convolution over interleaved channels
    (``2i`` real, ``2i + 1`` imaginary) and read as ``[C, 2, C, 2]``, so
    the pairs are never brought side by side on the lanes."""

    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, z):
        c = z.shape[-2]
        kernel = self.param("kernel", torch_default_init,
                            (1, 1, 2 * c, 2 * c), jnp.float32)
        dtype = self.dtype or jnp.result_type(z.dtype, kernel.dtype)
        return jnp.einsum("nhwcq,cqdr->nhwdr", z.astype(dtype),
                          kernel.reshape(c, 2, c, 2).astype(dtype))


class FourierUnit(nn.Module):
    """``F(h)`` of the module docstring on ``[N, H, W, C]``. Between the
    transforms the spectrum is ``[N, H, W/2+1, C, 2]``: the parameters
    are those of the interleaved ``2C`` channels (a checkpoint holds
    ``conv/kernel`` ``[1, 1, 2C, 2C]`` and BatchNorm's vectors of ``2C``),
    read as ``[C, 2]``."""

    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, h, train: bool):
        # the transform's own dtype: float32 (float64 where a test runs
        # the module in it)
        wide = jnp.promote_types(h.dtype, jnp.float32)
        with jax.named_scope("ffc_fft"):
            z = rfft2(h.astype(wide)).astype(h.dtype)
        z = save_conv_out(_PairConv(self.dtype, name="conv")(z))
        z = relu_y(BatchNorm(use_running_average=not train, dtype=self.dtype,
                             feature_axes=2, name="bn")(z))
        with jax.named_scope("ffc_fft"):
            return irfft2(z.astype(wide), h.shape[2]).astype(h.dtype)


class SpectralTransform(nn.Module):
    """``S(x_g)``: ``C_g -> features`` through half of ``features``."""

    features: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool):
        with jax.named_scope("ffc_spectral"):
            h = save_conv_out(
                _conv(self.features // 2, 1, self.dtype, "conv1")(x))
            h = relu_y(BatchNorm(use_running_average=not train,
                                 dtype=self.dtype, name="bn1")(h))
            f = FourierUnit(dtype=self.dtype, name="fu")(h, train)
            return save_conv_out(
                _conv(self.features, 1, self.dtype, "conv2")(h + f))


class FFCBNAct(nn.Module):
    """A k3 FFC on a pair at ``ratio`` in and out, then BatchNorm and ReLU
    on each branch."""

    features: int
    ratio: float
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x_l, x_g, train: bool):
        out_l, out_g = split_channels(self.features, self.ratio)
        with jax.named_scope("ffc_local"):
            # one pad a branch serves both convolutions that read it
            p_l, p_g = reflect_pad_2d(x_l, 1), reflect_pad_2d(x_g, 1)
            y_l = save_conv_out(
                _conv(out_l, 3, self.dtype, "l2l")(p_l)
                + _conv(out_l, 3, self.dtype, "g2l")(p_g))
            y_g = _conv(out_g, 3, self.dtype, "l2g")(p_l)
        y_g = save_conv_out(y_g + SpectralTransform(
            out_g, dtype=self.dtype, name="g2g")(x_g, train))
        bn = lambda name: BatchNorm(  # noqa: E731
            use_running_average=not train, dtype=self.dtype, name=name)
        return relu_y(bn("bn_l")(y_l)), relu_y(bn("bn_g")(y_g))


class FFCResnetBlock(nn.Module):
    features: int
    ratio: float
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x_l, x_g, train: bool):
        kw = dict(features=self.features, ratio=self.ratio, dtype=self.dtype)
        y_l, y_g = FFCBNAct(name="conv1", **kw)(x_l, x_g, train)
        y_l, y_g = FFCBNAct(name="conv2", **kw)(y_l, y_g, train)
        return x_l + y_l, x_g + y_g


class LamaGenerator(nn.Module):
    """``G`` of the module docstring; ``x`` is ``[N, H, W, 4]`` in
    [-1, 1] (the masked image, the mask as -1 / 1), H and W multiples of
    :data:`EXTENT_MULTIPLE`."""

    ngf: int = 64
    n_blocks: int = 18
    ratio: float = 0.75
    out_channels: int = 3
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        if x.shape[1] % EXTENT_MULTIPLE or x.shape[2] % EXTENT_MULTIPLE:
            raise ValueError(
                f"the LaMa generator halves its input {N_DOWN} times: "
                f"{x.shape[1]}x{x.shape[2]} is no multiple of "
                f"{EXTENT_MULTIPLE}")
        dt = self.dtype or x.dtype
        bn_relu = lambda y, name: relu_y(BatchNorm(  # noqa: E731
            use_running_average=not train, dtype=self.dtype, name=name)(y))

        y = x.astype(dt) * 0.5 + 0.5
        y = ConvLayer(self.ngf, 7, use_bias=False, dtype=self.dtype,
                      kernel_init=torch_default_init, name="stem")(y)
        y = bn_relu(y, "stem_bn")
        width = self.ngf
        for i in range(N_DOWN):
            width *= 2
            y = ConvLayer(width, 3, stride=2, use_bias=False,
                          dtype=self.dtype, kernel_init=torch_default_init,
                          name=f"down_{i}")(y)
            # the last one writes the pair: one BatchNorm a branch, which
            # on channels side by side is one BatchNorm over them all
            y = bn_relu(y, f"down_{i}_bn")
        c_l, c_g = split_channels(width, self.ratio)
        if not (c_l and c_g):
            raise ValueError(
                f"ffc_ratio {self.ratio} leaves a branch of {width} channels "
                "empty: both the local and the global branch carry some")
        y_l, y_g = y[..., :c_l], y[..., c_l:]
        for i in range(self.n_blocks):
            y_l, y_g = FFCResnetBlock(
                width, self.ratio, dtype=self.dtype,
                name=f"block_{i}")(y_l, y_g, train)
        y = jnp.concatenate([y_l, y_g], axis=-1)
        for i in range(N_DOWN):
            width //= 2
            # ConvTranspose2d(k3, stride 2, padding 1, output_padding 1)
            y = save_conv_out(nn.ConvTranspose(
                width, (3, 3), strides=(2, 2), padding=((1, 2), (1, 2)),
                use_bias=False, dtype=self.dtype,
                kernel_init=torch_default_init, name=f"up_{i}")(y))
            y = bn_relu(y, f"up_{i}_bn")
        y = ConvLayer(self.out_channels, 7, dtype=self.dtype,
                      kernel_init=torch_default_init, name="head")(y)
        pred = (2.0 * jax.nn.sigmoid(y.astype(
            jnp.promote_types(y.dtype, jnp.float32))) - 1.0).astype(y.dtype)
        if train:
            return pred
        mask = (x[..., MASK_CHANNEL:MASK_CHANNEL + 1] > 0).astype(dt)
        return mask * pred + (1 - mask) * x[..., :MASK_CHANNEL].astype(dt)


def ffc_arithmetic(ngf: int, n_blocks: int, ratio: float, h: int, w: int
                   ) -> Dict[str, float]:
    """What the generator does for one ``h`` x ``w`` image, from its
    shapes: the FFC layers (every FFC_BN_ACT of the source: the stem, the
    downsamplings, two a block), how many of them hold a Fourier unit, the
    transforms a training step runs (one forward and one inverse a unit,
    and as many again in the backward), how many of them go by matrix
    products (all: there is one path), the global branch's channels and the
    forward pass's multiply-adds."""
    width = ngf * 2 ** N_DOWN
    c_l, c_g = split_channels(width, ratio)
    hb, wb = h // EXTENT_MULTIPLE, w // EXTENT_MULTIPLE
    ffc = (9 * (c_l * c_l + 2 * c_l * c_g)
           + c_g * (c_g // 2) * 2) * hb * wb
    unit = c_g * c_g * hb * (wb // 2 + 1)
    macs = 2 * n_blocks * (ffc + unit)
    macs += 49 * 4 * ngf * h * w + 49 * ngf * 3 * h * w
    for i in range(N_DOWN):
        cin = ngf * 2 ** i
        # a stride-2 convolution and the transposed one that mirrors it
        macs += 2 * 9 * cin * 2 * cin * (h >> (i + 1)) * (w >> (i + 1))
    units = 2 * n_blocks if c_g else 0
    return {"ffc_layers": float(1 + N_DOWN + 2 * n_blocks),
            "ffc_fourier_units": float(units),
            "ffc_fft_calls_per_step": float(4 * units),
            "ffc_dft_transforms_per_step": float(4 * units),
            "ffc_global_channels": float(c_g),
            "generator_gflop_per_image": 2.0 * macs / 1e9}
