"""VGG19 feature extractor for the perceptual loss.

Behavior parity with /root/reference/networks.py:32-62: the torchvision
VGG19 ``features`` trunk split at indices 2/7/12/21/30, returning the five
activations after relu1_1, relu2_1, relu3_1, relu4_1, relu5_1. The
reference feeds [-1,1] images with NO ImageNet normalization
(networks.py:26); that choice is preserved at the loss level
(LossConfig.vgg_imagenet_norm).

Weights: this environment has no torchvision / no egress, so pretrained
weights load from an ``.npz`` asset when available (path via
``P2P_TPU_VGG19_NPZ`` or ``p2p_tpu/assets/vgg19.npz``); otherwise the
extractor falls back to a FIXED-SEED random init — still a valid (random
projection) perceptual loss for smoke tests, and flagged via
``vgg19_params_source()`` so quality claims are made only with real weights.
``scripts/convert_vgg19.py`` converts torchvision's state-dict when run in an
environment that has it.
"""

from __future__ import annotations

import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from p2p_tpu.ops.conv import save_conv_out
from p2p_tpu.ops.activations import relu_y

# (name, out_channels); 'M' = maxpool. Standard VGG19 trunk through conv5_1.
_CFG = [
    ("conv1_1", 64), ("conv1_2", 64), ("M", 0),
    ("conv2_1", 128), ("conv2_2", 128), ("M", 0),
    ("conv3_1", 256), ("conv3_2", 256), ("conv3_3", 256), ("conv3_4", 256), ("M", 0),
    ("conv4_1", 512), ("conv4_2", 512), ("conv4_3", 512), ("conv4_4", 512), ("M", 0),
    ("conv5_1", 512),
]
# Taps after these convs' relus == torchvision indices 2/7/12/21/30.
_TAPS = ("conv1_1", "conv2_1", "conv3_1", "conv4_1", "conv5_1")

# VGG16 through conv5_3, tapped after relu1_2, relu2_2, relu3_3, relu4_3,
# relu5_3 (torchvision indices 3/8/15/22/29): what LPIPS reads
# (losses/lpips.py).
_CFG16 = [
    ("conv1_1", 64), ("conv1_2", 64), ("M", 0),
    ("conv2_1", 128), ("conv2_2", 128), ("M", 0),
    ("conv3_1", 256), ("conv3_2", 256), ("conv3_3", 256), ("M", 0),
    ("conv4_1", 512), ("conv4_2", 512), ("conv4_3", 512), ("M", 0),
    ("conv5_1", 512), ("conv5_2", 512), ("conv5_3", 512),
]
_TAPS16 = ("conv1_2", "conv2_2", "conv3_3", "conv4_3", "conv5_3")

# VGG19 through conv5_4, tapped at conv1_2, conv2_2, conv3_4, conv4_4,
# conv5_4 BEFORE the ReLU (torchvision indices 2/7/16/25/34): what the
# ESRGAN lineage's perceptual loss reads (losses/perceptual.py "preact").
_CFG19_FULL = _CFG + [("conv5_2", 512), ("conv5_3", 512), ("conv5_4", 512)]
_TAPS19_PREACT = ("conv1_2", "conv2_2", "conv3_4", "conv4_4", "conv5_4")
#: arch -> (layer table, tapped layers)
ARCHS = {"vgg19": (_CFG, _TAPS), "vgg16": (_CFG16, _TAPS16),
         "vgg19_preact": (_CFG19_FULL, _TAPS19_PREACT)}
#: the archs whose taps are the convolution's output before its ReLU
PREACT_ARCHS = frozenset({"vgg19_preact"})

_IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
_IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def _conv3x3(x, w, preferred_element_type=None):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=preferred_element_type)


@jax.custom_vjp
def conv3x3_relu_stored(x, w, b):
    """``relu(conv3x3(x, w) + b)`` stored in ``x.dtype``: the products of
    the operands as the MXU reads them (``x`` and ``w`` in ``x.dtype``),
    their sum, the bias and the ReLU in float32, ONE rounding at the store.

    A float32 ``nn.Conv`` at the default precision computes the same sum
    on the chip and keeps a float32 copy that the next convolution rounds
    to bf16 as it reads it; this stores what is read and nothing wider.
    The backward takes its mask from the stored output (as ``relu_y``) and
    the cotangents in the stored dtype: the input gradient is one
    convolution with a float32 sum inside and one rounding. ``w`` and
    ``b`` are float32 parameters; their gradients (VGG19 is frozen, no
    step asks for them) come out of operands in the stored dtype."""
    z = _conv3x3(x, w.astype(x.dtype), jnp.float32)
    return jnp.maximum(z + b.astype(jnp.float32), 0).astype(x.dtype)


def _stored_fwd(x, w, b):
    y = conv3x3_relu_stored(x, w, b)
    return y, (x, w, b, y)


def _stored_bwd(res, ct):
    x, w, b, y = res
    dz = jnp.where(y > 0, ct, jnp.zeros_like(ct))
    # transposing a convolution that widens its result is not something
    # lax does (operands of two dtypes); the same-dtype one is the same
    # linear map
    dx, dw = jax.vjp(_conv3x3, x, w.astype(x.dtype))[1](dz)
    db = jnp.sum(dz, (0, 1, 2), dtype=jnp.float32)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


conv3x3_relu_stored.defvjp(_stored_fwd, _stored_bwd)


@jax.custom_vjp
def conv3x3_bias_stored(x, w, b):
    """``conv3x3(x, w) + b`` stored in ``x.dtype`` with the float32 sum and
    ONE rounding of :func:`conv3x3_relu_stored`, and no ReLU: a tapped
    layer of a pre-activation table keeps this tensor and hands its ReLU
    (``relu_y``, masked from its own output) to the next layer."""
    z = _conv3x3(x, w.astype(x.dtype), jnp.float32)
    return (z + b.astype(jnp.float32)).astype(x.dtype)


def _bias_stored_fwd(x, w, b):
    return conv3x3_bias_stored(x, w, b), (x, w, b)


def _bias_stored_bwd(res, ct):
    x, w, b = res
    dx, dw = jax.vjp(_conv3x3, x, w.astype(x.dtype))[1](ct)
    db = jnp.sum(ct, (0, 1, 2), dtype=jnp.float32)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


conv3x3_bias_stored.defvjp(_bias_stored_fwd, _bias_stored_bwd)


class _StoredConvRelu(nn.Module):
    """``nn.Conv`` 3x3 + ReLU with ``nn.Conv``'s parameter tree, through
    :func:`conv3x3_relu_stored`."""

    features: int
    # True: the convolution's output before its ReLU (a tapped layer of a
    # pre-activation table)
    preact: bool = False

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (3, 3, x.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        if self.preact:
            return conv3x3_bias_stored(x, kernel, bias)
        return conv3x3_relu_stored(x, kernel, bias)


class VGG19Features(nn.Module):
    """Frozen VGG19 trunk; returns the 5 tap activations (NHWC).

    ``store_dtype`` (the perceptual loss sets it to the dtype of bf16
    images, nothing else sets it) keeps every activation between the
    layers in that dtype, rounded once from the float32 epilogue of its
    convolution (:func:`conv3x3_relu_stored`); ``None`` is the float32
    trunk as ``nn.Conv`` promotes it."""

    imagenet_norm: bool = False
    store_dtype: Optional[jnp.dtype] = None
    # "vgg16": the same trunk under VGG16's layer table and LPIPS's taps
    arch: str = "vgg19"

    @nn.compact
    def __call__(self, x) -> List[jax.Array]:
        cfg, taps = ARCHS[self.arch]
        preact = self.arch in PREACT_ARCHS
        if self.imagenet_norm:
            # incoming images are [-1,1]; map to [0,1] then standardize
            x = (x + 1.0) * 0.5
            x = (x - _IMAGENET_MEAN) / _IMAGENET_STD
        outs = []
        y = x if self.store_dtype is None else x.astype(self.store_dtype)
        for name, ch in cfg:
            if name == "M":
                y = nn.max_pool(y, (2, 2), strides=(2, 2))
                continue
            # a pre-activation table taps the convolution's own output and
            # hands its ReLU to the next layer
            pre = preact and name in taps
            if self.store_dtype is None:
                y = save_conv_out(nn.Conv(
                    ch, kernel_size=(3, 3), padding=1, name=name)(y))
                if not pre:
                    y = relu_y(y)
            else:
                y = _StoredConvRelu(ch, preact=pre, name=name)(y)
            if name in taps:
                outs.append(y)
            if pre:
                y = relu_y(y)
        return outs


_DEFAULT_ASSET = os.path.join(os.path.dirname(__file__), "..", "assets", "vgg19.npz")


def vgg19_npz_path() -> Optional[str]:
    p = os.environ.get("P2P_TPU_VGG19_NPZ", _DEFAULT_ASSET)
    return p if os.path.exists(p) else None


def vgg19_params_source() -> str:
    """'pretrained' if an npz asset is present, else 'random'."""
    return "pretrained" if vgg19_npz_path() else "random"


def load_vgg19_params(dtype=jnp.float32, seed: int = 190,
                      arch: str = "vgg19"):
    """Build the frozen VGG19 param tree (pretrained npz or fixed-seed
    random). ``arch`` "vgg19_preact" is the trunk through conv5_4 (the
    asset, where there is one, has to hold its three further layers).

    ``seed`` selects the random-feature draw when no pretrained asset
    exists — the multi-seed VFID robustness protocol
    (scripts/eval_fid_parity.py --seeds) scores the same predictions
    under several independent extractors; it is ignored when the npz
    asset is present.
    """
    path = vgg19_npz_path()
    model = VGG19Features(arch=arch)
    if path is None:
        dummy = jnp.zeros((1, 64, 64, 3), dtype)
        return model.init(jax.random.key(seed), dummy)["params"]
    data = np.load(path)
    params = {}
    for name, ch in ARCHS[arch][0]:
        if name == "M":
            continue
        kernel = jnp.asarray(data[f"{name}_kernel"], dtype)  # HWIO
        bias = jnp.asarray(data[f"{name}_bias"], dtype)
        assert kernel.shape[-1] == ch, (name, kernel.shape)
        params[name] = {"kernel": kernel, "bias": bias}
    return params


def load_vgg16_params(dtype=jnp.float32, seed: int = 160):
    """The frozen VGG16 tree LPIPS reads: fixed-seed random, as VGG19's
    is where no asset exists (this repo ships no VGG16 asset; speed does
    not depend on the values)."""
    dummy = jnp.zeros((1, 64, 64, 3), dtype)
    return VGG19Features(arch="vgg16").init(jax.random.key(seed),
                                            dummy)["params"]


def vgg_gflop_per_image(arch: str, h: int, w: int) -> float:
    """One forward of the trunk on one ``h`` x ``w`` image, 2 x
    multiply-adds, in GFLOP."""
    total, c = 0.0, 3
    for name, ch in ARCHS[arch][0]:
        if name == "M":
            h, w = h // 2, w // 2
            continue
        total += 2.0 * h * w * 9 * c * ch
        c = ch
    return total / 1e9
