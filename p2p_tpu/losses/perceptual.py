"""VGG19 perceptual loss.

Behavior parity with the reference ``VGGLoss`` (networks.py:18-30): L1
between the five tap activations with weights [1/32, 1/16, 1/8, 1/4, 1],
target features detached. The reference feeds [-1,1] images straight into
VGG with no ImageNet normalization (networks.py:26) — kept as the default
(``imagenet_norm=False``) since it changes the loss scale.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from p2p_tpu.models.vgg import VGG19Features
from p2p_tpu.obs.registry import get_registry

VGG_SLICE_WEIGHTS = (1.0 / 32, 1.0 / 16, 1.0 / 8, 1.0 / 4, 1.0)
#: ``LossConfig.vgg_taps`` -> (the trunk's table in models/vgg.ARCHS, the
#: five taps' weights). "preact" is the ESRGAN lineage's: conv1_2, conv2_2,
#: conv3_4, conv4_4, conv5_4 before the ReLU.
VGG_TAPS = {"relu": ("vgg19", VGG_SLICE_WEIGHTS),
            "preact": ("vgg19_preact", (0.1, 0.1, 1.0, 1.0, 1.0))}


#: the dtypes VGG19's activations can be stored in between its layers
VGG_ACT_DTYPES = ("float32", "bfloat16")


def vgg_loss_traces() -> dict:
    """activation dtype -> ``vgg_loss`` calls traced with it so far in
    this process (``vgg_loss_traces_total{act_dtype=...}``, counted like
    ``ops.conv.conv_form_sites``: a module cannot be handed a run's
    registry)."""
    reg = get_registry()
    return {d: int(reg.counter("vgg_loss_traces_total", act_dtype=d).value)
            for d in VGG_ACT_DTYPES}


def vgg_loss(
    vgg_params: Dict[str, Any],
    x: jax.Array,
    y: jax.Array,
    imagenet_norm: bool = False,
    taps: str = "relu",
) -> jax.Array:
    """Perceptual distance between x and y (target y stop-gradiented);
    ``taps`` picks the trunk's table and the weights (:data:`VGG_TAPS`).

    bf16 images (mixed precision) keep VGG19's activations in bf16, which
    is what its convolutions read of them on the MXU anyway; any other
    ``x`` runs the trunk as ``nn.Conv`` promotes it to the float32
    parameters. The taps' difference and its mean are float32 either way.
    """
    store = jnp.bfloat16 if x.dtype == jnp.bfloat16 else None
    get_registry().counter(
        "vgg_loss_traces_total",
        act_dtype="float32" if store is None else "bfloat16").inc()
    arch, weights = VGG_TAPS[taps]
    model = VGG19Features(imagenet_norm=imagenet_norm, store_dtype=store,
                          arch=arch)
    return tap_distance(
        model.apply({"params": vgg_params}, x),
        model.apply({"params": vgg_params}, jax.lax.stop_gradient(y)),
        weights)


def tap_distance(feats_x, feats_y, weights=VGG_SLICE_WEIGHTS) -> jax.Array:
    """The weighted L1 between two sets of VGG19 taps, in float32 (the
    second set stop-gradiented)."""
    total = jnp.zeros((), jnp.float32)
    for w, fx, fy in zip(weights, feats_x, feats_y):
        fy = jax.lax.stop_gradient(fy)
        total = total + w * jnp.mean(
            jnp.abs(fx.astype(jnp.float32) - fy.astype(jnp.float32))
        )
    return total


def hrf_loss(params: Dict[str, Any], x: jax.Array, y: jax.Array
             ) -> jax.Array:
    """The LaMa lineage's high-receptive-field perceptual distance
    (``ResNetPL``): the SUM over the four stages of the frozen dilated
    ResNet50 (models/resnet_dilated.py) of the mean squared difference of
    its features of ``x`` and ``y`` (``y`` stop-gradiented), images in
    [-1, 1], ImageNet-normalised by the trunk. bf16 images keep the
    trunk's activations in bf16, like ``vgg_loss``; the differences and
    their means are float32. Under the named scope ``loss_hrf``."""
    from p2p_tpu.models.resnet_dilated import ResNet50Dilated

    store = jnp.bfloat16 if x.dtype == jnp.bfloat16 else None
    model = ResNet50Dilated(store_dtype=store)
    with jax.named_scope("loss_hrf"):
        feats_x = model.apply({"params": params}, x)
        feats_y = model.apply({"params": params}, jax.lax.stop_gradient(y))
        total = jnp.zeros((), jnp.float32)
        for fx, fy in zip(feats_x, feats_y):
            total = total + jnp.mean(jnp.square(
                fx.astype(jnp.float32) - fy.astype(jnp.float32)))
        return total
