"""LPIPS, the perceptual term of the VQGAN lineage (Zhang et al. 2018,
arXiv:1801.03924, as ``taming/modules/losses/lpips.py`` runs it):

    P(x, y) = sum_taps mean_HW( lin_tap . (u(f_tap(x)) - u(f_tap(y)))^2 )

``f_tap``: VGG16 after relu1_2, relu2_2, relu3_3, relu4_3, relu5_3 on
``(image - shift) / scale`` (fixed per-channel constants, images in
[-1, 1]); ``u`` divides a position's channel vector by its L2 norm +
1e-10; ``lin_tap`` is a learned non-negative 1x1 convolution to one
channel with no bias. VGG16 and the five heads are frozen. The value
returned is the mean over the batch.

Weights: no asset in this repo, so VGG16 is fixed-seed random
(:func:`p2p_tpu.models.vgg.load_vgg16_params`) and the heads are drawn
non-negative from a fixed seed: a valid distance for timing and tests,
not the published one. The trunk is ``models/vgg.py``'s, with its
stored-activation convolutions for bf16 images (PR 26).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from p2p_tpu.models.vgg import ARCHS, VGG19Features, load_vgg16_params

_SHIFT = np.array([-0.030, -0.088, -0.188], np.float32)
_SCALE = np.array([0.458, 0.448, 0.450], np.float32)


def load_lpips_params(seed: int = 160) -> Dict[str, Any]:
    """``{"vgg16": <trunk>, "lin": {"lin0": [64], ..}}``: the frozen tree
    :func:`lpips_loss` reads; heads ``|N(0, 1)| / C`` a tap."""
    widths = dict(ARCHS["vgg16"][0])
    key = jax.random.key(seed + 1)
    lin = {}
    for i, tap in enumerate(ARCHS["vgg16"][1]):
        c = widths[tap]
        lin[f"lin{i}"] = jnp.abs(jax.random.normal(
            jax.random.fold_in(key, i), (c,), jnp.float32)) / c
    return {"vgg16": load_vgg16_params(seed=seed), "lin": lin}


@jax.checkpoint
def _tap_distance(fx, fy, lin):
    """One tap's term, in float32; recomputed in the backward so that no
    float32 copy of a tap is kept (the taps themselves are the trunk's
    stored activations)."""
    unit = lambda f: f / (jnp.sqrt(jnp.sum(  # noqa: E731
        jnp.square(f), -1, keepdims=True)) + 1e-10)
    d = jnp.square(unit(fx.astype(jnp.float32))
                   - unit(fy.astype(jnp.float32)))
    return jnp.mean(jnp.sum(d * lin, -1))


def lpips_loss(params: Dict[str, Any], x: jax.Array, y: jax.Array
               ) -> jax.Array:
    """Mean over the batch of ``P(x, y)`` (the target ``y``
    stop-gradiented). bf16 images keep VGG16's activations in bf16, like
    ``vgg_loss``; the taps' normalisation, difference and means are
    float32."""
    store = jnp.bfloat16 if x.dtype == jnp.bfloat16 else None
    model = VGG19Features(arch="vgg16", store_dtype=store)
    scaled = lambda im: ((im.astype(jnp.float32) - _SHIFT)  # noqa: E731
                         / _SCALE).astype(im.dtype)
    taps = lambda im: model.apply({"params": params["vgg16"]},  # noqa: E731
                                  scaled(im))
    total = jnp.zeros((), jnp.float32)
    for i, (fx, fy) in enumerate(zip(taps(x),
                                     taps(jax.lax.stop_gradient(y)))):
        total = total + _tap_distance(fx, jax.lax.stop_gradient(fy),
                                      params["lin"][f"lin{i}"])
    return total
