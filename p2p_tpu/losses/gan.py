"""Adversarial losses.

Behavior parity with the reference ``GANLoss`` (networks.py:808-850):
LSGAN (MSE) default, BCE option; multiscale nested-list predictions use only
the LAST feature per scale and the per-scale losses are SUMMED (not
averaged). The reference's lazily-cached CUDA target tensors (SURVEY Q6)
are replaced by ``jnp.full_like`` — free under XLA fusion and device-neutral.

Also provides hinge loss (standard in modern GAN training; not in the
reference) behind ``mode='hinge'``, and the non-saturating logistic loss
(``mode='nonsaturating'``: ``softplus(-p)`` towards real, ``softplus(p)``
towards fake) with an optional per-pixel target map, with the R1 gradient
penalty beside it (:func:`r1_penalty`): the LaMa lineage's
``NonSaturatingWithR1``.
"""

from __future__ import annotations

from typing import List, Sequence, Union

import jax
import jax.numpy as jnp

Preds = Union[Sequence[jax.Array], Sequence[Sequence[jax.Array]]]


def final_preds(preds: Preds) -> List[jax.Array]:
    """The logits of each scale: the last entry of a scale's features."""
    if isinstance(preds[0], (list, tuple)):
        return [scale[-1] for scale in preds]
    return [preds[-1]]


def nonsaturating(pred: jax.Array, target_is_real) -> jax.Array:
    """The mean of ``softplus(-p)`` (towards real) or ``softplus(p)``
    (towards fake) over the logits. ``target_is_real`` may be a per-pixel
    map in [0, 1] of the logits' shape: each logit is then pushed towards
    real with that weight and towards fake with the rest (the LaMa
    lineage's ``mask_as_fake_target``: the known pixels of a generated
    image count as real)."""
    p = pred.astype(jnp.float32)
    if isinstance(target_is_real, bool):
        per = jax.nn.softplus(-p if target_is_real else p)
    else:
        t = target_is_real.astype(jnp.float32)
        per = t * jax.nn.softplus(-p) + (1.0 - t) * jax.nn.softplus(p)
    return jnp.mean(per)


def resize_mask_nearest(mask: jax.Array, hw) -> jax.Array:
    """``[N, H, W, 1]`` to ``[N, h, w, 1]`` as ``F.interpolate(mode=
    "nearest")`` picks: output pixel i reads input pixel floor(i * H /
    h)."""
    h, w = hw
    rows = (jnp.arange(h) * mask.shape[1]) // h
    cols = (jnp.arange(w) * mask.shape[2]) // w
    return mask[:, rows][:, :, cols]


def r1_penalty(logits_sum_fn, x: jax.Array, pixel_range: float = 2.0):
    """``(R1, aux)``: the batch mean of ``|grad_x logits_sum_fn(x)|^2``
    (Mescheder et al. 2018), in float32, and what the forward hands back
    beside its sum. ``logits_sum_fn(x) -> (sum of D's logits, aux)``; the
    same forward serves the real term of D's loss through ``aux``. The
    gradient is taken per unit of an image in [0, 1] whatever range ``x``
    spans (``pixel_range`` 2 for [-1, 1]: d/dx01 = 2 d/dx11), so a
    published coefficient keeps its meaning. Differentiating the result
    with respect to D's parameters is second order: every layer under
    ``logits_sum_fn`` has to have a backward that can be differentiated."""
    grad, aux = jax.grad(logits_sum_fn, has_aux=True)(
        x.astype(jnp.float32))
    per_image = jnp.sum(jnp.square(grad), axis=tuple(range(1, grad.ndim)))
    return (pixel_range ** 2) * jnp.mean(per_image), aux


def _elementwise(pred: jax.Array, target_is_real: bool, mode: str,
                 for_discriminator: bool) -> jax.Array:
    p = pred.astype(jnp.float32)
    if mode == "nonsaturating":
        return nonsaturating(p, target_is_real)
    if mode == "lsgan":
        target = jnp.full_like(p, 1.0 if target_is_real else 0.0)
        return jnp.mean((p - target) ** 2)
    if mode == "vanilla":
        # BCE-with-logits (the reference applies BCE after an explicit
        # sigmoid stage; fused here for numerical stability).
        target = jnp.full_like(p, 1.0 if target_is_real else 0.0)
        return jnp.mean(
            jnp.maximum(p, 0) - p * target + jnp.log1p(jnp.exp(-jnp.abs(p)))
        )
    if mode == "hinge":
        if for_discriminator:
            if target_is_real:
                return jnp.mean(jax.nn.relu(1.0 - p))
            return jnp.mean(jax.nn.relu(1.0 + p))
        return -jnp.mean(p)
    raise ValueError(f"unknown gan mode {mode!r}")


def gan_loss(preds: Preds, target_is_real: bool, mode: str = "lsgan",
             for_discriminator: bool = True,
             scale_mean: bool = False) -> jax.Array:
    """Sum of per-scale losses on the final prediction map of each scale;
    their mean with ``scale_mean`` (``LossConfig.gan_scale_mean``, the
    SPADE lineage)."""
    losses = [
        _elementwise(p, target_is_real, mode, for_discriminator)
        for p in final_preds(preds)
    ]
    total = jnp.sum(jnp.stack(losses))
    return total / len(losses) if scale_mean else total
