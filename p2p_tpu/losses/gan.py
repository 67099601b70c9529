"""Adversarial losses.

Behavior parity with the reference ``GANLoss`` (networks.py:808-850):
LSGAN (MSE) default, BCE option; multiscale nested-list predictions use only
the LAST feature per scale and the per-scale losses are SUMMED (not
averaged). The reference's lazily-cached CUDA target tensors (SURVEY Q6)
are replaced by ``jnp.full_like`` — free under XLA fusion and device-neutral.

Also provides hinge loss (standard in modern GAN training; not in the
reference) behind ``mode='hinge'``.
"""

from __future__ import annotations

from typing import List, Sequence, Union

import jax
import jax.numpy as jnp

Preds = Union[Sequence[jax.Array], Sequence[Sequence[jax.Array]]]


def _final_preds(preds: Preds) -> List[jax.Array]:
    if isinstance(preds[0], (list, tuple)):
        return [scale[-1] for scale in preds]
    return [preds[-1]]


def _elementwise(pred: jax.Array, target_is_real: bool, mode: str,
                 for_discriminator: bool) -> jax.Array:
    p = pred.astype(jnp.float32)
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
        for p in _final_preds(preds)
    ]
    total = jnp.sum(jnp.stack(losses))
    return total / len(losses) if scale_mean else total
