"""Multiscale feature-matching loss.

Behavior parity with train.py:344-351: L1 between every intermediate D
activation of fake vs real (all but the final prediction map), weighted
``(4/(n_layers+1)) * (1/num_D) * lambda_feat``, with real features
stop-gradiented. The reference hardcodes Num_D=3 / N_Layers_D=3; here both
come from the prediction structure itself.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def feature_matching_loss(
    pred_fake: Sequence[Sequence[jax.Array]],
    pred_real: Sequence[Sequence[jax.Array]],
    n_layers: int = 3,
    lambda_feat: float = 10.0,
) -> jax.Array:
    num_D = len(pred_fake)
    feat_w = 4.0 / (n_layers + 1)
    d_w = 1.0 / num_D
    total = jnp.zeros((), jnp.float32)
    for scale_f, scale_r in zip(pred_fake, pred_real):
        for f, r in zip(scale_f[:-1], scale_r[:-1]):
            diff = jnp.abs(
                f.astype(jnp.float32) - jax.lax.stop_gradient(r).astype(jnp.float32)
            )
            total = total + d_w * feat_w * jnp.mean(diff) * lambda_feat
    return total


def feature_matching_mse(
    pred_fake: Sequence[Sequence[jax.Array]],
    pred_real: Sequence[Sequence[jax.Array]],
) -> jax.Array:
    """The LaMa lineage's feature matching: the MEAN over every
    intermediate D activation (all but the logits, all scales) of the mean
    squared difference, real features stop-gradiented. Unweighted."""
    terms = []
    for scale_f, scale_r in zip(pred_fake, pred_real):
        for f, r in zip(scale_f[:-1], scale_r[:-1]):
            terms.append(jnp.mean(jnp.square(
                f.astype(jnp.float32)
                - jax.lax.stop_gradient(r).astype(jnp.float32))))
    return jnp.mean(jnp.stack(terms))
