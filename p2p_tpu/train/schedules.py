"""Learning-rate schedules — exact reference policies, expressed per-step.

Reference ``get_scheduler`` (networks.py:104-118), stepped once per epoch
(networks.py:122-125):

- ``lambda``  multiplier 1 − max(0, e + epoch_count − niter)/(niter_decay+1)
- ``step``    ×0.1 every ``lr_decay_iters`` epochs
- ``plateau`` ReduceLROnPlateau(min, factor=0.2, threshold=0.01, patience=5)
- ``cosine``  CosineAnnealingLR(T_max=niter, eta_min=0)

Under jit the schedule must be a pure function of the step counter, so
epoch-wise policies take ``steps_per_epoch`` and floor-divide. ``plateau``
is inherently metric-driven, so it lives host-side as
:class:`PlateauController` feeding an ``optax.inject_hyperparams`` scale.
"""

from __future__ import annotations

import math
from typing import Callable

import jax.numpy as jnp

from p2p_tpu.core.config import OptimConfig


def lambda_rule(epoch, epoch_count: int, niter: int, niter_decay: int):
    """The reference's linear-decay multiplier (networks.py:106-109),
    clamped at 0: the reference formula goes NEGATIVE past
    ``niter + niter_decay`` (it never trains that long; a run that does —
    observed via a miscounted steps_per_epoch — flips to gradient ASCENT
    and detonates the loss within tens of steps)."""
    return jnp.maximum(
        0.0,
        1.0 - jnp.maximum(0.0, epoch + epoch_count - niter) / float(
            niter_decay + 1
        ),
    )


def make_schedule(cfg: OptimConfig, steps_per_epoch: int,
                  epoch_count: int = 1) -> Callable:
    """Per-step lr schedule implementing the epoch-wise reference policies.

    ``epoch_count`` is the 1-based epoch label of **step 0** (the reference's
    ``--epoch_count`` flag on a FRESH run). When restoring a checkpoint the
    step counter already encodes every prior epoch, so the caller must pass
    ``epoch_count=1`` — keeping a >1 offset would count those epochs twice
    and a decay-window resume would clamp the LR to 0
    (``Trainer.maybe_resume`` rebuilds the step functions accordingly).
    """
    base = cfg.lr

    def schedule(step):
        epoch = jnp.asarray(step) // steps_per_epoch
        if cfg.lr_policy == "lambda":
            # Only the lambda policy consumes --epoch_count, exactly like
            # the reference (StepLR / CosineAnnealingLR ignore it —
            # networks.py:110-117). On RESUME the caller must renormalize
            # epoch_count against the restored step (Trainer.maybe_resume)
            # or the offset double-counts into LR=0.
            mult = lambda_rule(epoch, epoch_count, cfg.niter, cfg.niter_decay)
        elif cfg.lr_policy == "step":
            mult = 0.1 ** (epoch // cfg.lr_decay_iters)
        elif cfg.lr_policy == "cosine":
            mult = 0.5 * (1.0 + jnp.cos(jnp.pi * epoch / cfg.niter))
        elif cfg.lr_policy in ("plateau", "constant"):
            # plateau: host-controlled via PlateauController
            mult = 1.0
        else:
            raise ValueError(f"unknown lr policy {cfg.lr_policy!r}")
        return base * mult

    return schedule


class PlateauController:
    """Host-side ReduceLROnPlateau with the reference's hyperparameters
    (mode='min', factor=0.2, threshold=0.01 relative, patience=5)."""

    def __init__(self, factor: float = 0.2, threshold: float = 0.01,
                 patience: int = 5):
        self.factor = factor
        self.threshold = threshold
        self.patience = patience
        self.best = math.inf
        self.bad_epochs = 0
        self.scale = 1.0

    def update(self, metric: float) -> float:
        """Feed one epoch's metric; returns the current lr scale."""
        if metric < self.best * (1.0 - self.threshold):
            self.best = metric
            self.bad_epochs = 0
        else:
            self.bad_epochs += 1
            if self.bad_epochs > self.patience:
                self.scale *= self.factor
                self.bad_epochs = 0
        return self.scale
