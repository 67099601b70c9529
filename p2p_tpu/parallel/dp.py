"""Data-parallel (and mixed data×spatial) execution of the train step.

The reference is strictly single-device (SURVEY.md §2.4: no DDP/DataParallel
anywhere; bs=1 at train.py:143,177). Here DP is a *sharding annotation*, not
a code path: the same jitted step from ``p2p_tpu.train.step`` runs over any
``Mesh`` — parameters and optimizer state replicated, batches sharded
``P('data', 'spatial', None, None)`` — and XLA/GSPMD inserts the gradient
all-reduces over ICI.

Sync-BatchNorm falls out for free: the step computes batch-stat means over
the *global* (sharded) batch axis inside jit, so GSPMD lowers those
reductions to cross-replica collectives — exactly the ``pmean``-of-stats
semantics ParallelConfig.sync_batchnorm asks for, with no extra code.

Loss semantics vs the reference: per-example losses are means over the
global batch, so gradients match a single-device run on the same global
batch (tested to fp tolerance in tests/test_parallel.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh

from p2p_tpu.core.config import Config
from p2p_tpu.core.mesh import (
    batch_sharding,
    mesh_context,
    replicated,
    video_sharding,
)
from p2p_tpu.train.step import build_train_step


def replicate_state(state: Any, mesh: Mesh) -> Any:
    """Place every leaf of the train state replicated over the mesh."""
    return jax.device_put(state, replicated(mesh))


def shard_batch(batch: Dict[str, jax.Array], mesh: Mesh) -> Dict[str, jax.Array]:
    """Place a host batch with N over data (and H over spatial, T over time
    for 5-D video tensors); multi-process assembly handled by
    :func:`p2p_tpu.data.pipeline.place_global`."""
    from p2p_tpu.data.pipeline import place_global

    img = batch_sharding(mesh)
    vid = video_sharding(mesh)
    return place_global(
        batch, lambda v: vid if getattr(v, "ndim", 4) == 5 else img
    )


def make_parallel_train_step(
    cfg: Config,
    mesh: Mesh,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
    state_sharding: Optional[Any] = None,
):
    """The single-device train step, jitted over ``mesh``.

    Returns ``step(state, batch) -> (state, metrics)`` where ``state`` is
    replicated and ``batch`` is sharded per :func:`shard_batch`. Gradient
    psums, BN stat reductions, and (for spatial>1) conv halo exchanges are
    all GSPMD-inserted.

    ``state_sharding``: optional NamedSharding pytree for the TrainState
    (``parallel.rules.state_target_shardings`` — Megatron TP over
    ``model``, ZeRO moments/EMA over ``fsdp``); defaults to fully
    replicated.
    """
    step = build_train_step(
        cfg, vgg_params, steps_per_epoch, train_dtype, jit=False
    )

    def step_in_mesh(state, batch):
        # mesh visible at trace time: ops needing manual sharding regions
        # (Pallas InstanceNorm) wrap themselves in shard_map over it.
        with mesh_context(mesh):
            return step(state, batch)

    rep = replicated(mesh)
    bsh = batch_sharding(mesh)
    ssh = rep if state_sharding is None else state_sharding
    return jax.jit(
        step_in_mesh,
        in_shardings=(ssh, bsh),
        out_shardings=(ssh, rep),
        donate_argnums=0,
    )


def make_parallel_multi_train_step(
    cfg: Config,
    mesh: Mesh,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
    state_sharding: Optional[Any] = None,
):
    """``build_multi_train_step`` (K steps per dispatch via lax.scan) jitted
    over ``mesh`` with explicit state/batch shardings — the scan-path twin
    of :func:`make_parallel_train_step`, used by the CLI trainer when
    ``scan_steps > 1`` on a TP mesh. Batches carry a leading K axis:
    ``P(None, 'data', 'spatial', None, None)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2p_tpu.core.mesh import BATCH_AXES, SPATIAL_AXIS

    inner = build_train_step(
        cfg, vgg_params, steps_per_epoch, train_dtype, jit=False
    )

    def multi_step(state, batches):
        with mesh_context(mesh):
            return jax.lax.scan(inner, state, batches)

    rep = replicated(mesh)
    stacked_bsh = NamedSharding(
        mesh, P(None, BATCH_AXES, SPATIAL_AXIS, None, None))
    ssh = rep if state_sharding is None else state_sharding
    return jax.jit(
        multi_step,
        in_shardings=(ssh, stacked_bsh),
        out_shardings=(ssh, rep),
        donate_argnums=0,
    )


def make_parallel_eval_step(cfg: Config, mesh: Mesh, train_dtype=None):
    from p2p_tpu.train.step import build_eval_step

    step = build_eval_step(cfg, train_dtype, jit=False)

    def step_in_mesh(state, batch):
        with mesh_context(mesh):
            return step(state, batch)

    rep = replicated(mesh)
    bsh = batch_sharding(mesh)
    return jax.jit(step_in_mesh, in_shardings=(rep, bsh),
                   out_shardings=(bsh, rep))
