"""TrainState — the single pytree holding everything the jitted step threads.

The reference scatters training state across three torch modules (params +
BN running stats + spectral u/v buffers mutated in-place), three Adam
optimizers and three schedulers, then loses most of it at checkpoint time
(SURVEY Q4). Here it is ONE pytree: save it, restore it, shard it, and the
step function is pure state-in/state-out — Q4/Q5 are unrepresentable.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from p2p_tpu.core.config import Config
from p2p_tpu.models.registry import (
    define_C,
    define_D,
    define_G,
    init_variables,
)


class TrainState(struct.PyTreeNode):
    step: jax.Array
    # Host-controlled LR multiplier (the 'plateau' policy's knob; 1.0
    # otherwise). Applied to every optimizer update inside the step.
    lr_scale: jax.Array
    # generator
    params_g: Any
    batch_stats_g: Any
    opt_g: optax.OptState
    # discriminator
    params_d: Any
    spectral_d: Any
    opt_d: optax.OptState
    # compression pre-filter (None-filled when disabled)
    params_c: Any
    batch_stats_c: Any
    opt_c: Optional[optax.OptState]
    # device-side historical-fake pool (TrainConfig.pool_size > 0);
    # None keeps the pytree structure unchanged when disabled
    pool: Optional[jax.Array] = None
    pool_n: Optional[jax.Array] = None
    # delayed int8 activation scales ('quant' collections, ops/int8.py).
    # None when int8_delayed is off — None flattens to an empty subtree,
    # so pre-round-3 checkpoints keep restoring bit-for-bit.
    quant_g: Any = None
    quant_d: Any = None
    quant_c: Any = None
    # Pipeline parallelism (parallel/pp.py pp_split_state): the generator
    # trunk's stacked [S, B, ...] stage variables sharded over the `pipe`
    # mesh axis, with their own optimizer state. None on every non-PP
    # path — None flattens to an empty subtree, so existing checkpoints
    # keep restoring bit-for-bit.
    pp_stages: Any = None
    opt_s: Optional[optax.OptState] = None
    # EMA generator params (HealthConfig.ema_decay — the ProGAN-lineage
    # stabilization lever): updated in-step, used by eval/serve when
    # present. None when EMA is off — None flattens to an empty subtree,
    # so pre-round-6 checkpoints keep restoring bit-for-bit.
    ema_g: Any = None
    # power-iteration vectors of a generator that carries spectral norm
    # (models/spade.py), threaded like spectral_d: one iteration a
    # training forward. None for every other generator (an empty subtree:
    # their checkpoints and their step are what they were).
    spectral_g: Any = None
    # running statistics of a BatchNorm discriminator (ModelConfig.norm_d
    # "batch"), threaded like spectral_d: two updates a step (fake call,
    # real call). None for every other discriminator (an empty subtree).
    batch_stats_d: Any = None
    # a seed as DATA (a uint32 scalar drawn from the init's rng, so it
    # follows ``--seed``), for a step that draws noise
    # (``ModelConfig.use_dropout``: U-Net dropout, stochastic depth): the
    # step folds it with the step counter, so neither the compiled step nor
    # the compiled init holds ``--seed`` as a constant and one cache entry
    # of each serves every seed. None where the step draws none (an empty
    # subtree: those checkpoints and steps are what they were).
    noise_seed: Any = None


class InferState(struct.PyTreeNode):
    """The serving-side state: ONLY what the generator eval path reads.

    A full :class:`TrainState` carries the discriminator, three Adam
    optimizers (2× params each) and the fake pool — none of which inference
    touches. Serving restores THIS subtree straight from a full-TrainState
    checkpoint (:meth:`p2p_tpu.train.checkpoint.CheckpointManager.
    restore_subtree` reads only these arrays from disk), so building the
    engine never materializes D or moments, and no --ndf/--pool_size
    template-rebuild knobs are needed to address a checkpoint.
    """

    step: jax.Array
    params_g: Any
    batch_stats_g: Any
    # compression pre-filter (None-filled when the preset has none)
    params_c: Any = None
    batch_stats_c: Any = None
    # delayed-int8 stored activation scales; in eval mode the 'quant'
    # collection is read-only, so these act as FROZEN inference scales
    quant_g: Any = None
    # net_c's stored scales (ModelConfig.int8_compression) — frozen at
    # serve time exactly like quant_g; None when the preset has no
    # quantized compression net (empty subtree, restore-compatible)
    quant_c: Any = None
    # EMA generator params, restored when the checkpoint carries them
    # (HealthConfig.ema_decay) — the serving engine swaps them in for
    # params_g (ProGAN-lineage: serve the smoothed generator)
    ema_g: Any = None
    # a spectrally normalised generator's vectors (models/spade.py): in
    # eval the collection is read-only, the weight served is W / sigma(u)
    spectral_g: Any = None


# State init runs as ONE jitted program per (config, dtype). Run eagerly,
# flax's ``module.init`` dispatches the whole forward op by op: on the
# chip that was ~400 tiny XLA compiles per trainer (~0.3-0.6 s each —
# two to four minutes of a cold start, found on bring-up, PR 21). The
# jitted builders are memoised so repeated inits of one config (tests,
# hot-swap templates) reuse the executable.


@functools.lru_cache(maxsize=16)
def _jitted_infer_init(cfg: Config, train_dtype):
    return jax.jit(functools.partial(
        _init_infer_state, cfg, train_dtype=train_dtype))


@functools.lru_cache(maxsize=16)
def _jitted_train_init(cfg: Config, steps_per_epoch: int, train_dtype):
    return jax.jit(functools.partial(
        _init_train_state, cfg, steps_per_epoch=steps_per_epoch,
        train_dtype=train_dtype))


def create_infer_state(
    cfg: Config,
    rng: jax.Array,
    sample_batch: Dict[str, jax.Array],
    train_dtype=None,
) -> InferState:
    """Generator(+compression-net)-only template — the abstract tree
    ``restore_subtree`` restores into. Initializes ONLY G (and C when the
    preset has one): no discriminator, no optimizer state, so the template
    itself is ~1/5 the size of a ``create_train_state`` template and needs
    no D hyperparameters (ndf) or pool sizing to match the checkpoint."""
    return _jitted_infer_init(cfg, train_dtype)(
        rng, {"input": sample_batch["input"]})


def _init_infer_state(cfg, rng, sample_batch, train_dtype=None):
    g = define_G(cfg.model, dtype=train_dtype, remat=cfg.parallel.remat)
    c = (define_C(cfg.model, dtype=train_dtype)
         if cfg.model.use_compression_net else None)
    kg, _, kc = jax.random.split(rng, 3)
    from p2p_tpu.utils.images import ingest_input

    x = ingest_input(jnp.asarray(sample_batch["input"]), cfg.model)
    vg = init_variables(g, kg, x, cfg.model.init_type, cfg.model.init_gain,
                        train=False)
    params_c = batch_stats_c = quant_c = None
    delayed = cfg.model.int8_delayed
    if c is not None:
        vc = init_variables(c, kc, x, cfg.model.init_type, cfg.model.init_gain,
                            train=False)
        params_c = vc["params"]
        batch_stats_c = vc.get("batch_stats", {})
        if delayed and cfg.model.int8_compression:
            quant_c = vc.get("quant", {})
    return InferState(
        step=jnp.zeros((), jnp.int32),
        params_g=vg["params"],
        batch_stats_g=vg.get("batch_stats", {}),
        params_c=params_c,
        batch_stats_c=batch_stats_c,
        quant_g=vg.get("quant", {}) if delayed else None,
        quant_c=quant_c,
        # with EMA on, the template names ema_g so restore_subtree reads
        # the smoothed weights from disk too (same tree as params_g)
        ema_g=(jax.tree_util.tree_map(jnp.copy, vg["params"])
               if cfg.health.ema_decay is not None else None),
        spectral_g=vg.get("spectral"),
    )


def infer_state_from_train(state: "TrainState") -> InferState:
    """Slice the serving subtree out of a live/full TrainState (the
    reference point ``restore_subtree`` is pinned bitwise-equal to)."""
    return InferState(
        step=state.step,
        params_g=state.params_g,
        batch_stats_g=state.batch_stats_g,
        params_c=state.params_c,
        batch_stats_c=state.batch_stats_c,
        quant_g=state.quant_g,
        quant_c=state.quant_c,
        ema_g=state.ema_g,
        spectral_g=state.spectral_g,
    )


def tree_bytes(tree: Any) -> int:
    """Total materialized array bytes across a pytree — the host/device
    memory pin for params-only vs full-state restore."""
    import math

    return sum(
        math.prod(getattr(leaf, "shape", ()) or (1,))
        * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def _zero_nonfinite() -> optax.GradientTransformation:
    """Replace non-finite (inf/NaN) gradient leaves' bad entries with 0,
    so a single blown-up sample is dropped rather than poisoning the
    Adam moments forever."""

    def update(updates, state, params=None):
        del params
        updates = jax.tree_util.tree_map(
            lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g)),
            updates,
        )
        return updates, state

    return optax.GradientTransformation(
        lambda params: optax.EmptyState(), update
    )


def count_nonfinite(tree: Any) -> jax.Array:
    """Total number of non-finite (inf/NaN) entries across a gradient
    pytree — the observability hook for ``_zero_nonfinite``: the guard
    silently drops bad entries, so the step surfaces this count in its
    metrics (``nonfinite_g``/``nonfinite_d``) whenever ``grad_clip > 0``;
    a sustained non-zero value is a diverging loss the guard is masking."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.int32)
    return sum(
        jnp.sum(~jnp.isfinite(g)).astype(jnp.int32) for g in leaves
    )


def losses_finite(*losses) -> jax.Array:
    """Scalar bool: every loss is finite — the in-jit skip guard's verdict
    (recovery-ladder rung 1, resilience/health.py). Checked on the LOSS
    scalars, not the gradient trees: the losses already reduce every
    forward activation, so a blown-up batch surfaces here without paying
    a separate full-gradient reduction pass on the healthy path."""
    ok = jnp.isfinite(losses[0])
    for l in losses[1:]:
        ok = ok & jnp.isfinite(l)
    return ok


def health_select(ok: jax.Array, new_tree: Any, old_tree: Any) -> Any:
    """Per-leaf ``where(ok, new, old)`` over matching pytrees — the skip
    guard's state gate. Each select fuses into the kernel that produced
    the ``new`` leaf (the old leaf was already read to compute it), so
    the guard adds no extra HBM pass on the healthy path."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new_tree, old_tree)


def zero_if_unhealthy(ok: jax.Array, grads: Any) -> Any:
    """``where(ok, g, 0)`` per gradient leaf. Uses where, NOT ``g * ok``:
    with non-finite gradients NaN·0 = NaN and the poison would reach the
    optimizer moments anyway."""
    return jax.tree_util.tree_map(
        lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)


def ema_update(ema: Any, params: Any, decay: float) -> Any:
    """``ema·d + params·(1−d)`` per leaf in the EMA's own dtype. d=0 makes
    the EMA track params EXACTLY (0·e + 1·p = p bitwise — the parity-pin
    mode); d→1 is the ProGAN-lineage smoothing."""
    d = float(decay)
    return jax.tree_util.tree_map(
        lambda e, p: (e * jnp.asarray(d, e.dtype)
                      + p.astype(e.dtype) * jnp.asarray(1.0 - d, e.dtype)),
        ema, params)


def scale_by_adam_lp(b1: float, b2: float, eps: float,
                     moment_dtype) -> optax.GradientTransformation:
    """Adam whose BOTH moments are STORED in ``moment_dtype`` (bf16 on the
    bs=1 path) while all arithmetic runs in f32.

    ``optax.adam(mu_dtype=...)`` casts only the first moment; the round-4
    bs=1 budget shows the binding constraint is per-step parameter+moment
    HBM traffic (≈2.0–2.3 ms of a 4.91 ms step), and nu is half of the
    moment share — so both get the treatment. The f32 compute keeps the
    bias correction and rsqrt well-conditioned; only the stored state
    rounds to bf16 (relative step-size error ~2⁻⁸, far below GAN training
    noise — pinned against f32 Adam in tests/test_train.py)."""
    mdt = jnp.dtype(moment_dtype)

    def init(params):
        z = lambda p: jnp.zeros_like(p, dtype=mdt)  # noqa: E731
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(z, params),
            nu=jax.tree_util.tree_map(z, params),
        )

    def update(updates, state, params=None):
        del params
        f32 = jnp.float32
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m.astype(f32) + (1 - b1) * g.astype(f32),
            state.mu, updates)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v.astype(f32)
            + (1 - b2) * jnp.square(g.astype(f32)),
            state.nu, updates)
        count = optax.safe_int32_increment(state.count)
        bc1 = 1 - b1 ** count.astype(f32)
        bc2 = 1 - b2 ** count.astype(f32)
        out = jax.tree_util.tree_map(
            lambda m, v, g: (
                (m / bc1) / (jnp.sqrt(v / bc2) + eps)).astype(g.dtype),
            mu, nu, updates)
        cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x.astype(mdt), t)
        return out, optax.ScaleByAdamState(
            count=count, mu=cast(mu), nu=cast(nu))

    return optax.GradientTransformation(init, update)


def make_optimizers(cfg: Config, steps_per_epoch: int):
    """Three Adam optimizers (G, D, C) with the reference hyperparameters
    (lr=2e-4, β=(0.5, 0.999) — train.py:241-243) on the configured
    schedule; D's runs at ``OptimConfig.lr_d`` where that is set (TTUR).

    ``OptimConfig.grad_clip > 0`` prepends global-norm clipping — off by
    default (the reference has none), but the practical guard against
    per-sample-norm gradient blowups: a near-constant image makes EVERY
    InstanceNorm in its sample amplify backward cotangents by
    rsqrt(eps) ≈ 316, and ~20 stacked norms overflow f32 (inf) in one
    step. torch's InstanceNorm2d has the identical failure math.
    """
    from p2p_tpu.train.schedules import make_schedule

    def make_one(lr=None):
        optim = (cfg.optim if lr is None
                 else dataclasses.replace(cfg.optim, lr=lr))
        sched = make_schedule(optim, steps_per_epoch, cfg.train.epoch_count)
        clip = cfg.optim.grad_clip

        def inner(learning_rate):
            if cfg.optim.moment_dtype:
                # bf16-stored moments (OptimConfig.moment_dtype): same
                # update math in f32, half the optimizer-state traffic
                adam = optax.chain(
                    scale_by_adam_lp(cfg.optim.beta1, cfg.optim.beta2,
                                     1e-8, cfg.optim.moment_dtype),
                    optax.scale_by_learning_rate(learning_rate),
                )
            else:
                adam = optax.adam(
                    learning_rate, b1=cfg.optim.beta1, b2=cfg.optim.beta2
                )
            if clip > 0:
                # Non-finite grads must be zeroed BEFORE the clip: with
                # an inf gradient clip_by_global_norm scales by
                # max_norm/inf = 0 and inf·0 = NaN updates — the exact
                # blowup this guard exists for (optax.zero_nans only
                # handles NaN, not inf). Built INSIDE inject_hyperparams
                # so the top-level opt state keeps .hyperparams
                # (Trainer.current_lr, checkpoint layout).
                return optax.chain(
                    _zero_nonfinite(),
                    optax.clip_by_global_norm(clip),
                    adam,
                )
            return adam

        return optax.inject_hyperparams(inner)(learning_rate=sched)

    return make_one(), make_one(cfg.optim.lr_d), make_one()


def build_models(cfg: Config, train_dtype=None):
    g = define_G(cfg.model, dtype=train_dtype, remat=cfg.parallel.remat)
    d = define_D(cfg.model, dtype=train_dtype)
    c = define_C(cfg.model, dtype=train_dtype) if cfg.model.use_compression_net else None
    return g, d, c


def create_train_state(
    cfg: Config,
    rng: jax.Array,
    sample_batch: Dict[str, jax.Array],
    steps_per_epoch: int = 1,
    train_dtype=None,
) -> TrainState:
    return _jitted_train_init(cfg, steps_per_epoch, train_dtype)(
        rng, {k: sample_batch[k] for k in ("input", "target")})


def _init_train_state(cfg, rng, sample_batch, steps_per_epoch=1,
                      train_dtype=None):
    g, d, c = build_models(cfg, train_dtype)
    opt_g, opt_d, opt_c = make_optimizers(cfg, steps_per_epoch)

    kg, kd, kc = jax.random.split(rng, 3)
    from p2p_tpu.utils.images import ingest, ingest_input

    # uint8 samples (DataConfig.uint8_pipeline) normalize to f32 here so
    # shape/dtype inference at init matches what the step's ingest feeds
    # (a label map one-hots: D's stem is conditioning + image channels)
    x = ingest_input(jnp.asarray(sample_batch["input"]), cfg.model)
    pair = ingest(jnp.asarray(sample_batch["target"]))
    if cfg.model.d_conditional:
        pair = jnp.concatenate([x, pair], axis=-1)

    vg = init_variables(g, kg, x, cfg.model.init_type, cfg.model.init_gain,
                        train=False)
    vd = init_variables(d, kd, pair, cfg.model.init_type, cfg.model.init_gain)

    params_c = batch_stats_c = None
    opt_c_state = None
    if c is not None:
        vc = init_variables(c, kc, x, cfg.model.init_type, cfg.model.init_gain,
                            train=False)
        params_c = vc["params"]
        batch_stats_c = vc.get("batch_stats", {})
        opt_c_state = opt_c.init(params_c)

    pool = pool_n = None
    if cfg.train.pool_size > 0:
        pool = jnp.zeros(
            (cfg.train.pool_size,) + pair.shape[1:],
            train_dtype or jnp.float32,
        )
        pool_n = jnp.zeros((), jnp.int32)

    delayed = cfg.model.int8_delayed
    quant_c = None
    if c is not None and delayed and cfg.model.int8_compression:
        quant_c = vc.get("quant", {})
    # EMA generator (HealthConfig.ema_decay): seeded with the init params
    # so step 1's blend is well-defined; decay=0 keeps ema == params
    # bitwise (the parity-pin mode), decay->1 smooths
    ema_g = (jax.tree_util.tree_map(jnp.copy, vg["params"])
             if cfg.health.ema_decay is not None else None)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        lr_scale=jnp.ones((), jnp.float32),
        params_g=vg["params"],
        batch_stats_g=vg.get("batch_stats", {}),
        opt_g=opt_g.init(vg["params"]),
        params_d=vd["params"],
        spectral_d=vd.get("spectral", {}),
        opt_d=opt_d.init(vd["params"]),
        params_c=params_c,
        batch_stats_c=batch_stats_c,
        opt_c=opt_c_state,
        pool=pool,
        pool_n=pool_n,
        quant_g=vg.get("quant", {}) if delayed else None,
        quant_d=vd.get("quant", {}) if delayed else None,
        quant_c=quant_c,
        ema_g=ema_g,
        spectral_g=vg.get("spectral"),
        batch_stats_d=(vd.get("batch_stats", {})
                       if cfg.model.norm_d == "batch" else None),
        # drawn from the init's rng ARGUMENT (not from cfg.train.seed, which
        # would be a constant of this jitted init and compile it anew a seed)
        noise_seed=(jax.random.bits(jax.random.fold_in(rng, 0x5EED), (),
                                    jnp.uint32)
                    if cfg.model.use_dropout else None),
    )
