"""vid2vid-style video training step (BASELINE configs[4]).

The reference is image-only; this step lifts the framework to clips:

- **G** runs per-frame (frames folded into the batch dim — on TPU this is
  pure win: N·T images batch onto the MXU together).
- **Spatial D**: the image MultiscaleDiscriminator on every (cond ‖ frame)
  pair, frames folded into batch.
- **Temporal D**: MultiscaleTemporalDiscriminator on the (cond ‖ frames)
  NTHWC clip — 3-D convs see motion; this is the component that gets
  sequence-parallelized over the ``time`` mesh axis (shard the clip
  ``P('data','time',None,None,None)`` and GSPMD inserts the frame halo
  exchanges; hand shard_map primitives in p2p_tpu.parallel.temporal).

Losses mirror the image step (LSGAN + feature matching + VGG + TV with the
reference weights) plus the temporal GAN and temporal feature-matching
terms. Three optimizers: G, spatial D, temporal D.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from p2p_tpu.core.config import Config
from p2p_tpu.losses import feature_matching_loss, gan_loss, vgg_loss
from p2p_tpu.models.registry import define_D, define_G, init_variables
from p2p_tpu.models.temporal_d import MultiscaleTemporalDiscriminator
from p2p_tpu.ops.tv import total_variation_loss
from p2p_tpu.train.state import make_optimizers
from p2p_tpu.train.step import single_forward_d_losses
from p2p_tpu.utils.images import ingest


class VideoTrainState(struct.PyTreeNode):
    step: jax.Array
    lr_scale: jax.Array
    params_g: Any
    batch_stats_g: Any
    opt_g: optax.OptState
    params_d: Any
    spectral_d: Any
    opt_d: optax.OptState
    params_dt: Any
    spectral_dt: Any
    opt_dt: optax.OptState


def _fold(x: jax.Array) -> jax.Array:
    """NTHWC → (N·T)HWC."""
    n, t = x.shape[0], x.shape[1]
    return x.reshape((n * t,) + x.shape[2:])


def _clip_pair(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.concatenate([a, b], axis=-1)


def build_video_models(cfg: Config, train_dtype=None):
    if cfg.model.int8_delayed:
        # the video step threads only the 'spectral' collection through
        # its D applies (d_fwd/dt_fwd below); delayed scaling needs the
        # 'quant' amax state threaded like train/step.py does. Fail with
        # a clear message instead of an obscure flax collection error.
        raise ValueError(
            "--int8_delayed is supported on image presets only "
            "(the video step does not thread the 'quant' collection); "
            "use dynamic-scale --int8 for video presets")
    g = define_G(cfg.model, dtype=train_dtype, remat=cfg.parallel.remat)
    d = define_D(cfg.model, dtype=train_dtype)
    dt = MultiscaleTemporalDiscriminator(
        ndf=cfg.model.ndf, n_layers=cfg.model.n_layers_D,
        num_D=max(1, cfg.model.num_D - 1),
        use_spectral_norm=cfg.model.use_spectral_norm, dtype=train_dtype,
    )
    return g, d, dt


def create_video_train_state(
    cfg: Config,
    rng: jax.Array,
    sample_batch: Dict[str, jax.Array],
    steps_per_epoch: int = 1,
    train_dtype=None,
) -> VideoTrainState:
    if cfg.health.ema_decay is not None:
        # the VideoTrainState carries no EMA tree (image presets only, like
        # int8_delayed) — decline loudly rather than silently not smoothing
        raise ValueError(
            "health.ema_decay is supported on image presets only (the "
            "VideoTrainState carries no EMA tree); unset it for video")
    g, d, dt = build_video_models(cfg, train_dtype)
    opt_g, opt_d, opt_dt = make_optimizers(cfg, steps_per_epoch)

    kg, kd, kt = jax.random.split(rng, 3)
    x = ingest(jnp.asarray(sample_batch["input"]))     # NTHWC
    tgt = ingest(jnp.asarray(sample_batch["target"]))
    frames = _fold(x)
    pair_2d = jnp.concatenate([frames, _fold(tgt)], axis=-1)
    pair_3d = _clip_pair(x, tgt)

    vg = init_variables(g, kg, frames, cfg.model.init_type,
                        cfg.model.init_gain, train=False)
    vd = init_variables(d, kd, pair_2d, cfg.model.init_type,
                        cfg.model.init_gain)
    vt = init_variables(dt, kt, pair_3d, cfg.model.init_type,
                        cfg.model.init_gain)

    return VideoTrainState(
        step=jnp.zeros((), jnp.int32),
        lr_scale=jnp.ones((), jnp.float32),
        params_g=vg["params"],
        batch_stats_g=vg.get("batch_stats", {}),
        opt_g=opt_g.init(vg["params"]),
        params_d=vd["params"],
        spectral_d=vd.get("spectral", {}),
        opt_d=opt_d.init(vd["params"]),
        params_dt=vt["params"],
        spectral_dt=vt.get("spectral", {}),
        opt_dt=opt_dt.init(vt["params"]),
    )


def build_video_train_step(
    cfg: Config,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
    jit: bool = True,
):
    """Returns ``step(state, batch) -> (state, metrics)`` for NTHWC batches."""
    g, d, dt = build_video_models(cfg, train_dtype)
    opt_g, opt_d, opt_dt = make_optimizers(cfg, steps_per_epoch)
    L = cfg.loss
    need_vgg = (L.lambda_vgg > 0) and vgg_params is not None
    use_dropout = cfg.model.use_dropout

    def g_frames(params, bstats, frames, rng=None):
        rngs = {"dropout": rng} if (use_dropout and rng is not None) else None
        out, v = g.apply(
            {"params": params, "batch_stats": bstats}, frames, True,
            mutable=["batch_stats"], rngs=rngs,
        )
        return out, v["batch_stats"]

    # dict-of-collections convention shared with train/step.py's
    # single_forward_d_losses (video presets thread 'spectral' only)
    def d_fwd(params, dvars, x):
        out, mut = d.apply(
            {"params": params, **dvars}, x, mutable=["spectral"]
        )
        return out, {"spectral": mut["spectral"]}

    def dt_fwd(params, dvars, x):
        out, mut = dt.apply(
            {"params": params, **dvars}, x, mutable=["spectral"]
        )
        return out, {"spectral": mut["spectral"]}

    def step(state: VideoTrainState, batch: Dict[str, jax.Array]):
        # uint8 clips (DataConfig.uint8_pipeline) normalize on device
        real_a = ingest(batch["input"], train_dtype)   # NTHWC conditioning
        real_b = ingest(batch["target"], train_dtype)  # NTHWC target clip
        a_f = _fold(real_a)
        b_f = _fold(real_b)

        drop_rng = (
            jax.random.fold_in(jax.random.key(cfg.train.seed), state.step)
            if use_dropout else None
        )
        # ONE generator forward via explicit jax.vjp (see train/step.py:
        # CSE of a duplicated forward structurally fails for instance-norm
        # generators, the vid2vid default).
        def g_primal(params_g):
            out, bs = g_frames(params_g, state.batch_stats_g, a_f, drop_rng)
            return out, bs

        fake_f, g_vjp, bs_g = jax.vjp(g_primal, state.params_g, has_aux=True)
        fake_clip = fake_f.reshape(real_b.shape)

        in_c = real_a.shape[-1]

        # ---- spatial + temporal D: ONE D(fake) forward each serves the
        # D loss (params cotangent) and the G loss (pair cotangent) — the
        # shared single-forward structure of train/step.py. Power
        # iteration advances 2×/step per discriminator, not 3×.
        loss_d, grads_d, pred_fake, pred_real, dv2, pull_d = (
            single_forward_d_losses(
                d_fwd, {"spectral": state.spectral_d}, state.params_d,
                jnp.concatenate([a_f, fake_f], axis=-1),
                jnp.concatenate([a_f, b_f], axis=-1),
                L.gan_mode,
            )
        )
        loss_dt, grads_dt, pred_fake_t, pred_real_t, dvt2, pull_dt = (
            single_forward_d_losses(
                dt_fwd, {"spectral": state.spectral_dt}, state.params_dt,
                _clip_pair(real_a, fake_clip),
                _clip_pair(real_a, real_b),
                L.gan_mode,
            )
        )
        spectral2 = dv2["spectral"]
        spectral_t2 = dvt2["spectral"]

        # ---- G losses on the primal fake + the shared D outputs -----------
        def g_losses(fake, pred_fake_g, pred_fake_tg):
            l_gan = gan_loss(pred_fake_g, True, L.gan_mode,
                             for_discriminator=False)
            l_gan_t = gan_loss(pred_fake_tg, True, L.gan_mode,
                               for_discriminator=False)
            parts = {"g_gan": l_gan, "g_gan_t": l_gan_t}
            total = l_gan + l_gan_t
            if L.lambda_feat > 0:
                l_feat = feature_matching_loss(
                    pred_fake_g, pred_real, cfg.model.n_layers_D, L.lambda_feat
                ) + feature_matching_loss(
                    pred_fake_tg, pred_real_t, cfg.model.n_layers_D,
                    L.lambda_feat,
                )
                parts["g_feat"] = l_feat
                total = total + l_feat
            if need_vgg:
                l_vgg = vgg_loss(
                    vgg_params, fake, b_f, L.vgg_imagenet_norm
                ) * L.lambda_vgg
                parts["g_vgg"] = l_vgg
                total = total + l_vgg
            if L.lambda_tv > 0:
                l_tv = total_variation_loss(fake) * L.lambda_tv
                parts["g_tv"] = l_tv
                total = total + l_tv
            if L.lambda_l1 > 0:
                # elementwise diff in the train dtype, f32 accumulation
                # (see train/step.py g_losses).
                l_l1 = jnp.mean(
                    jnp.abs(fake - b_f), dtype=jnp.float32
                ) * L.lambda_l1
                parts["g_l1"] = l_l1
                total = total + l_l1
            return total, parts

        (loss_g, g_parts), (ct_fake, ct_pred, ct_pred_t) = jax.value_and_grad(
            g_losses, argnums=(0, 1, 2), has_aux=True
        )(fake_f, pred_fake, pred_fake_t)
        # params cotangents die (reference zero_grad before the D steps)
        grad_fake = (
            ct_fake
            + pull_d(ct_pred)[..., in_c:]
            + pull_dt(ct_pred_t)[..., in_c:].reshape(fake_f.shape)
        )
        (grads_g,) = g_vjp(grad_fake)

        # skip guard (health ladder rung 1 — same contract as the image
        # step): a non-finite step applies NO update to G, D or the
        # temporal D, and keeps the old BN/spectral state
        ok = None
        if cfg.health.enabled:
            from p2p_tpu.train.state import (
                health_select,
                losses_finite,
                zero_if_unhealthy,
            )

            ok = losses_finite(loss_g, loss_d, loss_dt)
            grads_g = zero_if_unhealthy(ok, grads_g)
            grads_d = zero_if_unhealthy(ok, grads_d)
            grads_dt = zero_if_unhealthy(ok, grads_dt)

        scale = state.lr_scale.astype(jnp.float32)
        if ok is not None:
            scale = scale * ok.astype(jnp.float32)
        scale_tree = lambda ups: jax.tree_util.tree_map(  # noqa: E731
            lambda u: u * scale.astype(u.dtype), ups
        )
        up_g, opt_g1 = opt_g.update(grads_g, state.opt_g, state.params_g)
        params_g1 = optax.apply_updates(state.params_g, scale_tree(up_g))
        up_d, opt_d1 = opt_d.update(grads_d, state.opt_d, state.params_d)
        params_d1 = optax.apply_updates(state.params_d, scale_tree(up_d))
        up_dt, opt_dt1 = opt_dt.update(grads_dt, state.opt_dt, state.params_dt)
        params_dt1 = optax.apply_updates(state.params_dt, scale_tree(up_dt))
        if ok is not None:
            opt_g1 = health_select(ok, opt_g1, state.opt_g)
            opt_d1 = health_select(ok, opt_d1, state.opt_d)
            opt_dt1 = health_select(ok, opt_dt1, state.opt_dt)
            bs_g = health_select(ok, bs_g, state.batch_stats_g)
            spectral2 = health_select(ok, spectral2, state.spectral_d)
            spectral_t2 = health_select(ok, spectral_t2, state.spectral_dt)

        new_state = state.replace(
            step=state.step + 1,
            params_g=params_g1, batch_stats_g=bs_g, opt_g=opt_g1,
            params_d=params_d1, spectral_d=spectral2, opt_d=opt_d1,
            params_dt=params_dt1, spectral_dt=spectral_t2, opt_dt=opt_dt1,
        )
        metrics = {
            "loss_d": loss_d.astype(jnp.float32),
            "loss_dt": loss_dt.astype(jnp.float32),
            "loss_g": loss_g.astype(jnp.float32),
            **{k: v.astype(jnp.float32) for k, v in g_parts.items()},
        }
        if ok is not None:
            metrics["health_ok"] = ok.astype(jnp.float32)
        return new_state, metrics

    if jit:
        step = jax.jit(step, donate_argnums=0)
    return step


def build_multi_video_train_step(
    cfg: Config,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
):
    """K video steps per dispatch via lax.scan (the video analogue of
    ``p2p_tpu.train.step.build_multi_train_step``); ``batches`` carry a
    leading (K,) scan axis over NTHWC clips."""
    inner = build_video_train_step(
        cfg, vgg_params, steps_per_epoch, train_dtype, jit=False
    )

    def multi_step(state: VideoTrainState, batches: Dict[str, jax.Array]):
        return jax.lax.scan(inner, state, batches)

    return jax.jit(multi_step, donate_argnums=0)


def make_parallel_video_step(
    cfg: Config,
    mesh,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
):
    """The video step jitted over a (data, time[, spatial]) mesh: state
    replicated, clips sharded N over data and T over time — GSPMD inserts
    the temporal-conv frame halo exchanges over ICI."""
    from p2p_tpu.core.mesh import replicated, video_sharding

    step = build_video_train_step(
        cfg, vgg_params, steps_per_epoch, train_dtype, jit=False
    )
    rep = replicated(mesh)
    vsh = video_sharding(mesh)
    return jax.jit(
        step,
        in_shardings=(rep, vsh),
        out_shardings=(rep, rep),
        donate_argnums=0,
    )
