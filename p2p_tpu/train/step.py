"""The jitted train/eval steps — all three network updates in ONE compile.

Semantics mirror the reference iteration (train.py:269-443, call stack
SURVEY §3.1) with its live bugs fixed by design:

1. ``compressed = quantize(net_c(real_b), bits)`` — C runs ONCE per step
   (the reference reuses the same tensor at train.py:297 and 392).
2. ``fake_b = G(stop_grad(compressed))``.
3. D loss on (real_a ‖ stop_grad(fake_b)) vs (real_a ‖ real_b), LSGAN,
   averaged ×0.5 (train.py:308-320).
4. G loss: GAN + feature-matching(×10) + VGG(×10) + TV(×1) [+ L1×λ — dead
   in the reference (Q3), live here for the pix2pix presets]
   (train.py:336-380).
5. G and D updates applied (reference order: G first — train.py:384-390).
6. C branch against the UPDATED generator: MSE(G(compressed), real_b) +
   VGG(compressed, real_b)×10, gradients reaching C through the
   straight-through quantizer (fixing Q1's mis-wired optimizer and Q2's
   zero-gradient round).

Stateful-op functionalization: BatchNorm stats thread through
``batch_stats`` (C once, G twice per step — same update count as the
reference); spectral-norm u/v thread through ``spectral``.

TPU notes — single-forward structure. BOTH expensive forwards run exactly
once per step via explicit ``jax.vjp``:

- **G** runs once; every loss graph consumes the primal value and G's
  parameter gradient is the VJP of the d(loss_g)/d(fake_b) cotangent.
- **D(fake)** runs once (the reference runs it twice: train.py:308 for the
  D loss, train.py:336 for the G loss — 3 full multiscale-D forwards/step
  counting D(real)). Here one ``jax.vjp`` over ``(params_d, fake_pair) →
  pred_fake`` serves both: the D-loss cotangent is pulled back to the
  *params* slot (the pair cotangent is dead code XLA removes — exactly the
  reference's ``fake_b.detach()``), and the G-loss cotangent is pulled back
  to the *pair* slot (the params cotangent dies — the reference's
  ``zero_grad`` before the D step). The VJP's linearity makes the two
  pulls independent; the residuals are shared, so only the cheap
  activation-gradient chain runs twice, never the forward.

Documented deviation: with one D(fake) forward the spectral-norm power
iteration advances 2× per step (fake, real) instead of the reference's 3×
(networks.py:580-582), and the G-side GAN loss sees the u/v state of the
step's first iteration rather than its third. Power iteration tracks the
same principal singular vector either way; only its warm-up rate changes.
When the historical-fake pool is active (``pool_size > 0``) the D-loss pair
differs from the G-loss pair and the step falls back to the reference's
3-forward structure.

The whole step is one XLA program: no host round-trips between
"optimizers".
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from p2p_tpu.core.config import Config
from p2p_tpu.losses import (
    feature_matching_loss,
    gan_loss,
    psnr,
    ssim,
    vgg_loss,
)
from p2p_tpu.losses.feature_matching import feature_matching_mse
from p2p_tpu.losses.gan import (
    final_preds,
    nonsaturating,
    r1_penalty,
    resize_mask_nearest,
)
from p2p_tpu.losses.perceptual import hrf_loss
from p2p_tpu.models.registry import generator_side, input_mask_channel
from p2p_tpu.ops.quantize import quantize, quantize_ste
from p2p_tpu.ops.tv import total_variation_loss
from p2p_tpu.train.state import TrainState, build_models, make_optimizers
from p2p_tpu.utils.images import ingest, ingest_input


#: Whose work an op of the step program is: every net, loss and optimizer
#: update runs under ONE of these ``jax.named_scope`` names, so the first
#: of them in an op's ``op_name`` (``jit(step)/jvp(G)/...`` forward,
#: ``.../transpose(jvp(G))/...`` backward) names its owner in the compiled
#: text, and through it in a device trace (benchmark/scope_time.py).
#: ``compress`` is C + the quantizer; D has two forwards, ``D_fake`` (whose
#: residuals also serve the G-loss pull through D) and ``D_real``;
#: ``loss_vgg`` holds the style loss too (the same VGG features);
#: ``loss_pix`` the pixel-space terms (L1, angular, sobel); ``C_branch`` is
#: the compression branch's whole pass against the updated G; the ``opt_*``
#: hold the skip guard's selects on what they update, ``opt_g`` G's EMA.
#: ``loss_lpips`` is the LPIPS term (VGG16), ``loss_adaptive`` the
#: adaptive adversarial weight (one weight-gradient convolution of the
#: generator's last layer for two cotangents, and their norms),
#: ``loss_codebook`` the code-usage numbers of a learned quantizer (its
#: loss is computed inside ``G``, under ``vq``). ``loss_hrf`` is the
#: high-receptive-field perceptual term (the dilated ResNet50). The R1
#: penalty's passes run under ``d_r1`` INSIDE ``D_real`` (its forward is
#: D's real call), so a join by these names counts them as D's and a join
#: by ``d_r1`` alone reads the penalty.
STEP_SCOPES = ("compress", "G", "D_fake", "D_real", "loss_gan", "loss_fm",
               "loss_vgg", "loss_tv", "loss_pix", "C_branch", "opt_g",
               "opt_d", "opt_c", "loss_lpips", "loss_adaptive",
               "loss_codebook", "loss_hrf")


#: weight of the codebook loss a generator with a learned quantizer hands
#: the step (the VQGAN lineage's ``codebook_weight``)
_CODEBOOK_WEIGHT = 1.0


def _concat_pair(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.concatenate([a, b], axis=-1)


def single_forward_d_losses(d_apply, dvars0, params_d, fake_pair,
                            real_pair, gan_mode: str,
                            scale_mean: bool = False):
    """ONE D(fake) forward whose vjp serves both the D loss and (later) the
    G loss — the "single-forward structure" of the module docstring, shared
    by the image step (spatial D) and the video step (spatial + temporal D).

    ``d_apply(params, dvars, x) -> (preds, new_dvars)`` is the
    discriminator apply fn; ``dvars`` is the dict of threaded non-param
    collections (``{'spectral': ...}``, plus ``'quant'`` when delayed int8
    scaling is on). Returns ``(loss_d, grads_d, pred_fake, pred_real,
    dvars2, pull)`` where ``pull(ct_pred) -> cotangent wrt fake_pair``
    re-uses the fake forward's residuals (its params cotangent is dead
    code XLA removes — the reference's zero_grad before the D step), and
    ``dvars2`` is the collection state after the fake→real forward chain
    (2 spectral power iterations per step; deviation documented above).
    """
    def fake_primal(params, pair):
        with jax.named_scope("D_fake"):
            pred, v1 = d_apply(params, dvars0, pair)
        return pred, v1

    def d_gan_loss(pred, is_real):
        with jax.named_scope("loss_gan"):
            return 0.5 * gan_loss(pred, is_real, gan_mode,
                                  scale_mean=scale_mean)

    pred_fake, d_vjp, dvars1 = jax.vjp(
        fake_primal, params_d, fake_pair, has_aux=True
    )
    loss_fake, ct_fake = jax.value_and_grad(
        lambda p: d_gan_loss(p, False)
    )(pred_fake)
    gd_fake = d_vjp(ct_fake)[0]  # pair cotangent dead → DCE

    def real_fn(params):
        with jax.named_scope("D_real"):
            pred_real, v2 = d_apply(params, dvars1, real_pair)
        return d_gan_loss(pred_real, True), (v2, pred_real)

    (loss_real, (dvars2, pred_real)), gd_real = jax.value_and_grad(
        real_fn, has_aux=True
    )(params_d)
    loss_d = loss_fake + loss_real
    grads_d = jax.tree_util.tree_map(jnp.add, gd_fake, gd_real)
    pred_real = jax.tree_util.tree_map(jax.lax.stop_gradient, pred_real)
    return loss_d, grads_d, pred_fake, pred_real, dvars2, (
        lambda ct: d_vjp(ct)[1]
    )


def masked_r1_d_losses(d_apply, dvars0, params_d, fake, real, mask,
                       gp_coef: float):
    """:func:`single_forward_d_losses` for the LaMa lineage's
    ``NonSaturatingWithR1`` on an unconditional D: the same ONE D(fake)
    forward whose vjp serves D's loss and (later, ``pull``) G's, the same
    fake -> real order of D's threaded collections, the same 0.5 on the
    whole. ``mask`` is ``[N, H, W, 1]``, 1 where the generated image was
    filled in. D minimises

        0.5 * ( softplus(-D(x)) + gp_coef * R1(x)
                + softplus(D(y)) * m' + softplus(-D(y)) * (1 - m') )

    ``m'`` the mask resized (nearest) to the logits: the known pixels of
    a generated image count as real. ``R1`` is ``losses.gan.r1_penalty``
    on the real call: its forward IS the real call (one forward, under
    ``D_real``), its gradient with respect to the image one backward, and
    the gradient of that with respect to D's parameters the step's only
    second-order pass; all three under the scope ``d_r1``. Returns what
    ``single_forward_d_losses`` returns and the penalty's value (before
    ``gp_coef``)."""
    def fake_primal(params, x):
        with jax.named_scope("D_fake"):
            pred, v1 = d_apply(params, dvars0, x)
        return pred, v1

    pred_fake, d_vjp, dvars1 = jax.vjp(fake_primal, params_d, fake,
                                       has_aux=True)

    def fake_loss(pred):
        with jax.named_scope("loss_gan"):
            total = jnp.zeros((), jnp.float32)
            for logits in final_preds(pred):
                known = 1.0 - resize_mask_nearest(mask, logits.shape[1:3])
                total = total + nonsaturating(logits, known)
            return 0.5 * total

    loss_fake, ct_fake = jax.value_and_grad(fake_loss)(pred_fake)
    gd_fake = d_vjp(ct_fake)[0]  # image cotangent dead → DCE

    def real_fn(params):
        def logits_sum(x):
            pred, v2 = d_apply(params, dvars1, x.astype(real.dtype))
            total = sum(jnp.sum(p.astype(jnp.float32))
                        for p in final_preds(pred))
            return total, (pred, v2)

        with jax.named_scope("D_real"), jax.named_scope("d_r1"):
            r1, (pred_real, v2) = r1_penalty(logits_sum, real)
        with jax.named_scope("loss_gan"):
            loss = 0.5 * (gan_loss(pred_real, True, "nonsaturating")
                          + gp_coef * r1)
        return loss, (v2, pred_real, r1)

    (loss_real, (dvars2, pred_real, r1)), gd_real = jax.value_and_grad(
        real_fn, has_aux=True)(params_d)
    loss_d = loss_fake + loss_real
    grads_d = jax.tree_util.tree_map(jnp.add, gd_fake, gd_real)
    pred_real = jax.tree_util.tree_map(jax.lax.stop_gradient, pred_real)
    return loss_d, grads_d, pred_fake, pred_real, dvars2, (
        lambda ct: d_vjp(ct)[1]), jax.lax.stop_gradient(r1)


def adaptive_gan_weight(last_input, last_kernel, ct_nll, ct_gan):
    """The VQGAN lineage's ``lambda = |grad_W nll| / (|grad_W g| + 1e-4)``,
    clipped to [0, 1e4] and held constant, ``W`` the generator's last
    kernel (a k3 convolution on a zero pad of 1, ``last_input`` its
    input) and ``ct_nll`` / ``ct_gan`` the cotangents of the two terms
    with respect to the generated image. A weight gradient is linear in
    the cotangent and does not depend on ``W``'s value, so both come from
    ONE pull of the two cotangents side by side through a convolution of
    twice the output channels: one pass over ``last_input``, and no
    second backward through the decoder."""
    c = ct_nll.shape[-1]
    both = jnp.concatenate([ct_nll, ct_gan], axis=-1).astype(last_input.dtype)
    conv = lambda w: jax.lax.conv_general_dilated(  # noqa: E731
        last_input, w.astype(last_input.dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    w2 = jnp.zeros(last_kernel.shape[:3] + (2 * c,), jnp.float32)
    (gw,) = jax.vjp(conv, w2)[1](both)
    norm = lambda g: jnp.sqrt(jnp.sum(jnp.square(  # noqa: E731
        g.astype(jnp.float32))))
    lam = norm(gw[..., :c]) / (norm(gw[..., c:]) + 1e-4)
    return jax.lax.stop_gradient(jnp.clip(lam, 0.0, 1e4))


def make_g_loss_fn(cfg: Config, vgg_params: Optional[Any] = None,
                   steps_per_epoch: int = 1):
    """The generator-side loss surface (GAN + feature-matching + VGG +
    style + TV + angular + sobel + L1 per the config), factored out so the
    standard step and the pipelined step (``build_pp_train_step``) share
    ONE definition. Returns ``g_losses(fake_b, pred_fake_g, pred_real,
    real_a, real_b, step) -> (total, parts)``; differentiation wrt
    ``pred_fake_g`` routes the GAN + feature-matching cotangent back
    through D."""
    L = cfg.loss
    need_vgg = (L.lambda_vgg > 0) and vgg_params is not None
    need_lpips = (L.lambda_lpips > 0) and vgg_params is not None
    need_hrf = (L.lambda_hrf > 0) and vgg_params is not None
    # an input that carries a mask (inpainting): the L1 term counts the
    # KNOWN pixels alone
    mask_ch = input_mask_channel(cfg.model)

    def g_losses(fake_b, pred_fake_g, pred_real, real_a, real_b, step):
        with jax.named_scope("loss_gan"):
            l_gan = gan_loss(pred_fake_g, True, L.gan_mode,
                             for_discriminator=False,
                             scale_mean=L.gan_scale_mean)
        if L.gan_weight != 1.0:
            l_gan = l_gan * L.gan_weight
        parts = {"g_gan": l_gan}
        total = l_gan
        if L.lambda_feat > 0:
            with jax.named_scope("loss_fm"):
                if L.feat_mode == "mse":
                    l_feat = feature_matching_mse(
                        pred_fake_g, pred_real) * L.lambda_feat
                else:
                    l_feat = feature_matching_loss(
                        pred_fake_g, pred_real, cfg.model.n_layers_D,
                        L.lambda_feat
                    )
            parts["g_feat"] = l_feat
            total = total + l_feat
        if need_vgg:
            with jax.named_scope("loss_vgg"):
                l_vgg = vgg_loss(
                    vgg_params, fake_b, real_b, L.vgg_imagenet_norm,
                    L.vgg_taps) * L.lambda_vgg
            parts["g_vgg"] = l_vgg
            total = total + l_vgg
        if need_lpips:
            from p2p_tpu.losses.lpips import lpips_loss

            with jax.named_scope("loss_lpips"):
                l_lpips = lpips_loss(vgg_params, fake_b,
                                     real_b) * L.lambda_lpips
            parts["g_lpips"] = l_lpips
            total = total + l_lpips
        if need_hrf:
            l_hrf = hrf_loss(vgg_params, fake_b, real_b) * L.lambda_hrf
            parts["g_hrf"] = l_hrf
            total = total + l_hrf
        if L.lambda_style > 0 and vgg_params is not None:
            from p2p_tpu.losses.style import style_loss

            with jax.named_scope("loss_vgg"):
                l_style = style_loss(
                    vgg_params, fake_b, real_b, L.vgg_imagenet_norm
                ) * L.lambda_style
            parts["g_style"] = l_style
            total = total + l_style
        if L.lambda_tv > 0:
            with jax.named_scope("loss_tv"):
                l_tv = total_variation_loss(fake_b) * L.lambda_tv
            parts["g_tv"] = l_tv
            total = total + l_tv
        with jax.named_scope("loss_pix"):
            total = pixel_terms(total, parts, fake_b, real_a, real_b, step)
        return total, parts

    def pixel_terms(total, parts, fake_b, real_a, real_b, step):
        if L.lambda_angular > 0:
            from p2p_tpu.ops.sobel import angular_loss

            # The reference's commented experiment (train.py:356-360)
            # compares ILLUMINATION QUOTIENTS, not raw images:
            #   illum_gt   = real_a / max(real_b, 1e-4)
            #   illum_pred = real_a / max(fake_b, 1e-4)
            eps = jnp.asarray(1e-4, real_b.dtype)
            illum_gt = real_a / jnp.maximum(real_b, eps)
            illum_pred = real_a / jnp.maximum(fake_b, eps)
            l_ang = angular_loss(illum_gt, illum_pred) * L.lambda_angular
            parts["g_angular"] = l_ang
            total = total + l_ang
        if L.lambda_sobel > 0:
            from p2p_tpu.ops.sobel import sobel_edges

            lam = jnp.float32(L.lambda_sobel)
            if L.sobel_warmup_epochs > 0:
                # reference warmup shape (train.py:445-448):
                # weight ramps linearly with the epoch index,
                # saturating at lambda_sobel after warmup epochs
                epoch = 1 + step // max(steps_per_epoch, 1)
                lam = lam * jnp.minimum(
                    epoch.astype(jnp.float32) / L.sobel_warmup_epochs,
                    1.0,
                )
            l_sobel = jnp.mean(jnp.abs(
                sobel_edges(fake_b) - sobel_edges(real_b)
            )) * lam
            parts["g_sobel"] = l_sobel
            total = total + l_sobel
        if L.lambda_l1 > 0 and mask_ch is not None:
            # the LaMa lineage's masked L1 at weight_missing 0: the mean
            # over EVERY element of |y - x| on the known pixels
            known = (real_a[..., mask_ch:mask_ch + 1] <= 0).astype(
                fake_b.dtype)
            l_l1 = jnp.mean(
                jnp.abs(fake_b - real_b) * known, dtype=jnp.float32
            ) * L.lambda_l1
            parts["g_l1_known"] = l_l1
            total = total + l_l1
        elif L.lambda_l1 > 0:
            # elementwise diff in the train dtype (bf16 cotangents),
            # accumulation in f32 — halves the loss-side HBM traffic
            # at 256²·bs128 vs an f32 elementwise chain.
            l_l1 = jnp.mean(
                jnp.abs(fake_b - real_b), dtype=jnp.float32
            ) * L.lambda_l1
            parts["g_l1"] = l_l1
            total = total + l_l1
        return total

    return g_losses


def build_train_step(
    cfg: Config,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
    jit: bool = True,
):
    """Returns ``train_step(state, batch) -> (state, metrics)``."""
    g, d, c = build_models(cfg, train_dtype)
    opt_g, opt_d, opt_c = make_optimizers(cfg, steps_per_epoch)
    L = cfg.loss
    bits = cfg.model.quant_bits
    quant = quantize_ste if cfg.model.quant_ste else quantize
    use_c = cfg.model.use_compression_net
    # net_c on the delayed-int8 path stores its amax as quant_c
    use_qc = (use_c and cfg.model.int8_delayed
              and cfg.model.int8_compression)
    need_vgg = (L.lambda_vgg > 0) and vgg_params is not None

    use_dropout = cfg.model.use_dropout
    if cfg.model.split_d_pairs and cfg.train.pool_size > 0:
        # the historical-fake pool stores CONCATENATED pairs (its ring
        # buffer holds one 6-ch tensor per slot), so the split-stem form
        # cannot apply on the pool path — fail loudly rather than
        # silently losing the HD optimization the flag promises
        raise ValueError(
            "split_d_pairs is incompatible with pool_size > 0 (the fake "
            "pool stores concatenated pairs); set one of them off")

    # NOTE on residual policy: wrapping these forwards in jax.checkpoint
    # with save_only_these_names('conv_out', 'norm_stats') was measured
    # SLOWER (52→67 ms/step @ bs64 on v5e; measured on the pre-vjp
    # structure): the recompute costs more than the saved residual
    # traffic at these activation sizes. The checkpoint_name tags remain
    # in the models for the big-activation presets, where remat is useful
    # anyway. (The duplicated D(fake) subgraph that note originally
    # discussed is now structurally gone — see the module docstring.)
    # delayed int8 scaling threads a 'quant' collection (stored activation
    # amax, ops/int8.py) through G and D exactly like batch_stats/spectral
    use_quant = cfg.model.int8_delayed
    d_colls = ("spectral", "quant") if use_quant else ("spectral",)
    # a BatchNorm discriminator threads its running statistics the same
    # way (TrainState.batch_stats_d)
    d_bn = cfg.model.norm_d == "batch"
    if d_bn:
        d_colls = d_colls + ("batch_stats",)
    # a generator with a learned quantizer leaves its codebook loss, its
    # indices and its last convolution's input in a collection of its own
    # (models/registry.GeneratorSide); None for every other generator
    side = generator_side(cfg.model)
    adaptive = L.adaptive_gan_weight > 0
    if adaptive and (side is None or L.lambda_feat > 0):
        raise ValueError(
            "adaptive_gan_weight needs a generator that exposes its last "
            "convolution's input (models/registry.generator_side) and no "
            "feature matching (the GAN term alone may reach the image "
            "through D)")
    if (side or not cfg.model.d_conditional) and cfg.train.pool_size > 0:
        raise ValueError("the historical-fake pool holds conditional "
                         "pairs of a generator with one output: set "
                         "pool_size 0")
    # an input that carries a mask (models/registry.input_mask_channel):
    # D's loss is the masked non-saturating one with its R1 penalty
    mask_ch = input_mask_channel(cfg.model)
    if mask_ch is not None and (cfg.model.d_conditional
                                or L.gan_mode != "nonsaturating"
                                or cfg.train.pool_size > 0):
        raise ValueError(
            "a generator whose input carries a mask trains under the "
            "masked non-saturating loss on an unconditional D: set "
            "gan_mode 'nonsaturating', d_conditional False, pool_size 0")
    if L.gp_coef > 0 and mask_ch is None:
        raise ValueError("gp_coef (the R1 penalty) is wired for the masked "
                         "non-saturating D loss alone")
    g_loss_fn = make_g_loss_fn(cfg, vgg_params, steps_per_epoch)
    # Self-healing (resilience/health.py, rung 1 of the recovery ladder):
    # a non-finite step SKIPS — gradients are zeroed before they can
    # poison the Adam moments, the update scale folds to 0 (params
    # bitwise unchanged: p + 0·u = p), and every threaded collection
    # selects its old value. The selects fuse into the kernels that
    # produce the new values, so the healthy path pays ~nothing.
    health_guard = cfg.health.enabled
    ema_decay = cfg.health.ema_decay

    def g_fwd(params, bstats, quant, x, rng=None, spectral=None):
        rngs = {"dropout": rng} if (use_dropout and rng is not None) else None
        variables = {"params": params, "batch_stats": bstats}
        mut = ["batch_stats"]
        if use_quant:
            variables["quant"] = quant
            mut.append("quant")
        if spectral is not None:
            # a spectrally normalised generator (models/spade.py): one
            # power iteration a training forward, like D's
            variables["spectral"] = spectral
            mut.append("spectral")
        if side:
            mut.append(side.collection)
        with jax.named_scope("G"):
            out, v = g.apply(variables, x, True, mutable=mut, rngs=rngs)
        if side:
            # the codebook loss is a second differentiable output
            out = (out, side.read(v[side.collection]))
        return out, v.get("batch_stats", {}), (
            v.get("quant", {}) if use_quant else None), v.get("spectral")

    def d_fwd(params, dvars, x):
        out, mut = d.apply(
            {"params": params, **dvars}, x, mutable=list(d_colls)
        )
        return out, {k: mut.get(k, {}) for k in d_colls}

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        # uint8 batches (DataConfig.uint8_pipeline) normalize here — fused
        # into the first conv's input read; bit-exact with host f32 input
        # (a label-map input one-hots here instead: ingest_input)
        real_a = ingest_input(batch["input"], cfg.model, train_dtype)
        real_b = ingest(batch["target"], train_dtype)

        # ---- 1. compression pre-filter + quantizer ----------------------
        # delayed-int8 net_c threads its stored amax like batch_stats:
        # the step-1 run's update is the one stored (the C-branch rerun
        # below reads the same start-of-step scales and discards its
        # proposal, mirroring the batch_stats_c convention)
        def compressed_fn(params_c):
            variables = {"params": params_c,
                         "batch_stats": state.batch_stats_c}
            mut = ["batch_stats"]
            if use_qc:
                variables["quant"] = state.quant_c
                mut.append("quant")
            raw, vc = c.apply(variables, real_b, True, mutable=mut)
            return (quant(raw, bits), vc["batch_stats"],
                    vc.get("quant") if use_qc else state.quant_c)

        if use_c:
            with jax.named_scope("compress"):
                compressed, bs_c1, quant_c1 = compressed_fn(state.params_c)
        else:
            compressed, bs_c1, quant_c1 = (real_a, state.batch_stats_c,
                                           state.quant_c)

        g_input = jax.lax.stop_gradient(compressed)

        # per-step dropout noise (pix2pix's noise source); the seed is the
        # state's own (TrainState.noise_seed: the program holds no --seed)
        drop_rng = (
            jax.random.fold_in(jax.random.key(state.noise_seed), state.step)
            if use_dropout else None
        )

        # ONE generator forward via explicit jax.vjp: every loss graph
        # consumes the primal VALUE, and G's parameter gradient is pulled
        # through g_vjp with the cotangent d(loss_g)/d(fake_b). The earlier
        # structure (a primal call + value_and_grad of a second g_fwd)
        # relied on XLA CSE to dedupe the two forwards — which structurally
        # FAILS for instance-norm generators (the jvp rewrite of the
        # var/mean primal diverges after the first norm), silently doubling
        # the cityscapes/pix2pixHD generator cost.
        def g_primal(params_g):
            out, bs, qg, sg = g_fwd(params_g, state.batch_stats_g,
                                    state.quant_g, g_input, drop_rng,
                                    state.spectral_g)
            if side:
                # (image, codebook loss) are both pulled back through;
                # the indices and the last convolution's input ride along
                image, beside = out
                return (image, beside["codebook_loss"]), (
                    bs, qg, sg, (beside["indices"], beside["last_input"]))
            return out, (bs, qg, sg)

        if side:
            ((fake_b_primal, loss_codebook), g_vjp,
             (bs_g1, quant_g1, spectral_g1, (vq_indices, vq_last_input))
             ) = jax.vjp(g_primal, state.params_g, has_aux=True)
        else:
            fake_b_primal, g_vjp, (bs_g1, quant_g1, spectral_g1) = jax.vjp(
                g_primal, state.params_g, has_aux=True
            )

        # historical-fake pool (reference train.py:307: the CONCAT pair is
        # pooled into D's fake branch; size 0 = passthrough). Device-side
        # ring buffer in TrainState — no host round-trip inside the scan.
        use_pool = cfg.train.pool_size > 0 and state.pool is not None
        pool1, pool_n1 = state.pool, state.pool_n

        # G-side loss terms (make_g_loss_fn — ONE definition shared with
        # the pipelined step), shared by both step structures here.
        # ``pred_fake_g`` is the multiscale D output on (real_a ‖ fake_b).
        def g_losses(fake_b, pred_fake_g):
            return g_loss_fn(fake_b, pred_fake_g, pred_real,
                             real_a, real_b, state.step)

        if not use_pool:
            # ---- 2+3. ONE D(fake) forward serving both losses -----------
            # (module docstring, "single-forward structure"); sequential
            # fake→real forwards preserve the reference's u/v threading
            # order when spectral norm is on. (A batched fake‖real single
            # forward was tried and measured SLOWER on v5e: the doubled
            # batch worsened the big D convs' backward tiling by ~6
            # ms/step at bs=128.)
            dvars0 = {"spectral": state.spectral_d}
            if use_quant:
                dvars0["quant"] = state.quant_d
            if d_bn:
                dvars0["batch_stats"] = state.batch_stats_d
            # Pair form is MEASURED shape-dependent (ModelConfig.
            # split_d_pairs): concat wins at 256²/bs128 (1661 vs 1701 —
            # two 3-ch stem convs tile the MXU's contraction dim worse,
            # 2×48-wide im2col vs one 96-wide, and the concat was already
            # fused into the stem's window gather); the split-stem (a, b)
            # form (models/patchgan._SplitStemConv — no materialized 6-ch
            # pair tensors, CSE-shared conv(real_a, W_a), structurally
            # dead real_a dgrad) wins at HD extents where the round-4
            # profile has the pair tensors at 26 GB/s. Equivalence pinned
            # by tests/test_models.py::test_split_stem_pair_path_equals
            # _concat; both branches share single_forward_d_losses (the
            # pair is a pytree either way).
            split = cfg.model.split_d_pairs
            in_c = real_a.shape[-1]
            if not cfg.model.d_conditional:
                # an unconditional D sees the image alone
                split, in_c = False, 0
                fake_pair, real_pair = fake_b_primal, real_b
            elif split:
                fake_pair = (real_a, fake_b_primal)
                real_pair = (real_a, real_b)
            else:
                fake_pair = _concat_pair(real_a, fake_b_primal)
                real_pair = _concat_pair(real_a, real_b)
            if mask_ch is not None:
                filled = (real_a[..., mask_ch:mask_ch + 1] > 0).astype(
                    jnp.float32)
                (loss_d, grads_d, pred_fake, pred_real, dvars2, pull,
                 loss_d_r1) = masked_r1_d_losses(
                    d_fwd, dvars0, state.params_d, fake_pair, real_pair,
                    filled, L.gp_coef)
            else:
                loss_d, grads_d, pred_fake, pred_real, dvars2, pull = (
                    single_forward_d_losses(
                        d_fwd, dvars0, state.params_d,
                        fake_pair, real_pair, L.gan_mode, L.gan_scale_mean,
                    )
                )

            (loss_g, g_parts), (ct_fake_direct, ct_pred) = jax.value_and_grad(
                g_losses, argnums=(0, 1), has_aux=True
            )(fake_b_primal, pred_fake)
            # params cotangent dead (reference zero_grad) → DCE; on the
            # split path the pair cotangent is already the (a, b) tuple
            ct_through_d = (pull(ct_pred)[1] if split
                            else pull(ct_pred)[..., in_c:])
            if adaptive:
                # ct_fake_direct is the cotangent of nll (what reaches
                # the image directly), ct_through_d the GAN term's: each
                # is pulled through the last convolution ALONE for the
                # weight, then the weighted sum goes through g_vjp once
                last_kernel = state.params_g
                for key in side.last_kernel:
                    last_kernel = last_kernel[key]
                with jax.named_scope("loss_adaptive"):
                    d_weight = adaptive_gan_weight(
                        vq_last_input, last_kernel, ct_fake_direct,
                        ct_through_d)
                    gan_w = L.adaptive_gan_weight * d_weight
                    ct_through_d = gan_w.astype(
                        ct_through_d.dtype) * ct_through_d
                loss_g = loss_g + (gan_w - 1.0) * g_parts["g_gan"]
            grad_fake = ct_fake_direct + ct_through_d
        else:
            # Pool active: D's fake pair is the pooled history, not the live
            # fake — the forwards genuinely differ, keep the reference's
            # 3-forward structure (train.py:308,315,336).
            from p2p_tpu.utils.pool import device_pool_query

            real_pair = _concat_pair(real_a, real_b)
            pool_rng = jax.random.fold_in(
                jax.random.key(cfg.train.seed ^ 0x705501), state.step
            )
            fake_pair, pool1, pool_n1 = device_pool_query(
                state.pool, state.pool_n,
                _concat_pair(real_a, jax.lax.stop_gradient(fake_b_primal)),
                pool_rng,
            )
            fake_pair = jax.lax.stop_gradient(fake_pair)

            dvars0 = {"spectral": state.spectral_d}
            if use_quant:
                dvars0["quant"] = state.quant_d

            def loss_d_fn(params_d):
                with jax.named_scope("D_fake"):
                    pred_fake, v1 = d_fwd(params_d, dvars0, fake_pair)
                with jax.named_scope("D_real"):
                    pred_real, v2 = d_fwd(params_d, v1, real_pair)
                with jax.named_scope("loss_gan"):
                    loss = 0.5 * (
                        gan_loss(pred_fake, False, L.gan_mode,
                                 scale_mean=L.gan_scale_mean)
                        + gan_loss(pred_real, True, L.gan_mode,
                                   scale_mean=L.gan_scale_mean)
                    )
                return loss, (v2, pred_real)

            (loss_d, (dvars1, pred_real)), grads_d = jax.value_and_grad(
                loss_d_fn, has_aux=True
            )(state.params_d)
            pred_real = jax.tree_util.tree_map(
                jax.lax.stop_gradient, pred_real
            )

            def loss_g_fn(fake_b):
                with jax.named_scope("D_fake"):
                    pred_fake_g, v3 = d_fwd(
                        jax.lax.stop_gradient(state.params_d),
                        dvars1,
                        _concat_pair(real_a, fake_b),
                    )
                total, parts = g_losses(fake_b, pred_fake_g)
                return total, (v3, parts)

            (loss_g, (dvars2, g_parts)), grad_fake = jax.value_and_grad(
                loss_g_fn, has_aux=True
            )(fake_b_primal)

        if side:
            # the codebook loss enters G's loss with the weight
            # _CODEBOOK_WEIGHT, and its cotangent is that weight
            (grads_g,) = g_vjp((grad_fake, jnp.asarray(
                _CODEBOOK_WEIGHT, loss_codebook.dtype)))
            loss_g = loss_g + _CODEBOOK_WEIGHT * loss_codebook
            g_parts["g_codebook"] = loss_codebook
        else:
            (grads_g,) = g_vjp(grad_fake)
        spectral2 = dvars2["spectral"]
        quant_d1 = dvars2.get("quant") if use_quant else None
        bs_d1 = dvars2["batch_stats"] if d_bn else state.batch_stats_d

        # ---- skip guard (health ladder rung 1) --------------------------
        ok = None
        if health_guard:
            from p2p_tpu.train.state import (
                health_select,
                losses_finite,
                zero_if_unhealthy,
            )

            ok = losses_finite(loss_g, loss_d)
            with jax.named_scope("opt_g"):
                grads_g = zero_if_unhealthy(ok, grads_g)
            with jax.named_scope("opt_d"):
                grads_d = zero_if_unhealthy(ok, grads_d)

        # ---- 4. apply G then D updates (reference order) ----------------
        # lr_scale: Adam updates are linear in lr, so the host-driven
        # plateau multiplier is applied to the update trees directly.
        scale = state.lr_scale.astype(jnp.float32)
        if ok is not None:
            # skipped step: updates scale to 0 — params unchanged bitwise
            scale = scale * ok.astype(jnp.float32)
        scale_tree = lambda ups: jax.tree_util.tree_map(  # noqa: E731
            lambda u: u * scale.astype(u.dtype), ups
        )
        with jax.named_scope("opt_g"):
            up_g, opt_g1 = opt_g.update(grads_g, state.opt_g, state.params_g)
            params_g1 = optax.apply_updates(state.params_g, scale_tree(up_g))
        with jax.named_scope("opt_d"):
            up_d, opt_d1 = opt_d.update(grads_d, state.opt_d, state.params_d)
            params_d1 = optax.apply_updates(state.params_d, scale_tree(up_d))
        if ok is not None:
            # a skipped step must not advance the optimizer moments/count
            # (zeroed grads still decay them) or absorb the step's NaN-
            # tainted collection updates
            with jax.named_scope("opt_g"):
                opt_g1 = health_select(ok, opt_g1, state.opt_g)
            with jax.named_scope("opt_d"):
                opt_d1 = health_select(ok, opt_d1, state.opt_d)
                spectral2 = health_select(ok, spectral2, state.spectral_d)
                if d_bn:
                    bs_d1 = health_select(ok, bs_d1, state.batch_stats_d)
            if use_quant:
                quant_g1 = health_select(ok, quant_g1, state.quant_g)
                quant_d1 = health_select(ok, quant_d1, state.quant_d)
            if spectral_g1 is not None:
                with jax.named_scope("opt_g"):
                    spectral_g1 = health_select(ok, spectral_g1,
                                                state.spectral_g)
            if use_pool:
                pool1 = health_select(ok, pool1, state.pool)
                pool_n1 = health_select(ok, pool_n1, state.pool_n)

        # ---- EMA generator (HealthConfig.ema_decay) ---------------------
        ema_g1 = state.ema_g
        if ema_decay is not None and state.ema_g is not None:
            from p2p_tpu.train.state import ema_update

            with jax.named_scope("opt_g"):
                ema_g1 = ema_update(state.ema_g, params_g1, ema_decay)
            if ok is not None:
                from p2p_tpu.train.state import health_select

                with jax.named_scope("opt_g"):
                    ema_g1 = health_select(ok, ema_g1, state.ema_g)

        # ---- 5. compression branch vs the UPDATED generator -------------
        loss_c = jnp.zeros((), jnp.float32)
        params_c1, opt_c1, bs_g2 = state.params_c, state.opt_c, bs_g1
        if use_c:
            @jax.named_scope("C_branch")
            def loss_c_fn(params_c):
                cq, _, _ = compressed_fn(params_c)
                c_rng = (jax.random.fold_in(drop_rng, 1)
                         if drop_rng is not None else None)
                fake_ac, bs2, _, _ = g_fwd(params_g1, bs_g1, quant_g1, cq,
                                           c_rng, spectral_g1)
                loss = jnp.mean(
                    (fake_ac.astype(jnp.float32) - real_b.astype(jnp.float32)) ** 2
                )
                if need_vgg:
                    loss = loss + vgg_loss(
                        vgg_params, cq, real_b, L.vgg_imagenet_norm
                    ) * L.lambda_vgg
                return loss, bs2

            (loss_c, bs_g2), grads_c = jax.value_and_grad(
                loss_c_fn, has_aux=True
            )(state.params_c)
            if cfg.optim.train_compression_net:
                with jax.named_scope("opt_c"):
                    up_c, opt_c1 = opt_c.update(
                        grads_c, state.opt_c, state.params_c)
                    params_c1 = optax.apply_updates(
                        state.params_c, scale_tree(up_c))

        ok_all = ok
        if ok is not None:
            # the C branch runs after the G/D gate and can blow up on its
            # own; the BN stats (G advanced twice, C once) absorb NaN
            # activations even when the loss scalars read finite late —
            # gate them all on the combined verdict
            if use_c:
                ok_all = ok & jnp.isfinite(loss_c)
                with jax.named_scope("opt_c"):
                    params_c1 = health_select(ok_all, params_c1,
                                              state.params_c)
                    opt_c1 = health_select(ok_all, opt_c1, state.opt_c)
                if use_qc:
                    quant_c1 = health_select(ok_all, quant_c1,
                                             state.quant_c)
            bs_g2 = health_select(ok_all, bs_g2, state.batch_stats_g)
            bs_c1 = health_select(ok_all, bs_c1, state.batch_stats_c)

        new_state = state.replace(
            step=state.step + 1,
            params_g=params_g1,
            batch_stats_g=bs_g2,
            opt_g=opt_g1,
            params_d=params_d1,
            spectral_d=spectral2,
            opt_d=opt_d1,
            params_c=params_c1,
            batch_stats_c=bs_c1,
            opt_c=opt_c1,
            pool=pool1,
            pool_n=pool_n1,
            quant_g=quant_g1,
            quant_d=quant_d1,
            quant_c=quant_c1,
            ema_g=ema_g1,
            spectral_g=spectral_g1,
            batch_stats_d=bs_d1,
        )
        metrics = {
            "loss_d": loss_d.astype(jnp.float32),
            "loss_g": loss_g.astype(jnp.float32),
            "loss_c": loss_c,
            **{k: v.astype(jnp.float32) for k, v in g_parts.items()},
        }
        if adaptive:
            metrics["d_weight"] = d_weight.astype(jnp.float32)
        if mask_ch is not None:
            # the penalty's own value, before gp_coef
            metrics["loss_d_r1"] = loss_d_r1.astype(jnp.float32)
        if side:
            with jax.named_scope("loss_codebook"):
                used, perplexity = side.usage(vq_indices)
            metrics["vq_codes_used"] = used
            metrics["vq_perplexity"] = perplexity
        if ok_all is not None:
            # 1.0 = updates applied, 0.0 = the skip guard dropped this
            # step; the host sentinel counts the skips off this flag
            metrics["health_ok"] = ok_all.astype(jnp.float32)
        if cfg.debug.grad_norms:
            # in-graph global norms; they ride the metrics fetch the loop
            # already pays for — no extra sync
            from p2p_tpu.obs.taps import grad_norm_taps

            grad_norm_taps(metrics, g=grads_g, d=grads_d,
                           c=grads_c if use_c else None)
        if cfg.debug.nan_sentinel:
            # async host callback (obs/taps.py): fires an obs event when a
            # loss/metric goes non-finite; NO fence on the happy path.
            # Also watches the effective update scale so loss-scale /
            # plateau collapse is visible alongside the NaN itself.
            from p2p_tpu.obs.taps import nan_sentinel

            nan_sentinel({**metrics, "lr_scale": scale}, tag="train_step")
        if cfg.optim.grad_clip > 0:
            # the _zero_nonfinite guard silently drops inf/NaN gradient
            # entries; surface the count so a sustained blowup is visible
            # in the metrics stream instead of masked (tiny reduction over
            # param-sized trees — off the headline path, which has clip=0)
            from p2p_tpu.train.state import count_nonfinite

            metrics["nonfinite_g"] = count_nonfinite(grads_g).astype(
                jnp.float32)
            metrics["nonfinite_d"] = count_nonfinite(grads_d).astype(
                jnp.float32)
            if use_c:
                # the same guard sits in opt_c's chain — count it too
                metrics["nonfinite_c"] = count_nonfinite(grads_c).astype(
                    jnp.float32)
        return new_state, metrics

    if jit:
        step = jax.jit(step, donate_argnums=0)
    return step


def build_pp_train_step(
    cfg: Config,
    mesh,
    n_micro: int,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
    jit: bool = True,
):
    """The full alternating G/D(/C) train step with the generator's
    residual trunk on the GPipe schedule over ``mesh``'s ``pipe`` axis.

    ``state`` must be prepared by :func:`p2p_tpu.parallel.pp.pp_split_state`
    (trunk variables stacked into pipe-sharded ``pp_stages`` with their own
    optimizer state ``opt_s``); ``batch`` is the standard flat batch (data-
    sharded), carved into ``n_micro`` microbatches mb-major inside the step.
    Loss surface, D single-forward structure, and update order are the
    unpipelined step's own (shared code: ``make_g_loss_fn``,
    ``single_forward_d_losses``), so losses match it within the documented
    norm-semantics bound (parallel/pp.py): exact for the instance-norm
    family, eval-stat norms for BatchNorm models — ``batch_stats_g`` is not
    advanced by this step. The delayed-int8 trunk's 'quant' scales ride the
    stage stack and update exactly like the unpipelined step's
    (ops/int8.py ``amax_update``).

    v1 bounds (documented in docs/PARALLELISM.md): expand/resnet trunk
    families only; no historical-fake pool.
    """
    from p2p_tpu.core.mesh import mesh_context
    from p2p_tpu.parallel.pp import (
        mb_major_flatten,
        mb_major_unflatten,
        pp_generator_forward,
        trunk_prefix,
    )

    if cfg.health.ema_decay is not None:
        # the EMA blend needs the FUSED generator params; the PP state
        # splits the trunk into the stage stack — decline loudly rather
        # than silently track only the encoder/decoder
        raise ValueError(
            "health.ema_decay is not supported on the pipelined step "
            "(v1 bound: the trunk lives in pp_stages); run EMA configs "
            "unpipelined")
    trunk_prefix(cfg.model)  # fail early on non-trunk generator families
    if cfg.train.pool_size > 0:
        raise ValueError(
            "build_pp_train_step does not support the historical-fake "
            "pool (pool_size > 0); run pooled configs unpipelined")
    _, d, c = build_models(cfg, train_dtype)
    opt_g, opt_d, opt_c = make_optimizers(cfg, steps_per_epoch)
    # optax transforms are stateless: the generator optimizer also drives
    # the stage stack — per-leaf Adam makes the split trajectory identical
    # to the fused params_g one
    opt_s = opt_g
    L = cfg.loss
    bits = cfg.model.quant_bits
    quant = quantize_ste if cfg.model.quant_ste else quantize
    use_c = cfg.model.use_compression_net
    use_qc = (use_c and cfg.model.int8_delayed
              and cfg.model.int8_compression)
    need_vgg = (L.lambda_vgg > 0) and vgg_params is not None
    use_quant_d = cfg.model.int8_delayed
    d_colls = ("spectral", "quant") if use_quant_d else ("spectral",)
    g_loss_fn = make_g_loss_fn(cfg, vgg_params, steps_per_epoch)
    health_guard = cfg.health.enabled
    # latency-hiding schedule (parallel/pp.py gpipe_trunk overlap=): the
    # stage hand-off ppermute is double-buffered against stage compute
    pp_overlap = cfg.parallel.pp_overlap

    def d_fwd(params, dvars, x):
        out, mut = d.apply(
            {"params": params, **dvars}, x, mutable=list(d_colls)
        )
        return out, {k: mut.get(k, {}) for k in d_colls}

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        if state.pp_stages is None:
            raise ValueError(
                "state has no pp_stages — prepare it with "
                "parallel.pp.pp_split_state(state, cfg, mesh)")
        real_a = ingest(batch["input"], train_dtype)
        real_b = ingest(batch["target"], train_dtype)
        n = int(real_a.shape[0])
        if n % n_micro:
            raise ValueError(
                f"batch {n} not divisible by n_micro={n_micro}")
        # mb-major carve (the ONE definition lives in parallel/pp.py): the
        # data-sharded batch axis stays outermost so the microbatch slots
        # align with the data shards
        unflat = lambda t: mb_major_unflatten(t, n_micro)  # noqa: E731
        flat = mb_major_flatten

        # ---- 1. compression pre-filter + quantizer (unpipelined: <1% of
        # the FLOPs; its BatchNorm keeps train-mode stats; delayed-int8
        # amax threads as quant_c exactly like the unpipelined step) ----
        def compressed_fn(params_c):
            variables = {"params": params_c,
                         "batch_stats": state.batch_stats_c}
            mut = ["batch_stats"]
            if use_qc:
                variables["quant"] = state.quant_c
                mut.append("quant")
            raw, vc = c.apply(variables, real_b, True, mutable=mut)
            return (quant(raw, bits), vc["batch_stats"],
                    vc.get("quant") if use_qc else state.quant_c)

        if use_c:
            compressed, bs_c1, quant_c1 = compressed_fn(state.params_c)
        else:
            compressed, bs_c1, quant_c1 = (real_a, state.batch_stats_c,
                                           state.quant_c)
        g_input = jax.lax.stop_gradient(compressed)

        stages_aux = {k: v for k, v in state.pp_stages.items()
                      if k != "params"}
        has_q = "quant" in stages_aux

        def g_pp(params_g, stages_p, x, quant_stack):
            variables = {"params": params_g,
                         "batch_stats": state.batch_stats_g}
            stk = {"params": stages_p, **stages_aux}
            if has_q:
                stk["quant"] = quant_stack
            out_mb, qnew = pp_generator_forward(
                cfg.model, variables, unflat(x), mesh, stacked=stk,
                dtype=train_dtype, with_quant=True, overlap=pp_overlap)
            return flat(out_mb), qnew

        # ONE pipelined generator forward via explicit jax.vjp (the same
        # single-forward structure as the unpipelined step): the backward
        # re-enters the pipeline in reverse via the ppermute transpose.
        def g_primal(params_g, stages_p):
            out, qnew = g_pp(params_g, stages_p, g_input,
                             stages_aux.get("quant"))
            return out, qnew

        fake_b_primal, g_vjp, quant_s1 = jax.vjp(
            g_primal, state.params_g, state.pp_stages["params"],
            has_aux=True,
        )

        # ---- 2+3. ONE D(fake) forward serving both losses --------------
        dvars0 = {"spectral": state.spectral_d}
        if use_quant_d:
            dvars0["quant"] = state.quant_d
        split = cfg.model.split_d_pairs
        in_c = real_a.shape[-1]
        if split:
            fake_pair = (real_a, fake_b_primal)
            real_pair = (real_a, real_b)
        else:
            fake_pair = _concat_pair(real_a, fake_b_primal)
            real_pair = _concat_pair(real_a, real_b)
        loss_d, grads_d, pred_fake, pred_real, dvars2, pull = (
            single_forward_d_losses(
                d_fwd, dvars0, state.params_d,
                fake_pair, real_pair, L.gan_mode,
            )
        )

        def g_losses(fake_b, pred_fake_g):
            return g_loss_fn(fake_b, pred_fake_g, pred_real,
                             real_a, real_b, state.step)

        (loss_g, g_parts), (ct_fake_direct, ct_pred) = jax.value_and_grad(
            g_losses, argnums=(0, 1), has_aux=True
        )(fake_b_primal, pred_fake)
        grad_fake = ct_fake_direct + (
            pull(ct_pred)[1] if split else pull(ct_pred)[..., in_c:])
        grads_g, grads_s = g_vjp(grad_fake)

        # skip guard (health ladder rung 1) — same contract as the
        # unpipelined step: a non-finite step applies NO update anywhere,
        # stage stack included
        ok = None
        if health_guard:
            from p2p_tpu.train.state import (
                health_select,
                losses_finite,
                zero_if_unhealthy,
            )

            ok = losses_finite(loss_g, loss_d)
            grads_g = zero_if_unhealthy(ok, grads_g)
            grads_s = zero_if_unhealthy(ok, grads_s)
            grads_d = zero_if_unhealthy(ok, grads_d)

        # ---- 4. apply G (enc/dec + pipe-sharded stages) then D ---------
        scale = state.lr_scale.astype(jnp.float32)
        if ok is not None:
            scale = scale * ok.astype(jnp.float32)
        scale_tree = lambda ups: jax.tree_util.tree_map(  # noqa: E731
            lambda u: u * scale.astype(u.dtype), ups
        )
        up_g, opt_g1 = opt_g.update(grads_g, state.opt_g, state.params_g)
        params_g1 = optax.apply_updates(state.params_g, scale_tree(up_g))
        up_s, opt_s1 = opt_s.update(grads_s, state.opt_s,
                                    state.pp_stages["params"])
        stages_p1 = optax.apply_updates(
            state.pp_stages["params"], scale_tree(up_s))
        up_d, opt_d1 = opt_d.update(grads_d, state.opt_d, state.params_d)
        params_d1 = optax.apply_updates(state.params_d, scale_tree(up_d))
        dvars2_spectral = dvars2["spectral"]
        quant_s_out = quant_s1
        quant_d_out = dvars2.get("quant") if use_quant_d else None
        if ok is not None:
            opt_g1 = health_select(ok, opt_g1, state.opt_g)
            opt_s1 = health_select(ok, opt_s1, state.opt_s)
            opt_d1 = health_select(ok, opt_d1, state.opt_d)
            dvars2_spectral = health_select(ok, dvars2_spectral,
                                            state.spectral_d)
            if has_q:
                quant_s_out = health_select(ok, quant_s1,
                                            stages_aux.get("quant"))
            if use_quant_d:
                quant_d_out = health_select(ok, quant_d_out, state.quant_d)

        # ---- 5. compression branch vs the UPDATED pipelined generator --
        loss_c = jnp.zeros((), jnp.float32)
        params_c1, opt_c1 = state.params_c, state.opt_c
        if use_c:
            def loss_c_fn(params_c):
                cq, _, _ = compressed_fn(params_c)
                fake_ac, _ = g_pp(params_g1, stages_p1, cq, quant_s1)
                loss = jnp.mean(
                    (fake_ac.astype(jnp.float32)
                     - real_b.astype(jnp.float32)) ** 2
                )
                if need_vgg:
                    loss = loss + vgg_loss(
                        vgg_params, cq, real_b, L.vgg_imagenet_norm
                    ) * L.lambda_vgg
                return loss

            loss_c, grads_c = jax.value_and_grad(loss_c_fn)(state.params_c)
            if cfg.optim.train_compression_net:
                up_c, opt_c1 = opt_c.update(grads_c, state.opt_c,
                                            state.params_c)
                params_c1 = optax.apply_updates(
                    state.params_c, scale_tree(up_c))

        ok_all = ok
        if ok is not None and use_c:
            ok_all = ok & jnp.isfinite(loss_c)
            params_c1 = health_select(ok_all, params_c1, state.params_c)
            opt_c1 = health_select(ok_all, opt_c1, state.opt_c)
            if use_qc:
                quant_c1 = health_select(ok_all, quant_c1, state.quant_c)
        if ok is not None:
            bs_c1 = health_select(ok_all, bs_c1, state.batch_stats_c)

        pp_stages1 = {"params": stages_p1, **stages_aux}
        if has_q:
            pp_stages1["quant"] = quant_s_out
        new_state = state.replace(
            step=state.step + 1,
            params_g=params_g1,
            opt_g=opt_g1,
            pp_stages=pp_stages1,
            opt_s=opt_s1,
            params_d=params_d1,
            spectral_d=dvars2_spectral,
            opt_d=opt_d1,
            params_c=params_c1,
            batch_stats_c=bs_c1,
            opt_c=opt_c1,
            quant_d=quant_d_out,
            quant_c=quant_c1,
        )
        metrics = {
            "loss_d": loss_d.astype(jnp.float32),
            "loss_g": loss_g.astype(jnp.float32),
            "loss_c": loss_c,
            **{k: v.astype(jnp.float32) for k, v in g_parts.items()},
        }
        if ok_all is not None:
            metrics["health_ok"] = ok_all.astype(jnp.float32)
        # same debug surface as build_train_step — the obs flags must not
        # silently no-op just because the generator is pipelined
        if cfg.debug.grad_norms:
            from p2p_tpu.obs.taps import grad_norm_taps

            grad_norm_taps(metrics,
                           g={"rest": grads_g, "stages": grads_s},
                           d=grads_d, c=grads_c if use_c else None)
        if cfg.debug.nan_sentinel:
            from p2p_tpu.obs.taps import nan_sentinel

            nan_sentinel({**metrics, "lr_scale": scale},
                         tag="pp_train_step")
        if cfg.optim.grad_clip > 0:
            from p2p_tpu.train.state import count_nonfinite

            metrics["nonfinite_g"] = (
                count_nonfinite(grads_g) + count_nonfinite(grads_s)
            ).astype(jnp.float32)
            metrics["nonfinite_d"] = count_nonfinite(grads_d).astype(
                jnp.float32)
            if use_c:
                metrics["nonfinite_c"] = count_nonfinite(grads_c).astype(
                    jnp.float32)
        return new_state, metrics

    if jit:
        def step_in_mesh(state, batch):
            with mesh_context(mesh):
                return step(state, batch)

        return jax.jit(step_in_mesh, donate_argnums=0)
    return step


def build_multi_train_step(
    cfg: Config,
    vgg_params: Optional[Any] = None,
    steps_per_epoch: int = 1,
    train_dtype=None,
):
    """``multi_step(state, batches) -> (state, metrics)`` scanning K train
    steps in ONE dispatch.

    ``batches`` is the single-step batch dict with a leading scan axis:
    ``{"input": (K, N, H, W, C), "target": (K, N, H, W, C)}``. Metrics are
    per-step stacked (K,). One XLA program per K steps amortizes host
    dispatch: where the per-call overhead is comparable to the step itself
    (small steps, bs=1) the device otherwise idles between dispatches.
    """
    inner = build_train_step(
        cfg, vgg_params, steps_per_epoch, train_dtype, jit=False
    )

    def multi_step(state: TrainState, batches: Dict[str, jax.Array]):
        return jax.lax.scan(inner, state, batches)

    return jax.jit(multi_step, donate_argnums=0)


def make_infer_forward(cfg: Config, train_dtype=None,
                       with_metrics: bool = True):
    """The ONE generator inference definition, shared by the trainer's
    eval step and the serving engine (p2p_tpu.serve).

    Returns ``fwd(state, batch) -> (pred, metrics)`` where ``state`` is
    anything exposing the generator-side fields (a full :class:`TrainState`
    or the serving :class:`~p2p_tpu.train.state.InferState`). Reference
    eval semantics (train.py:450-502): with a compression net G is driven
    from the quantized compressed TARGET (the stored input is unused —
    Q10); otherwise from the stored input, standard pix2pix eval. In eval
    mode the delayed-int8 'quant' collection is read-only, so restored
    activation scales act as FROZEN inference scales.

    ``with_metrics=False`` (the pure serving path, no targets on hand)
    skips the PSNR/SSIM graph and returns ``metrics = {}``.
    """
    g, _, c = build_models(cfg, train_dtype)
    bits = cfg.model.quant_bits

    def fwd(state, batch: Dict[str, jax.Array]):
        real_a = ingest_input(batch["input"], cfg.model, train_dtype)
        if cfg.model.use_compression_net:
            real_b = ingest(batch["target"], train_dtype)
            c_vars = {"params": state.params_c,
                      "batch_stats": state.batch_stats_c}
            if cfg.model.int8_delayed and cfg.model.int8_compression:
                # frozen-scale serving for net_c: the stored amax is
                # read-only here, exactly like quant_g below
                c_vars["quant"] = state.quant_c
            raw = c.apply(c_vars, real_b, False)
            g_in = quantize(raw, bits)
        else:
            g_in = real_a
        g_vars = {"params": state.params_g,
                  "batch_stats": state.batch_stats_g}
        if cfg.model.int8_delayed:
            g_vars["quant"] = state.quant_g
        if getattr(state, "spectral_g", None) is not None:
            # read-only here: the weight served is W / sigma(u)
            g_vars["spectral"] = state.spectral_g
        pred = g.apply(g_vars, g_in, False)
        metrics = {}
        if with_metrics:
            real_b = ingest(batch["target"], train_dtype)
            # Per-image vectors so the driver can report the reference's
            # mean AND max over individual test images (train.py:498-502)
            # even at test_batch_size > 1 — and so the serving engine can
            # mask bucket-padding rows off by slicing.
            metrics = {
                "psnr": psnr(real_b, pred, per_image=True),
                "ssim": ssim(real_b, pred, per_image=True),
            }
        return pred, metrics

    return fwd


def build_eval_step(cfg: Config, train_dtype=None, jit: bool = True):
    """``eval_step(state, batch) -> (prediction, metrics)`` — the trainer's
    per-epoch eval, a jitted :func:`make_infer_forward`."""
    step = make_infer_forward(cfg, train_dtype)
    if jit:
        step = jax.jit(step)
    return step
