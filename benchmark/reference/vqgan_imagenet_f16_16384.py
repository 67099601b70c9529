"""Plain reference of the ``vqgan_imagenet_f16_16384`` configuration: the
autoencoder, the quantizer, LPIPS, the discriminator and ONE WHOLE TRAIN
STEP.

VQGAN (Esser, Rombach, Ommer, CVPR 2021, arXiv:2012.09841, section 3.1;
the sizes of github.com/CompVis/taming-transformers, released
``vqgan_imagenet_f16_16384``). NHWC; ``GN`` = GroupNorm(32, eps 1e-6,
affine), ``sw(x) = x * sigmoid(x)``; every k3 convolution pads 1 with
zeros and has a bias.

  Res(cin, cout)(x) = s(x) + conv3(sw(GN(conv3(sw(GN(x)))))), s the
    identity or conv1x1(cin -> cout).
  Attn(c)(x) = x + proj(softmax(q k^T * c^-0.5) v); q, k, v =
    conv1x1(GN(x)); one head over the H*W positions.
  Down(x) = conv3_stride2_pad0(pad(x, bottom 1, right 1, zeros));
    Up(x) = conv3(nearest_x2(x)).
  Encoder: conv3(3 -> ch); levels with their Res (+ Attn at the 16
    extent) and Down between them; Res Attn Res; conv3(sw(GN(.))).
  Quantizer: z = conv1x1(enc); d_ij = |z_i|^2 + |e_j|^2 - 2 z_i . e_j;
    k_i = argmin_j d_ij; zq_i = e_{k_i}; L_q = mean((sg(zq) - z)^2) +
    beta * mean((zq - sg(z))^2) (the code's legacy form: beta on the
    CODEBOOK term; the paper's eq. 4 has it on the commitment term);
    forward value z + sg(zq - z); then conv1x1.
  Decoder: conv3; Res Attn Res; the levels in reverse, one Res more
    each, Up between them; conv3(sw(GN(.)), ch -> 3); no tanh.
  LPIPS P(x, y): (image - shift) / scale; VGG16 after relu1_2, relu2_2,
    relu3_3, relu4_3, relu5_3; each position's channel vector over its
    L2 norm + 1e-10; squared difference; a 1x1 head to one channel, no
    bias; mean over H, W; summed over the taps.
  D: conv4_s2(3 -> 64) lrelu(0.2); conv4_s2(64 -> 128, no bias) BN
    lrelu; conv4_s1(128 -> 256, no bias) BN lrelu; conv4_s1(256 -> 1);
    pad 1; on the image alone; BN the batch's own biased moments, eps
    1e-5.
  Step (this Trainer's; the configuration file states the departure):
    r = AE(x) ONCE. nll = mean|x - r| + mean_batch P(x, r); g = -mean
    D(r); lambda = clip(|grad_W nll| / (|grad_W g| + 1e-4), 0, 1e4),
    constant, W the decoder's last kernel; autoencoder loss nll +
    disc_weight * lambda * g + codebook_weight * L_q; D loss 0.5 *
    (mean relu(1 - D(x)) + mean relu(1 + D(sg(r)))), the fake call first,
    D's running statistics updated by each call (0.9 old + 0.1 batch,
    biased variance); Adam(beta1, beta2, eps 1e-8) on both.

``d_ij`` is returned LESS ``|z_i|^2`` (``code_distances``): no argmin over
j sees that term, and at a seeded codebook (rows ~1e-4 apart under
``|z|^2`` ~ 1e2) float32 would round the rest away with it.

Only ``jax.numpy`` / ``lax`` in float32, every product at
``Precision.HIGHEST``; nothing of the program is imported, and of this
package ``nn`` alone. The structure (levels, blocks, where attention
sits, the shortcuts) is read off the names of the state's leaves. GN is
per image, so the autoencoder, LPIPS and the generator's gradient are
taken AN IMAGE AT A TIME, each image a call of one jitted function and
the sums made outside it (no ``lax.scan``: PERF.md section 7 records
wrong float32 weight gradients from this chip under a row-blocked scan),
ON THE HOST CPU (``HOST``: the chip returned wrong gradients from these
very functions too, PERF.md section 6, PR 34);
D's BatchNorm couples the images, so everything of D runs on the whole
batch. lambda's two gradients are ``jax.grad`` of ``nll`` and of ``g``
with respect to ``W``, separately, each through the whole graph of its
image(s): ``adaptive_weight_whole`` on a batch that fits, and the same
two gradients summed image by image in ``StepReference``.

State is a flat dict: ``params_g/encoder/down_0_block_0/conv1/Conv_0/
kernel``, ``params_g/quantize/embedding``, ``params_d/scale0/
_PlainConv_1/Conv_0/kernel``, ``batch_stats_d/scale0/BatchNorm_0/
BatchNorm_0/mean``, ``vgg/vgg16/conv1_1/kernel``, ``vgg/lin/lin0``.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nn

BATCH_KEY = "target"
NETS = ("params_g", "params_d")
GROUPS = 32
GN_EPS = 1e-6
LAST_KERNEL = "params_g/decoder/conv_out/Conv_0/kernel"
CODEBOOK = "params_g/quantize/embedding"
VGG16 = (("conv1_1", "conv1_2"), ("conv2_1", "conv2_2"),
         ("conv3_1", "conv3_2", "conv3_3"), ("conv4_1", "conv4_2", "conv4_3"),
         ("conv5_1", "conv5_2", "conv5_3"))
LPIPS_SHIFT = (-0.030, -0.088, -0.188)
LPIPS_SCALE = (0.458, 0.448, 0.450)
#: the margin of ``index_disagrees_beyond_margin_share``: positions whose
#: best and second-best distance (the reference's, on the program's own
#: latent) lie further apart than this share of the distances' spread
#: over the codes must carry the program's index
INDEX_MARGIN = 1e-3

#: Limits of ``correct`` (the readings and the reasons: PERF.md section 2,
#: PR 34; sound readings over 25 seeds for the generator path and 15 for
#: the steps, all on the chip at the cell's size).
#: - the reconstruction given the program's codes: between the sound
#:   program (mean 0.825 .. 0.972 levels, p99 4.39 .. 5.46) and the int8
#:   control (1.320 .. 1.557, 7.01 .. 8.73: a steady 1.55 - 1.61 x the
#:   same seed's sound error), with the more room above the sound runs;
#: - the nearest-code search on the program's own latent: between float32
#:   on the chip (2.88e-7 .. 3.03e-7 of the matrix's norm, no index apart
#:   beyond the margin) and the bfloat16 control (2.77e-3, 0.5% - 1.2%);
#: - lambda, the codebook's own gradient (its norm; beta on the wrong
#:   term would read 3.0), D's first gradient (its worst leaf's norm),
#:   D's running statistics: three times the widest sound reading or
#:   more; D's gradient as a vector between the sound runs (0.023 ..
#:   0.060) and a step that saw half of its batch (0.123 .. 0.286 on
#:   five seeds);
#: - the step-one losses D's, LPIPS and the codebook's, each on the same
#:   state and the same codes: three times the widest sound reading
#:   (1.5e-3, 2.5e-3, 4.3e-4); a term left out or mis-scaled reads ~1;
#: - the parameters' change after three steps: between the widest
#:   reading (G 0.21, D 0.076) and the 1.0 of a state left unchanged;
#: - the DIRECTION of G's first gradient, 1 - cosine with the
#:   reference's, encoder and decoder each as one vector: between the
#:   sound program (0.35 .. 0.50, three seeds: at the seeded start the
#:   direction does not survive bf16, and the program in float32 reads
#:   5e-5 at this very size on a CPU) and the 1.0 of a backward
#:   uncorrelated with the truth (a flipped sign reads 1.5), nearer the
#:   1.0 because three seeds are few. A step that saw half of its batch
#:   reads 0.48 .. 0.64, inside that room: D's vector refuses it.
#: Printed and not judged: the latent's gap (the int8 control is only
#: 1.25x away from the widest sound reading), ``step1_loss_g`` (a mean of
#: logits near zero carries it: 3e-4 .. 0.15), every later-step number
#: (section 7), and G's first-gradient worst leaf, a gap of NORMS that
#: reads 0.28 .. 1.00 on every seed, section 6.
LIMITS = {
    "generator_mean_abs_levels": 1.18,
    "generator_p99_abs_levels": 6.4,
    "distance_rel_gap": 3e-5,
    "index_disagrees_beyond_margin_share": 0.001,
    "step1_d_weight_rel_gap": 0.25,
    "first_grad_d_worst_leaf_gap": 0.08,
    "first_grad_d_diff_over_norm": 0.1,
    "params_change_g_worst_leaf_gap": 0.55,
    "params_change_d_worst_leaf_gap": 0.3,
    "codebook_first_grad_gap": 0.03,
    "batch_stats_d_widest_gap": 0.08,
    "step1_loss_d_rel_gap": 0.0045,
    "step1_g_lpips_rel_gap": 0.008,
    "step1_g_codebook_rel_gap": 0.0015,
    "first_grad_g_encoder_cosine_gap": 0.9,
    "first_grad_g_decoder_cosine_gap": 0.9,
}

#: images a call of the step reference's jitted functions takes
CHUNK = 1
#: True: ``StepReference.follow`` runs on the host CPU backend whatever
#: the process's default device is. It is: on the chip the same float32
#: functions, an image a call, came back WRONG (PR 34, PERF.md section 6:
#: at batch 2 lambda 0.907 and loss_g 0.5642 where the host CPU, both
#: ways, and the chip's one-program form read 1.012 and 0.5493; the median
#: leaf of G's gradient 0.6 of its norm off). The host is the truth of
#: last resort, at ~1 min a step of twelve images on 8 cores.
HOST = True

Flat = Dict[str, jnp.ndarray]


def sub(p: Flat, prefix: str) -> Flat:
    return {k: v for k, v in p.items() if k.startswith(prefix + "/")}


def zero_gradient_leaves(state) -> set:
    """The trainable leaves of ``state`` whose gradient is identically
    zero: the bias of ``k`` in every attention block. It adds ``q_i .
    b`` to every logit of row i alike, which the softmax over the row does
    not see. Both programs hand Adam rounding noise there, and Adam turns
    it into steps of its own size: nothing to compare."""
    return {k for k in state if k.endswith("/k/Conv_0/bias")}


# ------------------------------------------------------------ autoencoder


def group_norm(p: Flat, path: str, x, swish: bool):
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, GROUPS, c // GROUPS)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 3), keepdims=True)
    y = ((g - mean) * jax.lax.rsqrt(var + GN_EPS)).reshape(x.shape)
    y = y * p[f"{path}/scale"] + p[f"{path}/bias"]
    return y * jax.nn.sigmoid(y) if swish else y


def _conv(p: Flat, path: str, x, pad: int, stride: int = 1):
    return nn.zero_conv(x, p[f"{path}/Conv_0/kernel"],
                        p.get(f"{path}/Conv_0/bias"), stride=stride, pad=pad)


def res_block(p: Flat, path: str, x):
    h = _conv(p, f"{path}/conv1", group_norm(p, f"{path}/norm1", x, True), 1)
    h = _conv(p, f"{path}/conv2", group_norm(p, f"{path}/norm2", h, True), 1)
    if f"{path}/nin_shortcut/Conv_0/kernel" in p:
        x = _conv(p, f"{path}/nin_shortcut", x, 0)
    return x + h


def attn_block(p: Flat, path: str, x):
    n, hh, ww, c = x.shape
    h = group_norm(p, f"{path}/norm", x, False)
    q, k, v = (_conv(p, f"{path}/{name}", h, 0).reshape(n, hh * ww, c)
               for name in ("q", "k", "v"))
    w = jax.nn.softmax(jnp.einsum("nic,njc->nij", q, k,
                                  precision=nn.HIGHEST) * c ** -0.5, axis=-1)
    out = jnp.einsum("nij,njc->nic", w, v, precision=nn.HIGHEST)
    return x + _conv(p, f"{path}/proj_out", out.reshape(n, hh, ww, c), 0)


def _level(p: Flat, net: str, name: str, x):
    j = 0
    while f"{net}/{name}_block_{j}/conv1/Conv_0/kernel" in p:
        x = res_block(p, f"{net}/{name}_block_{j}", x)
        if f"{net}/{name}_attn_{j}/q/Conv_0/kernel" in p:
            x = attn_block(p, f"{net}/{name}_attn_{j}", x)
        j += 1
    return x


def _middle(p: Flat, net: str, x):
    x = res_block(p, f"{net}/mid_block_1", x)
    x = attn_block(p, f"{net}/mid_attn_1", x)
    return res_block(p, f"{net}/mid_block_2", x)


def _levels(p: Flat, net: str, stem: str) -> int:
    i = 0
    while f"{net}/{stem}_{i}_block_0/conv1/Conv_0/kernel" in p:
        i += 1
    return i


def encoder(p: Flat, x):
    e = "params_g/encoder"
    x = _conv(p, f"{e}/conv_in", x, 1)
    for i in range(_levels(p, e, "down")):
        x = _level(p, e, f"down_{i}", x)
        if f"{e}/down_{i}_downsample/Conv_0/kernel" in p:
            x = _conv(p, f"{e}/down_{i}_downsample",
                      jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0))), 0, 2)
    x = _middle(p, e, x)
    return _conv(p, f"{e}/conv_out", group_norm(p, f"{e}/norm_out", x, True),
                 1)


def decoder(p: Flat, zq, last_kernel=None):
    """The image and the last convolution's input. ``last_kernel``
    stands in the last kernel's place (for its gradient alone)."""
    d = "params_g/decoder"
    x = _middle(p, d, _conv(p, f"{d}/conv_in", zq, 1))
    for i in reversed(range(_levels(p, d, "up"))):
        x = _level(p, d, f"up_{i}", x)
        if f"{d}/up_{i}_upsample/Conv_0/kernel" in p:
            x = _conv(p, f"{d}/up_{i}_upsample", nn.upsample_nearest(x, 2),
                      1)
    h = group_norm(p, f"{d}/norm_out", x, True)
    kernel = p[LAST_KERNEL] if last_kernel is None else last_kernel
    return nn.zero_conv(h, kernel, p[f"{d}/conv_out/Conv_0/bias"], pad=1), h


def code_distances(z, codebook):
    """``|e_j|^2 - 2 z_i . e_j`` ``[M, K]``: ``d_ij`` less ``|z_i|^2``."""
    return jnp.sum(jnp.square(codebook), axis=1)[None, :] - 2.0 * jnp.einsum(
        "md,kd->mk", z, codebook, precision=nn.HIGHEST)


def quantizer(p: Flat, z, beta: float, code=None):
    """``z`` ``[N, h, w, D]`` -> (straight-through value, L_q, indices
    ``[N, h, w]``, distances ``[N*h*w, K]``). ``code``: indices to take in
    the argmin's place."""
    e = p[CODEBOOK]
    flat = z.reshape(-1, z.shape[-1])
    dist = code_distances(jax.lax.stop_gradient(flat), e)
    idx = jnp.argmin(dist, axis=1) if code is None else code.reshape(-1)
    zq = e[idx].reshape(z.shape)
    sg = jax.lax.stop_gradient
    loss = (jnp.mean(jnp.square(sg(zq) - z))
            + beta * jnp.mean(jnp.square(zq - sg(z))))
    return z + sg(zq - z), loss, idx.reshape(z.shape[:-1]), dist


def autoencoder(p: Flat, x, beta: float = 0.25, code=None,
                last_kernel=None):
    """``x`` float32 in [-1, 1] -> dict: ``image``, ``codebook_loss``,
    ``indices``, ``distances``, ``latent`` (z, flat) and ``last_input``."""
    z = _conv(p, "params_g/quant_conv", encoder(p, x), 0)
    zq, loss, idx, dist = quantizer(p, z, beta, code)
    image, h = decoder(p, _conv(p, "params_g/post_quant_conv", zq, 0),
                       last_kernel)
    return {"image": image, "codebook_loss": loss, "indices": idx,
            "distances": dist, "latent": z.reshape(-1, z.shape[-1]),
            "last_input": h}


def generator_path(params: Flat, image_uint8, train: bool,
                   code: Optional[jnp.ndarray] = None,
                   latent: Optional[jnp.ndarray] = None):
    """The house contract ``(pred, pre_code, moments)``: ``pre_code`` is
    the distance matrix (less ``|z|^2``), ``code``, when given, the
    PROGRAM's indices, so that the decoder is held against the program on
    the same codes. ``latent``, when given, is the program's own z
    ``[M, D]``: ``moments["distances_on_latent"]`` is then this
    quantizer's distance matrix on it, which holds the program's
    nearest-code search against float32 arithmetic on the same numbers
    (``moments["latent"]`` is this encoder's z)."""
    del train
    out = autoencoder(params, nn.to_unit(jnp.asarray(image_uint8)),
                      code=None if code is None else jnp.asarray(code))
    moments = {"latent": out["latent"], "indices": out["indices"]}
    if latent is not None:
        moments["distances_on_latent"] = code_distances(
            jnp.asarray(latent, jnp.float32), params[CODEBOOK])
    return out["image"], out["distances"], moments


# ------------------------------------------------------------------ LPIPS


def vgg16_taps(p: Flat, x):
    taps = []
    for i, block in enumerate(VGG16):
        if i:
            x = nn.max_pool_2(x)
        for name in block:
            x = jnp.maximum(nn.zero_conv(
                x, p[f"vgg/vgg16/{name}/kernel"],
                p[f"vgg/vgg16/{name}/bias"], pad=1), 0)
        taps.append(x)
    return taps


def lpips(p: Flat, x, y):
    """``P(x, y)`` of each image of the batch, ``[N]``."""
    scaled = lambda im: ((im - jnp.asarray(LPIPS_SHIFT))  # noqa: E731
                         / jnp.asarray(LPIPS_SCALE))
    unit = lambda f: f / (jnp.sqrt(jnp.sum(  # noqa: E731
        jnp.square(f), -1, keepdims=True)) + 1e-10)
    total = jnp.zeros((x.shape[0],), jnp.float32)
    for i, (fx, fy) in enumerate(zip(vgg16_taps(p, scaled(x)),
                                     vgg16_taps(p, scaled(y)))):
        d = jnp.square(unit(fx) - unit(fy)) * p[f"vgg/lin/lin{i}"]
        total = total + jnp.mean(jnp.sum(d, -1), axis=(1, 2))
    return total


# ---------------------------------------------------------- discriminator


def discriminator(p: Flat, x, scale: str = "scale0"
                  ) -> Tuple[jnp.ndarray, Flat]:
    """Logits ``[N, h, w, 1]`` and D's running statistics after this
    call (train mode: the batch's own biased moments normalise)."""
    d, s = f"params_d/{scale}", f"batch_stats_d/{scale}"
    conv = lambda i, y, stride: nn.zero_conv(  # noqa: E731
        y, p[f"{d}/_PlainConv_{i}/Conv_0/kernel"],
        p.get(f"{d}/_PlainConv_{i}/Conv_0/bias"), stride=stride, pad=1)
    y = nn.leaky_relu(conv(0, x, 2))
    new: Flat = {}
    i = 1
    while f"{d}/BatchNorm_{i - 1}/BatchNorm_0/scale" in p:
        # every inner convolution but the last has stride 2
        last = f"{d}/BatchNorm_{i}/BatchNorm_0/scale" not in p
        b = f"BatchNorm_{i - 1}/BatchNorm_0"
        y, (mean, var) = nn.batch_norm(
            conv(i, y, 1 if last else 2), p[f"{d}/{b}/scale"],
            p[f"{d}/{b}/bias"])
        new[f"{s}/{b}/mean"] = 0.9 * p[f"{s}/{b}/mean"] + 0.1 * mean
        new[f"{s}/{b}/var"] = 0.9 * p[f"{s}/{b}/var"] + 0.1 * var
        y = nn.leaky_relu(y)
        i += 1
    return conv(i, y, 1), new


def d_loss(p: Flat, x, r):
    """0.5 * (hinge real + hinge fake), the fake call first; D's
    statistics after both calls."""
    fake, s1 = discriminator(p, jax.lax.stop_gradient(r))
    real, s2 = discriminator({**p, **s1}, x)
    return 0.5 * (jnp.mean(jnp.maximum(1.0 - real, 0))
                  + jnp.mean(jnp.maximum(1.0 + fake, 0))), s2


def g_term(p: Flat, r):
    """``g = -mean D(r)``."""
    return -jnp.mean(discriminator(p, r)[0])


def nll_terms(p: Flat, x, r):
    """``mean|x - r|`` and ``mean_batch P(x, r)``."""
    return jnp.mean(jnp.abs(x - r)), jnp.mean(lpips(p, r, x))


def adaptive_weight(gw_nll, gw_g):
    norm = lambda g: jnp.sqrt(jnp.sum(jnp.square(g)))  # noqa: E731
    return jnp.clip(norm(gw_nll) / (norm(gw_g) + 1e-4), 0.0, 1e4)


def adaptive_weight_whole(p: Flat, x, beta: float = 0.25):
    """lambda on a batch that fits in one piece: ``jax.grad`` of ``nll``
    and of ``g`` with respect to the decoder's last kernel, each through
    the whole graph (autoencoder, LPIPS; autoencoder, D)."""
    recon = lambda w: autoencoder(p, x, beta, last_kernel=w)["image"]  # noqa
    gw_nll = jax.grad(lambda w: sum(nll_terms(p, x, recon(w))))(
        p[LAST_KERNEL])
    gw_g = jax.grad(lambda w: g_term(p, recon(w)))(p[LAST_KERNEL])
    return adaptive_weight(gw_nll, gw_g)


# ---------------------------------------------------------------- the step


class StepReference:
    """``hyper``: the configuration file's ``train_reference`` group
    (``lr_g``, ``lr_d``, ``beta1``, ``beta2``, ``eps``, ``disc_weight``,
    ``codebook_weight``, ``perceptual_weight``, ``vq_beta``)."""

    def __init__(self, hyper: dict, chunk: int = CHUNK):
        self.h = hyper
        self.chunk = chunk
        self._fwd = jax.jit(self._forward)
        self._dside = jax.jit(self._discriminator_side)
        self._wgrads = jax.jit(self._last_kernel_grads)
        self._ggrads = jax.jit(self._generator_grads)
        self._adam = jax.jit(self._adam_update, static_argnums=(5,))

    def _forward(self, p: Flat, x, code=None):
        out = autoencoder(p, x, self.h["vq_beta"], code)
        return out["image"], out["codebook_loss"]

    def _discriminator_side(self, p: Flat, x, r):
        """On the whole batch (BatchNorm couples the images): D's loss,
        its gradient, D's statistics after the step, ``g`` and its
        gradient with respect to the reconstruction."""
        d_params = sub(p, "params_d")
        rest = {k: v for k, v in p.items() if k not in d_params}
        (ld, stats), gd = jax.value_and_grad(
            lambda dp: d_loss({**rest, **dp}, x, r), has_aux=True)(d_params)
        g, ct_g = jax.value_and_grad(lambda rr: g_term(p, rr))(r)
        return ld, gd, stats, g, ct_g

    def _last_kernel_grads(self, p: Flat, x, ct_g, n: int, code=None):
        """One image's share of ``grad_W nll`` and of ``grad_W g``: each a
        ``jax.grad`` with respect to the last kernel through the whole
        autoencoder of that image (``g``'s through D is ``ct_g``, taken on
        the whole batch)."""
        recon = lambda w: autoencoder(  # noqa: E731
            p, x, self.h["vq_beta"], code, last_kernel=w)["image"]

        def nll(w):
            l1, lp = nll_terms(p, x, recon(w))
            return (l1 + self.h["perceptual_weight"] * lp) * (
                x.shape[0] / n)

        gw_nll = jax.grad(nll)(p[LAST_KERNEL])
        gw_g = jax.grad(lambda w: jnp.vdot(recon(w), ct_g))(p[LAST_KERNEL])
        return gw_nll, gw_g

    def _generator_grads(self, p: Flat, x, ct_g, gan_weight, n: int,
                         code=None):
        """One image's share of the autoencoder loss and of its gradient
        with respect to the autoencoder's parameters."""
        h = self.h
        g_params = sub(p, "params_g")
        rest = {k: v for k, v in p.items() if k not in g_params}

        def loss(gp):
            q = {**rest, **gp}
            out = autoencoder(q, x, h["vq_beta"], code)
            l1, lp = nll_terms(q, x, out["image"])
            share = x.shape[0] / n
            parts = {"g_l1": l1 * share,
                     "g_lpips": h["perceptual_weight"] * lp * share,
                     "g_codebook": out["codebook_loss"] * share}
            total = (parts["g_l1"] + parts["g_lpips"]
                     + h["codebook_weight"] * parts["g_codebook"]
                     + gan_weight * jnp.vdot(out["image"], ct_g))
            return total, parts

        (_, parts), grads = jax.value_and_grad(loss, has_aux=True)(g_params)
        return parts, grads

    def _adam_update(self, p: Flat, grads: Flat, mom: Flat, v: Flat, count,
                     lr: float):
        h = self.h
        b1, b2 = h["beta1"], h["beta2"]
        t = (count + 1).astype(jnp.float32)
        out_p, out_m, out_v = {}, {}, {}
        for k, g in grads.items():
            out_m[k] = b1 * mom[k] + (1 - b1) * g
            out_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g)
            step = (out_m[k] / (1 - b1 ** t)) / (
                jnp.sqrt(out_v[k] / (1 - b2 ** t)) + h["eps"])
            out_p[k] = p[k] - lr * step
        return out_p, out_m, out_v

    def step(self, p: Flat, x, code=None):
        """One step from ``p`` on the float32 batch ``x``: the losses, the
        gradients of both nets and D's statistics after it. ``code``
        ``[N, h, w]``: indices to take in the argmin's place (the
        program's own, so that both sides decode the same codes)."""
        pick = lambda at: None if code is None else code[at]  # noqa: E731
        n, m = x.shape[0], self.chunk
        spans = [slice(i, i + m) for i in range(0, n, m)]
        recon, l_q = zip(*(self._fwd(p, x[at], pick(at)) for at in spans))
        r = jnp.concatenate(recon)
        loss_d, grads_d, stats, g, ct_g = self._dside(p, x, r)
        add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)  # noqa
        gws = None
        for at in spans:
            one = self._wgrads(p, x[at], ct_g[at], n, pick(at))
            gws = one if gws is None else add(gws, one)
        lam = adaptive_weight(*gws)
        gan_weight = self.h["disc_weight"] * lam
        parts = grads_g = None
        for at in spans:
            pt, gr = self._ggrads(p, x[at], ct_g[at], gan_weight, n,
                                  pick(at))
            parts = pt if parts is None else add(parts, pt)
            grads_g = gr if grads_g is None else add(grads_g, gr)
        losses = {"loss_d": loss_d, "g_gan": g, "d_weight": lam, **parts}
        losses["loss_g"] = (parts["g_l1"] + parts["g_lpips"]
                            + self.h["codebook_weight"] * parts["g_codebook"]
                            + gan_weight * g)
        return losses, {**grads_g, **grads_d}, stats

    def follow(self, state: Flat, batches, first_code=None):
        """Follow ``batches`` (uint8 ``target``s) from ``state``;
        ``first_code``, when given, is the PROGRAM's indices in its first
        step, which the first step then decodes in its own argmin's place
        (at a seeded codebook, rows ~1e-4 apart, the bf16 latent picks
        another code at a share of the positions and the decoder, which
        normalises that tiny input up to unit size, paints another image:
        gradients are then compared on the same codes; the later steps
        take this reference's own). Returns
        each step's losses, the first step's gradients as each optimizer
        got them, the parameters after the last step and D's running
        statistics after it, all as numpy, by leaf."""
        with (jax.default_device(jax.devices("cpu")[0]) if HOST
              else contextlib.nullcontext()):
            return self._follow(state, batches, first_code)

    def _follow(self, state: Flat, batches, first_code):
        p = {k: jnp.asarray(v) for k, v in state.items()}
        trainable = {k for k in p if k.split("/", 1)[0] in NETS}
        mom = {k: jnp.zeros_like(p[k]) for k in trainable}
        v = {k: jnp.zeros_like(p[k]) for k in trainable}
        all_losses, first_grads = [], None
        for i, batch in enumerate(batches):
            count = jnp.asarray(i, jnp.int32)
            losses, grads, stats = self.step(
                p, nn.to_unit(jnp.asarray(batch[BATCH_KEY])),
                None if i or first_code is None else jnp.asarray(first_code))
            for net, lr in (("params_g", self.h["lr_g"]),
                            ("params_d", self.h["lr_d"])):
                new_p, new_m, new_v = self._adam(
                    sub(p, net), sub(grads, net), sub(mom, net),
                    sub(v, net), count, float(lr))
                p.update(new_p), mom.update(new_m), v.update(new_v)
            p.update(stats)
            all_losses.append({k: float(x) for k, x in losses.items()})
            if first_grads is None:
                first_grads = {k: np.asarray(g) for k, g in grads.items()}
            del grads
        params = {k: np.asarray(p[k]) for k in trainable}
        stats = {k: np.asarray(x) for k, x in p.items()
                 if k.startswith("batch_stats_d/")}
        return all_losses, first_grads, params, stats
