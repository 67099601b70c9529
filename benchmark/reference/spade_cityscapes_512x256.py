"""Plain reference of the ``spade_cityscapes_512x256`` configuration: the
generator, the discriminator and ONE WHOLE TRAIN STEP.

SPADE / GauGAN (Park, Liu, Wang, Zhu, CVPR 2019, arXiv:1903.07291,
section 3 and appendix A; the sizes of the authors' code,
``SPADEGenerator`` 'normal', cityscapes options). ``m`` is the
conditioning map: one-hot of the class ids + the instance-edge bit.

  SPADE_C(x, m) = BN0(x) * (1 + gamma) + beta; BN0 = batch norm without
    affine, eps 1e-5, the batch's own biased moments in training;
    a = relu(conv3x3(nearest(m, size(x)), M -> 128)); gamma, beta =
    conv3x3(a, 128 -> C) each; bias, zero padding 1, no spectral norm.
  ResBlk(fin, fout), fmid = min(fin, fout):
    dx = conv3x3_sn(lrelu(SPADE_fin(x, m)), fin -> fmid)
    dx = conv3x3_sn(lrelu(SPADE_fmid(dx, m)), fmid -> fout)
    xs = x, or conv1x1_sn_nobias(SPADE_fin(x, m)) where fin != fout
    out = xs + dx              (LeakyReLU 0.2: the authors' code; the
                                paper's figure draws ReLU)
  G: conv3x3(nearest(m, H/32 x W/32), M -> 16nf); head_0 (16,16); up;
    G_middle_0, G_middle_1 (16,16); up; up_0 (16,8); up; up_1 (8,4); up;
    up_2 (4,2); up; up_3 (2,1); tanh(conv3x3(lrelu(x), nf -> 3)).
  D: 2 scales (3x3 s2 average pool between them), each C64(s2) - C128(s2)
    - C256(s2) - C512(s1) - 1, k4, pad 2, LeakyReLU 0.2, spectral norm +
    affine-free instance norm on the three inner convs; input
    concat(m, image).
  Step (this Trainer's, stated as a departure in the configuration file):
    fake = G(m) ONCE; D loss = 0.5 * (hinge(D(m | stop(fake)), fake) +
    hinge(D(m | real), real)), each the MEAN over the scales, one power
    iteration per D call, fake first; G loss = -mean D(m | fake) (mean
    over scales, the D of the fake call) + 10 / num_D * L1 feature
    matching against the real call's detached features + 10 * VGG19 L1
    (relu1_1 .. relu5_1, weights 1/32 .. 1); Adam(beta 0, 0.9, eps 1e-8)
    on G at lr_g and on D at lr_d. G's spectral norms run one power
    iteration in its forward.
  A bias whose convolution feeds nothing but BN0s (the first conv, every
  conv_0, conv_1 of all blocks but the last) is cancelled by the norm and
  has a zero gradient; the program's layout leaves those out, and a bias
  is added here wherever the state holds one.

Only ``jax.numpy`` / ``lax`` in float32 at ``Precision.HIGHEST``; nothing
of the program is imported. BN0 couples the rows, so the generator runs
on the whole batch, each SPADE stage and each ResBlk recomputed in the
backward (``remat``); G's losses after the generator are means over rows
and are taken in blocks of ``ROW_BLOCK`` rows; D's own loss and gradient
are taken on the whole batch (``StepReference._discriminator_grads`` says
why).

State is a flat dict: ``params_g/<blk>/conv_0/kernel``,
``params_g/<blk>/norm_0/{shared,gamma,beta}/Conv_0/{kernel,bias}``,
``spectral_g/<blk>/conv_0/u``, ``params_d/scale1/...``,
``spectral_d/scale1/SpectralConv_0/u``, ``vgg/conv1_1/kernel``, ...
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nn
from .train_step import (_blocks, feature_matching, spectral_kernel, sub,
                         vgg_distance)

BATCH_KEY = "input"
#: ResBlks in order: name, fin and fout in units of nf, upsample after it
BLOCKS = (("head_0", 16, 16, True), ("G_middle_0", 16, 16, False),
          ("G_middle_1", 16, 16, True), ("up_0", 16, 8, True),
          ("up_1", 8, 4, True), ("up_2", 4, 2, True), ("up_3", 2, 1, False))
DOWN = 32
NUM_D = 2
#: rows per block where the losses are followed in float32
ROW_BLOCK = 2
NETS = ("params_g", "params_d")

#: Limits of ``correct``, read on one "TPU v5 lite" at the cell's own
#: size (bs 8, 256x512; PERF.md section 2 has every reading). The sound
#: readings of what follows the steps are those of my chip run 7, PR 29
#: (the committed files: seven runs of the cell and one of
#: ``tools/control_labels.py --kind steps``, eight fresh seeds); the
#: generator's are of 21 seeds (runs 3, 4 and 7). The nine runs before
#: ``_discriminator_grads`` was repaired followed a D the chip had
#: stepped with a wrong gradient: their D-side readings are not used.
LIMITS = {
    # sound 0.030 .. 0.037; control (every generator kernel rounded to
    # int8, ``control_labels.py --kind train``, 5 seeds) 0.108 .. 0.120
    "generator_mean_abs_levels": 0.06,
    # sound 0.110 .. 0.132, control 0.396 .. 0.444
    "generator_p99_abs_levels": 0.23,
    # -- the Trainer's own first three steps against StepReference ------
    # Hinge at a seeded start has every logit inside the margin: loss_d
    # is 1.000 to five digits on both sides (the accepted cells' limit)
    "step1_loss_d_rel_gap": 6e-4,             # sound 1.2e-7 .. 1.5e-6
    # Printed, not judged: step1_loss_g_rel_gap (sound 0.0015 .. 0.0018;
    # bf16 against float32 in a sum of ~8), later_loss_g_rel_gap (0.012 ..
    # 0.114) and later_loss_d_rel_gap (0.0006 .. 0.014): the accepted
    # cells' 0.003 and 0.05 leave those readings no three times of room.
    # Every D weight moves by lr_d = 4e-4 in step one, 1.6 times its own
    # xavier-gain-0.02 std (2.5e-4 for the 256 -> 512 kernel), so two
    # precisions part at once in what follows D, and loss_d stays 1.00
    # whatever D does.
    # The first gradients, as each optimizer got them. Their control is a
    # step that saw half of its batch (``control_labels.py --kind steps``:
    # the reference follows the first batch with its second half replaced
    # by its first), which reads 0.414 (G) and 0.379 (D)
    "first_grad_g_worst_leaf_gap": 0.03,      # sound 0.0037 .. 0.0081
    "first_grad_d_worst_leaf_gap": 0.03,      # sound 0.0046 .. 0.0082
    # the norm of the parameters' change: a state left unchanged reads
    # 1.0; the more room above the readings, fresh seeds read higher
    "params_change_g_worst_leaf_gap": 0.4,    # sound 0.028 .. 0.057
    "params_change_d_worst_leaf_gap": 0.4,    # sound 0.013 .. 0.108
    # G's power-iteration vectors after three steps against the
    # reference's: sound 0.096 .. 0.312 (seven of eight at 0.141 or
    # under; the widest leaf is always some ResBlk's conv_0); vectors the
    # step does not thread stay the seeded ones and read 1.41 (two random
    # unit vectors). D's are printed, not judged: 0.50 .. 0.77 (D is
    # mostly its update after one step)
    "spectral_g_u_widest_gap": 0.8,
}

Flat = Dict[str, jnp.ndarray]


def one_hot(labels, classes: int, edge: bool = True):
    """Integer ``(N, H, W, 2)`` — class id, edge bit — to the float32
    conditioning map ``m``: ``classes`` one-hot channels + the edge bit."""
    m = (labels[..., :1] == jnp.arange(classes, dtype=labels.dtype))
    m = m.astype(jnp.float32)
    if edge:
        m = jnp.concatenate(
            [m, (labels[..., 1:2] != 0).astype(jnp.float32)], axis=-1)
    return m


def label_classes(p: Flat) -> int:
    """The number of classes the state was built for: the first conv's
    input channels less the edge channel."""
    return p["params_g/fc/Conv_0/kernel"].shape[2] - 1


def zero_gradient_leaves(state) -> set:
    """The trainable leaves of ``state`` whose gradient is identically
    zero. In G the bias of ``beta`` in the SPADE of a learned shortcut
    (``norm_s``) of every block but the last: the shortcut has no
    activation and its 1x1 convolution no bias, so that bias adds a
    per-channel constant to the block's output, which only BN0s read. In
    D the biases of the three inner convolutions of each scale, which
    the instance norm after them cancels. Both programs hand Adam
    rounding noise there, and Adam(beta1 0) turns it into +-lr steps:
    nothing to compare."""
    dead = {f"params_g/{name}/norm_s/beta/Conv_0/bias"
            for name, _, _, _ in BLOCKS[:-1]
            if f"params_g/{name}/conv_s/kernel" in state}
    return dead | {k for k in state if k.startswith("params_d/")
                   and "/SpectralConv_" in k and k.endswith("/bias")}


def _conv(p: Flat, path: str, x, pad: int = 1):
    return nn.zero_conv(x, p[f"{path}/kernel"], p.get(f"{path}/bias"),
                        pad=pad)


def spade(p: Flat, path: str, x, m, stats=None):
    """``SPADE_C(x, m)`` with the parameters under ``path`` (``shared``,
    ``gamma``, ``beta``); ``m`` at the output's full extent. ``stats``:
    running (mean, var) for eval, None for the batch's own."""
    f = m.shape[1] // x.shape[1]
    a = jnp.maximum(_conv(p, f"{path}/shared/Conv_0", m[:, ::f, ::f]), 0)
    gamma = _conv(p, f"{path}/gamma/Conv_0", a)
    beta = _conv(p, f"{path}/beta/Conv_0", a)
    xn, _ = nn.batch_norm(x, 1.0, 0.0, stats)
    return xn * (1.0 + gamma) + beta


def _sn_conv(p: Flat, net: str, path: str, x, new_u: Flat, pad: int):
    kernel, u = spectral_kernel(p[f"params_{net}/{path}/kernel"],
                                p[f"spectral_{net}/{path}/u"])
    new_u[f"spectral_{net}/{path}/u"] = u
    return nn.zero_conv(x, kernel, p.get(f"params_{net}/{path}/bias"),
                        pad=pad)


def _running(p: Flat, path: str):
    b = f"batch_stats_g/{path}/norm/BatchNorm_0"
    return (p[f"{b}/mean"], p[f"{b}/var"])


def res_block(p: Flat, name: str, x, m, train: bool = True,
              remat: bool = False) -> Tuple[jnp.ndarray, Flat]:
    """One SPADE ResBlk and the power-iteration vectors after it."""
    g = f"params_g/{name}"
    learned = f"{g}/conv_s/kernel" in p
    new_u: Flat = {}
    ck = jax.checkpoint if remat else (lambda f: f)
    stats = lambda s: None if train else _running(p, f"{name}/{s}")  # noqa

    def stage(site, conv, pad, act):
        def run(x):
            y = spade(p, f"{g}/{site}", x, m, stats(site))
            u: Flat = {}
            y = _sn_conv(p, "g", f"{name}/{conv}",
                         nn.leaky_relu(y) if act else y, u, pad)
            return y, u
        return ck(run)

    xs = x
    if learned:
        xs, u = stage("norm_s", "conv_s", 0, False)(x)
        new_u.update(u)
    dx, u = stage("norm_0", "conv_0", 1, True)(x)
    new_u.update(u)
    dx, u = stage("norm_1", "conv_1", 1, True)(dx)
    new_u.update(u)
    return xs + dx, new_u


def g_forward(p: Flat, m, remat: bool = False, train: bool = True
              ) -> Tuple[jnp.ndarray, Flat]:
    """The generator on the conditioning map ``m`` (float32, full extent).
    Returns the image in [-1, 1] and G's power-iteration vectors after
    this forward. ``remat``: every ResBlk, and every SPADE stage inside
    it, is recomputed in the backward (float32 at the cell's batch does
    not fit the chip otherwise)."""
    x = _conv(p, "params_g/fc/Conv_0", m[:, ::DOWN, ::DOWN])
    new_u: Flat = {}
    for name, _, _, up in BLOCKS:
        block = lambda x, name=name: res_block(  # noqa: E731
            p, name, x, m, train, remat)
        x, u = (jax.checkpoint(block) if remat else block)(x)
        new_u.update(u)
        if up:
            x = nn.upsample_nearest(x, 2)
    x = _conv(p, "params_g/conv_img/Conv_0", nn.leaky_relu(x))
    return jnp.tanh(x), new_u


def generator_path(params: Flat, labels_uint8, train: bool,
                   code: Optional[jnp.ndarray] = None):
    """Same contract as every reference: ``(pred, pre_code, moments)``.
    No quantizer on this path."""
    del code
    m = one_hot(jnp.asarray(labels_uint8), label_classes(params))
    return g_forward(params, m, train=train)[0], None, {}


# ---------------------------------------------------------- discriminator


def patch_discriminator(p: Flat, scale: str, x) -> Tuple[list, Flat]:
    """C64 - C128 - C256 - C512 - 1, k4, zero padding 2, strides 2, 2, 2,
    1, 1; LeakyReLU(0.2) after all but the last; the three inner convs
    spectrally normalised and followed by affine-free instance norm.
    Returns the five activations and the vectors after this call."""
    d = f"params_d/{scale}"
    feats, new_u = [], {}
    y = nn.leaky_relu(nn.zero_conv(
        x, p[f"{d}/_PlainConv_0/Conv_0/kernel"],
        p[f"{d}/_PlainConv_0/Conv_0/bias"], stride=2, pad=2))
    feats.append(y)
    for i, stride in enumerate((2, 2, 1)):
        kernel, u = spectral_kernel(
            p[f"{d}/SpectralConv_{i}/kernel"],
            p[f"spectral_d/{scale}/SpectralConv_{i}/u"])
        new_u[f"spectral_d/{scale}/SpectralConv_{i}/u"] = u
        y = nn.leaky_relu(nn.instance_norm(nn.zero_conv(
            y, kernel, p[f"{d}/SpectralConv_{i}/bias"], stride=stride,
            pad=2)))
        feats.append(y)
    feats.append(nn.zero_conv(y, p[f"{d}/_PlainConv_1/Conv_0/kernel"],
                              p[f"{d}/_PlainConv_1/Conv_0/bias"], pad=2))
    return feats, new_u


def discriminator(p: Flat, pair, num_d: int = NUM_D):
    """Finest scale first; scale i sees the pair average-pooled i times.
    The finest is ``scale{num_d - 1}`` in the state's naming."""
    out, new_u, x = [], {}, pair
    for i in range(num_d):
        feats, u = patch_discriminator(p, f"scale{num_d - 1 - i}", x)
        out.append(feats)
        new_u.update(u)
        if i != num_d - 1:
            x = nn.avg_pool_3s2(x)
    return out, new_u


def hinge_d(preds, real: bool):
    sign = -1.0 if real else 1.0
    return sum(jnp.mean(jnp.maximum(1.0 + sign * s[-1], 0))
               for s in preds) / len(preds)


def hinge_g(preds):
    return -sum(jnp.mean(s[-1]) for s in preds) / len(preds)


# ---------------------------------------------------------------- the step


class StepReference:
    """``hyper``: the configuration file's ``train_reference`` group
    (``lr_g``, ``lr_d``, ``beta1``, ``beta2``, ``eps``, ``lambda_feat``,
    ``lambda_vgg``, ``n_layers_D``)."""

    def __init__(self, hyper: dict, row_block: int = ROW_BLOCK):
        self.h, self.rows = hyper, row_block
        self._fwd = jax.jit(self._forward)
        self._dgrads = jax.jit(self._discriminator_grads)
        self._losses = jax.jit(self._row_block_losses)
        self._gback = jax.jit(self._generator_grads)
        self._adam = jax.jit(self._adam_update, static_argnums=(5,))

    def _forward(self, p: Flat, batch):
        m = one_hot(batch["input"], label_classes(p))
        fake, u_g = g_forward(p, m, remat=True)
        return m, nn.to_unit(batch["target"]), fake, u_g

    def _discriminator_grads(self, p: Flat, m, b, fake):
        """D's loss, its gradient and D's vectors after the step, on the
        WHOLE batch. Not in blocks of rows: under ``lax.scan`` at two rows
        a block the chip returned the weight gradients of each scale's
        last two convolutions wrong in float32 (``SpectralConv_2/kernel``
        at 0.79 of its norm; the CPU gives the two ways the same to seven
        digits, and the program's own float32 gradient agrees with this
        one to four; PERF.md section 6, PR 29). D is small: the whole
        batch holds ~1 GiB here."""
        d_params = sub(p, "params_d")
        rest = {k: v for k, v in p.items() if k not in d_params}

        def d_loss(dp):
            q = {**rest, **dp}
            pf, u1 = discriminator(q, jnp.concatenate([m, fake], -1))
            pr, u2 = discriminator({**q, **u1}, jnp.concatenate([m, b], -1))
            return 0.5 * (hinge_d(pf, False) + hinge_d(pr, True)), u2

        (ld, u2), gd = jax.value_and_grad(d_loss, has_aux=True)(d_params)
        return ld, gd, u2

    def _row_block_losses(self, p: Flat, m, b, fake):
        """G's loss parts and their gradient with respect to the generated
        image, block of rows by block of rows (every part is a mean over
        rows; D's instance norm is per row)."""
        h, rows = self.h, min(self.rows, m.shape[0])
        weight = rows / m.shape[0]

        def one(carry, blk):
            mb, bb, fb = blk
            # the real call's features, from the vectors the fake call left
            _, u1 = discriminator(p, jnp.concatenate([mb, fb], -1))
            pred_real, _ = discriminator({**p, **u1},
                                         jnp.concatenate([mb, bb], -1))

            def g_loss(f):
                pf, _ = discriminator(p, jnp.concatenate([mb, f], -1))
                parts = {"g_gan": hinge_g(pf)}
                if h["lambda_feat"] > 0:
                    parts["g_feat"] = feature_matching(
                        pf, pred_real, h["n_layers_D"], h["lambda_feat"])
                if h["lambda_vgg"] > 0:
                    parts["g_vgg"] = h["lambda_vgg"] * vgg_distance(p, f, bb)
                return sum(parts.values()), parts

            (lg, parts), gf = jax.value_and_grad(g_loss, has_aux=True)(fb)
            carry = jax.tree_util.tree_map(lambda c, x: c + weight * x,
                                           carry, {"loss_g": lg, **parts})
            return carry, weight * gf

        zero = {"loss_g": 0.0, "g_gan": 0.0}
        for name in ("feat", "vgg"):
            if h[f"lambda_{name}"] > 0:
                zero[f"g_{name}"] = 0.0
        zero = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                      zero)
        acc, grad_fake = jax.lax.scan(
            one, zero, (_blocks(m, rows), _blocks(b, rows),
                        _blocks(jax.lax.stop_gradient(fake), rows)))
        return acc, grad_fake.reshape(fake.shape)

    def _generator_grads(self, p: Flat, m, grad_fake):
        rest = {k: v for k, v in p.items() if not k.startswith("params_g/")}
        return jax.grad(lambda g: jnp.vdot(
            g_forward({**rest, **g}, m, remat=True)[0], grad_fake))(
                sub(p, "params_g"))

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

    def follow(self, state: Flat, batches):
        """Follow ``batches`` from ``state``. Returns each step's losses,
        the first step's gradients as each optimizer got them, the
        parameters after the last step and the power-iteration vectors
        of G and D after it — all as numpy, by leaf."""
        p = {k: jnp.asarray(v) for k, v in state.items()}
        trainable = {k for k in p if k.split("/", 1)[0] in NETS}
        mom = {k: jnp.zeros_like(p[k]) for k in trainable}
        v = {k: jnp.zeros_like(p[k]) for k in trainable}
        losses, first_grads = [], None
        for i, batch in enumerate(batches):
            count = jnp.asarray(i, jnp.int32)
            m, b, fake, u_g = self._fwd(p, batch)
            loss_d, grads_d, u_d = self._dgrads(p, m, b, fake)
            acc, grad_fake = self._losses(p, m, b, fake)
            acc = {"loss_d": loss_d, **acc}
            del fake
            grads = dict(self._gback(p, m, grad_fake))
            grads.update(grads_d)
            del grad_fake, m, b
            for net, lr in (("params_g", self.h["lr_g"]),
                            ("params_d", self.h["lr_d"])):
                new_p, new_m, new_v = self._adam(
                    sub(p, net), sub(grads, net), sub(mom, net),
                    sub(v, net), count, float(lr))
                p.update(new_p), mom.update(new_m), v.update(new_v)
            p.update(u_g), p.update(u_d)
            losses.append({k: float(x) for k, x in acc.items()})
            if first_grads is None:
                first_grads = {k: np.asarray(g) for k, g in grads.items()}
            del grads
        params = {k: np.asarray(p[k]) for k in trainable}
        vectors = {k: np.asarray(x) for k, x in p.items()
                   if k.startswith("spectral_")}
        return losses, first_grads, params, vectors
