"""Plain reference of the ``big_lama_places256`` configuration: the
generator of fast Fourier convolutions, the discriminator, the dilated
ResNet50 of the perceptual term, every loss and ONE WHOLE TRAIN STEP.

Big LaMa (Suvorov et al., WACV 2022, arXiv:2109.07161, sections 2.1-2.4
and 3; the sizes of github.com/advimman/lama ``big-lama``, as recalled).
NHWC, kernels HWIO; BN = BatchNorm over N, H, W with the batch's own
biased moments, eps 1e-5; every FFC convolution pads by reflection and
has no bias.

  x image in [0, 1], m mask (1 = missing), input u = [x * (1 - m), m].
  FFC(x_l, x_g): y_l = conv3(x_l; l2l) + conv3(x_g; g2l);
                 y_g = conv3(x_l; l2g) + S(x_g); then BN, ReLU a branch.
  S(x_g) = conv1(h + F(h)), h = ReLU(BN(conv1(x_g; C_g -> C_g/2))).
  F(h): Z = rfft2(h) over H, W, orthonormal ([H, W/2+1] complex);
    channels 2i = Re Z_i, 2i + 1 = Im Z_i; conv1 (2c -> 2c), BN, ReLU;
    back to complex; irfft2 to [H, W], orthonormal.
  G(u): reflect pad 3, conv7 4 -> 64, BN, ReLU; three conv3 stride 2
    (reflect pad 1), BN, ReLU, 64 -> 512; split 128 local / 384 global;
    18 blocks x -> x + FFC(FFC(x)) on both branches; concatenate; three
    ConvTranspose(k3, stride 2, pad 1, output pad 1; the source's bias,
    which the BN behind it cancels, left out), BN, ReLU, 512 -> 64;
    reflect pad 3, conv7 64 -> 3 (bias); y = sigmoid.
  D(x): conv4 s2 (3 -> 64, bias) lrelu 0.2; conv4 s2 BN lrelu to 128,
    256, 512; conv4 s1 BN lrelu 512; conv4 s1 (512 -> 1, bias); zero pad
    2; every layer's output is a feature.
  phi: the dilated ResNet50 (deep stem, bottlenecks 3-4-6-3, stages 3 and
    4 at stage 2's extent with dilation 2 and 4), frozen BN, on (x - mean)
    / std of ImageNet; its four stages' outputs.
  Step (this Trainer's; the configuration file states the departures):
    y = G(u) ONCE. m' = m resized (nearest) to the logits.
    L_D = 0.5 * ( mean softplus(-D(x)) + gp_coef * mean_n |grad_x01 sum
      D(x)|^2 + mean( softplus(D(sg y)) * m' + softplus(-D(sg y)) * (1 -
      m') ) ), the fake call first, D's running statistics updated by each
      call (0.9 old + 0.1 batch, biased variance);
    L_G = gan_weight * mean softplus(-D(y)) + l1_weight * mean(|y - x| *
      (1 - m)) + fm_weight * mean_layers mse(D_l(y), sg D_l(x)) +
      hrf_weight * sum_stages mse(phi_s(y), sg phi_s(x)), D the step's
      start; G's running statistics updated once; Adam(beta1, beta2, eps)
      on both, G at lr_g, D at lr_d.

In this system images travel in [-1, 1]: the wire holds uint8, a network
maps ``(v - 127.5) / 127.5`` to the authors' [0, 1] where it needs them
(G at its first layer, phi), D reads [-1, 1], the L1 is taken there
(``l1_weight`` states the [0, 1] weight: half of it multiplies the [-1, 1]
difference) and the penalty's gradient is per unit of the [0, 1] image
(twice the gradient with respect to the [-1, 1] tensor, four times its
square).

Only ``jax.numpy`` / ``lax`` in float32 under
``jax.default_matmul_precision("highest")`` (the ``fft`` is ``jnp.fft``,
held against a DFT by matrix products in the tests); nothing of the
program is imported, and of this package ``nn`` alone. The structure (how
many blocks, D's depth) is read off the names of the state's leaves.
G's and D's BatchNorm couple the images, so everything of G and D runs on
the whole batch. In the step G runs PART BY PART (encoder, each residual
block, decoder: one jitted function serves all 18 blocks, so a cold run
compiles one block and not eighteen) and its gradient is pulled back the
same way, each part recomputing its activations from its input; phi's is
frozen, so it runs in BLOCKS OF ROWS (``ROWS``), each block a call of one
jitted function and the sums made outside it (no ``lax.scan``).
``HOST`` False: the followed steps run on the accelerator in float32
(benchmark/tools/control_inpaint.py ``--kind chip_reference`` holds that
program against the host's at the cell's own size).

State is a flat dict: ``params_g/block_3/conv1/g2g/fu/conv/kernel``,
``batch_stats_g/stem_bn/BatchNorm_0/mean``, ``params_d/scale0/
_PlainConv_1/Conv_0/kernel``, ``batch_stats_d/scale0/BatchNorm_0/
BatchNorm_0/var``, ``vgg/layer3_0/conv2/kernel``.
"""

from __future__ import annotations

import contextlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import nn

BATCH_KEY = "input"
NETS = ("params_g", "params_d")
STATS = ("batch_stats_g", "batch_stats_d")
MASK_CHANNEL = 3
N_DOWN = 3
LRELU = 0.2
MOMENTUM = 0.9
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
#: (blocks, planes, stride, dilation handed to the stage)
RESNET_STAGES = ((3, 64, 1, 1), (4, 128, 2, 1), (6, 256, 2, 2),
                 (3, 512, 2, 4))
#: rows of the batch a call of the perceptual network takes
ROWS = 4
#: False: the followed steps run where the process's default device is
#: (the chip), in float32 at the highest precision; True: on the host CPU
HOST = False



def named_leaves(state) -> Dict[str, str]:
    """The leaves whose first gradient is also compared as a VECTOR: the
    1x1 kernel of the LAST Fourier unit (the last block's second FFC: the
    gradient of an early block has passed through every later block's
    BatchNorms and ReLUs, whose masks bf16 flips, and holds little of its
    direction at a seeded start: PERF.md section 6, PR 41) and D's last
    kernel (under the penalty)."""
    last = max(int(k.split("/")[1][len("block_"):]) for k in state
               if k.startswith("params_g/block_"))
    return {
        "fu_kernel": f"params_g/block_{last}/conv2/g2g/fu/conv/kernel",
        "d_last_kernel": "params_d/scale0/_PlainConv_5/Conv_0/kernel",
    }


# Limits of the comparison that decides ``correct`` (PERF.md section 2
# has the readings they were set from: the sound program's largest over
# its seeds, and the controls that must come out not correct).
LIMITS = {
    # the generator's image against this reference, in 8-bit levels: as
    # the step computes it (bf16) and from the same modules at float32
    "generator_mean_abs_levels": 1.5,
    "generator_p99_abs_levels": 5.5,
    "generator_f32_mean_abs_levels": 0.004,
    # the followed steps: every term at step one
    "step1_loss_d_rel_gap": 0.01,
    "step1_loss_d_r1_rel_gap": 0.02,
    "step1_g_gan_rel_gap": 0.01,
    "step1_g_feat_rel_gap": 0.005,
    "step1_g_hrf_rel_gap": 0.01,
    "step1_g_l1_known_rel_gap": 0.001,
    # first gradients: D's worst leaf, and two leaves as vectors
    "first_grad_d_worst_leaf_gap": 0.04,
    "first_grad_d_last_kernel_diff_over_norm": 0.04,
    "first_grad_fu_kernel_diff_over_norm": 0.3,
    # a state left unchanged reads 1; statistics the step does not thread
    # stay at their start, a fifth of the way behind after two steps at
    # momentum 0.9 (the rehearsal plants both)
    "params_change_g_worst_leaf_gap": 0.2,
    "params_change_d_worst_leaf_gap": 0.05,
    "batch_stats_g_widest_gap": 0.25,
    "batch_stats_d_widest_gap": 0.008,
}

Flat = Dict[str, jnp.ndarray]


def sub(p: Flat, prefix: str) -> Flat:
    return {k: v for k, v in p.items() if k.startswith(prefix + "/")}


def _highest():
    return jax.default_matmul_precision("highest")


# ------------------------------------------------------------------ layers


def conv(x, kernel, stride: int = 1, pad: int = 0, dilation: int = 1):
    """A cross-correlation on zero padding ``pad``."""
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), ((pad, pad), (pad, pad)),
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=nn.HIGHEST)


def reflect_conv(x, kernel, stride: int = 1):
    return nn.reflect_conv(x, kernel, None, stride)


def conv_transpose(x, kernel):
    """ConvTranspose2d(k3, stride 2, padding 1, output_padding 1) as the
    convolution of the input dilated by 2 and padded (1, 2), with the
    kernel as stored (no flip)."""
    return lax.conv_general_dilated(
        x, kernel, (1, 1), ((1, 2), (1, 2)), lhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=nn.HIGHEST)


def batch_norm(p: Flat, new: Flat, net: str, path: str, x, train: bool):
    """BN of the layer ``path`` of ``net`` ("g" / "d"); in training the
    running statistics after the call go into ``new``."""
    scale = p[f"params_{net}/{path}/BatchNorm_0/scale"]
    bias = p[f"params_{net}/{path}/BatchNorm_0/bias"]
    s = f"batch_stats_{net}/{path}/BatchNorm_0"
    if not train:
        return nn.batch_norm(x, scale, bias,
                             (p[f"{s}/mean"], p[f"{s}/var"]))[0]
    y, (mean, var) = nn.batch_norm(x, scale, bias)
    new[f"{s}/mean"] = MOMENTUM * p[f"{s}/mean"] + (1 - MOMENTUM) * mean
    new[f"{s}/var"] = MOMENTUM * p[f"{s}/var"] + (1 - MOMENTUM) * var
    return y


#: the pair of transforms of a Fourier unit
TRANSFORMS = (jnp.fft.rfft2, jnp.fft.irfft2)


def rounded_transforms(dtype):
    """:data:`TRANSFORMS` with their operands rounded to ``dtype``'s
    exponent and mantissa bits (``lax.reduce_precision``; the transforms
    themselves stay float32): the reference of a CONTROL, what a program
    that handed its transforms narrower operands would compute."""
    info = jnp.finfo(dtype)
    narrow = lambda a: lax.reduce_precision(a, info.nexp, info.nmant)  # noqa

    def rfft2(h, **kw):
        return jnp.fft.rfft2(narrow(h), **kw)

    def irfft2(z, **kw):
        return jnp.fft.irfft2(lax.complex(narrow(z.real), narrow(z.imag)),
                              **kw)

    return rfft2, irfft2


def fourier_unit(p: Flat, new: Flat, path: str, h, train: bool,
                 fft=TRANSFORMS):
    """``F(h)``; ``fft``: the pair of transforms (the tests put a DFT by
    matrix products in their place, a control :func:`rounded_transforms`).
    """
    n, hh, ww, c = h.shape
    z = fft[0](h, axes=(1, 2), norm="ortho")
    z = jnp.stack([z.real, z.imag], axis=-1).reshape(n, hh, ww // 2 + 1,
                                                     2 * c)
    z = conv(z, p[f"params_g/{path}/conv/kernel"])
    z = jnp.maximum(batch_norm(p, new, "g", f"{path}/bn", z, train), 0)
    z = z.reshape(n, hh, ww // 2 + 1, c, 2)
    return fft[1](lax.complex(z[..., 0], z[..., 1]), s=(hh, ww),
                  axes=(1, 2), norm="ortho")


def spectral_transform(p: Flat, new: Flat, path: str, x, train: bool,
                       fft=TRANSFORMS):
    h = conv(x, p[f"params_g/{path}/conv1/kernel"])
    h = jnp.maximum(batch_norm(p, new, "g", f"{path}/bn1", h, train), 0)
    f = fourier_unit(p, new, f"{path}/fu", h, train, fft)
    return conv(h + f, p[f"params_g/{path}/conv2/kernel"])


def ffc_bn_act(p: Flat, new: Flat, path: str, x_l, x_g, train: bool,
               fft=TRANSFORMS):
    k = lambda name: p[f"params_g/{path}/{name}/kernel"]  # noqa: E731
    y_l = reflect_conv(x_l, k("l2l")) + reflect_conv(x_g, k("g2l"))
    y_g = reflect_conv(x_l, k("l2g")) + spectral_transform(
        p, new, f"{path}/g2g", x_g, train, fft)
    return (jnp.maximum(batch_norm(p, new, "g", f"{path}/bn_l", y_l, train),
                        0),
            jnp.maximum(batch_norm(p, new, "g", f"{path}/bn_g", y_g, train),
                        0))


def n_blocks(p: Flat) -> int:
    n = 0
    while f"params_g/block_{n}/conv1/l2l/kernel" in p:
        n += 1
    return n


#: a residual block's leaves are handed to :func:`block` under this name in
#: place of ``block_<i>``: ONE function (one compile) serves every block
BLOCK = "block"


def encode(p: Flat, u, train: bool):
    """The stem and the three downsamplings: their output (both branches
    side by side) and the running statistics they made."""
    new: Flat = {}
    bn_relu = lambda path, y: jnp.maximum(  # noqa: E731
        batch_norm(p, new, "g", path, y, train), 0)
    y = u * 0.5 + 0.5
    y = bn_relu("stem_bn", reflect_conv(y, p["params_g/stem/Conv_0/kernel"]))
    for i in range(N_DOWN):
        y = bn_relu(f"down_{i}_bn", reflect_conv(
            y, p[f"params_g/down_{i}/Conv_0/kernel"], stride=2))
    return y, new


def split(p: Flat, y):
    """The encoder's output as the pair ``(y_l, y_g)``: the first block's
    local kernel says how many channels are local."""
    c_l = p["params_g/block_0/conv1/l2l/kernel"].shape[2]
    return y[..., :c_l], y[..., c_l:]


def outer_leaves(p: Flat) -> Flat:
    """G's leaves outside its residual blocks (the encoder's and the
    decoder's)."""
    return {k: v for k, v in p.items()
            if k.startswith(("params_g/", "batch_stats_g/"))
            and "/block_" not in k}


def block(q: Flat, a_l, a_g, train: bool, fft=TRANSFORMS):
    """One residual block on the pair; ``q`` holds its leaves under the
    name :data:`BLOCK` (:func:`block_leaves`)."""
    made: Flat = {}
    b_l, b_g = ffc_bn_act(q, made, f"{BLOCK}/conv1", a_l, a_g, train, fft)
    b_l, b_g = ffc_bn_act(q, made, f"{BLOCK}/conv2", b_l, b_g, train, fft)
    return a_l + b_l, a_g + b_g, made


def block_leaves(p: Flat, i: int) -> Flat:
    """Block ``i``'s parameters and statistics under the name
    :data:`BLOCK`."""
    return {k.replace(f"/block_{i}/", f"/{BLOCK}/"): v
            for k, v in p.items() if f"/block_{i}/" in k}


def block_named(tree: Flat, i: int) -> Flat:
    """The inverse of :func:`block_leaves` on what a block hands back."""
    return {k.replace(f"/{BLOCK}/", f"/block_{i}/"): v
            for k, v in tree.items()}


def decode(p: Flat, u, y_l, y_g, train: bool):
    """The three transposed convolutions and the head: the predicted image
    in [-1, 1] in training, the composite in evaluation."""
    new: Flat = {}
    y = jnp.concatenate([y_l, y_g], axis=-1)
    for i in range(N_DOWN):
        y = jnp.maximum(batch_norm(p, new, "g", f"up_{i}_bn", conv_transpose(
            y, p[f"params_g/up_{i}/kernel"]), train), 0)
    y = reflect_conv(y, p["params_g/head/Conv_0/kernel"]) \
        + p["params_g/head/Conv_0/bias"]
    pred = 2.0 * jax.nn.sigmoid(y) - 1.0
    if train:
        return pred, new
    m = (u[..., MASK_CHANNEL:MASK_CHANNEL + 1] > 0).astype(pred.dtype)
    return m * pred + (1 - m) * u[..., :MASK_CHANNEL], new


def generator(p: Flat, u, train: bool, parts=(encode, block, decode),
              fft=TRANSFORMS) -> Tuple[jnp.ndarray, Flat]:
    """``u`` ``[N, H, W, 4]`` in [-1, 1] (the masked image, the mask as
    -1 / 1). The predicted image in [-1, 1] in training, the composite in
    evaluation; and G's running statistics after the call. ``parts``:
    the three functions, or jitted copies of them (one compile serves
    every block); ``fft``: the blocks' pair of transforms."""
    enc, blk, dec = parts
    y, new = enc(outer_leaves(p), u, train)
    y_l, y_g = split(p, y)
    for i in range(n_blocks(p)):
        y_l, y_g, made = blk(block_leaves(p, i), y_l, y_g, train, fft)
        new.update(block_named(made, i))
    pred, made = dec(outer_leaves(p), u, y_l, y_g, train)
    return pred, {**new, **made}


_JITTED_PARTS = (jax.jit(encode, static_argnames="train"),
                 jax.jit(block, static_argnames=("train", "fft")),
                 jax.jit(decode, static_argnames="train"))


def generator_path(params: Flat, input_uint8, train: bool, fft=TRANSFORMS):
    """What the benchmark's generator check calls: the image for a uint8
    wire input ``[N, H, W, 4]``, and G's running statistics after it;
    part by part, each a jitted call, on the default device."""
    with _highest():
        return generator({k: jnp.asarray(v) for k, v in params.items()},
                         nn.to_unit(jnp.asarray(input_uint8)), train,
                         _JITTED_PARTS, fft)


def discriminator(p: Flat, x, scale: str = "scale0"
                  ) -> Tuple[List[jnp.ndarray], Flat]:
    """Every layer's output (the logits last) for ``x`` in [-1, 1], and
    D's running statistics after this call."""
    d = f"params_d/{scale}"
    new: Flat = {}
    k = lambda i: p[f"{d}/_PlainConv_{i}/Conv_0/kernel"]  # noqa: E731
    y = nn.leaky_relu(conv(x, k(0), 2, 2)
                      + p[f"{d}/_PlainConv_0/Conv_0/bias"], LRELU)
    feats = [y]
    i = 1
    while f"{d}/BatchNorm_{i - 1}/BatchNorm_0/scale" in p:
        # every inner convolution but the last has stride 2
        last = f"{d}/BatchNorm_{i}/BatchNorm_0/scale" not in p
        y = conv(y, k(i), 1 if last else 2, 2)
        y = nn.leaky_relu(batch_norm(p, new, "d",
                                     f"{scale}/BatchNorm_{i - 1}", y, True),
                          LRELU)
        feats.append(y)
        i += 1
    feats.append(conv(y, k(i), 1, 2) + p[f"{d}/_PlainConv_{i}/Conv_0/bias"])
    return feats, new


def resnet50_dilated(p: Flat, x) -> List[jnp.ndarray]:
    """phi's four stages for ``x`` in [-1, 1]."""
    def bn(path, y):
        a = p[f"vgg/{path}/scale"] * lax.rsqrt(p[f"vgg/{path}/var"]
                                               + nn.EPS)
        return y * a + (p[f"vgg/{path}/bias"] - p[f"vgg/{path}/mean"] * a)

    k = lambda path: p[f"vgg/{path}/kernel"]  # noqa: E731
    y = ((x + 1.0) * 0.5 - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(
        IMAGENET_STD)
    for i, stride in enumerate((2, 1, 1), start=1):
        y = jnp.maximum(bn(f"stem_bn{i}",
                           conv(y, k(f"stem_conv{i}"), stride, 1)), 0)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    outs = []
    for s, (blocks, _, stride, dilate) in enumerate(RESNET_STAGES, start=1):
        for b in range(blocks):
            # ``_nostride_dilate``: a dilated stage has no stride; its
            # first block's k3 convolution takes half the dilation
            if dilate == 1:
                k3_stride, k3_dil = (stride if b == 0 else 1), 1
            else:
                k3_stride, k3_dil = 1, (dilate // 2 if b == 0 else dilate)
            name = f"layer{s}_{b}"
            z = jnp.maximum(bn(f"{name}/bn1", conv(y, k(f"{name}/conv1"))),
                            0)
            z = jnp.maximum(bn(f"{name}/bn2", conv(
                z, k(f"{name}/conv2"), k3_stride, k3_dil, k3_dil)), 0)
            z = bn(f"{name}/bn3", conv(z, k(f"{name}/conv3")))
            if f"vgg/{name}/downsample/kernel" in p:
                y = bn(f"{name}/downsample_bn",
                       conv(y, k(f"{name}/downsample"), k3_stride))
            y = jnp.maximum(z + y, 0)
        outs.append(y)
    return outs


# ------------------------------------------------------------------ losses


def resize_nearest(m, hw):
    """``F.interpolate(mode="nearest")``: output pixel i reads input pixel
    floor(i * H / h)."""
    rows = (jnp.arange(hw[0]) * m.shape[1]) // hw[0]
    cols = (jnp.arange(hw[1]) * m.shape[2]) // hw[1]
    return m[:, rows][:, :, cols]


def r1(p: Flat, x):
    """``mean_n |grad_x01 sum D(x)|^2`` for ``x`` in [-1, 1], the real
    call's features and D's statistics after it: ``jax.grad`` inside, so a
    ``jax.grad`` of this with respect to D's parameters is the second
    order the penalty asks for."""
    def logits_sum(v):
        feats, new = discriminator(p, v)
        return jnp.sum(feats[-1]), (feats, new)

    grad, (feats, new) = jax.grad(logits_sum, has_aux=True)(x)
    return 4.0 * jnp.mean(jnp.sum(jnp.square(grad), axis=(1, 2, 3))), \
        feats, new


def d_loss(p: Flat, x, y, m, gp_coef: float, use_penalty: bool = True):
    """``L_D``, the fake call first; D's statistics after both calls, the
    penalty's value and the real call's features."""
    fake, s1 = discriminator(p, lax.stop_gradient(y))
    penalty, real, s2 = r1({**p, **s1}, x)
    known = 1.0 - resize_nearest(m, fake[-1].shape[1:3])
    loss_fake = jnp.mean(known * jax.nn.softplus(-fake[-1])
                         + (1.0 - known) * jax.nn.softplus(fake[-1]))
    loss_real = jnp.mean(jax.nn.softplus(-real[-1]))
    total = 0.5 * (loss_fake + loss_real
                   + (gp_coef * penalty if use_penalty else 0.0))
    return total, (s2, penalty, real)


def g_terms_through_d(p: Flat, y, real_feats):
    """``mean softplus(-D(y))`` and ``mean_layers mse(D_l(y), D_l(x))``."""
    fake, _ = discriminator(p, y)
    gan = jnp.mean(jax.nn.softplus(-fake[-1]))
    fm = jnp.mean(jnp.stack([
        jnp.mean(jnp.square(f - lax.stop_gradient(r)))
        for f, r in zip(fake[:-1], real_feats[:-1])]))
    return gan, fm


def l1_known(y, x, m, ignore_mask: bool = False):
    """``mean(|y - x| * (1 - m))`` over every element, images in
    [-1, 1]."""
    w = 1.0 if ignore_mask else (1.0 - m)
    return jnp.mean(jnp.abs(y - x) * w)


def hrf_sum(p: Flat, y, x):
    """``sum_stages sum_elements (phi_s(y) - phi_s(x))^2 / (elements of
    ONE image's stage)``: a block of rows' share before the division by
    the batch."""
    total = 0.0
    for fy, fx in zip(resnet50_dilated(p, y),
                      resnet50_dilated(p, lax.stop_gradient(x))):
        total = total + jnp.sum(jnp.square(fy - fx)) / fy[0].size
    return total


# ---------------------------------------------------------------- the step


class StepReference:
    """``hyper``: the configuration file's ``train_reference`` group
    (``lr_g``, ``lr_d``, ``beta1``, ``beta2``, ``eps``, ``gan_weight``,
    ``l1_weight``, ``fm_weight``, ``hrf_weight``, ``gp_coef``). The
    keyword switches plant the faults the controls and rehearsals hold
    ``correct`` against: the penalty left out of D's loss, the mask
    ignored in the L1."""

    def __init__(self, hyper: dict, rows: int = ROWS,
                 use_penalty: bool = True, ignore_mask_in_l1: bool = False):
        self.h = hyper
        self.rows = rows
        self.use_penalty = use_penalty
        self.ignore_mask_in_l1 = ignore_mask_in_l1
        self._dside = jax.jit(self._discriminator_side)
        self._hrf = jax.jit(self._hrf_block)
        # the pullbacks of G's three parts; each takes the part's inputs
        # again (its activations are recomputed, the values are the same)
        self._enc_vjp = jax.jit(lambda p, u, ct: jax.vjp(
            lambda q: encode(q, u, True)[0], p)[1](ct)[0])
        self._blk_vjp = jax.jit(lambda q, a_l, a_g, ct_l, ct_g: jax.vjp(
            lambda w, x_l, x_g: block(w, x_l, x_g, True)[:2], q, a_l, a_g
        )[1]((ct_l, ct_g)))
        self._dec_vjp = jax.jit(lambda p, u, y_l, y_g, ct: jax.vjp(
            lambda q, x_l, x_g: decode(q, u, x_l, x_g, True)[0], p, y_l, y_g
        )[1](ct))
        self._adam = jax.jit(self._adam_update, static_argnums=(5,))
        self._pullbacks_called = False

    def _discriminator_side(self, p: Flat, x, y, m):
        """On the whole batch: D's loss, gradient and statistics, and
        what of G's loss goes through D or straight to the image, with
        its gradient with respect to the image."""
        h = self.h
        d_params = sub(p, "params_d")
        rest = {k: v for k, v in p.items() if k not in d_params}
        (ld, (stats, penalty, real)), gd = jax.value_and_grad(
            lambda dp: d_loss({**rest, **dp}, x, y, m, h["gp_coef"],
                              self.use_penalty), has_aux=True)(d_params)

        def through(v):
            gan, fm = g_terms_through_d(p, v, real)
            l1 = l1_known(v, x, m, self.ignore_mask_in_l1)
            # the [0, 1] weight on a [-1, 1] difference
            parts = {"g_gan": h["gan_weight"] * gan,
                     "g_feat": h["fm_weight"] * fm,
                     "g_l1_known": 0.5 * h["l1_weight"] * l1}
            return sum(parts.values()), parts

        (_, parts), ct = jax.value_and_grad(through, has_aux=True)(y)
        return ld, gd, stats, penalty, parts, ct

    def _hrf_block(self, p: Flat, y, x, n: int):
        """A block of rows' share of the perceptual term and of its
        gradient with respect to the image."""
        return jax.value_and_grad(
            lambda v: self.h["hrf_weight"] * hrf_sum(p, v, x) / n)(y)

    def _generator(self, p: Flat, u):
        """G part by part: the image, the statistics, and each block's
        input pair (what the backward starts the block from again)."""
        enc, blk, dec = _JITTED_PARTS
        y, new = enc(outer_leaves(p), u, True)
        y_l, y_g = split(p, y)
        inputs = []
        for i in range(n_blocks(p)):
            inputs.append((y_l, y_g))
            y_l, y_g, made = blk(block_leaves(p, i), y_l, y_g, True)
            new.update(block_named(made, i))
        pred, made = dec(outer_leaves(p), u, y_l, y_g, True)
        return pred, {**new, **made}, inputs + [(y_l, y_g)]

    def _generator_grads(self, p: Flat, u, ct, pairs) -> Flat:
        """The gradient of ``vdot(G(u), ct)`` with respect to G's
        parameters, pulled back part by part (the chain rule in the
        open: decoder, the blocks last to first, encoder)."""
        g_of = lambda tree: sub(tree, "params_g")  # noqa: E731
        outer = outer_leaves(p)
        grads, ct_l, ct_g = self._dec_vjp(outer, u, *pairs[-1], ct)
        grads = g_of(grads)
        for i in reversed(range(n_blocks(p))):
            own, ct_l, ct_g = self._blk_vjp(block_leaves(p, i), *pairs[i],
                                            ct_l, ct_g)
            grads.update(g_of(block_named(own, i)))
        encoder = self._enc_vjp(outer, u,
                                jnp.concatenate([ct_l, ct_g], axis=-1))
        # a leaf of the decoder has a zero from the encoder's pull, and
        # the other way round: the two sum
        for k, g in g_of(encoder).items():
            grads[k] = grads[k] + g
        return grads

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

    def step(self, p: Flat, u, x):
        """One step from ``p`` on the float32 batch (``u`` the input,
        ``x`` the target, both in [-1, 1]): the losses, both nets'
        gradients and the running statistics of G and D after it."""
        n = x.shape[0]
        m = (u[..., MASK_CHANNEL:MASK_CHANNEL + 1] > 0).astype(jnp.float32)
        y, stats_g, pairs = self._generator(p, u)

        def perceptual():
            hrf, ct_hrf = 0.0, []
            for at in range(0, n, self.rows):
                rows = slice(at, at + self.rows)
                value, grad = self._hrf(p, y[rows], x[rows], n)
                hrf, ct_hrf = hrf + value, ct_hrf + [grad]
            return hrf, ct_hrf

        # two programs that wait for nothing of each other: side by side,
        # so that a cold run compiles them at once; and with them, once,
        # G's three pullbacks on zeros (results dropped): most of a cold
        # run's seconds are these compiles one after the other (read on
        # the chip: 36 s off a cold run's followed steps, 4 s onto a warm
        # run's; PERF.md section 6, PR 41)
        thunks = [lambda: self._dside(p, x, y, m), perceptual]
        if not self._pullbacks_called:
            self._pullbacks_called = True
            zeros, outer = jnp.zeros_like, outer_leaves(p)
            thunks += [
                lambda: self._dec_vjp(outer, u, *pairs[-1], zeros(y)),
                lambda: self._blk_vjp(block_leaves(p, 0), *pairs[0],
                                      *map(zeros, pairs[0])),
                lambda: self._enc_vjp(
                    outer, u, zeros(jnp.concatenate(pairs[0], axis=-1)))]
        (loss_d, grads_d, stats_d, penalty, parts, ct), (hrf, ct_hrf) = (
            self._side_by_side(*thunks)[:2])
        grads_g = self._generator_grads(p, u, ct + jnp.concatenate(ct_hrf),
                                        pairs)
        losses = {"loss_d": loss_d, "loss_d_r1": penalty, "g_hrf": hrf,
                  **parts}
        losses["loss_g"] = hrf + sum(parts.values())
        return losses, {**grads_g, **grads_d}, {**stats_g, **stats_d}

    @staticmethod
    def _side_by_side(*thunks):
        """Each thunk in a thread of its own, under the caller's device,
        matmul precision and x64 setting (jax's contexts are a thread's
        own and a new thread starts from the defaults)."""
        now = jax.config
        held = (jax.default_device, now.jax_default_device), (
            jax.default_matmul_precision, now.jax_default_matmul_precision
        ), (jax.enable_x64, now.jax_enable_x64)

        def as_the_caller(thunk):
            with contextlib.ExitStack() as stack:
                for enter, value in held:
                    stack.enter_context(enter(value))
                return thunk()

        with ThreadPoolExecutor(len(thunks)) as pool:
            return list(pool.map(as_the_caller, thunks))

    def follow(self, state: Flat, batches):
        """Follow ``batches`` (uint8 ``input`` / ``target``) from
        ``state``. Returns each step's losses, the first step's gradients
        as each optimizer got them, the parameters after the last step
        and the running statistics of G and D after it, all as numpy, by
        leaf."""
        place = (jax.default_device(jax.devices("cpu")[0]) if HOST
                 else contextlib.nullcontext())
        with place, _highest():
            return self._follow(state, batches)

    def _follow(self, state: Flat, batches):
        p = {k: jnp.asarray(v) for k, v in state.items()}
        trainable = {k for k in p if k.split("/", 1)[0] in NETS}
        mom = {k: jnp.zeros_like(p[k]) for k in trainable}
        v = {k: jnp.zeros_like(p[k]) for k in trainable}
        all_losses, first_grads, stats = [], None, {}
        for i, batch in enumerate(batches):
            count = jnp.asarray(i, jnp.int32)
            losses, grads, stats = self.step(
                p, nn.to_unit(jnp.asarray(batch["input"])),
                nn.to_unit(jnp.asarray(batch["target"])))
            for net, lr in (("params_g", self.h["lr_g"]),
                            ("params_d", self.h["lr_d"])):
                new_p, new_m, new_v = self._adam(
                    sub(p, net), sub(grads, net), sub(mom, net),
                    sub(v, net), count, lr)
                p.update(new_p)
                mom.update(new_m)
                v.update(new_v)
            p.update(stats)
            all_losses.append({k: float(x) for k, x in losses.items()})
            if first_grads is None:
                first_grads = {k: np.asarray(g) for k, g in grads.items()}
        return (all_losses, first_grads,
                {k: np.asarray(p[k]) for k in trainable},
                {k: np.asarray(x) for k, x in stats.items()})
