"""Plain reference of the ``swinir_m_realsr_x4_gan`` configuration: the
SwinIR generator, the U-Net discriminator with its spectral norm, the
pre-activation VGG19 perceptual term and ONE WHOLE TRAIN STEP.

SwinIR (Liang et al., ICCVW 2021, arXiv:2108.10257, sections 3 and 4.1) at
the sizes of the authors' real-world x4 GAN model; D is Real-ESRGAN's
``UNetDiscriminatorSN`` (Wang et al. 2021, arXiv:2107.10833). NHWC; images
in [0, 1]; the tokens of an image are its positions in row-major order;
``LN`` = LayerNorm over the channels (eps 1e-5, affine); every k3
convolution pads 1 with zeros and has a bias unless said.

  head:  f0 = conv3(x - m, 3 -> C), m = (0.4488, 0.4371, 0.4040); t = LN(f0)
  STL_j(t) = u + DP_j(MLP(LN(u))),  u = t + DP_j(WMSA_s(LN(t))); s = 0 for
    even j, window / 2 for odd j within a group; MLP(h) = fc2(gelu(fc1(h))),
    gelu(x) = x/2 (1 + erf(x / sqrt 2)); DP_j multiplies an image's branch
    by keep / (1 - p_j), keep in {0, 1}, p_j linear from 0 to 0.1 over all
    layers (a row of ``keep`` for each of a layer's two branches).
  WMSA_s(h): token (y, x) of window (wy, wx) of the rolled image is the
    image's token ((wy w + y + s) mod H, (wx w + x + s) mod W); q, k, v =
    split(Linear(C -> 3C)(h)), channel = which * C + head * d + i; A =
    softmax(q k^T d^-0.5 + B[idx] + M_s), idx = (dy + w - 1)(2w - 1) + dx +
    w - 1, M_s = -100 between two tokens whose ROLLED positions lie in
    different bands (rows [0, H - w), [H - w, H - s), [H - s, H); columns
    alike), 0 for s = 0; Linear(C -> C)(A v); every token back to where it
    came from.
  RSTB_i(t) = t + conv3(STL_n(... STL_1(t)));  f = f0 + conv3(LN(RSTB_G(...
    RSTB_1(t))))
  upsampler: a = lrelu_0.01(conv3(f, C -> 64)); a = lrelu_0.2(conv3(
    nearest_x2(a))) twice; a = lrelu_0.2(conv3(a)); y = conv3(a, 64 -> 3) + m
  SN(W, u): W as [out, -1]; v = W^T u / |W^T u|; u' = W v / |W v|; sigma =
    u'^T W v (u', v constants); the convolution runs on W / sigma and u'
    replaces u (one iteration a call).
  D: x0 = lrelu(conv3(y, 3 -> F)); x1..x3 = lrelu(SN conv4_s2_pad1, no
    bias), F -> 2F -> 4F -> 8F; x4 = lrelu(SN conv3(up(x3), -> 4F)) + x2;
    x5 = lrelu(SN conv3(up(x4), -> 2F)) + x1; x6 = lrelu(SN conv3(up(x5), ->
    F)) + x0; two lrelu(SN conv3(F -> F)); conv3(F -> 1). lrelu slope 0.2;
    up = bilinear x2 with align_corners False: out[2i] = x[i] 3/4 +
    x[i - 1] / 4, out[2i + 1] = x[i] 3/4 + x[i + 1] / 4, indices clamped.
  phi_l: VGG19's conv1_2, conv2_2, conv3_4, conv4_4, conv5_4 outputs BEFORE
    the ReLU, on (image - mean) / std of ImageNet; w = (0.1, 0.1, 1, 1, 1).
  Step (this Trainer's; the configuration file states the departures):
    y = G(lq) ONCE, with the keep masks the program drew. L_G = l1_weight
    mean|y - r| + perceptual_weight sum_l w_l mean|phi_l(y) - phi_l(r)| +
    gan_weight BCE(D(y), 1); L_D = d_loss_scale (BCE(D(sg(y)), 0) + BCE(D(r),
    1)), the fake call first, D's u advanced by each call and G's GAN term
    reading the fake call's logits; BCE on logits; Adam(beta1, beta2, eps)
    on both; ema = ema_decay ema + (1 - ema_decay) params_g after G's update.

Only ``jax.numpy`` / ``lax`` in float32, every product at
``Precision.HIGHEST``; nothing of the program is imported, and of this
package ``nn`` alone. The structure (groups, layers, heads, window) is read
off the state's leaves. Nothing couples two images (LayerNorm is per token,
D has no batch statistic, sigma is a function of the weights alone), so the
step is taken ``CHUNK`` images at a time, each chunk a call of one jitted
function and the sums made outside it (no ``lax.scan``), ON THE HOST CPU
(``HOST``: PERF.md sections 6 and 7 record wrong float32 gradients from
this chip for one-image programs of another configuration).

State is a flat dict: ``params_g/group_0_layer_0/attn/qkv/kernel``,
``params_g/group_0_layer_0/attn/relative_position_bias_table``,
``params_d/conv3/kernel``, ``spectral_d/conv3/u``, ``ema_g/...``,
``vgg/conv1_1/kernel``.
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nn

BATCH_KEY = "input"
NETS = ("params_g", "params_d")
MEAN = (0.4488, 0.4371, 0.4040)
LN_EPS = 1e-5
DROP_PATH = 0.1
MASK_VALUE = -100.0
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
VGG_LAYERS = ((64, 64), (128, 128), (256, 256, 256, 256),
              (512, 512, 512, 512), (512, 512, 512, 512))
TAP_WEIGHTS = (0.1, 0.1, 1.0, 1.0, 1.0)
#: the leaves the comparison names beside each net's worst
NAMED_LEAVES = {
    "bias_table": "params_g/group_0_layer_1/attn/relative_position_bias_table",
    "qkv_kernel": "params_g/group_0_layer_1/attn/qkv/kernel",
    "d_x3_kernel": "params_d/conv3/kernel",
}
#: images a call of the step's jitted function
CHUNK = 1
#: where ``StepReference.follow`` and the generator path run
HOST = True

Flat = Dict[str, jnp.ndarray]

# Limits: each lies between what the sound program read on the chip over
# its seeds and what a control read there (PERF.md section 6, PR 38, the
# review session's table: my chip runs 5 and 6). Both comparisons start from the
# check's state, the seeded start off its init (drivers/train_sr.widened).
LIMITS = {
    # G's x4 output, masks off, 8-bit levels, on an image that spreads
    # 21 - 42 levels. From the modules at float32, products at HIGHEST: what
    # the configuration states as float32 (softmax, logits, LayerNorm's
    # moments) and the arithmetic itself. Sound 2.7e-5 - 3.5e-5 / 9.1e-5 -
    # 1.2e-4 / 2.1e-4 - 2.7e-4; the least control, a softmax with its
    # intermediates in bfloat16, 0.038 - 0.040 / 0.134 - 0.138 / 0.28 -
    # 0.32 (LayerNorm's 0.17 / 0.62 / 1.5, int8 kernels 0.44 / 1.5 / 3.2):
    # ~30x to either side
    "generator_f32_mean_abs_levels": 0.001,
    "generator_f32_p99_abs_levels": 0.004,
    "generator_f32_max_abs_levels": 0.01,
    # as the step computes it (bf16 operands): sound 0.39 - 0.52 / 1.34 -
    # 1.86 / 2.8 - 4.0 over 4 seeds on the chip and 8 on the host; held
    # with 1.3x of room, no more: int8 kernels read 0.60 - 0.77 mean (1.5x
    # the same seed's sound error, inside these on most seeds), a bf16
    # softmax 1.00x and a bf16 LayerNorm 1.06x. The float32 numbers above
    # refuse all three; these judge a coarser fault
    "generator_mean_abs_levels": 0.68,
    "generator_p99_abs_levels": 2.4,
    "generator_max_abs_levels": 6.0,
    # the three followed steps' terms; in brackets what a step that saw
    # half of every batch reads. Step one: loss_d 5.6e-5 - 1.5e-4 [0.0020],
    # loss_g 4.3e-5 - 2.7e-4 [0.018], g_l1 1.5e-4 - 5.6e-4 [0.025], g_vgg
    # 1.5e-5 - 4.4e-4 [0.012], g_gan 7e-6 - 4.7e-4 [0.0003: it does not see
    # that fault; a term left out reads 1]
    "step1_loss_d_rel_gap": 0.001,
    "step1_loss_g_rel_gap": 0.002,
    "step1_g_l1_rel_gap": 0.003,
    "step1_g_vgg_rel_gap": 0.004,
    "step1_g_gan_rel_gap": 0.002,
    # the widest of steps two and three: loss_d 2.6e-5 - 1.2e-4 [0.0018],
    # loss_g 4.1e-4 - 2.1e-3 [0.057], g_l1 2.0e-4 - 2.1e-3 [0.067], g_vgg
    # 1.0e-3 - 3.0e-3 [0.085], g_gan 1.5e-4 - 6.4e-4 [0.0078]
    "later_loss_d_rel_gap": 0.0005,
    "later_loss_g_rel_gap": 0.01,
    "later_g_l1_rel_gap": 0.01,
    "later_g_vgg_rel_gap": 0.015,
    "later_g_gan_rel_gap": 0.002,
    # per net the worst leaf's gap of norms. First gradient: G 0.009 -
    # 0.020 [0.50], D 0.0039 - 0.045 (the init's twelve seeds too; D's state
    # is the init's) [0.14]. The parameters' change: G 0.089 - 0.128, always
    # a qkv BIAS (its k third has no gradient but rounding, which Adam
    # scales up to a full step) [0.15; a state left unchanged reads 1], D
    # 0.0010 - 0.017 [0.008; unchanged 1]
    "first_grad_g_worst_leaf_gap": 0.06,
    "first_grad_d_worst_leaf_gap": 0.1,
    "params_change_g_worst_leaf_gap": 0.3,
    "params_change_d_worst_leaf_gap": 0.05,
    # the named leaves: the norm of the first gradient's DIFFERENCE from the
    # reference's over the reference's norm: the bias table 0.028 - 0.056
    # [0.50], a qkv kernel 0.013 - 0.017 [0.33]. D's x3 kernel is printed
    # and not judged: 0.018 - 0.120 sound over fifteen seeds, [0.091 - 0.119]
    "first_grad_bias_table_diff_over_norm": 0.15,
    "first_grad_qkv_kernel_diff_over_norm": 0.07,
    # D's spectral vectors after the last step (0.0020 - 0.0134 [0.012];
    # vectors the step does not thread read 1.41) and G's EMA's change
    # (3.0e-4 - 9e-4 [0.030]; left alone it reads 1)
    "spectral_d_widest_gap": 0.05,
    "ema_g_change_gap": 0.02,
}


def sub(p: Flat, prefix: str) -> Flat:
    return {k: v for k, v in p.items() if k.startswith(prefix + "/")}


# -------------------------------------------------------------- generator


def layer_norm(p: Flat, path: str, x):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + LN_EPS) * p[f"{path}/scale"]
            + p[f"{path}/bias"])


def linear(p: Flat, path: str, x):
    return jnp.matmul(x, p[f"{path}/kernel"],
                      precision=nn.HIGHEST) + p[f"{path}/bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _conv3(p: Flat, path: str, x):
    return nn.zero_conv(x, p[f"{path}/Conv_0/kernel"],
                        p[f"{path}/Conv_0/bias"], pad=1)


def window_tokens(h: int, w: int, win: int, shift: int) -> np.ndarray:
    """``[nW, win^2]``: for each window of the image rolled by ``(-shift,
    -shift)`` the flat row-major indices, in the UNROLLED image, of its
    tokens."""
    out = np.zeros((h // win, w // win, win, win), np.int64)
    for wy in range(h // win):
        for wx in range(w // win):
            for y in range(win):
                for x in range(win):
                    sy = (wy * win + y + shift) % h
                    sx = (wx * win + x + shift) % w
                    out[wy, wx, y, x] = sy * w + sx
    return out.reshape(-1, win * win)


def region_mask(h: int, w: int, win: int, shift: int) -> np.ndarray:
    """``[nW, win^2, win^2]``: 0 where two tokens of a window of the rolled
    image lie in the same band of rows and of columns, MASK_VALUE else."""
    def band(i, extent):
        return 0 if i < extent - win else (1 if i < extent - shift else 2)

    nwy, nwx, t = h // win, w // win, win * win
    label = np.zeros((nwy * nwx, t), np.int64)
    for wy in range(nwy):
        for wx in range(nwx):
            for y in range(win):
                for x in range(win):
                    label[wy * nwx + wx, y * win + x] = (
                        3 * band(wy * win + y, h) + band(wx * win + x, w))
    return np.where(label[:, :, None] == label[:, None, :], 0.0,
                    MASK_VALUE).astype(np.float32)


def relative_index(win: int) -> np.ndarray:
    t = win * win
    idx = np.zeros((t, t), np.int64)
    for i in range(t):
        for j in range(t):
            dy, dx = i // win - j // win, i % win - j % win
            idx[i, j] = (dy + win - 1) * (2 * win - 1) + dx + win - 1
    return idx


def window_attention(p: Flat, path: str, x, shift: int):
    """``WMSA_s`` of the image-shaped tokens ``x`` ``[N, H, W, C]``."""
    n, h, w, c = x.shape
    table = p[f"{path}/relative_position_bias_table"]
    heads = table.shape[1]
    win = (int(round(math.sqrt(table.shape[0]))) + 1) // 2
    d = c // heads
    tokens = window_tokens(h, w, win, shift)            # [nW, T]
    xw = x.reshape(n, h * w, c)[:, tokens]              # [N, nW, T, C]
    qkv = linear(p, f"{path}/qkv", xw)
    bias = table[relative_index(win)]                   # [T, T, heads]
    mask = region_mask(h, w, win, shift) if shift else None
    outs = []
    for head in range(heads):
        q, k, v = (qkv[..., i * c + head * d:i * c + (head + 1) * d]
                   for i in range(3))
        logits = jnp.einsum("nwqd,nwkd->nwqk", q, k, precision=nn.HIGHEST)
        logits = logits * (d ** -0.5) + bias[:, :, head]
        if mask is not None:
            logits = logits + mask[None]
        logits = logits - jnp.max(logits, -1, keepdims=True)
        e = jnp.exp(logits)
        a = e / jnp.sum(e, -1, keepdims=True)
        outs.append(jnp.einsum("nwqk,nwkd->nwqd", a, v,
                               precision=nn.HIGHEST))
    out = linear(p, f"{path}/proj", jnp.concatenate(outs, -1))
    # every token back to its place: invert the permutation
    back = np.argsort(tokens.reshape(-1))
    return out.reshape(n, h * w, c)[:, back].reshape(n, h, w, c)


def swin_layer(p: Flat, path: str, t, shift: int, keep=None):
    """``keep`` ``[2, N]``: the two branches' masks over 1 - p_j."""
    drop = lambda x, row: (x if keep is None  # noqa: E731
                           else x * keep[row][:, None, None, None])
    u = t + drop(window_attention(
        p, f"{path}/attn", layer_norm(p, f"{path}/norm1", t), shift), 0)
    m = linear(p, f"{path}/fc2", gelu(linear(
        p, f"{path}/fc1", layer_norm(p, f"{path}/norm2", u))))
    return u + drop(m, 1)


def structure(p: Flat) -> Tuple[int, int, int]:
    """(groups, layers a group, window) from the leaves' names."""
    groups = 0
    while f"params_g/group_{groups}_conv/Conv_0/kernel" in p:
        groups += 1
    per = 0
    while f"params_g/group_0_layer_{per}/norm1/scale" in p:
        per += 1
    rows = p["params_g/group_0_layer_0/attn/relative_position_bias_table"
             ].shape[0]
    return groups, per, (int(round(math.sqrt(rows))) + 1) // 2


def generator(p: Flat, lq, keep=None, upto: Optional[str] = None):
    """LQ ``[N, H, W, 3]`` in [0, 1] -> its x4 image in [0, 1]. ``keep``
    ``[2 * layers, N]`` of 0 / 1 (stochastic depth on) or None.
    ``upto``: return that intermediate instead (tests): ``"patch_norm"``,
    ``"group_0_layer_<j>"``, ``"group_<i>"``, ``"body"``."""
    g = "params_g"
    groups, per, win = structure(p)
    layers = groups * per
    if keep is not None:
        rates = np.repeat(np.linspace(0.0, DROP_PATH, layers), 2)
        keep = keep / jnp.asarray(1.0 - rates, jnp.float32)[:, None]
    f0 = _conv3(p, f"{g}/conv_first", lq - jnp.asarray(MEAN, jnp.float32))
    t = layer_norm(p, f"{g}/patch_norm", f0)
    if upto == "patch_norm":
        return t
    for i in range(groups):
        x = t
        for j in range(per):
            k = i * per + j
            x = swin_layer(p, f"{g}/group_{i}_layer_{j}", x,
                           (win // 2) * (j % 2),
                           None if keep is None else keep[2 * k:2 * k + 2])
            if upto == f"group_{i}_layer_{j}":
                return x
        t = t + _conv3(p, f"{g}/group_{i}_conv", x)
        if upto == f"group_{i}":
            return t
    f = f0 + _conv3(p, f"{g}/conv_after_body", layer_norm(p, f"{g}/norm", t))
    if upto == "body":
        return f
    return upsampler(p, f)


def upsampler(p: Flat, f):
    g = "params_g"
    a = nn.leaky_relu(_conv3(p, f"{g}/conv_before_upsample", f), 0.01)
    for name in ("conv_up1", "conv_up2"):
        a = nn.leaky_relu(_conv3(p, f"{g}/{name}",
                                 nn.upsample_nearest(a, 2)), 0.2)
    a = nn.leaky_relu(_conv3(p, f"{g}/conv_hr", a), 0.2)
    return _conv3(p, f"{g}/conv_last", a) + jnp.asarray(MEAN, jnp.float32)


def generator_path(params: Flat, image_uint8, train: bool, keep=None):
    """The driver's view: LQ uint8 -> (the x4 image in [-1, 1], the scale
    the program carries images in), with stochastic depth off unless
    ``keep`` is given."""
    del train
    y = generator(params, image_uint8.astype(jnp.float32) / 255.0, keep)
    return y * 2.0 - 1.0, None, {}


# ---------------------------------------------------------- discriminator


def spectral_sigma(kernel, u):
    """One power iteration: (sigma, u')."""
    w = jnp.transpose(kernel, (3, 0, 1, 2)).reshape(kernel.shape[3], -1)
    ws = jax.lax.stop_gradient(w)
    unit = lambda a: a / (jnp.sqrt(jnp.sum(jnp.square(a))) + 1e-12)  # noqa
    v = unit(jnp.matmul(ws.T, u, precision=nn.HIGHEST))
    u1 = unit(jnp.matmul(ws, v, precision=nn.HIGHEST))
    sigma = jnp.vdot(u1, jnp.matmul(w, v, precision=nn.HIGHEST))
    return sigma, u1


def bilinear_up2(x):
    """``align_corners=False``: each output the 3/4 - 1/4 blend of its two
    nearest inputs, the edge's neighbour being the edge itself."""
    def along(a, axis):
        n = a.shape[axis]
        idx = np.arange(n)
        prev = jnp.take(a, np.maximum(idx - 1, 0), axis=axis)
        nxt = jnp.take(a, np.minimum(idx + 1, n - 1), axis=axis)
        even, odd = 0.75 * a + 0.25 * prev, 0.75 * a + 0.25 * nxt
        both = jnp.stack([even, odd], axis=axis + 1)
        shape = list(a.shape)
        shape[axis] = 2 * n
        return both.reshape(shape)

    return along(along(x, 1), 2)


def discriminator(p: Flat, x) -> Tuple[jnp.ndarray, Flat]:
    """Logits ``[N, H, W, 1]`` of images in [0, 1] and D's spectral vectors
    after this call."""
    d, s = "params_d", "spectral_d"
    new: Flat = {}

    def sn(name, y, k, stride):
        sigma, u1 = spectral_sigma(p[f"{d}/{name}/kernel"],
                                   p[f"{s}/{name}/u"])
        new[f"{s}/{name}/u"] = u1
        return nn.leaky_relu(nn.zero_conv(
            y, p[f"{d}/{name}/kernel"] / sigma, None, stride=stride, pad=1))

    x0 = nn.leaky_relu(_conv3(p, f"{d}/conv0", x))
    x1 = sn("conv1", x0, 4, 2)
    x2 = sn("conv2", x1, 4, 2)
    x3 = sn("conv3", x2, 4, 2)
    x4 = sn("conv4", bilinear_up2(x3), 3, 1) + x2
    x5 = sn("conv5", bilinear_up2(x4), 3, 1) + x1
    x6 = sn("conv6", bilinear_up2(x5), 3, 1) + x0
    out = sn("conv8", sn("conv7", x6, 3, 1), 3, 1)
    return _conv3(p, f"{d}/conv9", out), new


def bce(logits, target_is_real: bool):
    """Binary cross-entropy on logits against all ones or all zeros."""
    z = logits if target_is_real else -logits
    # -log sigmoid(z) = softplus(-z)
    return jnp.mean(jnp.maximum(-z, 0) + jnp.log1p(jnp.exp(-jnp.abs(z))))


# -------------------------------------------------------------- perceptual


def vgg_taps(p: Flat, x01):
    """The five pre-activation taps of images in [0, 1]."""
    x = (x01 - jnp.asarray(IMAGENET_MEAN, jnp.float32)) / jnp.asarray(
        IMAGENET_STD, jnp.float32)
    taps = []
    for b, block in enumerate(VGG_LAYERS, start=1):
        if b > 1:
            x = nn.max_pool_2(x)
        for i in range(1, len(block) + 1):
            name = f"conv{b}_{i}"
            z = nn.zero_conv(x, p[f"vgg/{name}/kernel"],
                             p[f"vgg/{name}/bias"], pad=1)
            if i == len(block):
                taps.append(z)
            x = jnp.maximum(z, 0)
    return taps


def perceptual(p: Flat, y, r):
    total = 0.0
    for w, fy, fr in zip(TAP_WEIGHTS, vgg_taps(p, y),
                         vgg_taps(p, jax.lax.stop_gradient(r))):
        total = total + w * jnp.mean(jnp.abs(fy - fr))
    return total


# ---------------------------------------------------------------- the step


class StepReference:
    """``hyper``: the configuration file's ``train_reference`` group
    (``lr_g``, ``lr_d``, ``beta1``, ``beta2``, ``eps``, ``l1_weight``,
    ``perceptual_weight``, ``gan_weight``, ``d_loss_scale``,
    ``ema_decay``)."""

    def __init__(self, hyper: dict, chunk: int = CHUNK):
        self.h = hyper
        self.chunk = chunk
        self._chunk = jax.jit(self._step_chunk, static_argnums=(4,))
        self._adam = jax.jit(self._adam_update, static_argnums=(5,))

    def _step_chunk(self, p: Flat, lq, hq, keep, n: int):
        """One chunk's share of the step's losses and of both nets'
        gradients, and D's spectral vectors after its two calls."""
        h = self.h
        share = lq.shape[0] / n
        g_params, d_params = sub(p, "params_g"), sub(p, "params_d")
        rest = {k: v for k, v in p.items()
                if k not in g_params and k not in d_params}

        def g_loss(gp):
            q = {**rest, **d_params, **gp}
            y = generator(q, lq, keep)
            parts = {
                "g_l1": h["l1_weight"] * jnp.mean(jnp.abs(y - hq)),
                "g_vgg": h["perceptual_weight"] * perceptual(q, y, hq),
                "g_gan": h["gan_weight"] * bce(discriminator(q, y)[0], True),
            }
            parts = {k: v * share for k, v in parts.items()}
            return sum(parts.values()), (parts, y)

        (_, (parts, y)), grads_g = jax.value_and_grad(
            g_loss, has_aux=True)(g_params)

        def d_loss(dp):
            q = {**rest, **g_params, **dp}
            fake, u1 = discriminator(q, jax.lax.stop_gradient(y))
            real, u2 = discriminator({**q, **u1}, hq)
            return h["d_loss_scale"] * share * (
                bce(fake, False) + bce(real, True)), u2

        (loss_d, spectral), grads_d = jax.value_and_grad(
            d_loss, has_aux=True)(d_params)
        parts["loss_d"] = loss_d
        return parts, {**grads_g, **grads_d}, spectral

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

    def step(self, p: Flat, lq, hq, keep):
        """One step from ``p`` on the float32 batch in [0, 1]; ``keep``
        ``[2 * layers, N]``. Returns the losses, the gradients of both
        nets and D's spectral vectors after it."""
        n, m = lq.shape[0], self.chunk
        add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)  # noqa
        parts = grads = spectral = None
        for i in range(0, n, m):
            at = slice(i, i + m)
            pt, gr, spectral = self._chunk(p, lq[at], hq[at], keep[:, at], n)
            parts = pt if parts is None else add(parts, pt)
            grads = gr if grads is None else add(grads, gr)
        losses = dict(parts)
        losses["loss_g"] = parts["g_l1"] + parts["g_vgg"] + parts["g_gan"]
        return losses, grads, spectral

    def follow(self, state: Flat, batches, keeps):
        """Follow ``batches`` (uint8 ``input`` / ``target``) from ``state``
        with the keep masks ``keeps`` (one ``[2 * layers, N]`` a step, the
        program's own draws). Returns each step's losses, the first step's
        gradients, the parameters after the last step, D's spectral
        vectors and G's EMA after it, all as numpy, by leaf."""
        with (jax.default_device(jax.devices("cpu")[0]) if HOST
              else contextlib.nullcontext()):
            return self._follow(state, batches, keeps)

    def _follow(self, state: Flat, batches, keeps):
        p = {k: jnp.asarray(v) for k, v in state.items()}
        trainable = {k for k in p if k.split("/", 1)[0] in NETS}
        mom = {k: jnp.zeros_like(p[k]) for k in trainable}
        v = {k: jnp.zeros_like(p[k]) for k in trainable}
        decay = self.h["ema_decay"]
        all_losses, first_grads = [], None
        unit = lambda b, key: jnp.asarray(  # noqa: E731
            b[key]).astype(jnp.float32) / 255.0
        for i, (batch, keep) in enumerate(zip(batches, keeps)):
            count = jnp.asarray(i, jnp.int32)
            losses, grads, spectral = self.step(
                p, unit(batch, "input"), unit(batch, "target"),
                jnp.asarray(keep, jnp.float32))
            for net, lr in (("params_g", self.h["lr_g"]),
                            ("params_d", self.h["lr_d"])):
                new_p, new_m, new_v = self._adam(
                    sub(p, net), sub(grads, net), sub(mom, net),
                    sub(v, net), count, float(lr))
                p.update(new_p), mom.update(new_m), v.update(new_v)
            p.update(spectral)
            for k in sub(p, "ema_g"):
                p[k] = decay * p[k] + (1.0 - decay) * p[
                    "params_g" + k[len("ema_g"):]]
            all_losses.append({k: float(x) for k, x in losses.items()})
            if first_grads is None:
                first_grads = {k: np.asarray(g) for k, g in grads.items()}
            del grads
        keep_np = lambda prefix: {  # noqa: E731
            k: np.asarray(x) for k, x in p.items()
            if k.startswith(prefix + "/")}
        params = {k: np.asarray(p[k]) for k in trainable}
        return (all_losses, first_grads, params, keep_np("spectral_d"),
                keep_np("ema_g"))
