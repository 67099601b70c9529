"""Plain reference of the ``pix2pixhd_2048x1024`` generator path.

This configuration's own copy of the pix2pixHD reference (a configuration
brings its reference with it; nothing is imported from the 1024x512 one).
pix2pixHD's coarse-to-fine generator (Wang et al. 2018, section 3.1) as
the preset lays it out with reflection-padded resize-convolutions:

  G1 (global, on the 3x3/s2 average-pooled input): c7s1-64, four k3 s2
  downsamples to 1024 channels, 9 x [conv k3, IN, ReLU, conv k3, IN,
  + identity], four nearest-x2 + conv k3 upsamples back to 64 channels;
  every conv followed by InstanceNorm (no affine) + ReLU; its 64-channel
  feature map (not an image) is handed on.
  G2 (local enhancer, full resolution): c7s1-32, conv k3 s2 to 64 (IN +
  ReLU each), + G1's features, 3 residual blocks at 64, nearest-x2 +
  conv k3 to 32 (IN + ReLU), c7s1-3 with bias, tanh.

Convs before a norm carry no bias. Imports nothing of the program.

Memory, reckoned before the first run (one chip, 15.75 GiB, float32): one
2048x1024 activation of the enhancer's 32 channels is 268 MB, the
upsampled 64-channel one 537 MB, and the backward of one whole row keeps
some forty of them: the one-chip configuration's generator backward
peaked at 8.9 GiB for two 1024x512 rows, so one row here would take
~18 GiB. ``g_forward(remat=True)`` therefore runs row by row
(InstanceNorm couples no rows) and keeps only the inputs of seven stages
of a row, recomputing each stage in the backward.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import nn

BATCH_KEY = "input"
N_DOWN = 4
N_BLOCKS_GLOBAL = 9
N_BLOCKS_LOCAL = 3
MAX_FEATURES = 1024
#: rows per block where the whole train step is followed in float32
ROW_BLOCK = 1


#: Limits of ``correct`` (PERF.md section 2 has the table; "TPU v5 lite",
#: my chip runs of PR 25). The two generator numbers are set between this
#: shape's own two readings. The numbers of the followed steps, which the
#: lower precision hardly moves, are the one-chip configuration's
#: (``pix2pixhd_1024x512.py``: the same model in the same precision, a
#: chip here holds that cell's pixels; 15 sound seeds there), with what
#: was read at this shape beside each: two runs of the cell on four chips
#: (seeds 2147483777 and 2147490001; their ``first_grad_g`` read 1.12 and
#: 1.22 because the followed step's pool backward was miscomputed on the
#: chip, below) and the same comparison made in two halves at seed
#: 2147483777 with the pool below (marked *). The ``steps`` control needs
#: four chips and was not read at this shape.
LIMITS = {
    # sound 2.77 .. 3.40 (one chip, 3 seeds, the Pallas norm) and 2.94,
    # 3.14 (four chips); int8 control 6.61 .. 7.49 (one chip, 3 seeds)
    "generator_mean_abs_levels": 4.5,
    # sound 10.96 .. 12.51 and 11.29, 11.93; control 25.73 .. 26.61
    "generator_p99_abs_levels": 17.0,
    # -- the Trainer's own first TWO steps against train_step.py ---------
    # (a step that returns its state unchanged reads 1.0 in every norm
    # gap and ~0.6 in the later losses; a loss term or a part of the
    # batch left out moves a step-one loss or a first gradient)
    "step1_loss_d_rel_gap": 6e-4,             # 1.0e-4, 3.1e-5, 1.0e-4*
    "step1_loss_g_rel_gap": 0.003,            # 6.5e-4, 9.4e-4, 6.5e-4*
    "later_loss_d_rel_gap": 0.02,             # 0.0015, 0.0025, 0.0019*
    "later_loss_g_rel_gap": 0.05,             # 0.0071, 0.0003, 0.0010*
    "first_grad_g_worst_leaf_gap": 0.25,      # 0.023*
    "first_grad_d_worst_leaf_gap": 0.18,      # 0.083, 0.080, 0.083*
    "params_change_g_worst_leaf_gap": 0.013,  # 0.0026, 0.0026, 0.0007*
    "params_change_d_worst_leaf_gap": 0.25,   # 0.024, 0.021, 0.024*
}


# ------------------------------------------- the pyramid's pool on the chip
#
# One "TPU v5 lite" miscomputes the BACKWARD of ``nn.avg_pool_3s2`` at
# this configuration's row, ``f32[1,1024,2048,6]``: jax transposes the
# ``reduce_window`` sum into one base-dilated ``reduce-window``, and the
# chip returned a cotangent of norm 296.6 where the host CPU returns 591.8
# (relative difference 1.119, read twice; at [1,512,1024,6], [2,512,1024,6]
# and [8,256,256,6] it is right to 3e-8; PERF.md section 6).
# ``train_step.py`` pulls the generator's GAN and feature-matching losses
# back through that pool (D's input pyramid), so on one chip the followed
# step read half the generator's first gradient. The same function is
# therefore given a backward of its own here, written as what it is, nine
# shifted copies of ``g / n`` on the stride-2 grid (``lax.pad`` with an
# interior of 1, no ``reduce-window``; on the chip it agrees with the host
# CPU to the last bit at all four extents); the forward is ``nn``'s own
# op, untouched. ``train_step.py`` reaches the pool through ``nn``, and that
# file is not this configuration's to edit, so importing this module puts
# the function in ``nn``'s place for the process (one benchmark run = one
# configuration). ``benchmark/tests/test_spatial4_cell.py`` holds it
# against ``nn``'s own on the CPU, values and gradients.

_PLAIN_POOL = getattr(nn, "_plain_avg_pool_3s2", nn.avg_pool_3s2)


@jax.custom_vjp
def avg_pool_3s2(x):
    """``nn.avg_pool_3s2``: AvgPool2d(3, stride=2, padding=1,
    count_include_pad=False)."""
    return _PLAIN_POOL(x)


def _pool_fwd(x):
    return _PLAIN_POOL(x), x


def _pool_bwd(x, g):
    h, w = x.shape[1:3]
    # window i covers padded rows 2i .. 2i + 2 of the h + 2 there are;
    # it counts those of them that are rows of the image (1 .. h)
    def count(windows, size):
        rows = 2 * np.arange(windows)[:, None] + np.arange(3)
        return ((rows >= 1) & (rows <= size)).sum(axis=1)

    n = np.outer(count(g.shape[1], h), count(g.shape[2], w))
    gn = g / jnp.asarray(n[None, :, :, None], g.dtype)
    span_h, span_w = 2 * g.shape[1] - 1, 2 * g.shape[2] - 1
    total = 0.0
    for dy in range(3):
        for dx in range(3):
            total = total + lax.pad(gn, jnp.zeros((), g.dtype), (
                (0, 0, 0), (dy, h + 2 - span_h - dy, 1),
                (dx, w + 2 - span_w - dx, 1), (0, 0, 0)))
    return (total[:, 1:h + 1, 1:w + 1],)


avg_pool_3s2.defvjp(_pool_fwd, _pool_bwd)
nn._plain_avg_pool_3s2 = _PLAIN_POOL
nn.avg_pool_3s2 = avg_pool_3s2


def _widths(ngf: int):
    return [min(ngf * 2 ** i, MAX_FEATURES) for i in range(N_DOWN + 1)]


def param_shapes(ngf: int = 64, n_blocks_global: int = N_BLOCKS_GLOBAL
                 ) -> Dict[str, Tuple[int, ...]]:
    s: Dict[str, Tuple[int, ...]] = {}
    g, w = "params_g", _widths(ngf)
    s[f"{g}/global/ConvLayer_0/Conv_0/kernel"] = (7, 7, 3, w[0])
    for i in range(N_DOWN):
        s[f"{g}/global/ConvLayer_{i + 1}/Conv_0/kernel"] = (
            3, 3, w[i], w[i + 1])
    for b in range(n_blocks_global):
        for j in range(2):
            s[f"{g}/global/ResnetBlock_{b}/ConvLayer_{j}/Conv_0/kernel"] = (
                3, 3, w[-1], w[-1])
    for i in range(N_DOWN):
        s[f"{g}/global/UpsampleConvLayer_{i}/Conv_0/kernel"] = (
            3, 3, w[N_DOWN - i], w[N_DOWN - i - 1])
    local = ngf // 2
    s[f"{g}/ConvLayer_0/Conv_0/kernel"] = (7, 7, 3, local)
    s[f"{g}/ConvLayer_1/Conv_0/kernel"] = (3, 3, local, ngf)
    for b in range(N_BLOCKS_LOCAL):
        for j in range(2):
            s[f"{g}/ResnetBlock_{b}/ConvLayer_{j}/Conv_0/kernel"] = (
                3, 3, ngf, ngf)
    s[f"{g}/UpsampleConvLayer_0/Conv_0/kernel"] = (3, 3, ngf, local)
    s[f"{g}/ConvLayer_2/Conv_0/kernel"] = (7, 7, local, 3)
    s[f"{g}/ConvLayer_2/Conv_0/bias"] = (3,)
    return s


def _conv(p, path, x, stride=1, up=0):
    if up:
        x = nn.upsample_nearest(x, up)
    return nn.reflect_conv(x, p[f"{path}/Conv_0/kernel"],
                           p.get(f"{path}/Conv_0/bias"), stride)


def _norm_relu(x):
    return jnp.maximum(nn.instance_norm(x), 0)


def _block(p, path, x):
    y = _norm_relu(_conv(p, f"{path}/ConvLayer_0", x))
    return nn.instance_norm(_conv(p, f"{path}/ConvLayer_1", y)) + x


def global_features(p, x):
    g = "params_g/global"
    y = _norm_relu(_conv(p, f"{g}/ConvLayer_0", x))
    for i in range(N_DOWN):
        y = _norm_relu(_conv(p, f"{g}/ConvLayer_{i + 1}", y, stride=2))
    n_blocks = sum(1 for k in p if re.fullmatch(
        rf"{g}/ResnetBlock_\d+/ConvLayer_0/Conv_0/kernel", k))
    for b in range(n_blocks):
        y = _block(p, f"{g}/ResnetBlock_{b}", y)
    for i in range(N_DOWN):
        y = _norm_relu(_conv(p, f"{g}/UpsampleConvLayer_{i}", y, up=2))
    return y


def generator_path(params: Dict[str, jnp.ndarray], image_uint8,
                   train: bool, code: Optional[jnp.ndarray] = None):
    """Same contract as every reference: ``(pred, pre_code, moments)``.
    InstanceNorm has no state and this path has no quantizer, so the last
    two are None / empty and ``train`` changes nothing."""
    del train, code
    return g_forward(params, nn.to_unit(image_uint8)), None, {}


def _stages(params: Dict[str, jnp.ndarray]):
    """The generator as a chain of stages ``carry -> carry`` on
    ``(x, y, feats)``: the image, the enhancer's running activation and
    G1's feature map. ``g_forward`` composes them; with ``remat`` each is
    a ``jax.checkpoint`` of its own, so a backward holds one stage's
    activations beside the stages' inputs."""
    g = "params_g"

    def g1(c):
        x, y, _ = c
        return x, y, global_features(params, avg_pool_3s2(x))

    def stem(c):
        x, _, feats = c
        return x, _norm_relu(_conv(params, f"{g}/ConvLayer_0", x)), feats

    def down(c):
        x, y, feats = c
        y = _norm_relu(_conv(params, f"{g}/ConvLayer_1", y, stride=2))
        return x, y + feats, feats

    def block(b):
        def run(c):
            x, y, feats = c
            return x, _block(params, f"{g}/ResnetBlock_{b}", y), feats
        return run

    def up(c):
        x, y, feats = c
        return x, _norm_relu(
            _conv(params, f"{g}/UpsampleConvLayer_0", y, up=2)), feats

    def head(c):
        x, y, feats = c
        return x, jnp.tanh(_conv(params, f"{g}/ConvLayer_2", y)), feats

    return [g1, stem, down] + [block(b) for b in range(N_BLOCKS_LOCAL)] + [
        up, head]


def _g_rows(params, x, remat: bool):
    carry = (x, jnp.zeros((), x.dtype), jnp.zeros((), x.dtype))
    for stage in _stages(params):
        carry = (jax.checkpoint(stage) if remat else stage)(carry)
    return carry[1]


def g_forward(params: Dict[str, jnp.ndarray], x, remat: bool = False):
    """The generator on images in [-1, 1]. InstanceNorm couples no rows,
    so with ``remat`` (the followed train step asks for it) the rows run
    one after another and a row's backward recomputes its stages one at a
    time (module docstring); the values are those of the plain chain."""
    if not remat:
        return _g_rows(params, x, False)
    return jax.lax.map(
        jax.checkpoint(lambda row: _g_rows(params, row[None], True)[0]), x)
