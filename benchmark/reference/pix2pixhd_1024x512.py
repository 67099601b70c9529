"""Plain reference of the ``pix2pixhd_1024x512`` generator path.

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
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from . import nn

BATCH_KEY = "input"
N_DOWN = 4
N_BLOCKS_GLOBAL = 9
N_BLOCKS_LOCAL = 3
MAX_FEATURES = 1024
#: rows per block where the whole train step is followed in float32
ROW_BLOCK = 1


#: Limits of ``correct`` (see reference_256.py for how they are read).
#: "TPU v5 lite", bs2, 1024x512, 12 seeds of benchmark/tools/control.py
#: plus 4 benchmark runs (my chip runs, PR 22). bf16's own error through
#: ~40 layers is large here, so the int8 control (24 of the 41 convs in
#: int8) is only 1.7x - 1.8x away; both numbers are steady from seed to
#: seed (each within +-12% of its median), so a limit between them holds.
LIMITS = {
    # sound 2.50 .. 3.13, control 5.26 .. 6.78
    "generator_mean_abs_levels": 4.0,
    # sound 10.55 .. 11.70, control 21.37 .. 25.25
    "generator_p99_abs_levels": 15.5,
    # -- the Trainer's own first three steps against train_step.py -------
    # ("TPU v5 lite", bs2; 12 seeds of tools/control.py --kind steps and 3
    # benchmark runs, my chip runs 11 and 12, PR 22). The int8 control (3
    # seeds) moves none of these by 3x, so each is held against the fault
    # it is there to catch (a step that returns its state unchanged reads
    # 1.0 in every norm gap and ~0.6 in the later losses, which halve from
    # step to step; a loss term or a part of the batch left out moves a
    # step-one loss or a first gradient) at three times the sound runs'
    # largest or more (reference_256's later seeds showed how heavy the
    # tails of the later losses and the widest gaps are):
    "step1_loss_d_rel_gap": 6e-4,             # sound 1e-6 .. 1.6e-4
    "step1_loss_g_rel_gap": 0.003,            # sound 0.0005 .. 0.0009
    "later_loss_d_rel_gap": 0.02,             # sound 0.0005 .. 0.0031
    "later_loss_g_rel_gap": 0.05,             # sound 0.0008 .. 0.0080
    "first_grad_g_worst_leaf_gap": 0.25,      # sound 0.011 .. 0.080
    # steady at 4-6% in every seed, the worst leaf D's logits head
    # (scale2/_PlainConv_1, the kn2row form) in 10 of 15 readings
    "first_grad_d_worst_leaf_gap": 0.18,      # sound 0.042 .. 0.058
    "params_change_g_worst_leaf_gap": 0.013,  # sound 0.0010 .. 0.0044
    "params_change_d_worst_leaf_gap": 0.25,   # sound 0.027 .. 0.082
}


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


def g_forward(params: Dict[str, jnp.ndarray], x, remat: bool = False):
    """The generator on images in [-1, 1]. InstanceNorm couples no rows
    and the widest tensors are the enhancer's few, so nothing is
    recomputed: ``remat`` is taken and ignored."""
    del remat
    g = "params_g"
    feats = global_features(params, nn.avg_pool_3s2(x))
    y = _norm_relu(_conv(params, f"{g}/ConvLayer_0", x))
    y = _norm_relu(_conv(params, f"{g}/ConvLayer_1", y, stride=2))
    y = y + feats
    for b in range(N_BLOCKS_LOCAL):
        y = _block(params, f"{g}/ResnetBlock_{b}", y)
    y = _norm_relu(_conv(params, f"{g}/UpsampleConvLayer_0", y, up=2))
    return jnp.tanh(_conv(params, f"{g}/ConvLayer_2", y))
