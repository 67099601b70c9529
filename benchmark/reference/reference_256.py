"""Plain reference of the ``reference_256`` generator path.

CompressionNetwork -> 3-bit quantizer -> ExpandNetwork, written from the
layer equations of the reference implementation (networks.py:201-236,
429-523; generate_dataset.py:29-34) as SURVEY.md section 0 cites them:

  C(x):  conv k5 3->64 + PReLU; conv k3 64->64 + BN + PReLU; conv k3 s2
         64->12; PixelShuffle(2); per-pixel L2-normalise over channels;
         x + residual.                       (every conv reflect-padded)
  q(x):  round(clamp(x, 0, 1) * 7) / 7.
  G(x):  PixelUnshuffle(2) -> nearest x2 (3 -> 12 channels, same extent);
         conv k9 12->32, conv k3 s2 32->64, conv k3 s2 64->128, each
         BN + PReLU (ONE shared PReLU scalar); 9 x [conv k3, BN, ReLU,
         conv k3, BN, + identity, ReLU]; + long skip, LeakyReLU(0.2);
         up x2 conv k3 128->64, up x2 conv k3 64->32 (BN + PReLU each);
         conv k9 32->3, BN, tanh.  Convs before a BN carry no bias.

The generator is driven from the TARGET image (the reference's own
train/eval semantics: the stored input is unused when a compression net
is present). Imports nothing of the program.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import nn

QUANT_BITS = 3
N_BLOCKS = 9
BATCH_KEY = "target"
#: rows per block where the whole train step is followed in float32
#: (benchmark/reference/train_step.py): VGG19 and D on 8 rows of 256x256
ROW_BLOCK = 8


#: Limits of ``correct`` for this configuration; every number a run
#: compares is printed beside its limit. Set from readings on the chip
#: ("TPU v5 lite", bs32, 256x256; benchmark/tools/control.py over 12 seeds
#: plus 4 benchmark runs, my chip runs, PR 22): the largest the SOUND path
#: gave / the smallest the CONTROL gave (the program's own int8 generator,
#: ModelConfig.int8 + int8_generator, in the sound path's place).
LIMITS = {
    # sound 2.37 .. 2.77, control 9.15 .. 12.03 (3.3x apart): between them
    "generator_mean_abs_levels": 5.0,
    # the compression net is the same in the control, so these three are
    # held against the fault they are there to catch (a compression net
    # or quantizer that computes something else) at ~3x the sound runs'
    # largest: 0.0040, 0.0094 and 3e-6
    "prequant_mean_abs": 0.012,
    "code_differs_share": 0.03,
    "code_off_by_more_than_one_share": 1e-5,
    # -- the Trainer's own first three steps against train_step.py -------
    # ("TPU v5 lite", bs32; 20 seeds of tools/control.py --kind steps and 4
    # benchmark runs, my chip runs 10, 12, 13, 14, PR 22; per-step losses
    # from the last 14). The int8 control (3 seeds) moves none of these by 3x
    # (norms and losses hardly feel a precision; the generator numbers
    # above catch it), so each is held against the fault it is there to
    # catch - a step that returns its state unchanged reads 1.0 in every
    # norm gap and some tens of percent in the later losses (they halve
    # from step to step), a term or a part of the batch left out moves a
    # step-one loss or a first gradient - at three times the sound runs'
    # largest or more.
    # step one, both on the same state: steady from seed to seed
    "step1_loss_d_rel_gap": 3e-4,            # sound 5e-6 .. 8.7e-5
    "step1_loss_g_rel_gap": 0.004,           # sound 0.0003 .. 0.0010
    "step1_loss_c_rel_gap": 0.025,           # sound 1e-5 .. 0.0075
    # steps two and three, two precisions parting: heavy-tailed (C read
    # 0.052 once, 0.018 twice, under 0.009 in 20 others)
    "later_loss_d_rel_gap": 0.02,            # sound 0.0003 .. 0.0034
    "later_loss_g_rel_gap": 0.04,            # sound 0.0001 .. 0.0084
    "later_loss_c_rel_gap": 0.2,             # sound 0.0007 .. 0.052
    "first_grad_g_worst_leaf_gap": 0.4,      # sound 0.017 .. 0.113
    "first_grad_d_worst_leaf_gap": 0.012,    # sound 0.0027 .. 0.0039
    "first_grad_c_worst_leaf_gap": 0.5,      # sound 0.036 .. 0.167
    "params_change_g_worst_leaf_gap": 0.19,  # sound 0.025 .. 0.062
    "params_change_d_worst_leaf_gap": 0.04,  # sound 0.0014 .. 0.0134
    "params_change_c_worst_leaf_gap": 0.93,  # sound 0.093 .. 0.309
}


def param_shapes(ngf: int = 32, c_features: int = 64,
                 n_blocks: int = N_BLOCKS) -> Dict[str, Tuple[int, ...]]:
    """Every leaf this reference reads, with its shape, under the path the
    system's state tree uses (so a seeded tree can be handed to both)."""
    s: Dict[str, Tuple[int, ...]] = {}

    def bn(prefix, stats_prefix, c):
        s[f"{prefix}/BatchNorm_0/scale"] = (c,)
        s[f"{prefix}/BatchNorm_0/bias"] = (c,)
        s[f"{stats_prefix}/BatchNorm_0/mean"] = (c,)
        s[f"{stats_prefix}/BatchNorm_0/var"] = (c,)

    g, gs = "params_g", "batch_stats_g"
    s[f"{g}/ConvLayer_0/Conv_0/kernel"] = (9, 9, 12, ngf)
    s[f"{g}/ConvLayer_1/Conv_0/kernel"] = (3, 3, ngf, 2 * ngf)
    s[f"{g}/ConvLayer_2/Conv_0/kernel"] = (3, 3, 2 * ngf, 4 * ngf)
    for i, c in enumerate((ngf, 2 * ngf, 4 * ngf, 2 * ngf, ngf, 3)):
        bn(f"{g}/BatchNorm_{i}", f"{gs}/BatchNorm_{i}", c)
    s[f"{g}/PReLU_0/alpha"] = ()
    for b in range(n_blocks):
        for j in range(2):
            s[f"{g}/ResidualBlock_{b}/ConvLayer_{j}/Conv_0/kernel"] = (
                3, 3, 4 * ngf, 4 * ngf)
            bn(f"{g}/ResidualBlock_{b}/BatchNorm_{j}",
               f"{gs}/ResidualBlock_{b}/BatchNorm_{j}", 4 * ngf)
    s[f"{g}/UpsampleConvLayer_0/Conv_0/kernel"] = (3, 3, 4 * ngf, 2 * ngf)
    s[f"{g}/UpsampleConvLayer_1/Conv_0/kernel"] = (3, 3, 2 * ngf, ngf)
    s[f"{g}/UpsampleConvLayer_2/Conv_0/kernel"] = (9, 9, ngf, 3)
    c, cs = "params_c", "batch_stats_c"
    for i, (k, cin, cout) in enumerate(((5, 3, c_features),
                                        (3, c_features, c_features),
                                        (3, c_features, 12))):
        s[f"{c}/ConvLayer_{i}/Conv_0/kernel"] = (k, k, cin, cout)
        s[f"{c}/ConvLayer_{i}/Conv_0/bias"] = (cout,)
    bn(f"{c}/BatchNorm_0", f"{cs}/BatchNorm_0", c_features)
    s[f"{c}/PReLU_0/alpha"] = ()
    s[f"{c}/PReLU_1/alpha"] = ()
    return s


class _Net:
    """Reads leaves by path; applies BatchNorm in train or eval mode and
    records the moments each BN saw (the calibration of a seeded tree)."""

    def __init__(self, params: Dict[str, jnp.ndarray], train: bool,
                 remat: bool = False):
        self.p, self.train, self.moments = params, train, {}
        #: recompute each residual block in the backward pass (float32
        #: training at the cell's batch would not fit otherwise); the
        #: moments are then not recorded
        self.remat = remat

    def conv(self, path, x, stride=1, up=0):
        if up:
            x = nn.upsample_nearest(x, up)
        return nn.reflect_conv(x, self.p[f"{path}/Conv_0/kernel"],
                               self.p.get(f"{path}/Conv_0/bias"), stride)

    def bn(self, net, path, x):
        stats_root = {"params_g": "batch_stats_g",
                      "params_c": "batch_stats_c"}[net]
        sp = f"{stats_root}/{path}/BatchNorm_0"
        stats = None if self.train else (self.p[f"{sp}/mean"],
                                         self.p[f"{sp}/var"])
        y, (mean, var) = nn.batch_norm(
            x, self.p[f"{net}/{path}/BatchNorm_0/scale"],
            self.p[f"{net}/{path}/BatchNorm_0/bias"], stats)
        if not self.remat:
            self.moments[f"{sp}/mean"], self.moments[f"{sp}/var"] = mean, var
        return y


def compress(net: _Net, x):
    c = "params_c"
    y = nn.prelu(net.conv(f"{c}/ConvLayer_0", x), net.p[f"{c}/PReLU_0/alpha"])
    y = net.bn(c, "BatchNorm_0", net.conv(f"{c}/ConvLayer_1", y))
    y = nn.prelu(y, net.p[f"{c}/PReLU_1/alpha"])
    y = nn.pixel_shuffle(net.conv(f"{c}/ConvLayer_2", y, stride=2), 2)
    norm = jnp.maximum(
        jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True)), 1e-12)
    return x + y / norm


def expand(net: _Net, x):
    g = "params_g"
    a = net.p[f"{g}/PReLU_0/alpha"]
    y = nn.upsample_nearest(nn.pixel_unshuffle(x, 2), 2)
    y = nn.prelu(net.bn(g, "BatchNorm_0", net.conv(f"{g}/ConvLayer_0", y)), a)
    y = nn.prelu(net.bn(g, "BatchNorm_1",
                        net.conv(f"{g}/ConvLayer_1", y, stride=2)), a)
    y = nn.prelu(net.bn(g, "BatchNorm_2",
                        net.conv(f"{g}/ConvLayer_2", y, stride=2)), a)
    skip = y
    n_blocks = sum(1 for k in net.p if re.fullmatch(
        rf"{g}/ResidualBlock_\d+/ConvLayer_0/Conv_0/kernel", k))

    def block(r, y):
        z = net.bn(g, f"{r}/BatchNorm_0", net.conv(f"{g}/{r}/ConvLayer_0", y))
        z = jnp.maximum(z, 0)
        z = net.bn(g, f"{r}/BatchNorm_1", net.conv(f"{g}/{r}/ConvLayer_1", z))
        return jnp.maximum(z + y, 0)

    for b in range(n_blocks):
        fn = functools.partial(block, f"ResidualBlock_{b}")
        y = jax.checkpoint(fn)(y) if net.remat else fn(y)
    y = nn.leaky_relu(y + skip, 0.2)
    y = nn.prelu(net.bn(g, "BatchNorm_3",
                        net.conv(f"{g}/UpsampleConvLayer_0", y, up=2)), a)
    y = nn.prelu(net.bn(g, "BatchNorm_4",
                        net.conv(f"{g}/UpsampleConvLayer_1", y, up=2)), a)
    y = net.bn(g, "BatchNorm_5", net.conv(f"{g}/UpsampleConvLayer_2", y))
    return jnp.tanh(y)


def generator_path(params: Dict[str, jnp.ndarray], image_uint8,
                   train: bool, code: Optional[jnp.ndarray] = None):
    """The path from one uint8 image batch to the generated image.

    Returns ``(pred, pre_code, moments)``: the image in [-1, 1], the
    compression net's output BEFORE the quantizer (so a caller can see
    how close to a rounding boundary each value lay), and the moments
    every BatchNorm saw. ``code``, if given, replaces the quantizer's
    output as the generator's input (levels in [0, 1])."""
    net = _Net(params, train)
    pre = compress(net, nn.to_unit(image_uint8))
    q = nn.quantize(pre, QUANT_BITS) if code is None else code
    return expand(net, q), pre, net.moments


def c_forward(params: Dict[str, jnp.ndarray], real_b):
    """The compression net in train mode on images in [-1, 1]: its value
    BEFORE the quantizer."""
    return compress(_Net(params, True, remat=True), real_b)


def g_forward(params: Dict[str, jnp.ndarray], code, remat: bool = False):
    """The generator in train mode on a code in [0, 1] levels."""
    return expand(_Net(params, True, remat=remat), code)
