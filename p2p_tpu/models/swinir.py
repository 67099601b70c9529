"""SwinIR: the image-restoration Swin transformer of Liang, Cao, Sun, Zhang,
Van Gool, Timofte, "SwinIR: Image Restoration Using Swin Transformer"
(ICCVW 2021, arXiv:2108.10257, section 3), at the sizes of the authors'
real-world x4 model (github.com/JingyunLiang/SwinIR ``models/
network_swinir.py``, ``003_realSR_BSRGAN_DFO_s64w8_SwinIR-M_x4_GAN``).

NHWC; an image's tokens are its positions in row-major order, so the token
stream IS the ``[N, H, W, C]`` tensor and the group convolutions read it as
it stands. ``LN`` = LayerNorm over the channels (eps 1e-5, affine); every
k3 convolution pads 1 with zeros and has a bias (:class:`p2p_tpu.ops.conv.
ConvLayer` / ``UpsampleConvLayer``, so its form is ``ops/conv.py``'s choice
from the shape). The module takes and returns images in this system's
[-1, 1]; inside, as the authors', they are in [0, 1]:

    head:  f0 = conv3(x01 - m, 3 -> C), m = MEAN;  t = LN(f0)
    STL_j(t) = u + DP_j(MLP(LN(u))),  u = t + DP_j(WMSA_s(LN(t)))
        s = 0 for even j, WINDOW // 2 for odd j within a group
        MLP(h) = fc2(gelu(fc1(h))), C -> MLP_RATIO * C -> C, gelu the erf form
        DP_j: stochastic depth, a per-image keep mask of probability 1 - p_j
        divided by 1 - p_j, p_j linear from 0 to DROP_PATH over all layers
    WMSA_s(h): roll h by (-s, -s); split into WINDOW x WINDOW windows;
        q, k, v = split(Linear(C -> 3C)(h)), ``heads`` heads;
        A = softmax(q k^T * d^-0.5 + B[idx] + M_s), B a learned
        [(2 WINDOW - 1)^2, heads] table, idx the relative offset of two
        positions of a window, M_s = -100 between tokens the roll brought
        together from different sides of the image (0 for s = 0);
        Linear(C -> C)(A v); merge windows; roll back by (s, s)
    RSTB_i(t) = t + conv3(STL_6(... STL_1(t)))       ('1conv')
    body:  f = f0 + conv3(LN(RSTB_n(... RSTB_1(t))))
    upsampler 'nearest+conv' (x4): a = lrelu_0.01(conv3(f, C -> 64));
        a = lrelu_0.2(conv3(nearest_x2(a), 64 -> 64)) twice;
        a = lrelu_0.2(conv3(a, 64 -> 64));  y01 = conv3(a, 64 -> 3) + m

Under a bf16 compute dtype the linear layers and the two attention products
read bf16 operands and sum in float32; LayerNorm's moments, the logits with
their bias and mask, and the softmax are float32 (``softmax_dtype`` /
``norm_dtype`` narrower than that are a control: every intermediate a
program of that precision would store is rounded to it, :func:`_stored`).

Scopes: ``swin_ln`` (every LayerNorm), ``swin_window`` (roll, partition,
reverse, roll back: layout only), ``swin_attn`` (qkv, logits + bias + mask,
softmax, A v, proj: on the chip the part between qkv and proj is one Pallas
call a direction, ``ops/pallas/window_attention.py``), ``swin_mlp``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from p2p_tpu.ops.activations import leaky_relu_y
from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer
from p2p_tpu.ops.pallas import window_attention as attention
from p2p_tpu.ops.pallas.window_attention import stored as _stored

#: what SwinIR-M fixes (the authors' ``window_size``, ``mlp_ratio``,
#: ``drop_path_rate``, layers a group, head width, upsampler width, the
#: RGB mean of DIV2K and LayerNorm's eps)
WINDOW = 8
MLP_RATIO = 2
DROP_PATH = 0.1
LAYERS_PER_GROUP = 6
HEAD_DIM = 30
UP_FEATURES = 64
MEAN = (0.4488, 0.4371, 0.4040)
LN_EPS = 1e-5
MASK_VALUE = -100.0

#: torch's default Conv2d kernel init (uniform(+-1/sqrt(fan_in))); the
#: linear layers draw truncated normal 0.02 as the authors' ``_init_weights``
_CONV_INIT = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")
_DENSE_INIT = nn.initializers.truncated_normal(0.02)


def _conv(features: int, name: str, dtype) -> ConvLayer:
    return ConvLayer(features, kernel_size=3, pad_mode="zero", dtype=dtype,
                     kernel_init=_CONV_INIT, name=name)


# ------------------------------------------------------------ constants


@functools.lru_cache(maxsize=None)
def relative_position_index(window: int) -> np.ndarray:
    """``[window^2, window^2]`` int32: for tokens i, j of a window (row
    major) the row ``(dy + window - 1) * (2 window - 1) + dx + window - 1``
    of the bias table, ``(dy, dx)`` = position of i less position of j."""
    ys, xs = np.divmod(np.arange(window * window), window)
    dy = ys[:, None] - ys[None, :] + window - 1
    dx = xs[:, None] - xs[None, :] + window - 1
    return (dy * (2 * window - 1) + dx).astype(np.int32)


@functools.lru_cache(maxsize=None)
def shift_region_labels(h: int, w: int, window: int) -> np.ndarray:
    """``[nW, window^2]`` int32: for an ``h`` x ``w`` image rolled by half
    a window, which of the nine regions (three bands a side: the bulk, the
    last window less the shift, the shift) each token of each window came
    from. Two tokens of a window attend to each other only within a
    region."""
    shift = window // 2
    img = np.zeros((h, w), np.int32)
    bands = (slice(0, -window), slice(-window, -shift), slice(-shift, None))
    for i, ys in enumerate(bands):
        for j, xs in enumerate(bands):
            img[ys, xs] = 3 * i + j
    img = img.reshape(h // window, window, w // window, window)
    return img.transpose(0, 2, 1, 3).reshape(-1, window * window)


def shift_mask(h: int, w: int, window: int) -> jax.Array:
    """``[nW, window^2, window^2]`` float32: 0 within a region, MASK_VALUE
    across two (:func:`shift_region_labels`)."""
    lab = jnp.asarray(shift_region_labels(h, w, window))
    return jnp.where(lab[:, :, None] == lab[:, None, :], 0.0,
                     MASK_VALUE).astype(jnp.float32)


def drop_path_rates(layers: int) -> Tuple[float, ...]:
    """p_j of every layer: linear from 0 to DROP_PATH."""
    return tuple(float(p) for p in np.linspace(0.0, DROP_PATH, layers))


def drop_path_keep(key: jax.Array, layers: int, batch: int) -> jax.Array:
    """The keep masks of one forward, ``[layers, batch]`` float32 of 0 / 1:
    one ``jax.random.uniform`` for all layers, image n of layer j kept
    where its draw is at least p_j. Both residual branches of a layer
    share a mask row of their own: rows ``2 j`` (attention) and ``2 j + 1``
    (MLP) of a ``[2 layers, batch]`` draw."""
    rates = jnp.repeat(jnp.asarray(drop_path_rates(layers), jnp.float32), 2)
    draws = jax.random.uniform(key, (2 * layers, batch), jnp.float32)
    return (draws >= rates[:, None]).astype(jnp.float32)


# --------------------------------------------------------------- layout


def window_partition(x: jax.Array, window: int) -> jax.Array:
    """``[N, H, W, C]`` -> ``[N * nW, window^2, C]``, windows row-major."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // window, window, w // window, window, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, window * window, c)


def window_reverse(x: jax.Array, window: int, h: int, w: int) -> jax.Array:
    """The inverse of :func:`window_partition`."""
    c = x.shape[-1]
    x = x.reshape(-1, h // window, w // window, window, window, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, h, w, c)


# --------------------------------------------------------------- modules


class LayerNorm(nn.Module):
    """LayerNorm over the last axis, moments in ``norm_dtype`` (float32;
    narrower, every intermediate is rounded to it), the result in the
    input's dtype."""

    norm_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        with jax.named_scope("swin_ln"):
            r = functools.partial(_stored, dtype=self.norm_dtype)
            xf = r(x)
            mean = r(jnp.mean(xf, axis=-1, keepdims=True))
            var = r(jnp.mean(r(jnp.square(r(xf - mean))), axis=-1,
                             keepdims=True))
            y = r(r(xf - mean) * r(jax.lax.rsqrt(var + LN_EPS)))
            return r(y * scale + bias).astype(x.dtype)


class Dense(nn.Module):
    """``x @ kernel + bias`` over the last axis: operands in ``dtype``,
    the sum in float32, the result in ``dtype``. For the attention
    kernel's layout (lane-aligned groups of channels) zero columns go into
    the kernel at apply time, so the parameters keep their shapes:
    ``out_heads`` = (groups, heads) lays the output's columns out as
    ``ops/pallas/window_attention.pad_heads`` does; ``in_heads`` =
    (heads, width) says the input is ``width`` channels laid out so (the
    zeros meet zero rows; the kernel parameter has ``width`` rows)."""

    features: int
    dtype: Optional[jnp.dtype] = None
    out_heads: Tuple[int, int] = ()
    in_heads: Tuple[int, int] = ()

    @nn.compact
    def __call__(self, x):
        rows = self.in_heads[1] if self.in_heads else x.shape[-1]
        kernel = self.param("kernel", _DENSE_INIT, (rows, self.features),
                            jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,),
                          jnp.float32)
        if self.out_heads:
            kernel = attention.pad_heads(kernel, *self.out_heads)
            bias = attention.pad_heads(bias, *self.out_heads)
        if self.in_heads:
            kernel = attention.pad_heads(kernel.T, 1, self.in_heads[0]).T
            assert kernel.shape[0] == x.shape[-1], (kernel.shape, x.shape)
        dt = self.dtype or x.dtype
        y = jax.lax.dot_general(
            x.astype(dt), kernel.astype(dt),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (y + bias).astype(dt)


class WindowAttention(nn.Module):
    """``WMSA_s`` on windows already split: ``[B, T, C]`` -> ``[B, T, C]``,
    ``mask`` ``[nW, T, T]`` or None. Between the two linear layers the
    function is ``ops/pallas/window_attention``'s, as its kernel where that
    module's ``kernel_plan`` says so (the TPU, a float32 softmax, one
    device, a shape it takes) and as XLA's chain everywhere else."""

    heads: int
    window: int = WINDOW
    softmax_dtype: jnp.dtype = jnp.float32
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, mask=None):
        c = x.shape[-1]
        table = self.param(
            "relative_position_bias_table", _DENSE_INIT,
            ((2 * self.window - 1) ** 2, self.heads), jnp.float32)
        index = relative_position_index(self.window)
        # the kernel or XLA's chain: from what this site can observe
        wb, interpret = attention.kernel_plan(
            x.shape, self.heads, self.dtype or x.dtype, mask,
            self.softmax_dtype)
        attention.note_site(self.path, wb)
        if not wb:
            qkv = Dense(3 * c, self.dtype, name="qkv")(x)
            out = attention.window_attention(
                qkv, table, index, mask, self.heads, self.softmax_dtype)
            return Dense(c, self.dtype, name="proj")(out)
        qkv = Dense(3 * c, self.dtype, out_heads=(3, self.heads),
                    name="qkv")(x)
        out = attention.window_attention_fused(
            qkv, table, index, mask, self.heads, c // self.heads, wb,
            interpret)
        return Dense(c, self.dtype, in_heads=(self.heads, c),
                     name="proj")(out)


class SwinLayer(nn.Module):
    """``STL_j`` on the image-shaped token stream; ``keep`` ``[2, N]``
    (attention branch, MLP branch) already divided by 1 - p_j, or None."""

    heads: int
    shift: int
    window: int = WINDOW
    softmax_dtype: jnp.dtype = jnp.float32
    norm_dtype: jnp.dtype = jnp.float32
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, t, keep=None):
        n, h, w, c = t.shape
        s, win = self.shift, self.window
        x = LayerNorm(self.norm_dtype, name="norm1")(t)
        with jax.named_scope("swin_window"):
            if s:
                x = jnp.roll(x, (-s, -s), axis=(1, 2))
            x = window_partition(x, win)
        with jax.named_scope("swin_attn"):
            x = WindowAttention(
                self.heads, win, self.softmax_dtype, self.dtype, name="attn"
            )(x, shift_mask(h, w, win) if s else None)
        with jax.named_scope("swin_window"):
            x = window_reverse(x, win, h, w)
            if s:
                x = jnp.roll(x, (s, s), axis=(1, 2))
        u = t + _drop(x, keep, 0)
        x = LayerNorm(self.norm_dtype, name="norm2")(u)
        with jax.named_scope("swin_mlp"):
            x = Dense(MLP_RATIO * c, self.dtype, name="fc1")(x)
            x = jax.nn.gelu(x.astype(jnp.float32),
                            approximate=False).astype(x.dtype)
            x = Dense(c, self.dtype, name="fc2")(x)
        return u + _drop(x, keep, 1)


def _drop(x, keep, row: int):
    if keep is None:
        return x
    return x * keep[row].astype(x.dtype)[:, None, None, None]


class SwinIR(nn.Module):
    """LQ image ``[N, H, W, 3]`` in [-1, 1] -> its x4 image ``[N, 4H, 4W,
    3]``; H and W multiples of the window. ``train`` turns stochastic depth
    on (rng collection ``dropout``)."""

    embed: int = 180
    groups: int = 6
    out_channels: int = 3
    scale: int = 4
    # SwinIR-M's own; no preset or flag changes them (tests shrink them)
    layers_per_group: int = LAYERS_PER_GROUP
    head_dim: int = HEAD_DIM
    window: int = WINDOW
    # anything but float32 is a control (benchmark/tools/control_sr.py,
    # tests/test_swinir.py)
    softmax_dtype: jnp.dtype = jnp.float32
    norm_dtype: jnp.dtype = jnp.float32
    dtype: Optional[jnp.dtype] = None

    @property
    def heads(self) -> int:
        return self.embed // self.head_dim

    @property
    def layers(self) -> int:
        return self.groups * self.layers_per_group

    def keep_masks(self, batch: int) -> jax.Array:
        """The stochastic-depth masks a training forward of ``batch``
        images draws from the ``dropout`` rng it is applied with (what a
        reference is handed to follow the same step)."""
        return drop_path_keep(self.make_rng("dropout"), self.layers, batch)

    @nn.compact
    def __call__(self, x, train: bool = True, keep=None):
        """``keep``: the ``[2 * layers, N]`` masks of
        :func:`drop_path_keep` to use in place of a draw."""
        if self.scale != 4:
            raise ValueError("the 'nearest+conv' upsampler is x4, got "
                             f"scale {self.scale}")
        if self.embed % self.head_dim:
            raise ValueError(f"embed {self.embed} is not a multiple of the "
                             f"head width {self.head_dim}")
        n, h, w, _ = x.shape
        win = self.window
        if h % win or w % win:
            raise ValueError(f"SwinIR needs H and W divisible by {win}, "
                             f"got {x.shape}")
        dt = self.dtype
        layers = self.layers
        if train and keep is None:
            keep = self.keep_masks(n)
        if keep is not None:
            rates = np.repeat(np.asarray(drop_path_rates(layers)), 2)
            keep = keep / jnp.asarray(1.0 - rates, jnp.float32)[:, None]
        mean = jnp.asarray(MEAN, jnp.float32)
        x01 = (x.astype(jnp.float32) + 1.0) * 0.5 - mean
        f0 = _conv(self.embed, "conv_first", dt)(
            x01.astype(dt) if dt is not None else x01)
        kw = dict(heads=self.heads, window=win,
                  softmax_dtype=self.softmax_dtype,
                  norm_dtype=self.norm_dtype, dtype=dt)
        t = LayerNorm(self.norm_dtype, name="patch_norm")(f0)
        for i in range(self.groups):
            g = t
            for j in range(self.layers_per_group):
                k = i * self.layers_per_group + j
                g = SwinLayer(shift=(win // 2) * (j % 2),
                              name=f"group_{i}_layer_{j}", **kw)(
                    g, None if keep is None else keep[2 * k:2 * k + 2])
            t = t + _conv(self.embed, f"group_{i}_conv", dt)(g)
        f = f0 + _conv(self.embed, "conv_after_body", dt)(
            LayerNorm(self.norm_dtype, name="norm")(t))
        a = leaky_relu_y(_conv(UP_FEATURES, "conv_before_upsample", dt)(f),
                         0.01)
        for name in ("conv_up1", "conv_up2"):
            a = leaky_relu_y(UpsampleConvLayer(
                UP_FEATURES, kernel_size=3, upsample=2, pad_mode="zero",
                dtype=dt, kernel_init=_CONV_INIT, name=name)(a), 0.2)
        a = leaky_relu_y(_conv(UP_FEATURES, "conv_hr", dt)(a), 0.2)
        y01 = _conv(self.out_channels, "conv_last", dt)(a)
        return ((y01.astype(jnp.float32) + mean) * 2.0 - 1.0).astype(
            y01.dtype)


def swinir_arithmetic(embed: int, groups: int, h: int,
                      w: int) -> Dict[str, float]:
    """The generator's forward arithmetic for one ``h`` x ``w`` INPUT image
    from its shapes (2 x multiply-adds), by part, in GFLOP, with the number
    of layers and of windows."""
    tokens, c = h * w, embed
    layers = groups * LAYERS_PER_GROUP
    t2 = WINDOW * WINDOW
    conv = lambda px, ci, co: 2.0 * px * 9 * ci * co  # noqa: E731
    parts = {
        # q k^T and A v: tokens x window tokens x channels each
        "attn_products": layers * 2 * 2.0 * tokens * t2 * c,
        "qkv_proj": layers * 2.0 * tokens * (3 * c * c + c * c),
        "mlp": layers * 2 * 2.0 * tokens * c * MLP_RATIO * c,
        "group_convs": (groups + 1) * conv(tokens, c, c)
        + conv(tokens, 3, c),
        "upsampler": conv(tokens, c, UP_FEATURES)
        + conv(4 * tokens, UP_FEATURES, UP_FEATURES)
        + 2 * conv(16 * tokens, UP_FEATURES, UP_FEATURES)
        + conv(16 * tokens, UP_FEATURES, 3),
    }
    out = {f"swinir_{k}_gflop_per_image": v / 1e9 for k, v in parts.items()}
    out["generator_gflop_per_image"] = sum(parts.values()) / 1e9
    out["swinir_layers"] = float(layers)
    out["swinir_windows_per_image"] = float(tokens // t2)
    return out
