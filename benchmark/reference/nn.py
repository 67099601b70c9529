"""Plain layer equations the per-config references are written from.

Only ``jax.numpy`` / ``lax``, float32, every contraction at
``Precision.HIGHEST``; no kernels, no program code. Images are NHWC,
kernels HWIO. Parameters come as a FLAT dict ``{"a/b/kernel": ndarray}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
EPS = 1e-5


def to_unit(x_uint8):
    """uint8 [0,255] -> float32 [-1,1]: (x - 127.5) / 127.5."""
    return (x_uint8.astype(jnp.float32) - 127.5) * jnp.float32(1.0 / 127.5)


def reflect_conv(x, kernel, bias=None, stride: int = 1):
    """ReflectionPad2d(k // 2) then a VALID cross-correlation."""
    p = kernel.shape[0] // 2
    x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect")
    y = lax.conv_general_dilated(
        x, kernel, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y if bias is None else y + bias


def zero_conv(x, kernel, bias=None, stride: int = 1, pad: int = 0):
    """Conv2d(padding=pad): zero padding, then a cross-correlation."""
    y = lax.conv_general_dilated(
        x, kernel, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y if bias is None else y + bias


def max_pool_2(x):
    """MaxPool2d(2, stride=2)."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def upsample_nearest(x, factor: int):
    return jnp.repeat(jnp.repeat(x, factor, axis=1), factor, axis=2)


def batch_norm(x, scale, bias, stats=None):
    """Train mode (``stats`` None): biased moments over N, H, W.
    Eval mode: the running ``(mean, var)`` given. Returns (y, (mean, var))."""
    if stats is None:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:
        mean, var = stats
    y = (x - mean) * lax.rsqrt(var + EPS) * scale + bias
    return y, (mean, var)


def instance_norm(x):
    mean = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(1, 2), keepdims=True)
    return (x - mean) * lax.rsqrt(var + EPS)


def prelu(x, alpha):
    return jnp.maximum(x, 0) + alpha * jnp.minimum(x, 0)


def leaky_relu(x, slope: float = 0.2):
    return jnp.where(x >= 0, x, slope * x)


def pixel_unshuffle(x, r: int):
    """(N,H,W,C) -> (N,H/r,W/r,C*r*r); out channel = c*r*r + dy*r + dx."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // r, r, w // r, r, c)
    return x.transpose(0, 1, 3, 5, 2, 4).reshape(n, h // r, w // r, c * r * r)


def pixel_shuffle(x, r: int):
    """Inverse of :func:`pixel_unshuffle`."""
    n, h, w, crr = x.shape
    c = crr // (r * r)
    x = x.reshape(n, h, w, c, r, r)
    return x.transpose(0, 1, 4, 2, 5, 3).reshape(n, h * r, w * r, c)


def avg_pool_3s2(x):
    """AvgPool2d(3, stride=2, padding=1, count_include_pad=False)."""
    win, strides = (1, 3, 3, 1), (1, 2, 2, 1)
    pad = [(0, 0), (1, 1), (1, 1), (0, 0)]
    s = lax.reduce_window(x, 0.0, lax.add, win, strides, pad)
    n = lax.reduce_window(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype),
                          0.0, lax.add, win, strides, pad)
    return s / n


def quantize(x, bits: int):
    """round(clamp(x, 0, 1) * (2^b - 1)) / (2^b - 1)."""
    n = float(2 ** bits - 1)
    return jnp.round(jnp.clip(x, 0.0, 1.0) * n) / n


def to_uint8(y):
    """[-1,1] float -> uint8 level: clip(round((y + 1) / 2 * 255))."""
    return jnp.clip(jnp.round((y + 1.0) * 127.5), 0, 255).astype(jnp.uint8)


def on_cpu(fn):
    """Run ``fn`` jitted on the host CPU backend in true float32, whatever
    the process's default device is — the reference never takes the chip's
    memory or its matmul precision."""
    cpu = jax.devices("cpu")[0]
    jitted = jax.jit(fn)

    def call(*args):
        with jax.default_device(cpu):
            args = jax.device_put(args, cpu)
            return jax.device_get(jitted(*args))

    return call
