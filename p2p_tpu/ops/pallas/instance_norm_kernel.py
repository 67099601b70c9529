"""The fused InstanceNorm Pallas TPU kernel.

Two sequential-grid passes over NHWC data, blocked on H so arbitrarily large
spatial extents stream through VMEM:

1. stats pass — per (sample, H-block): accumulate Σx and Σx² tiles of shape
   (1, 1, 1, C) in fp32, revisiting the same output block across H-blocks
   (TPU grids execute sequentially, so first-visit init + accumulate is
   race-free).
2. normalize pass — per (sample, H-block): y = (x − μ)·rsqrt(σ² + ε)·γ + β
   with μ, σ², γ, β broadcast from (1,1,1,C) tiles.

The tiny μ/σ² computation between passes is plain jnp and fuses away.

One implementation serves both the single-device and the spatially-sharded
case: with ``axis_name`` set (call inside a shard_map whose x spec shards H
over that axis) the (N,1,1,C) stat tiles are psum'd across the axis between
the passes — the activations never cross devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pick_h_block(h: int, w: int, c: int, budget_bytes: int = 1024 * 1024) -> int:
    """Largest divisor of H whose (hb, W, C) fp32 block fits the VMEM budget.

    Sized against the PADDED tile: VMEM lays the (w, c) minor dims out in
    (8, 128) tiles, so a narrow channel dim (e.g. the 32-channel local
    enhancer at 1024×512) occupies 128 lanes regardless — ignoring that
    padding overflowed scoped vmem (23.8M > 16M limit) on the pix2pixHD
    preset. The budget covers the fp32 working copy; the bf16 in/out
    blocks and double-buffering ride in the remaining headroom."""
    padded_w = -(-w // 8) * 8
    padded_c = -(-c // 128) * 128
    row_bytes = max(1, padded_w * padded_c * 4)
    max_hb = max(1, budget_bytes // row_bytes)
    for hb in range(min(h, max_hb), 0, -1):
        if h % hb == 0:
            return hb
    return 1


def _stats_kernel(x_ref, s1_ref, s2_ref):
    hb = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    s1 = jnp.sum(x, axis=(0, 1, 2))[None, None, None, :]
    s2 = jnp.sum(x * x, axis=(0, 1, 2))[None, None, None, :]

    @pl.when(hb == 0)
    def _init():
        s1_ref[...] = s1
        s2_ref[...] = s2

    @pl.when(hb != 0)
    def _acc():
        s1_ref[...] += s1
        s2_ref[...] += s2


def _norm_kernel(x_ref, mean_ref, rstd_ref, scale_ref, bias_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    y = (x - mean_ref[...]) * rstd_ref[...]
    y = y * scale_ref[...] + bias_ref[...]
    y_ref[...] = y.astype(y_ref.dtype)


def _stats_local(x, interpret):
    """Pass 1 on the (possibly local-shard) array: per-(n,c) Σx, Σx²."""
    n, h, w, c = x.shape
    hb = _pick_h_block(h, w, c)
    x_spec = pl.BlockSpec((1, hb, w, c), lambda i, j: (i, j, 0, 0))
    cvec_spec = pl.BlockSpec((1, 1, 1, c), lambda i, j: (i, 0, 0, 0))
    return pl.pallas_call(
        _stats_kernel,
        grid=(n, h // hb),
        in_specs=[x_spec],
        out_specs=[cvec_spec, cvec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, 1, c), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def _norm_local(x, mean, rstd, scale, bias, interpret):
    """Pass 2: y = (x − μ)·rstd·γ + β on the (possibly local-shard) array."""
    n, h, w, c = x.shape
    hb = _pick_h_block(h, w, c)
    x_spec = pl.BlockSpec((1, hb, w, c), lambda i, j: (i, j, 0, 0))
    cvec_spec = pl.BlockSpec((1, 1, 1, c), lambda i, j: (i, 0, 0, 0))
    bcast_spec = pl.BlockSpec((1, 1, 1, c), lambda i, j: (0, 0, 0, 0))
    if scale is None:
        scale_t = jnp.ones((1, 1, 1, c), jnp.float32)
        bias_t = jnp.zeros((1, 1, 1, c), jnp.float32)
    else:
        scale_t = scale.reshape(1, 1, 1, c).astype(jnp.float32)
        bias_t = bias.reshape(1, 1, 1, c).astype(jnp.float32)
    return pl.pallas_call(
        _norm_kernel,
        grid=(n, h // hb),
        in_specs=[x_spec, cvec_spec, cvec_spec, bcast_spec, bcast_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, mean, rstd, scale_t, bias_t)


def _fwd_impl(x, scale, bias, eps: float, interpret: bool, axis_name=None):
    """Runs the two Pallas passes; returns (y, mean, rstd, count) with
    mean/rstd shaped (N,1,1,C) fp32. ``axis_name`` = spatial-sharded mode
    (see module docstring)."""
    n, h, w, c = x.shape
    s1, s2 = _stats_local(x, interpret)
    if axis_name is None:
        count = jnp.float32(h * w)
    else:
        with jax.named_scope("norm_psum"):
            s1 = jax.lax.psum(s1, axis_name)
            s2 = jax.lax.psum(s2, axis_name)
            count = float(h * w) * jax.lax.psum(
                jnp.ones((), jnp.float32), axis_name)
    mean = s1 / count
    var = jnp.maximum(s2 / count - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    y = _norm_local(x, mean, rstd, scale, bias, interpret)
    return y, mean, rstd, count


# pallas_call has no reverse-mode rule, so the fused forward carries an
# explicit instance-norm VJP (standard normalization backward; the two
# backward reductions are small and XLA-fused — psum'd across the spatial
# axis in sharded mode).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _in_fused(x, scale, bias, eps, interpret, axis_name):
    y, _, _, _ = _fwd_impl(x, scale, bias, eps, interpret, axis_name)
    return y


def _in_fused_fwd(x, scale, bias, eps, interpret, axis_name):
    y, mean, rstd, count = _fwd_impl(x, scale, bias, eps, interpret, axis_name)
    return y, (x, scale, bias, mean, rstd, count)


def _in_fused_bwd(eps, interpret, axis_name, res, g):
    x, scale, bias, mean, rstd, count = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    xhat = (x32 - mean) * rstd
    gamma = (
        jnp.float32(1.0) if scale is None
        else scale.reshape(1, 1, 1, -1).astype(jnp.float32)
    )
    dxhat = g32 * gamma
    # means over the (possibly sharded) global (H, W) extent
    m1 = jnp.sum(dxhat, axis=(1, 2), keepdims=True)
    m2 = jnp.sum(dxhat * xhat, axis=(1, 2), keepdims=True)
    if axis_name is not None:
        with jax.named_scope("norm_psum"):
            m1 = jax.lax.psum(m1, axis_name)
            m2 = jax.lax.psum(m2, axis_name)
    m1 = m1 / count
    m2 = m2 / count
    dx = (rstd * (dxhat - m1 - xhat * m2)).astype(x.dtype)
    if scale is None:
        dscale = dbias = None
    else:
        # local contributions in sharded mode; shard_map's transpose of
        # the replicated scale/bias in_specs psums these across devices
        dscale = jnp.sum(g32 * xhat, axis=(0, 1, 2)).astype(scale.dtype)
        dbias = jnp.sum(g32, axis=(0, 1, 2)).astype(bias.dtype)
    return dx, dscale, dbias


_in_fused.defvjp(_in_fused_fwd, _in_fused_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def instance_norm_fused(x, scale=None, bias=None, eps: float = 1e-5,
                        interpret: bool = False):
    return _in_fused(x, scale, bias, eps, interpret, None)


def instance_norm_fused_sharded(x, scale=None, bias=None, eps: float = 1e-5,
                                axis_name: str = "spatial",
                                interpret: bool = False):
    """InstanceNorm over an H-sharded NHWC shard (call inside shard_map)."""
    return _in_fused(x, scale, bias, eps, interpret, axis_name)
