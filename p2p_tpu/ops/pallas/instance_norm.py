"""Pallas-fused InstanceNorm (TPU).

Target: the pix2pixHD 1024×512 config (BASELINE.json configs[3]), where
instance-norm statistics over 512×1024 spatial extents are HBM-bound and
worth fusing: one pass accumulates per-(sample, channel) sum / sum-of-squares
tiles, a second normalizes — versus XLA's default which materializes the
centered tensor.

``pallas_instance_norm`` dispatches to the kernel on TPU and to a reference
XLA implementation elsewhere (CPU tests run the kernel in interpret mode via
``force_pallas=True``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.ops.pallas import kernel_dispatch, spans_devices


def _xla_instance_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(1, 2), keepdims=True)
    var = jnp.var(x32, axis=(1, 2), keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale + bias
    return y.astype(x.dtype)


def _xla_instance_norm_act(x, scale, bias, residual, act, slope, eps):
    """The lax reference for the fused epilogue — the CPU/tier-1 fallback
    of :func:`pallas_instance_norm_act` (same op order as the kernel:
    norm → affine → residual add → activation, all in f32).

    This chain is also the fusion-gap lint's flagged site
    (``perf-unfused-norm-chain``, analysis/perf_audit.py): in a program
    whose config says the epilogues fuse, these reference ops appearing
    in the jaxpr mean the dispatch below silently fell back — the lint
    CLI traces the fused program under ``P2P_TPU_FORCE_PALLAS=1`` so a
    regression here (a dispatch-condition typo, a new call site skipping
    :func:`p2p_tpu.ops.norm.make_norm_act`) fails ``lint --strict``
    instead of quietly costing a bench round."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(1, 2), keepdims=True)
    var = jnp.var(x32, axis=(1, 2), keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale + bias
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        from p2p_tpu.ops.activations import relu_y

        y = relu_y(y)
    elif act == "leaky":
        from p2p_tpu.ops.activations import leaky_relu_y

        y = leaky_relu_y(y, slope)
    return y.astype(x.dtype)


def sharded_pallas_instance_norm(
    x: jax.Array,
    scale: Optional[jax.Array],
    bias: Optional[jax.Array],
    eps: float,
    mesh,
    interpret: bool = False,
) -> jax.Array:
    """The Pallas InstanceNorm inside a manual-sharding (shard_map) region.

    GSPMD has no partitioning rule for custom calls: left alone under a
    ``P('data','spatial',...)`` activation sharding it would all-gather the
    full (N,H,W,C) tensor around the ``pallas_call`` — at pix2pixHD's
    1024×512 that silently defeats the spatial shard (VERDICT r1 weak#4).
    Here each device runs the kernel on its local H-shard and only the
    (N,1,1,C) stat tiles cross the ICI via psum.
    """
    from jax.sharding import PartitionSpec as P

    from p2p_tpu.core.mesh import (
        BATCH_AXES,
        SPATIAL_AXIS,
    )
    from p2p_tpu.ops.pallas.instance_norm_kernel import (
        instance_norm_fused_sharded,
    )

    # N splits over (data, fsdp) — core/mesh.batch_sharding; instance
    # stats are per-sample so only the spatial psum crosses devices
    x_spec = P(BATCH_AXES, SPATIAL_AXIS, None, None)
    if scale is None:
        fn = jax.shard_map(
            lambda xl: instance_norm_fused_sharded(
                xl, None, None, eps, SPATIAL_AXIS, interpret),
            mesh=mesh, in_specs=(x_spec,), out_specs=x_spec,
            check_vma=False,  # pallas out_shapes carry no vma info
        )
        return fn(x)
    fn = jax.shard_map(
        lambda xl, s, b: instance_norm_fused_sharded(
            xl, s, b, eps, SPATIAL_AXIS, interpret),
        mesh=mesh, in_specs=(x_spec, P(), P()), out_specs=x_spec,
        check_vma=False,  # pallas out_shapes carry no vma info
    )
    return fn(x, scale, bias)


def _sharding_mesh_for(x: jax.Array, interpret: bool = False):
    """``(mesh, multi)``: the visible mesh when it spans several devices
    and x can be laid out over (data×fsdp, spatial) on it, else None
    (core/mesh.spatial_shard_mesh); and whether the program being traced
    may span several devices (``ops/pallas.spans_devices``). In such a
    program the compiled kernel runs inside a ``shard_map`` or not at all
    (Mosaic refuses to partition it): an x that cannot be laid out takes
    the XLA norm, which GSPMD partitions."""
    from p2p_tpu.core.mesh import current_mesh, spatial_shard_mesh

    mesh = None if current_mesh() is None else spatial_shard_mesh(x)
    return mesh, spans_devices(interpret)


def pallas_instance_norm(
    x: jax.Array,
    scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """InstanceNorm on NHWC. Uses the Pallas kernel on TPU backends; in a
    program over several devices (core.mesh.current_mesh: the parallel
    step) it switches to the shard_map variant so the activations never
    get all-gathered."""
    use_kernel, interp = kernel_dispatch(force_pallas, interpret)
    if not use_kernel:
        # off-TPU: XLA norm — fast, and GSPMD partitions it natively (no
        # custom-call all-gather hazard). Fake-mesh CI opts into the real
        # shard_map + interpret-mode program via force_pallas=True or
        # P2P_TPU_FORCE_PALLAS=1.
        return _xla_instance_norm(x, scale, bias, eps)
    mesh, multi = _sharding_mesh_for(x, interp)
    if mesh is not None:
        return sharded_pallas_instance_norm(x, scale, bias, eps, mesh, interp)
    if multi:
        return _xla_instance_norm(x, scale, bias, eps)
    from p2p_tpu.ops.pallas.instance_norm_kernel import instance_norm_fused

    return instance_norm_fused(x, scale, bias, eps, interpret=interp)


def sharded_pallas_instance_norm_act(
    x, scale, bias, residual, act, slope, eps, mesh, interpret=False):
    """The fused norm+act(+residual) kernel inside a shard_map region —
    same GSPMD custom-call rationale as :func:`sharded_pallas_instance_norm`
    (the residual shards like ``x``; only stat tiles cross the ICI)."""
    from jax.sharding import PartitionSpec as P

    from p2p_tpu.core.mesh import (
        BATCH_AXES,
        SPATIAL_AXIS,
    )
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_fused_sharded

    # N over (data, fsdp) like the plain variant above and like
    # _sharding_mesh_for's divisibility test: with DATA_AXIS alone a
    # data x fsdp x spatial mesh would gather the fsdp shards of N
    x_spec = P(BATCH_AXES, SPATIAL_AXIS, None, None)
    affine = scale is not None
    has_res = residual is not None
    in_specs = [x_spec] + ([P(), P()] if affine else []) + (
        [x_spec] if has_res else [])
    args = (x,) + ((scale, bias) if affine else ()) + (
        (residual,) if has_res else ())

    def body(*a):
        it = iter(a)
        xl = next(it)
        s = next(it) if affine else None
        b = next(it) if affine else None
        r = next(it) if has_res else None
        return instance_norm_act_fused_sharded(
            xl, s, b, r, act=act, slope=slope, eps=eps,
            axis_name=SPATIAL_AXIS, interpret=interpret)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=x_spec,
        check_vma=False,  # pallas out_shapes carry no vma info
    )
    return fn(*args)


def pallas_instance_norm_act(
    x: jax.Array,
    scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    act: str = "none",
    slope: float = 0.2,
    eps: float = 1e-5,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """InstanceNorm with the whole post-conv epilogue fused:
    ``act(norm(x)·γ+β [+ residual])`` — the dispatch seam for the fused
    norm+activation chains (docs/PERFORMANCE.md). TPU backends run the
    Pallas kernel (ops/pallas/norm_act.py); inside a spatial-sharded step
    the shard_map variant keeps the custom call on local shards; elsewhere
    the lax reference runs (so CPU tier-1 exercises the same call sites)."""
    use_kernel, interp = kernel_dispatch(force_pallas, interpret)
    if not use_kernel:
        return _xla_instance_norm_act(x, scale, bias, residual, act, slope,
                                      eps)
    mesh, multi = _sharding_mesh_for(x, interp)
    if mesh is not None:
        return sharded_pallas_instance_norm_act(
            x, scale, bias, residual, act, slope, eps, mesh, interp)
    if multi:
        return _xla_instance_norm_act(x, scale, bias, residual, act, slope,
                                      eps)
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_fused

    return instance_norm_act_fused(x, scale, bias, residual, act=act,
                                   slope=slope, eps=eps, interpret=interp)


def pallas_instance_norm_act_quant(
    x: jax.Array,
    sx: jax.Array,
    scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    act: str = "none",
    slope: float = 0.2,
    eps: float = 1e-5,
    force_pallas: bool = False,
    interpret: bool = False,
):
    """The QUANTIZE-fused epilogue dispatch (ISSUE 14 bandwidth half):
    ``act(norm(x)·γ+β)`` clipped/rounded onto the int8 grid with stored
    scale ``sx`` → ``(q, amax)``, all in one two-pass streaming kernel
    (ops/pallas/norm_act.py ``instance_norm_act_quant``). Same seam
    shape as :func:`pallas_instance_norm_act`: TPU backends (or
    ``P2P_TPU_FORCE_PALLAS=1``) run the Pallas kernel, everywhere else
    the lax reference runs through the SAME custom-VJP STE law — CPU
    tier-1 exercises the identical call sites and backward. Spatially
    sharded shards fall back to the reference (the quant kernel has no
    shard_map variant yet — the D families this epilogue serves are not
    spatial-sharded)."""
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_quant

    use_kernel, interp = kernel_dispatch(force_pallas, interpret)
    use_kernel = use_kernel and not _sharding_mesh_for(x, interp)[1]
    return instance_norm_act_quant(
        x, sx, scale, bias, act=act, slope=slope, eps=eps,
        use_kernel=use_kernel, interpret=interp)


class PallasInstanceNorm(nn.Module):
    """Module wrapper matching :class:`p2p_tpu.ops.norm.InstanceNorm`."""

    affine: bool = False
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        scale = bias = None
        if self.affine:
            c = x.shape[-1]
            scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        y = pallas_instance_norm(x, scale, bias, self.epsilon)
        return y.astype(self.dtype or x.dtype)
