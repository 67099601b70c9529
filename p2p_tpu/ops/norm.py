"""Normalization layers.

The reference uses ``BatchNorm2d`` throughout its live model zoo
(networks.py:433 and others — the InstanceNorm ``get_norm_layer`` at
networks.py:93-102 is dead code), trained at batch size 1, which makes its
"batch" statistics effectively instance statistics with running-stat drift.
The build keeps BatchNorm as the reference-faithful default, and offers
InstanceNorm (pix2pixHD-style) plus a Pallas-fused InstanceNorm for the
1024×512 config.

Statistics are computed in fp32 regardless of the bf16 compute dtype.

Cross-device sync under data parallelism: all layers here compute statistics
with plain ``jnp`` reductions over a *logically global* batch — under
jit+GSPMD the mesh makes those reductions global automatically (XLA inserts
the psum over the ``data`` axis), which IS sync-BN. Under ``shard_map``
regions pass ``axis_name='data'`` to opt in explicitly.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name


def _gamma_init(key, shape, dtype=jnp.float32):
    # Reference BatchNorm affine init: γ ~ N(1, 0.02) (networks.py:144-146).
    return 1.0 + jax.random.normal(key, shape, dtype) * 0.02


@jax.custom_vjp
def dual_moments(xc):
    """Per-channel (Σxc, Σxc²) over all leading axes in ONE variadic
    reduction — f32 accumulation.

    Two separate ``jnp.mean`` reductions profile as one fused kernel that
    still READS the activation twice (534 MB moved for a 268 MB tensor —
    the round-3 BatchNorm_12 'add' kernel). A variadic ``lax.reduce`` with
    the square fused as an elementwise producer is a single pass at the
    HLO level. (XLA's reduce kernel still reads each operand separately;
    a hand-fused Pallas kernel that reads x once measured 6% SLOWER on
    the 256² step and was deleted in PR 27.) The VJP is the same closed
    form XLA derives for sum/sumsq: ``dxc = ds + 2·xc·dss`` (broadcast
    over channels).
    """
    xf = xc.astype(jnp.float32)
    dims = tuple(range(xc.ndim - 1))
    return jax.lax.reduce(
        (xf, jnp.square(xf)),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        dims,
    )


def _dual_moments_fwd(xc):
    out = dual_moments(xc)
    return out, xc


def _dual_moments_bwd(xc, ct):
    ds, dss = ct
    dxc = ds.astype(jnp.float32) + 2.0 * xc.astype(jnp.float32) * dss
    return (dxc.astype(xc.dtype),)


dual_moments.defvjp(_dual_moments_fwd, _dual_moments_bwd)


class _FastBatchNorm(nn.Module):
    """Hand-written BatchNorm tuned for TPU HBM traffic.

    ``flax.linen.BatchNorm`` materializes a full fp32 copy of the (bf16)
    activation for its statistics and runs a two-pass variance; on the
    256² U-Net step that shows up in the profile as standalone
    ``convert_element_type`` / ``reduce`` kernels re-reading the largest
    decoder activations several times. This version:

    - computes both moments in ONE pass (`mean`, `mean(x²)`) with fp32
      *accumulation* (``jnp.mean(..., dtype=f32)``) so the bf16→f32
      convert fuses into the reduction instead of materializing;
    - folds the normalization into a per-channel affine ``y = x·a + b``
      (a = γ·rsqrt(var+ε), b = β − μ·a), one fusable elementwise pass;
    - keeps flax param/stat names (scale/bias, mean/var) and semantics
      (biased batch variance stored in the running stats).
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None
    # False: BatchNorm2d(affine=False), no params (what SPADE modulates)
    affine: bool = True
    # how many trailing axes of ``x`` index channels. 2: ``[..., C, 2]``
    # (models/ffc.py's (channel, real / imaginary) pairs) under the SAME
    # flat vectors of 2C a ``[..., 2C]`` tensor would have, so the pairs
    # never have to be brought side by side on the lanes
    feature_axes: int = 1

    @nn.compact
    def __call__(self, x):
        feat = x.shape[-self.feature_axes:]
        c = math.prod(feat)
        if self.affine:
            scale = self.param("scale", _gamma_init, (c,), jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (c,),
                              jnp.float32)
        else:
            scale, bias = 1.0, 0.0
        init = self.is_initializing()
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), jnp.float32)
        )

        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            # Shifted one-pass moments: Var(x) = E[(x−c)²] − (μ−c)² for any
            # constant c; with c = the running mean (≈ μ after warm-up) the
            # subtraction is cancellation-safe where the naive E[x²]−E[x]²
            # form loses all precision for high-mean/low-variance channels.
            # Still a single read of x — the shift fuses into the reduces.
            shift = jax.lax.stop_gradient(ra_mean.value).astype(x.dtype)
            xc = x - shift.reshape(feat)
            n = x.size // c
            moments = dual_moments
            for _ in feat[1:]:
                moments = jax.vmap(moments, in_axes=-1, out_axes=-1)
            sum_c, sumsq_c = moments(xc)
            mean_c = sum_c.reshape(c) / n
            msq_c = sumsq_c.reshape(c) / n
            if self.axis_name is not None:
                mean_c = jax.lax.pmean(mean_c, self.axis_name)
                msq_c = jax.lax.pmean(msq_c, self.axis_name)
            # add back the exact shift
            mean = mean_c + shift.astype(jnp.float32)
            var = jnp.maximum(msq_c - jnp.square(mean_c), 0.0)
            if not init:
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
                ra_var.value = m * ra_var.value + (1.0 - m) * var

        a = scale * jax.lax.rsqrt(var + self.epsilon)
        b = bias - mean * a
        # Under the conv-residuals-only checkpoint policy (train/step.py),
        # keep the tiny per-channel affine so the backward never re-reduces
        # the full activation to recover the batch statistics.
        a = checkpoint_name(a, "norm_stats")
        b = checkpoint_name(b, "norm_stats")
        # Apply the folded affine in the input dtype: an f32 apply would pin a
        # materialized fp32 copy of the activation (multiple consumers defeat
        # fusion of the convert). Per-channel a/b quantization to bf16 is
        # ~2⁻⁸ relative — noise for GAN training; fp32 inputs are unaffected.
        y = (x * a.astype(x.dtype).reshape(feat)
             + b.astype(x.dtype).reshape(feat))
        return y.astype(self.dtype or x.dtype)


class BatchNorm(nn.Module):
    """BatchNorm over (N,H,W) in NHWC with running stats in 'batch_stats'.

    Affine init matches the reference: γ ~ N(1, 0.02), β = 0
    (networks.py:144-146). Inner module is pinned to the flax name
    ``BatchNorm_0`` so param/stat pytree paths stay stable.
    """

    use_running_average: bool = False
    momentum: float = 0.9  # flax convention; equals torch momentum=0.1
    epsilon: float = 1e-5
    axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None
    affine: bool = True
    feature_axes: int = 1

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        ura = (
            self.use_running_average
            if use_running_average is None
            else use_running_average
        )
        return _FastBatchNorm(
            use_running_average=ura,
            momentum=self.momentum,
            epsilon=self.epsilon,
            axis_name=self.axis_name,
            dtype=self.dtype,
            affine=self.affine,
            feature_axes=self.feature_axes,
            name="BatchNorm_0",
        )(x)


class InstanceNorm(nn.Module):
    """Per-sample, per-channel normalization over H,W (NHWC).

    Matches torch ``InstanceNorm2d(affine=affine)`` semantics: statistics are
    always per-forward (no running stats), eps inside the sqrt.
    """

    affine: bool = False
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = checkpoint_name(
            jnp.mean(x32, axis=(1, 2), keepdims=True), "norm_stats"
        )
        var = checkpoint_name(
            jnp.var(x32, axis=(1, 2), keepdims=True), "norm_stats"
        )
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        if self.affine:
            c = x.shape[-1]
            scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
            y = y * scale + bias
        return y.astype(self.dtype or orig_dtype)



@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def affine_act(x, a, b, swish: bool):
    """``act(x * a + b)`` with a per-(image, channel) pair ``a``, ``b``
    (float32 ``[N, C]``) and ``act`` swish (``z * sigmoid(z)``) or the
    identity: the normalise-and-activate pass of :class:`GroupNorm`.
    The chain runs in float32 and is stored once in ``x.dtype``; the
    backward keeps ``x`` alone and recomputes ``z``, so no float32 copy
    of the activation outlives the pass."""
    z = x.astype(jnp.float32) * a[:, None, None, :] + b[:, None, None, :]
    if swish:
        z = z * jax.nn.sigmoid(z)
    return z.astype(x.dtype)


def _affine_act_fwd(x, a, b, swish):
    return affine_act(x, a, b, swish), (x, a, b)


def _affine_act_bwd(swish, res, ct):
    x, a, b = res
    xf = x.astype(jnp.float32)
    dz = ct.astype(jnp.float32)
    if swish:
        z = xf * a[:, None, None, :] + b[:, None, None, :]
        s = jax.nn.sigmoid(z)
        dz = dz * (s * (1.0 + z * (1.0 - s)))
    dx = (dz * a[:, None, None, :]).astype(x.dtype)
    return dx, jnp.sum(dz * xf, (1, 2)), jnp.sum(dz, (1, 2))


affine_act.defvjp(_affine_act_fwd, _affine_act_bwd)


class GroupNorm(nn.Module):
    """``GroupNorm(groups, eps, affine)`` over NHWC, with its swish:

        y = act((x - mean_g) * rsqrt(var_g + eps) * scale + bias)

    ``mean_g`` / ``var_g`` (biased) are taken per (image, group) over H,
    W and the group's ``C / groups`` channels, in float32 whatever
    ``x.dtype`` is: one pass over ``x`` for the per-channel sums
    (:func:`dual_moments` an image), the groups combined on ``[N, C]``
    numbers; the normalisation folds into a per-(image, channel) affine
    that :func:`affine_act` applies with the activation in one pass.
    ``swish``: ``z * sigmoid(z)`` after it (the residual blocks' two
    sites and each net's last norm); without it the norm alone (the
    attention blocks'). Scale 1, bias 0 at init, as torch's.

    A swish site runs under ``jax.named_scope("gn_swish")``, a plain one
    under ``"gn"``."""

    groups: int = 32
    epsilon: float = 1e-6
    swish: bool = True

    @nn.compact
    def __call__(self, x):
        n, h, w, c = x.shape
        if c % self.groups:
            raise ValueError(f"GroupNorm: {c} channels do not divide "
                             f"into {self.groups} groups")
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        with jax.named_scope("gn_swish" if self.swish else "gn"):
            s, ss = jax.vmap(dual_moments)(x)
            cg = c // self.groups
            count = h * w * cg
            by_group = lambda t: jnp.sum(  # noqa: E731
                t.reshape(n, self.groups, cg), -1, keepdims=True) / count
            mean = by_group(s)
            var = jnp.maximum(by_group(ss) - jnp.square(mean), 0.0)
            a = scale.reshape(self.groups, cg) * jax.lax.rsqrt(
                var + self.epsilon)
            b = bias.reshape(self.groups, cg) - mean * a
            a = checkpoint_name(a.reshape(n, c), "norm_stats")
            b = checkpoint_name(b.reshape(n, c), "norm_stats")
            return affine_act(x, a, b, self.swish)


def make_norm_act(kind: str, *, train: bool = True,
                  axis_name: Optional[str] = None, dtype=None):
    """Factory for the post-conv epilogue ``act(norm(y) [+ residual])`` —
    the ONE seam the generator/discriminator blocks call so the
    ``pallas_instance`` kind can fuse the whole chain into the Pallas
    normalize pass (ops/pallas/norm_act.py) while every other kind keeps
    today's exact op order (norm module → residual add → output-masked
    activation). Returns ``apply(y, act="none", slope=0.2, residual=None)``;
    call inside ``@nn.compact`` (the non-fused kinds instantiate their norm
    module per call, so flax auto-naming — and therefore param/stat trees —
    is identical to the unfused ``make_norm`` layout)."""
    if kind == "pallas_instance":
        from p2p_tpu.ops.pallas.instance_norm import (
            pallas_instance_norm_act,
            pallas_instance_norm_act_quant,
        )

        def apply_fused(y, act: str = "none", slope: float = 0.2,
                        residual=None, quant_scale=None):
            if quant_scale is not None:
                # quantize-fused epilogue (ISSUE 14): emit the on-grid
                # activation + its amax proposal from the same two-pass
                # kernel; the caller feeds ops.int8.int8_conv_pq
                if residual is not None:
                    raise ValueError(
                        "quant_scale does not compose with residual "
                        "(no quantized resblock tail in the zoo)")
                return pallas_instance_norm_act_quant(
                    y, quant_scale, act=act, slope=slope)
            out = pallas_instance_norm_act(y, residual=residual, act=act,
                                           slope=slope)
            return out.astype(dtype or y.dtype)

        return apply_fused

    mk = make_norm(kind, train=train, axis_name=axis_name, dtype=dtype)

    def apply_ref(y, act: str = "none", slope: float = 0.2, residual=None,
                  quant_scale=None):
        from p2p_tpu.ops.activations import leaky_relu_y, relu_y

        if quant_scale is not None:
            if kind != "instance" or residual is not None:
                raise ValueError(
                    "quant_scale needs a stateless instance-family norm "
                    f"with no residual (kind={kind!r})")
            # the CPU/lax reference of the quantize-fused epilogue —
            # same custom-VJP STE law as the kernel path
            from p2p_tpu.ops.pallas.norm_act import instance_norm_act_quant

            return instance_norm_act_quant(y, quant_scale, act=act,
                                           slope=slope)
        z = mk()(y)
        if residual is not None:
            z = z + residual
        if act == "relu":
            return relu_y(z)
        if act == "leaky":
            return leaky_relu_y(z, slope)
        return z

    return apply_ref


def make_norm(kind: str, *, train: bool = True, axis_name: Optional[str] = None,
              dtype=None):
    """Factory mapping config ``norm`` strings to layer constructors.

    Returned callables construct a fresh module (use inside @nn.compact).
    """
    if kind == "batch":
        return lambda: BatchNorm(
            use_running_average=not train, axis_name=axis_name, dtype=dtype
        )
    if kind == "instance":
        return lambda: InstanceNorm(dtype=dtype)
    if kind == "pallas_instance":
        from p2p_tpu.ops.pallas.instance_norm import PallasInstanceNorm

        return lambda: PallasInstanceNorm(dtype=dtype)
    if kind == "group":
        return lambda: GroupNorm(swish=False)
    if kind == "group_swish":
        return lambda: GroupNorm(swish=True)
    if kind == "none":
        return lambda: (lambda x: x)
    raise ValueError(f"unknown norm kind {kind!r}")


class SPADE(nn.Module):
    """Spatially-adaptive normalisation (Park et al. 2019, section 3):

        SPADE_C(x, m) = BN0(x) * (1 + gamma) + beta
        a     = relu(conv3x3(resize_nearest(m, size(x)), M -> hidden))
        gamma = conv3x3(a, hidden -> C),  beta = conv3x3(a, hidden -> C)

    ``BN0`` is batch normalisation with no affine: the moments come from
    :class:`BatchNorm`'s own path (one pass, float32 accumulation, global
    under a ``data`` mesh, running statistics in eval), so ``gamma`` and
    ``beta`` are full ``[N,H,W,C]`` tensors where every other norm here
    has a per-channel pair or none. The three convolutions carry a bias,
    pad with zeros and run through ``ops/conv.ConvLayer`` like every
    other convolution (the form is ``_routed_conv``'s choice). ``m`` is
    the conditioning map at the generator's full extent or at ``x``'s own:
    nearest resize by a whole factor is a strided slice.

    The whole site runs under ``jax.named_scope("spade")`` with
    ``shared_conv``, ``gamma_beta`` and ``modulate`` inside it."""

    hidden: int = 128
    train: bool = True
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, m):
        from p2p_tpu.ops.activations import relu_y
        from p2p_tpu.ops.conv import ConvLayer

        if m.shape[1] % x.shape[1] or m.shape[2] % x.shape[2]:
            raise ValueError(f"SPADE: the map {m.shape} is no whole "
                             f"multiple of the activation {x.shape}")
        fh, fw = m.shape[1] // x.shape[1], m.shape[2] // x.shape[2]
        conv = lambda features, name: ConvLayer(  # noqa: E731
            features, kernel_size=3, pad_mode="zero", dtype=self.dtype,
            name=name)
        with jax.named_scope("spade"):
            with jax.named_scope("shared_conv"):
                a = relu_y(conv(self.hidden, "shared")(m[:, ::fh, ::fw]))
            with jax.named_scope("gamma_beta"):
                gamma = conv(x.shape[-1], "gamma")(a)
                beta = conv(x.shape[-1], "beta")(a)
            with jax.named_scope("modulate"):
                xn = BatchNorm(use_running_average=not self.train,
                               dtype=self.dtype, affine=False,
                               name="norm")(x)
                one = jnp.ones((), gamma.dtype)
                return xn * (one + gamma) + beta
