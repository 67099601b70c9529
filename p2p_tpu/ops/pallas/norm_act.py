"""Fused InstanceNorm + activation (+ residual add) Pallas TPU kernels.

The round-4/5 profiles put the remaining HD-generator headroom in the
reflect-pad copies and the InstanceNorm stat/normalize passes plus the
elementwise chains that follow them: XLA fuses the norm's second pass with
the activation *sometimes*, but the residual add in the resblock tail pins
a separate full-size read-modify-write, and the activation after the affine
is a third pass whenever the norm output has two consumers. This kernel
family extends ``instance_norm_kernel.py``'s two-pass structure with the
whole post-conv epilogue folded into the normalize pass:

    y = act( (x - mu) * rsqrt(var + eps) * gamma + beta  [+ residual] )

so the conv output is read exactly twice (stats, normalize) and written
once, with the activation and the residual add riding the normalize pass's
VMEM-resident block — the conv's entire epilogue in one streaming pass.

``act`` is one of ``"none" | "relu" | "leaky"`` (LeakyReLU slope for the
discriminator chains). The residual is added BEFORE the activation —
matching both resblock tails in the zoo: the classic ResnetBlock
(``x + norm(conv)``, act="none") and ExpandNetwork's ResidualBlock
(``relu(norm(conv) + x)``).

Backward follows the repo's output-mask idiom (ops/activations.py): relu
and positive-slope leaky-relu preserve sign, so the activation mask comes
from the OUTPUT and no pre-activation tensor is kept. The rest is the
standard instance-norm VJP in XLA (small reductions, fused), exactly like
the act-free kernel. With ``axis_name`` set the stat tiles psum across a
spatial shard_map axis — same contract as ``instance_norm_fused_sharded``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from p2p_tpu.ops.pallas.instance_norm_kernel import _pick_h_block, _stats_local

ACTS = ("none", "relu", "leaky")


def _norm_act_kernel(x_ref, mean_ref, rstd_ref, scale_ref, bias_ref, y_ref,
                     *, act: str, slope: float):
    x = x_ref[...].astype(jnp.float32)
    y = (x - mean_ref[...]) * rstd_ref[...]
    y = y * scale_ref[...] + bias_ref[...]
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "leaky":
        y = jnp.where(y >= 0.0, y, slope * y)
    y_ref[...] = y.astype(y_ref.dtype)


def _norm_act_res_kernel(x_ref, res_ref, mean_ref, rstd_ref, scale_ref,
                         bias_ref, y_ref, *, act: str, slope: float):
    x = x_ref[...].astype(jnp.float32)
    y = (x - mean_ref[...]) * rstd_ref[...]
    y = y * scale_ref[...] + bias_ref[...] + res_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "leaky":
        y = jnp.where(y >= 0.0, y, slope * y)
    y_ref[...] = y.astype(y_ref.dtype)


def _norm_act_local(x, residual, mean, rstd, scale, bias, act, slope,
                    interpret):
    """Pass 2 with the fused epilogue on the (possibly local-shard) array."""
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
    if residual is None:
        kern = functools.partial(_norm_act_kernel, act=act, slope=slope)
        in_specs = [x_spec, cvec_spec, cvec_spec, bcast_spec, bcast_spec]
        args = (x, mean, rstd, scale_t, bias_t)
    else:
        kern = functools.partial(_norm_act_res_kernel, act=act, slope=slope)
        in_specs = [x_spec, x_spec, cvec_spec, cvec_spec, bcast_spec,
                    bcast_spec]
        args = (x, residual, mean, rstd, scale_t, bias_t)
    return pl.pallas_call(
        kern,
        grid=(n, h // hb),
        in_specs=in_specs,
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(*args)


def _fwd_impl(x, scale, bias, residual, act, slope, eps, interpret,
              axis_name):
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
    y = _norm_act_local(x, residual, mean, rstd, scale, bias, act, slope,
                        interpret)
    return y, mean, rstd, count


# pallas_call has no reverse-mode rule — explicit VJP, like the act-free
# kernel. The activation mask comes from the saved OUTPUT (sign-preserving
# acts only — module docstring); the residual's cotangent is the masked
# upstream cotangent, free of the norm chain.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _in_act_fused(x, scale, bias, residual, act, slope, eps, interpret,
                  axis_name):
    y, _, _, _ = _fwd_impl(x, scale, bias, residual, act, slope, eps,
                           interpret, axis_name)
    return y


def _in_act_fused_fwd(x, scale, bias, residual, act, slope, eps, interpret,
                      axis_name):
    y, mean, rstd, count = _fwd_impl(x, scale, bias, residual, act, slope,
                                     eps, interpret, axis_name)
    # zero-sized dtype carrier (ops/int8.py idiom): the backward needs the
    # residual's presence + dtype, never its values
    res_tok = None if residual is None else jnp.zeros((0,), residual.dtype)
    return y, (x, scale, bias, res_tok, y, mean, rstd, count)


def _in_act_fused_bwd(act, slope, eps, interpret, axis_name, res, g):
    x, scale, bias, res_tok, y, mean, rstd, count = res
    g32 = g.astype(jnp.float32)
    if act == "relu":
        # grad 0 at y==0 — matches ops/activations.relu_y
        g32 = jnp.where(y > 0, g32, 0.0)
    elif act == "leaky":
        g32 = jnp.where(y >= 0, g32, slope * g32)
    x32 = x.astype(jnp.float32)
    xhat = (x32 - mean) * rstd
    gamma = (
        jnp.float32(1.0) if scale is None
        else scale.reshape(1, 1, 1, -1).astype(jnp.float32)
    )
    dxhat = g32 * gamma
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
        dscale = jnp.sum(g32 * xhat, axis=(0, 1, 2)).astype(scale.dtype)
        dbias = jnp.sum(g32, axis=(0, 1, 2)).astype(bias.dtype)
    # the residual bypasses the norm entirely: its cotangent is the
    # act-masked upstream cotangent
    dres = None if res_tok is None else g32.astype(res_tok.dtype)
    return dx, dscale, dbias, dres


_in_act_fused.defvjp(_in_act_fused_fwd, _in_act_fused_bwd)


def _check_act(act: str, slope: float) -> None:
    if act not in ACTS:
        raise ValueError(f"act must be one of {ACTS}, got {act!r}")
    if act == "leaky" and slope <= 0:
        raise ValueError(
            f"leaky needs slope > 0 (got {slope}); the output-based "
            "gradient mask is only valid for sign-preserving activations")


@functools.partial(jax.jit,
                   static_argnames=("act", "slope", "eps", "interpret"))
def instance_norm_act_fused(x, scale=None, bias=None, residual=None,
                            act: str = "none", slope: float = 0.2,
                            eps: float = 1e-5, interpret: bool = False):
    """Fused ``act(instance_norm(x)·γ+β [+ residual])`` on NHWC (TPU)."""
    _check_act(act, slope)
    return _in_act_fused(x, scale, bias, residual, act, slope, eps,
                         interpret, None)


def instance_norm_act_fused_sharded(x, scale=None, bias=None, residual=None,
                                    act: str = "none", slope: float = 0.2,
                                    eps: float = 1e-5,
                                    axis_name: str = "spatial",
                                    interpret: bool = False):
    """The fused epilogue over an H-sharded NHWC shard (inside shard_map);
    the residual must be sharded like ``x``."""
    _check_act(act, slope)
    return _in_act_fused(x, scale, bias, residual, act, slope, eps,
                         interpret, axis_name)


# ----------------------------------------------------- quantize-fused
# ISSUE 14, the bandwidth half: when the conv that CONSUMES a norm+act
# epilogue runs on the delayed-int8 path, the activation's clip/round
# quantize is one more elementwise pass XLA cannot fuse into the
# pallas_call producer — a full-size read+write the newly quantized
# layer would pay on top of the epilogue. This variant folds [normalize
# · affine · activation · clip/round quantize · amax measurement] into
# the SAME two-pass streaming kernel: the conv output is still read
# exactly twice (stats, normalize) and written once — but what is
# written is the activation already on the int8 grid, plus per-(n, c)
# amax partials (the delayed-scale update proposal) reduced outside on
# the tiny tile tensor.
#
# The quantized activation is carried in the COMPUTE dtype (bf16/f32)
# holding exact integer values in [-127, 127]: an int8-dtype output
# would surface float0 tangents at the op boundary and sever autodiff —
# the consumer (ops/int8.py ``int8_conv_pq``) converts to int8 in its
# operand read, a pure elementwise cast. The activation value is rounded
# THROUGH the compute dtype before the quantize (y.astype(x.dtype)) so
# the fused path is bitwise-equal to [unfused epilogue → int8_conv_ds].
#
# Backward mirrors the existing delayed-int8 STE law (ops/int8.py): the
# incoming cotangent is w.r.t. the dequantized surrogate sx·q and passes
# straight through clip/round; the activation mask is recomputed from
# the pre-activation (x, mean, rstd and the affine are residuals — the
# quantized output cannot mask: round() kills the sign information near
# zero), then the standard instance-norm VJP. ``sx`` is state (a stored
# amax), so its cotangent is zero, exactly like ``int8_conv_ds``.


def _norm_act_quant_kernel(x_ref, mean_ref, rstd_ref, scale_ref, bias_ref,
                           sx_ref, y_ref, am_ref, *, act: str, slope: float):
    x = x_ref[...].astype(jnp.float32)
    y = (x - mean_ref[...]) * rstd_ref[...]
    y = y * scale_ref[...] + bias_ref[...]
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "leaky":
        y = jnp.where(y >= 0.0, y, slope * y)
    # round through the activation dtype FIRST — bitwise what the
    # unfused [epilogue module → int8_conv_ds] chain quantizes
    yc = y.astype(y_ref.dtype).astype(jnp.float32)
    q = jnp.clip(jnp.round(yc / sx_ref[...]), -127.0, 127.0)
    y_ref[...] = q.astype(y_ref.dtype)
    # per-(n, c) running max over the h-block axis, the stats kernel's
    # accumulator pattern: Mosaic stores no scalar to VMEM and takes no
    # (1, 1) block of an (n, blocks) array (both refused by the v5e
    # compiler on bring-up, PR 21) — a (1,1,1,c) lane tile is legal
    am = jnp.max(jnp.abs(yc), axis=(0, 1, 2))[None, None, None, :]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        am_ref[...] = am

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        am_ref[...] = jnp.maximum(am_ref[...], am)


def _norm_act_quant_local(x, mean, rstd, scale, bias, sx, act, slope,
                          interpret):
    """Pass 2 with the quantize-fused epilogue: emits the on-grid
    activation (compute dtype) AND per-(n, c) amax partials."""
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
    sx_t = jnp.asarray(sx, jnp.float32).reshape(1, 1, 1, 1)
    kern = functools.partial(_norm_act_quant_kernel, act=act, slope=slope)
    yq, am = pl.pallas_call(
        kern,
        grid=(n, h // hb),
        in_specs=[x_spec, cvec_spec, cvec_spec, bcast_spec, bcast_spec,
                  pl.BlockSpec((1, 1, 1, 1), lambda i, j: (0, 0, 0, 0))],
        out_specs=[x_spec, cvec_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, 1, 1, c), jnp.float32)],
        interpret=interpret,
    )(x, mean, rstd, scale_t, bias_t, sx_t)
    return yq, jnp.max(am)


def _quant_fwd_impl(x, scale, bias, sx, act, slope, eps, use_kernel,
                    interpret):
    sx = jnp.maximum(jnp.asarray(sx, jnp.float32), 1e-12)
    if use_kernel:
        n, h, w, c = x.shape
        s1, s2 = _stats_local(x, interpret)
        count = jnp.float32(h * w)
        mean = s1 / count
        var = jnp.maximum(s2 / count - mean * mean, 0.0)
        rstd = jax.lax.rsqrt(var + eps)
        yq, amax = _norm_act_quant_local(x, mean, rstd, scale, bias, sx,
                                         act, slope, interpret)
        return yq, amax, mean, rstd, count
    # the lax reference — same op order as the unfused CPU chain
    # (instance_norm._xla_instance_norm_act → quantize): jnp moments,
    # normalize, affine, activation, cast to the activation dtype, THEN
    # clip/round — bitwise what [make_norm_act → int8_conv_ds] computes
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(1, 2), keepdims=True)
    var = jnp.var(x32, axis=(1, 2), keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x32 - mean) * rstd
    if scale is not None:
        y = y * scale.reshape(1, 1, 1, -1) + bias.reshape(1, 1, 1, -1)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "leaky":
        y = jnp.where(y >= 0.0, y, slope * y)
    yc = y.astype(x.dtype).astype(jnp.float32)
    yq = jnp.clip(jnp.round(yc / sx), -127.0, 127.0).astype(x.dtype)
    amax = jnp.max(jnp.abs(yc))
    count = jnp.float32(x.shape[1] * x.shape[2])
    return yq, amax, mean, rstd, count


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _in_act_quant(x, scale, bias, sx, act, slope, eps, use_kernel,
                  interpret):
    yq, amax, _, _, _ = _quant_fwd_impl(x, scale, bias, sx, act, slope,
                                        eps, use_kernel, interpret)
    return yq, amax


def _in_act_quant_fwd(x, scale, bias, sx, act, slope, eps, use_kernel,
                      interpret):
    yq, amax, mean, rstd, count = _quant_fwd_impl(
        x, scale, bias, sx, act, slope, eps, use_kernel, interpret)
    return (yq, amax), (x, scale, bias, mean, rstd, count)


def _in_act_quant_bwd(act, slope, eps, use_kernel, interpret, res, ct):
    g, _ = ct  # the amax output feeds a state update, never a loss
    x, scale, bias, mean, rstd, count = res
    # STE through clip/round: the incoming cotangent is w.r.t. the
    # dequantized surrogate sx·q ≈ y and passes through unchanged — the
    # composition with int8_conv_pq's surrogate-cotangent convention IS
    # the unfused int8_conv_ds VJP law.
    g32 = g.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    xhat = (x32 - mean) * rstd
    gamma = (
        jnp.float32(1.0) if scale is None
        else scale.reshape(1, 1, 1, -1).astype(jnp.float32)
    )
    beta = (
        jnp.float32(0.0) if bias is None
        else bias.reshape(1, 1, 1, -1).astype(jnp.float32)
    )
    # activation mask from the recomputed PRE-activation (the saved
    # output is quantized — round() erases the sign near zero); for the
    # sign-preserving acts this is the same mask the output-based law
    # (ops/activations.py) computes: y > 0 ⇔ h > 0, y ≥ 0 ⇔ h ≥ 0
    h = xhat * gamma + beta
    if act == "relu":
        g32 = jnp.where(h > 0, g32, 0.0)
    elif act == "leaky":
        g32 = jnp.where(h >= 0, g32, slope * g32)
    dxhat = g32 * gamma
    m1 = jnp.sum(dxhat, axis=(1, 2), keepdims=True) / count
    m2 = jnp.sum(dxhat * xhat, axis=(1, 2), keepdims=True) / count
    dx = (rstd * (dxhat - m1 - xhat * m2)).astype(x.dtype)
    if scale is None:
        dscale = dbias = None
    else:
        dscale = jnp.sum(g32 * xhat, axis=(0, 1, 2)).astype(scale.dtype)
        dbias = jnp.sum(g32, axis=(0, 1, 2)).astype(bias.dtype)
    # sx is state (a stored amax), not a trained parameter
    return dx, dscale, dbias, jnp.zeros((), jnp.float32)


_in_act_quant.defvjp(_in_act_quant_fwd, _in_act_quant_bwd)


def instance_norm_act_quant(x, sx, scale=None, bias=None,
                            act: str = "none", slope: float = 0.2,
                            eps: float = 1e-5, use_kernel: bool = False,
                            interpret: bool = False):
    """Quantize-fused ``act(instance_norm(x)·γ+β)`` → ``(q, amax)``:
    the activation clipped/rounded onto the int8 grid with stored scale
    ``sx`` (values in [-127,127], carried in ``x.dtype``) plus the max
    |activation| measured in the same pass. ``use_kernel`` selects the
    Pallas two-pass kernel (``interpret=True`` off-TPU); otherwise the
    lax reference with the SAME custom-VJP STE law. Feed ``q`` to
    ``ops.int8.int8_conv_pq`` with the same ``sx``."""
    _check_act(act, slope)
    return _in_act_quant(x, scale, bias, sx, act, slope, eps, use_kernel,
                         interpret)
