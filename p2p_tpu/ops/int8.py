"""int8 quantization-aware convolutions on the TPU MXU.

The v5e MXU executes s8×s8→s32 at 2× its bf16 rate (394 vs 197 peak
TOP/s; measured 229 TOP/s vs 139 TF/s on this repo's dominant
discriminator conv shape — 1.65× in practice). The reference trains
fp32 cuDNN convolutions (/root/reference/train.py:164
``cudnn.benchmark``); this module is the TPU-native opt-in
acceleration the hardware invites: symmetric dynamic quantization with
**int8 convs in the forward AND both backward contractions** (dgrad +
wgrad), so the MXU-bound ~80% of the step runs at the doubled rate.

Scheme (per conv, no state to thread):
- activations: per-tensor scale ``s_x = max|x| / 127``;
- weights: per-output-channel scale ``s_w[o] = max|w[..,o]| / 127``;
- forward: ``y = (Q(x) ⊛ Q(w))_int32 · s_x · s_w``;
- backward is the exact gradient of the dequantized surrogate
  (straight-through through both quantizers):
  - dgrad: the per-channel ``s_w`` is *folded into the cotangent*
    before its own quantization (``g̃ = g · s_w``), which turns the
    per-channel factor inside the contraction into a per-tensor one:
    ``dx = s_g̃ · (Q(g̃) ⊛ᵀ Q(w))``; the ``s_x`` factors cancel.
  - wgrad: ``dw = s_x · s_g · (Q(x) ⊛ Q(g))`` — per-tensor scales
    only; the ``s_w`` factors cancel.
- the int8 transpose convolutions replicate XLA's own conv-VJP
  padding/dilation algebra (jax._src.lax.convolution
  ``_conv_general_dilated_transpose_{lhs,rhs}``), with the dimension
  permutations done as explicit array transposes; exactness is pinned
  by tests that compare against ``jax.vjp`` of the float conv on
  integer-valued tensors (where quantization is lossless).

What stays bf16: quality- and bandwidth-critical layers — the 3/6-ch
stem convs and the image-producing head (they are HBM-bound, the MXU
gains nothing) — plus biases, norms, losses, and the optimizer. The
models opt in per-layer via ``QuantConv`` / ``QuantConvTranspose``,
which are parameter-compatible with ``nn.Conv`` / ``nn.ConvTranspose``
(same param names/shapes → checkpoints interchange with the bf16
path).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from p2p_tpu.ops.conv import normal_init, save_conv_out, subpixel_interleave

Pads = Tuple[Tuple[int, int], Tuple[int, int]]

_DN = ("NHWC", "HWIO", "NHWC")

# Dispatch bound for the unrolled int8 wgrad (see _int8_bwd_core): output
# spatial sizes up to MAX use the k²-unrolled int8 dot_general form; the
# rest fall back to the bf16 CHWN conv.
# - no lower bound: an early runtime kernel-faulted the int8 strided
#   slices below ~16² output positions and a guard routed those to bf16.
#   The unguarded path was run on the attached v5e (libtpu 0.0.34) at 2×2
#   and 1×1 outputs — the U-Net bottom of facades_int8_full — and passed
#   (chip run, PR 21); the guard and its env knob are gone.
#   tests/test_int8.py::test_tiny_spatial_wgrad_on_tpu is the standing
#   probe.
# - MAX = 4096 (64²): above it the k² slices of the padded input
#   materialize more HBM traffic than the int8 MXU rate buys back (the
#   "decoder int8 loses" finding).
_INT8_WGRAD_SLICE_MAX = int(
    os.environ.get("P2P_INT8_WGRAD_SLICE_MAX", "4096"))


def absmax_scale(x: jax.Array, axis=None) -> jax.Array:
    """Symmetric scale max|x|/127 in f32; keepdims when axis given."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis,
                keepdims=axis is not None)
    return jnp.maximum(m, 1e-12) / 127.0


def quantize_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    return jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)


def _conv_i32(lhs8, rhs8, strides, padding, lhs_dil=(1, 1), rhs_dil=(1, 1)):
    dn = jax.lax.conv_dimension_numbers(lhs8.shape, rhs8.shape, _DN)
    return jax.lax.conv_general_dilated(
        lhs8, rhs8, window_strides=strides, padding=padding,
        lhs_dilation=lhs_dil, rhs_dilation=rhs_dil, dimension_numbers=dn,
        preferred_element_type=jnp.int32,
    )


def _dilate(shape, dil):
    return tuple(0 if d == 0 else (d - 1) * r + 1 for d, r in zip(shape, dil))


def _vjp_lhs_padding(in_hw, k_hw, strides, out_hw, padding, lhs_dil, rhs_dil):
    """XLA's dgrad padding (jax._src.lax.convolution
    _conv_general_vjp_lhs_padding), inlined for the 2-spatial-dim case."""
    lhs_d = _dilate(in_hw, lhs_dil)
    rhs_d = _dilate(k_hw, rhs_dil)
    out_d = _dilate(out_hw, strides)
    lo = tuple(r - p[0] - 1 for r, p in zip(rhs_d, padding))
    hi = tuple(l + r - 1 - o - b
               for l, r, o, b in zip(lhs_d, rhs_d, out_d, lo))
    return tuple(zip(lo, hi))


def _vjp_rhs_padding(in_hw, k_hw, strides, out_hw, padding, lhs_dil, rhs_dil):
    """XLA's wgrad padding (_conv_general_vjp_rhs_padding), inlined."""
    lhs_d = _dilate(in_hw, lhs_dil)
    rhs_d = _dilate(k_hw, rhs_dil)
    out_d = _dilate(out_hw, strides)
    lo = tuple(p[0] for p in padding)
    hi = tuple((o - l) + (r - p - 1)
               for o, l, r, p in zip(out_d, lhs_d, rhs_d, lo))
    return tuple(zip(lo, hi))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def int8_conv(x: jax.Array, w: jax.Array, strides: Tuple[int, int],
              padding: Pads, lhs_dilation: Tuple[int, int] = (1, 1)):
    """NHWC ⊛ HWIO conv computed on the int8 MXU path.

    ``lhs_dilation`` ≠ 1 expresses transposed convolution (the flax
    ``ConvTranspose`` lowering: strides=(1,1), lhs_dilation=s).
    """
    y, _ = _int8_conv_fwd(x, w, strides, padding, lhs_dilation)
    return y


def _int8_conv_fwd(x, w, strides, padding, lhs_dilation):
    sx = absmax_scale(x)                          # scalar
    sw = absmax_scale(w, axis=(0, 1, 2))          # (1,1,1,O)
    xq = quantize_int8(x, sx)
    wq = quantize_int8(w, sw)
    y32 = _conv_i32(xq, wq, strides, padding, lhs_dil=lhs_dilation)
    y = (y32.astype(jnp.float32) * (sx * sw.reshape(1, 1, 1, -1)))
    # zero-sized dtype carriers: residuals must be JAX types
    x_tok = jnp.zeros((0,), x.dtype)
    w_tok = jnp.zeros((0,), w.dtype)
    return y.astype(x.dtype), (xq, sx, wq, sw, x_tok, w_tok)


def _int8_bwd_core(strides, padding, lhs_dilation, res, g):
    """Mixed-form backward. Each contraction runs in whichever of int8 /
    bf16 measured faster on v5e for its structural form (chained
    microbenchmarks, see module docstring table):

    - dgrad is ``conv(g, rev(w)ᵀ, window_strides=lhs_dil, lhs_dil=strides)``
      — a *plain* conv when the forward had ``strides == 1`` (s1 conv) or
      when the forward was a transposed conv (then window_strides=2):
      int8 wins (2×/1.5×). When the forward had stride 2 the dgrad is
      lhs-dilated, where int8 measured SLOWER than bf16 → bf16 on the
      dequantized surrogate ŵ (keeps the exact-surrogate-VJP semantics).
    - wgrad as a conv puts the batch dim on channels (CHWN/IHWO), a
      layout whose int8 lowering is catastrophic (~5 T/s) and whose bf16
      lowering reaches only ~103 TF/s; an unrolled k² sum of strided-
      slice ``dot_general``s in int8 reaches ~157 TF/s → int8 dot_general
      for plain convs, bf16 conv for transposed (dilated-x) ones.
    """
    xq, sx, wq, sw, x_tok, w_tok = res
    x_dt, w_dt = x_tok.dtype, w_tok.dtype
    k_hw = wq.shape[:2]
    in_hw = xq.shape[1:3]
    out_hw = g.shape[1:3]
    gf = g.astype(jnp.float32)
    plain = lhs_dilation == (1, 1)

    # ---- dgrad --------------------------------------------------------
    pad_lhs = _vjp_lhs_padding(in_hw, k_hw, strides, out_hw, padding,
                               lhs_dilation, (1, 1))
    if strides == (1, 1):
        # plain (or transposed-fwd) dgrad → int8. Per-channel s_w folds
        # into the cotangent before quantization (module docstring).
        gt = gf * sw.reshape(1, 1, 1, -1)
        sgt = absmax_scale(gt)
        gtq = quantize_int8(gt, sgt)
        wq_r = wq[::-1, ::-1]
        dn = jax.lax.conv_dimension_numbers(
            gtq.shape, wq_r.shape, ("NHWC", "HWOI", "NHWC"))
        dx32 = jax.lax.conv_general_dilated(
            gtq, wq_r, window_strides=lhs_dilation, padding=pad_lhs,
            lhs_dilation=strides, dimension_numbers=dn,
            preferred_element_type=jnp.int32,
        )
        dx = (dx32.astype(jnp.float32) * sgt).astype(x_dt)
    else:
        # stride-2 dgrad is lhs-dilated → bf16 on the dequantized ŵ
        w_hat = (wq.astype(jnp.float32) * sw).astype(jnp.bfloat16)
        w_r = w_hat[::-1, ::-1]
        dn = jax.lax.conv_dimension_numbers(
            g.shape, w_r.shape, ("NHWC", "HWOI", "NHWC"))
        dx = jax.lax.conv_general_dilated(
            g.astype(jnp.bfloat16), w_r, window_strides=lhs_dilation,
            padding=pad_lhs, lhs_dilation=strides, dimension_numbers=dn,
            preferred_element_type=jnp.float32,
        ).astype(x_dt)

    # ---- wgrad --------------------------------------------------------
    ho, wo = out_hw
    # Static spatial dispatch bound (see _INT8_WGRAD_SLICE_MAX above):
    # above ~64² output positions the k² strided slices of the (already
    # large) padded input materialize more HBM traffic than the int8 MXU
    # rate buys back — those big-spatial wgrads take the bf16 CHWN conv
    # below.
    if plain and ho * wo <= _INT8_WGRAD_SLICE_MAX:
        sg = absmax_scale(gf)
        gq = quantize_int8(gf, sg)
        (plo_h, phi_h), (plo_w, phi_w) = padding
        sh, sw_ = strides
        kh_n, kw_n = k_hw
        n, _, _, cin = xq.shape
        xp = jnp.pad(xq, ((0, 0), (plo_h, phi_h + sh), (plo_w, phi_w + sw_),
                          (0, 0)))
        tiles = []
        for kh in range(kh_n):
            row = []
            for kw in range(kw_n):
                xs = jax.lax.slice(
                    xp, (0, kh, kw, 0),
                    (n, kh + sh * (ho - 1) + 1, kw + sw_ * (wo - 1) + 1, cin),
                    (1, sh, sw_, 1))
                row.append(jax.lax.dot_general(
                    xs, gq, (((0, 1, 2), (0, 1, 2)), ((), ())),
                    preferred_element_type=jnp.int32))
            tiles.append(jnp.stack(row))                   # (kw,I,O)
        dwk = jnp.stack(tiles)                             # (kh,kw,I,O)
        dw = (dwk.astype(jnp.float32) * (sx * sg)).astype(w_dt)
    else:
        # transposed-conv wgrad (dilated x) and tiny-spatial plain
        # wgrads → bf16 conv on the dequantized x̂, CHWN/IHWO layout
        x_hat = (xq.astype(jnp.float32) * sx).astype(jnp.bfloat16)
        pad_rhs = _vjp_rhs_padding(in_hw, k_hw, strides, out_hw, padding,
                                   lhs_dilation, (1, 1))
        dn = jax.lax.conv_dimension_numbers(
            x_hat.shape, g.shape, ("CHWN", "IHWO", "NHWC"))
        dw32 = jax.lax.conv_general_dilated(
            x_hat, g.astype(jnp.bfloat16), window_strides=(1, 1),
            padding=pad_rhs, lhs_dilation=lhs_dilation,
            rhs_dilation=strides, dimension_numbers=dn,
            preferred_element_type=jnp.float32,
        )
        dw = jnp.transpose(dw32, (1, 2, 0, 3)).astype(w_dt)
    return dx, dw


def _int8_conv_bwd(strides, padding, lhs_dilation, res, g):
    return _int8_bwd_core(strides, padding, lhs_dilation, res, g)


int8_conv.defvjp(_int8_conv_fwd, _int8_conv_bwd)


# ---------------------------------------------------------------- delayed
# Delayed (stored-scale) activation quantization — TransformerEngine-style
# amax bookkeeping adapted to convs. The dynamic path above serializes on
# a full absmax reduction over x before the quantize can start (two HBM
# passes over every quantized activation, and a latency chain XLA cannot
# hide). Here the scale comes from the PREVIOUS step (a "quant" flax
# collection threaded through TrainState like batch_stats), so the
# quantize fuses into the producer, and the current amax is measured in
# the SAME pass to update the stored value for the next step. Transient
# under-scaling clips symmetrically at ±127 for one step — the decaying-
# max update (module code) adapts the scale upward immediately after.
# Cotangent (backward) scales stay dynamic: custom_vjp backward passes
# cannot write state, and the cotangent absmax fuses with the g·s_w fold
# anyway.


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def int8_conv_ds(x: jax.Array, w: jax.Array, sx: jax.Array,
                 strides: Tuple[int, int], padding: Pads,
                 lhs_dilation: Tuple[int, int] = (1, 1)):
    """``int8_conv`` with a STORED per-tensor activation scale ``sx``.

    Returns ``(y, amax_x)`` — the conv output and the CURRENT max|x|
    measured in the quantize pass, for the caller's scale update.
    """
    out, _ = _int8_conv_ds_fwd(x, w, sx, strides, padding, lhs_dilation)
    return out


def _int8_conv_ds_fwd(x, w, sx, strides, padding, lhs_dilation):
    sx = jnp.maximum(sx.astype(jnp.float32), 1e-12)
    sw = absmax_scale(w, axis=(0, 1, 2))          # (1,1,1,O) — w is tiny
    xf = x.astype(jnp.float32)
    xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    amax = jnp.max(jnp.abs(xf))                   # fused into the same pass
    wq = quantize_int8(w, sw)
    y32 = _conv_i32(xq, wq, strides, padding, lhs_dil=lhs_dilation)
    y = y32.astype(jnp.float32) * (sx * sw.reshape(1, 1, 1, -1))
    x_tok = jnp.zeros((0,), x.dtype)
    w_tok = jnp.zeros((0,), w.dtype)
    return (y.astype(x.dtype), amax), (xq, sx, wq, sw, x_tok, w_tok)


def _int8_conv_ds_bwd(strides, padding, lhs_dilation, res, ct):
    g, _ = ct  # the amax output feeds a state update, never a loss
    dx, dw = _int8_bwd_core(strides, padding, lhs_dilation, res, g)
    return dx, dw, jnp.zeros((), jnp.float32)


int8_conv_ds.defvjp(_int8_conv_ds_fwd, _int8_conv_ds_bwd)


# ------------------------------------------------------------- kn2row
# int8 form of the kn2row tap decomposition (ops/conv.py
# kn2row_thin_conv) — the thin-output heads (PatchGAN 512→1) where the
# ONLY large-tensor traffic is the 1×1 tap matmul over x. Per-form
# dispatch table (chained v5e microbenchmarks, the ops/int8.py
# convention):
#
#   contraction                form              dtype   why
#   ---------------------------------------------------------------------
#   fwd    z = x @ w_taps      dot over C_in     int8    C_in wide (512),
#                                                        the one full-rate
#                                                        HBM pass over x —
#                                                        2× MXU
#   wgrad  dw = xᵀ · pz        dot over N·H·W    int8    contraction dim is
#                                                        the whole spatial
#                                                        extent; re-reads x
#                                                        (int8 = half the
#                                                        bytes) at 2× MXU
#   dgrad  dx = pz @ ŵᵀ        dot over k²·O     bf16    contraction dim is
#                                                        k²·O (= 16 for the
#                                                        k4→1 head) — far
#                                                        below one MXU tile;
#                                                        the s8 rate is
#                                                        unrealizable, bf16
#                                                        on the dequantized
#                                                        surrogate keeps the
#                                                        exact-VJP law
#
# The backward is the hand-derived patches-of-dz form
# (pz = im2col(pad(dz, k−1)) holds every shifted dz view both cotangents
# need), for the zero-padded stride-1 case:
# pz spans the PADDED input coordinates, dx crops the ring, dw reads the
# int8-padded xq (zero padding is exact in int8).


def _kn2row_i32(xq, wq, pad):
    """Quantized tap decomposition: int32 tap matmul + int32 shift-adds.
    xq (N,H,W,C) int8, wq (k,k,C,O) int8 → (N,H+2p−k+1,W+2p−k+1,O) int32.
    The k² partial sums accumulate in int32 — rounding once at the dequant
    exactly like the s32 conv accumulator it replaces."""
    kh, kw, c, o = wq.shape
    n, h, w, _ = xq.shape
    ho, wo = h + 2 * pad - kh + 1, w + 2 * pad - kw + 1
    wt = wq.reshape(kh * kw, c, o).transpose(1, 0, 2).reshape(
        c, kh * kw * o)
    z32 = jax.lax.dot_general(
        xq, wt, (((3,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).reshape(n, h, w, kh * kw, o)
    z32 = jnp.pad(z32, ((0, 0), (pad, pad), (pad, pad), (0, 0), (0, 0)))
    y32 = jnp.zeros((n, ho, wo, o), jnp.int32)
    for t in range(kh * kw):
        dh, dw = divmod(t, kw)
        y32 = y32 + jax.lax.dynamic_slice(
            z32, (0, dh, dw, t, 0), (n, ho, wo, 1, o)
        ).reshape(n, ho, wo, o)
    return y32


def _kn2row_fwd_core(x, w, sx, pad, amax_from_x):
    """Shared forward of the dynamic/delayed int8 kn2row pair. Returns
    ``((y, amax), residuals)``; ``amax_from_x`` measures max|x| in the
    same pass (the delayed-scale update proposal)."""
    sx = jnp.maximum(jnp.asarray(sx, jnp.float32), 1e-12)
    sw = absmax_scale(w, axis=(0, 1, 2))          # (1,1,1,O)
    xf = x.astype(jnp.float32)
    xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    amax = jnp.max(jnp.abs(xf)) if amax_from_x else jnp.zeros((), jnp.float32)
    wq = quantize_int8(w, sw)
    y32 = _kn2row_i32(xq, wq, pad)
    y = y32.astype(jnp.float32) * (sx * sw.reshape(1, 1, 1, -1))
    x_tok = jnp.zeros((0,), x.dtype)
    w_tok = jnp.zeros((0,), w.dtype)
    return (y.astype(x.dtype), amax), (xq, sx, wq, sw, x_tok, w_tok)


def _int8_kn2row_bwd_core(pad, res, g):
    """Patches-of-dz backward with the per-form dispatch above."""
    xq, sx, wq, sw, x_tok, w_tok = res
    from p2p_tpu.ops.conv import im2col_patches

    k = wq.shape[0]
    o = wq.shape[-1]
    cin = wq.shape[2]
    n, h, w_, _ = xq.shape
    gf = g.astype(jnp.float32)
    # pz[q, (kh',kw',o)] = dz[q − (k−1) + (kh',kw')] over PADDED x coords
    dzp = jnp.pad(gf, ((0, 0), (k - 1, k - 1), (k - 1, k - 1), (0, 0)))
    pz = im2col_patches(dzp.astype(jnp.bfloat16), k)   # (N,H+2p,W+2p,k²·O)
    # ---- dgrad (bf16 — tiny k²·O contraction, dispatch table above) ----
    w_hat = (wq.astype(jnp.float32) * sw).astype(jnp.bfloat16)
    wd = jnp.flip(w_hat, (0, 1)).transpose(0, 1, 3, 2).reshape(
        k * k * o, cin)
    # bf16 by the dispatch table above — the coverage waiver lives at the
    # custom-VJP CALL SITES (jax attributes backward eqns there), e.g.
    # ops/conv.py KN2RowConv
    dxp = jax.lax.dot_general(
        pz, wd, (((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dx = jax.lax.slice(
        dxp, (0, pad, pad, 0), (n, pad + h, pad + w_, cin)
    ).astype(x_tok.dtype)
    # ---- wgrad (int8 — the big N·H·W contraction re-reading x) --------
    xpq = jnp.pad(xq, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    spz = absmax_scale(pz)
    pzq = quantize_int8(pz, spz)
    dwm32 = jax.lax.dot_general(
        xpq, pzq, (((0, 1, 2), (0, 1, 2)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                          # (C, k²·O) in (kh',kw',o)
    dwm = dwm32.astype(jnp.float32) * (sx * spz)
    dw = jnp.flip(dwm.reshape(cin, k, k, o), (1, 2)).transpose(1, 2, 0, 3)
    return dx, dw.astype(w_tok.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def int8_kn2row_conv(x: jax.Array, w: jax.Array, pad: int):
    """Stride-1 thin-output conv on the int8 kn2row path (dynamic
    per-tensor activation scale). NHWC ⊛ HWIO, zero padding both sides."""
    (y, _), _ = _kn2row_fwd_core(x, w, absmax_scale(x), pad, False)
    return y


def _int8_kn2row_fwd(x, w, pad):
    (y, _), res = _kn2row_fwd_core(x, w, absmax_scale(x), pad, False)
    return y, res


def _int8_kn2row_bwd(pad, res, g):
    return _int8_kn2row_bwd_core(pad, res, g)


int8_kn2row_conv.defvjp(_int8_kn2row_fwd, _int8_kn2row_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def int8_kn2row_conv_ds(x: jax.Array, w: jax.Array, sx: jax.Array,
                        pad: int):
    """``int8_kn2row_conv`` with a STORED activation scale — returns
    ``(y, amax_x)`` like :func:`int8_conv_ds` (same delayed-scale
    contract; the cotangent-side scales stay dynamic)."""
    out, _ = _kn2row_fwd_core(x, w, sx, pad, True)
    return out


def _int8_kn2row_ds_fwd(x, w, sx, pad):
    return _kn2row_fwd_core(x, w, sx, pad, True)


def _int8_kn2row_ds_bwd(pad, res, ct):
    g, _ = ct  # the amax output feeds a state update, never a loss
    dx, dw = _int8_kn2row_bwd_core(pad, res, g)
    return dx, dw, jnp.zeros((), jnp.float32)


int8_kn2row_conv_ds.defvjp(_int8_kn2row_ds_fwd, _int8_kn2row_ds_bwd)


# ----------------------------------------------------- prequantized in
# The consumer half of the quantize-fused epilogue
# (ops/pallas/norm_act.py norm_act_quant): the producer kernel already
# clipped/rounded the activation onto the int8 grid (values in
# [-127,127], carried in the compute dtype so autodiff stays legal — an
# int8-dtype output would surface float0 tangents and sever the chain),
# so the conv's input quantize degenerates to a pure convert that fuses
# into the conv's operand read. The returned input cotangent is w.r.t.
# the DEQUANTIZED surrogate sx·q — the epilogue's straight-through
# backward consumes it as d/dy directly, which composes to exactly the
# unfused ``int8_conv_ds`` VJP law.


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def int8_conv_pq(xi: jax.Array, w: jax.Array, sx: jax.Array,
                 strides: Tuple[int, int], padding: Pads,
                 lhs_dilation: Tuple[int, int] = (1, 1)):
    """``int8_conv_ds`` whose activation arrives ALREADY on the int8 grid
    (integer values in [-127,127] in a float container, scale ``sx``)."""
    y, _ = _int8_conv_pq_fwd(xi, w, sx, strides, padding, lhs_dilation)
    return y


def _int8_conv_pq_fwd(xi, w, sx, strides, padding, lhs_dilation):
    sx = jnp.maximum(jnp.asarray(sx, jnp.float32), 1e-12)
    sw = absmax_scale(w, axis=(0, 1, 2))
    xq = xi.astype(jnp.int8)        # pure convert: values already on-grid
    wq = quantize_int8(w, sw)
    y32 = _conv_i32(xq, wq, strides, padding, lhs_dil=lhs_dilation)
    y = y32.astype(jnp.float32) * (sx * sw.reshape(1, 1, 1, -1))
    x_tok = jnp.zeros((0,), xi.dtype)
    w_tok = jnp.zeros((0,), w.dtype)
    return y.astype(xi.dtype), (xq, sx, wq, sw, x_tok, w_tok)


def _int8_conv_pq_bwd(strides, padding, lhs_dilation, res, g):
    dx, dw = _int8_bwd_core(strides, padding, lhs_dilation, res, g)
    return dx, dw, jnp.zeros((), jnp.float32)


int8_conv_pq.defvjp(_int8_conv_pq_fwd, _int8_conv_pq_bwd)


# Decaying-max amax update: responds upward immediately (next step uses
# the larger measured amax), decays 5%/step when activations shrink so a
# one-off spike doesn't pin the scale forever.
AMAX_DECAY = 0.95


def reshard_amax(amax: jax.Array, old_width: int,
                 new_width: int) -> jax.Array:
    """Closed-form amax resharding law for a TP-width change under
    delayed-int8 state (the elastic ``tp_amax_recalibrate`` migration,
    p2p_tpu.resilience.reshape).

    amax is a MAX statistic, so the law needs no data pass:

    - a **per-tensor** amax (scalar, or any leaf without a leading
      ``old_width`` shard axis — the repo's ``amax_x`` scalars, whose
      ``jnp.max`` is a GLOBAL reduction under GSPMD) is shard-width
      invariant: every shard of the activation quantizes with the same
      global scale — identity;
    - a **per-shard** amax (leading ``[old_width]`` axis) remaps so each
      new shard takes the max over the old shards overlapping its channel
      range: on WIDEN (more, smaller shards) each old shard broadcasts to
      its children (the containing shard's amax is a safe, exact-or-upper
      bound for every sub-range); on NARROW (fewer, bigger shards) each
      new shard maxes over the old shards it absorbs (exact: max of
      maxes). Widen-then-narrow round-trips bitwise
      (``max(a, a) == a`` — pinned in tests/test_int8.py).

    Widths must divide (the mesh resolve already enforces power-of-two
    style factorings); anything else raises with the two widths named.
    """
    amax = jnp.asarray(amax)
    old_width, new_width = int(old_width), int(new_width)
    if old_width == new_width:
        return amax
    if amax.ndim == 0 or amax.shape[0] != old_width:
        return amax  # per-tensor scale: shard-width invariant
    if new_width > old_width:
        if new_width % old_width:
            raise ValueError(
                f"cannot widen amax shards {old_width} -> {new_width}: "
                "widths must divide")
        return jnp.repeat(amax, new_width // old_width, axis=0)
    if old_width % new_width:
        raise ValueError(
            f"cannot narrow amax shards {old_width} -> {new_width}: "
            "widths must divide")
    k = old_width // new_width
    return jnp.max(amax.reshape((new_width, k) + amax.shape[1:]), axis=1)


def amax_update(cur_amax: jax.Array, stored: jax.Array) -> jax.Array:
    """The delayed-scale update law: max(cur, AMAX_DECAY·stored).

    Shared contract between the per-layer ``_delayed_scale`` plumbing below
    and the GPipe quant stacking (parallel/pp.py): because the pipelined
    forward quantizes every microbatch with the FROZEN start-of-step scale,
    the per-microbatch update *proposals* can be max-combined —
    max_m(max(amax_m, d·s)) == max(max_m(amax_m), d·s) == this law on the
    full-batch amax — so the stacked-quant pipeline reproduces the
    unpipelined update bitwise.
    """
    return jnp.maximum(cur_amax, AMAX_DECAY * stored)


def _norm_pair(v) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _fused_epilogue_scale(mod: nn.Module, x: jax.Array, ep: Callable):
    """The quantize-fused-epilogue twin of :func:`_delayed_scale`, shared
    by ``QuantConv`` and ``SpectralConv``: own the ``amax_x`` leaf (init
    = the epilogue's measured amax on the init batch — the amax output
    is scale-independent, so any positive probe works), read this step's
    stored scale, run the fused ``(y_raw, sx) -> (q, amax)`` epilogue,
    and store the update proposal when 'quant' is mutable. Returns
    ``(q, sx)`` — feed :func:`int8_conv_pq`; the dequantized tap is
    ``q·sx``."""
    amax_v = mod.variable(
        "quant", "amax_x",
        lambda: ep(x, jnp.ones((), jnp.float32))[1],
    )
    sx = jnp.maximum(amax_v.value, 1e-12) / 127.0
    q, amax = ep(x, sx)
    if mod.is_mutable_collection("quant"):
        amax_v.value = amax_update(amax, amax_v.value)
    return q, sx


def surrogate_tap(q: jax.Array, sx: jax.Array) -> jax.Array:
    """The dequantized feature tap of a fused epilogue: VALUE ``sx·q``
    (what the downstream conv contracts), but with the cotangent passed
    to ``q`` UNSCALED — the fused-epilogue VJP already interprets q's
    cotangent in the surrogate (d/dŷ) frame, and a plain ``q*sx`` would
    multiply it by ``sx`` a second time (≈amax/127, silently
    near-zeroing the feature-matching gradients through the tap)."""
    return q + jax.lax.stop_gradient(q * sx - q)


def _delayed_scale(mod: nn.Module, x: jax.Array):
    """Stored-scale plumbing shared by the Quant* modules: an ``amax_x``
    scalar in the 'quant' collection (initialized from the init batch),
    read as this step's scale. Returns ``(sx, update_fn)``; call
    ``update_fn(cur_amax)`` with the amax the conv measured."""
    amax_v = mod.variable(
        "quant", "amax_x",
        lambda: jnp.max(jnp.abs(x.astype(jnp.float32))),
    )
    sx = jnp.maximum(amax_v.value, 1e-12) / 127.0

    def update(cur_amax):
        if mod.is_mutable_collection("quant"):
            amax_v.value = amax_update(cur_amax, amax_v.value)

    return sx, update


class QuantConv(nn.Module):
    """Drop-in for the repo's ``nn.Conv`` uses, on the int8 MXU path.

    Parameter tree ("kernel" HWIO + optional "bias") matches ``nn.Conv``
    so bf16↔int8 checkpoints interchange. ``padding`` is an int (both
    sides) or explicit ((lo,hi),(lo,hi)). ``delayed`` switches the
    activation scale to the stored-amax path (see int8_conv_ds): the
    'quant' collection must then be threaded by the caller.

    ``epilogue`` (requires ``delayed``) is the quantize-fused input
    epilogue (ISSUE 14): a callable ``(y_raw, sx) -> (q, amax)`` — the
    model binds ``make_norm_act(...)``'s ``quant_scale`` form — applied
    to the RAW previous-conv output so [norm + act + clip/round + amax]
    run as one streaming pass; the conv then consumes the prequantized
    activation via :func:`int8_conv_pq`. The stored scale IS this
    module's own ``amax_x`` (same 'quant' leaf as the unfused path —
    checkpoints interchange; its init measures the epilogue's float
    output on the init batch). ``epilogue_tap=True`` additionally
    returns the dequantized surrogate ``sx·q`` — what the downstream
    conv actually sees — for feature-matching taps.
    """

    features: int
    kernel_size: int = 4
    strides: int = 1
    padding: int = 1
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()
    delayed: bool = False
    epilogue: Optional[Callable] = None
    epilogue_tap: bool = False

    @nn.compact
    def __call__(self, x):
        k = _norm_pair(self.kernel_size)
        kernel = self.param(
            "kernel", self.kernel_init, k + (x.shape[-1], self.features),
            jnp.float32,
        )
        pad = self.padding
        pad = ((pad, pad), (pad, pad)) if isinstance(pad, int) else pad
        dt = self.dtype or jnp.float32
        tap = None
        if self.epilogue is not None:
            if not self.delayed:
                raise ValueError(
                    "QuantConv(epilogue=...) needs delayed=True — the "
                    "fused quantize reads this module's stored amax")
            q, sx = _fused_epilogue_scale(self, x, self.epilogue)
            # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 per-form dispatch (_int8_bwd_core): same bf16 backward forms as the int8_conv_ds branch below, by design
            y = int8_conv_pq(q.astype(dt), kernel.astype(dt), sx,
                             _norm_pair(self.strides), pad)
            if self.epilogue_tap:
                tap = surrogate_tap(q.astype(dt), sx).astype(dt)
        elif self.delayed:
            sx, update = _delayed_scale(self, x)
            # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 per-form dispatch (_int8_bwd_core): the lhs-dilated stride-2 dgrad and the transposed/big-spatial wgrads measured SLOWER in int8 on v5e — those contractions stay bf16 on the dequantized surrogate while fwd, s1 dgrad and the unrolled wgrad run s8×s8→s32 (module docstring table; backward eqns attribute to this call site)
            y, amax = int8_conv_ds(x.astype(dt), kernel.astype(dt), sx,
                                   _norm_pair(self.strides), pad)
            update(amax)
        else:
            # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 per-form dispatch: see the delayed branch above — same _int8_bwd_core bf16 forms by design
            y = int8_conv(x.astype(dt), kernel.astype(dt),
                          _norm_pair(self.strides), pad)
        y = save_conv_out(y)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(y.dtype)
        if self.epilogue_tap:
            return y, tap
        return y


class QuantSubpixelDeconv(nn.Module):
    """ConvTranspose k4 s2 re-expressed as conv k2 s1 + shifted
    depth-to-space (ops/conv.py ``subpixel_interleave``) with the inner
    conv on the int8 path. The k2-s1 plain conv is the form where ALL THREE int8
    contractions win on v5e (fwd 2×, dgrad 2×, wgrad dot_general 1.5×),
    unlike the lhs-dilated ConvTranspose forward where int8 loses —
    which is why the int8 U-Net decoder uses this instead of
    ``QuantConvTranspose``. Param tree: ``Conv_0`` with kernel
    (2,2,C,4F); the exact weight mapping from a ConvTranspose checkpoint
    is documented at ``subpixel_interleave``.
    """

    features: int
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()
    delayed: bool = False

    @nn.compact
    def __call__(self, x):
        out = QuantConv(
            4 * self.features, kernel_size=2, strides=1,
            padding=((1, 1), (1, 1)), use_bias=self.use_bias,
            dtype=self.dtype, kernel_init=self.kernel_init, name="Conv_0",
            delayed=self.delayed,
        )(x)                                    # (N, H+1, W+1, 4F)
        return subpixel_interleave(out, self.features)


class QuantConvTranspose(nn.Module):
    """Drop-in for ``nn.ConvTranspose(k4, s2, 'SAME')`` on the int8 path.

    flax's ConvTranspose lowers to a conv with ``lhs_dilation=strides``
    and an un-flipped kernel; 'SAME' padding for k=4, s=2 is (2,2) per
    spatial dim (lax._conv_transpose_padding). Parameter tree matches
    ``nn.ConvTranspose``.
    """

    features: int
    kernel_size: int = 4
    strides: int = 2
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()
    delayed: bool = False

    @nn.compact
    def __call__(self, x):
        k = _norm_pair(self.kernel_size)
        s = _norm_pair(self.strides)
        kernel = self.param(
            "kernel", self.kernel_init, k + (x.shape[-1], self.features),
            jnp.float32,
        )
        # lax._conv_transpose_padding for 'SAME': total = k + s - 2,
        # lo = k - 1 if s > k - 1 else ceil(total / 2).
        pads = []
        for ki, si in zip(k, s):
            total = ki + si - 2
            lo = ki - 1 if si > ki - 1 else int(np.ceil(total / 2))
            pads.append((lo, total - lo))
        dt = self.dtype or jnp.float32
        if self.delayed:
            sx, update = _delayed_scale(self, x)
            y, amax = int8_conv_ds(x.astype(dt), kernel.astype(dt), sx,
                                   (1, 1), tuple(pads), lhs_dilation=s)
            update(amax)
        else:
            y = int8_conv(x.astype(dt), kernel.astype(dt), (1, 1),
                          tuple(pads), lhs_dilation=s)
        y = save_conv_out(y)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(y.dtype)
        return y
