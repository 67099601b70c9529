"""Convolution layers (NHWC, MXU-friendly).

Reference layer library (networks.py:395-423):
- ``ConvLayer``: ReflectionPad2d(k//2) + Conv2d, no norm/activation.
- ``UpsampleConvLayer``: optional nearest Upsample(×s) + ReflectionPad + Conv.

TPU-first notes: NHWC keeps channels on the 128-wide lane dimension; the
reflect pad is a cheap gather XLA fuses into the conv's input; upsampling is
nearest-neighbor (a broadcast-reshape, fusable) rather than transposed conv —
same choice the reference made to avoid checkerboard artifacts.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name


def remat_wrap(block_cls, mode, static_argnums=(2,)):
    """Wrap a flax module class in nn.remat according to ``mode``.

    - falsy: no remat.
    - "conv": remat with policy save_only_these_names('conv_out',
      'norm_stats') — conv outputs stay resident, only the elementwise
      norm-apply/activation chains are recomputed in the backward. Costs
      the conv-output memory but no extra MXU work; the measured sweet
      spot for the 1024×512 presets.
    - True / "full": classic full remat — minimum memory, recomputes the
      block's convs (+~⅓ generator MXU work).
    """
    if not mode:
        return block_cls
    if mode == "conv":
        return nn.remat(
            block_cls, static_argnums=static_argnums,
            policy=jax.checkpoint_policies.save_only_these_names(
                "conv_out", "norm_stats"
            ),
        )
    if mode is True or mode == "full":
        return nn.remat(block_cls, static_argnums=static_argnums)
    raise ValueError(
        f"unknown remat mode {mode!r}; expected False, True/'full', or 'conv'"
    )


def save_conv_out(y: jax.Array) -> jax.Array:
    """Tag a conv output as a named saveable residual (name ``conv_out``).

    Autodiff of a conv→norm→activation stack saves BOTH the conv output and
    the post-norm/activation tensors as residuals — ~2× the activation HBM
    traffic on the backward pass, which profiling shows is the bound on the
    256² pix2pix step. Under ``jax.checkpoint(fn,
    policy=save_only_these_names('conv_out', 'norm_stats'))`` (see
    train/step.py) only these tagged tensors are kept; the elementwise
    norm-apply/LeakyReLU/pad/upsample ops are recomputed in the backward,
    where they fuse into the gradient kernels for free.
    """
    return checkpoint_name(y, "conv_out")


# The smallest batch of post-upsample pixels (N*4*H*W) an
# UpsampleConvLayer(k3, upsample=2) site was read at on the chip: one image
# of ExpandNetwork's first upsample, [1,64,64,128] -> 64
# (nearest_up2_engages). Below it the layer keeps the plain chain: what was
# not measured is not rerouted (toy extents; no preset holds less).
_NEAREST_UP2_MIN_PIXELS = 16_384


def nearest_up2_engages(x, features: int) -> bool:
    """Whether an UpsampleConvLayer(k3, stride 1, upsample=2) site with
    the LOW-RES input ``x`` (N,H,W,C) takes the subpixel form
    (:class:`_NearestUp2Conv`), whichever ring its pad mode gives it:
    below 128 output channels, from ``_NEAREST_UP2_MIN_PIXELS``
    post-upsample pixels of the batch up.

    There the plain conv writes a part of the 128 lanes onto the
    4x-materialised upsampled tensor, and the four phases of the subpixel
    form fill them. Set from readings on one v5e, one layer alone, forward
    + both gradients in bf16, plain -> subpixel milliseconds (PERF.md
    section 6, PR 28): ExpandNetwork's [32,128,128,64] -> 32 21.25 ->
    11.16 and [32,64,64,128] -> 64 5.19 -> 4.04; the pix2pixhd enhancer's
    [2,256,512,64] -> 32 21.17 -> 6.87 and G1's last [2,128,256,128] -> 64
    4.84 -> 1.81; and in their steps' traces. One image of ExpandNetwork,
    the smallest read: [1,128,128,64] -> 32 1.24 -> 0.52 and [1,64,64,128]
    -> 64 0.51 -> 0.50 (the host's launch floor; device busy 0.33 ->
    0.18): the form loses at no extent read, so training, one-image
    inference and serving of a preset take one form (a gate on ONE
    image's 300k pixels, a pre-round bs1 reading, kept ExpandNetwork's
    sites out). A zero-padded site follows the same rule since PR 43 (the
    fold and the convolution are the reflect-padded site's, the ring is
    zeros): SwinIR's two, [4,64,64,64] -> 64 0.67 -> 0.52 and
    [4,128,128,64] -> 64 2.57 -> 1.36, and 36.61 -> 36.88 img/s in their
    step (PERF.md section 6, PR 43).

    From 128 output channels up the plain chain stays, read in PR 43 with
    the ceiling lifted (PERF.md section 6): the VQGAN decoder's zero-padded
    sites, C_in = C_out, lose alone ([12,128,128,128] -> 128 6.68 -> 8.46,
    [12,64,64,256] -> 256 5.87 -> 7.13, [12,32,32,256] -> 256 1.46 -> 1.86)
    and in their step (66.44 -> 65.21 img/s): at full lanes the saving and
    the depth-to-space cancel and the dense folded kernel is left over.
    G1's reflect-padded ones, C_in = 2 C_out, win alone ([2,64,128,256] ->
    128 2.06 -> 1.32, [2,32,64,512] -> 256 1.54 -> 1.32) for under 1% of
    their step, which no sound pair read; no width separates the two
    groups, and on a ``spatial`` > 1 mesh every engaged reflect-padded
    site adds a whole-shard collective-permute in the backward of its edge
    pad (PERF.md section 4 (3))."""
    n, h, w, _ = x.shape
    return features < 128 and n * 4 * h * w >= _NEAREST_UP2_MIN_PIXELS


# The blocked form (BlockedConv) below this padded area was never measured
# on the chip (ExpandNetwork's 264x264 is the smallest): what was not
# measured is not rerouted.
_BLOCKED_MIN_PIXELS = 65_000


def blocked_conv_block(x, features: int, kernel_size: int,
                       stride: int) -> int:
    """The block s (pixels along W) the blocked form takes for this
    layer, or 0 where the layer keeps the plain conv (x is the PADDED
    input).

    A stride-1 k>=7 conv whose thin side (min of C_in, C_out) has at most
    16 channels against at least twice that on the other side keeps 3-16
    of the MXU's 128 lanes busy; on blocks of s pixels the thin side has
    s times the channels. s is the smallest power of two, 4 or more, that
    brings it to 24 or more (3 channels: 8; 12: 4), and has to divide the
    output's width. Set from readings on one v5e at the extents the
    benchmark's cells hold (PERF.md section 6, PR 24); the k5 3->64 stem
    is no faster blocked than plain."""
    thin, wide = sorted((x.shape[-1], features))
    if not (stride == 1 and kernel_size >= 7 and thin <= 16
            and wide >= 2 * thin
            and x.shape[1] * x.shape[2] >= _BLOCKED_MIN_PIXELS):
        return 0
    s = 4
    while thin * s < 24:
        s *= 2
    return s if (x.shape[2] - kernel_size + 1) % s == 0 else 0


#: the non-plain forms a ConvLayer / UpsampleConvLayer call site can take
#: (a thin k7 / k9 site under a mesh that shards H takes two: ``halo``
#: around ``blocked``)
CONV_FORMS = ("blocked", "nearest_up2", "halo")


def _count_form(form: str) -> None:
    """One call site took ``form``: counted at TRACE time in the process
    registry as ``conv_form_sites_total{form=...}`` (a module cannot be
    handed a run's registry; ``conv_form_sites`` reads it back)."""
    from p2p_tpu.obs.registry import get_registry

    get_registry().counter("conv_form_sites_total", form=form).inc()


def conv_form_sites() -> dict:
    """form -> call sites traced into it so far in this process (every
    trace of a site counts: a net's init, each pass of the step)."""
    from p2p_tpu.obs.registry import get_registry

    reg = get_registry()
    return {f: int(reg.counter("conv_form_sites_total", form=f).value)
            for f in CONV_FORMS}


def halo_conv_mesh(x, kernel_size: int, stride: int):
    """The mesh over which a reflect-padded convolution site with the
    UNPADDED input ``x`` (N,H,W,C) runs as one ``shard_map``
    (``parallel.spatial.halo_conv``: halo exchange, local W pad, local
    VALID conv), or None where the site keeps the pad-then-conv chain.

    It engages on what the site can observe: a mesh made visible by
    ``core.mesh.mesh_context`` on which ``x`` lays out ``P((data, fsdp),
    spatial)`` and whose ``spatial`` axis is above 1, an odd kernel above
    1, a shard's rows more than the pad and a multiple of the stride
    (k3 stride 2 wants an even number of local rows), W wider than the
    pad. There GSPMD's own partition of the padded tensor, whose
    H + 2p rows do not split like the activation's H, re-windowed every
    layer (PERF.md section 6, PR 35). A mesh with ``model``, ``pipe`` or
    ``time`` above 1 keeps GSPMD's path: the ``shard_map`` takes its
    kernel replicated and would gather a tensor-parallel one at every
    site."""
    from p2p_tpu.core.mesh import BATCH_AXES, SPATIAL_AXIS, spatial_shard_mesh

    mesh = spatial_shard_mesh(x)
    if mesh is None:
        return None
    s = mesh.shape.get(SPATIAL_AXIS, 1)
    pad = kernel_size // 2
    rows = x.shape[1] // s
    if (s > 1 and pad and kernel_size % 2 and rows > pad
            and rows % stride == 0 and x.shape[2] > pad
            and all(n == 1 for a, n in mesh.shape.items()
                    if a not in (*BATCH_AXES, SPATIAL_AXIS))):
        return mesh
    return None


def _reflect_pad_h_sharded(x: jax.Array, pad: int, mesh) -> jax.Array:
    """Reflection-pad H of an NHWC tensor whose H is sharded over the
    ``spatial`` axis of ``mesh``, without moving the shard.

    ``jnp.pad(mode="reflect")`` with ``pad`` >= 2 REVERSES the border rows,
    and GSPMD has no rule for a reverse along a sharded dimension: in the
    pix2pixhd step on data=2 x spatial=2 it re-sharded the whole
    activation from H to W and back around every k7 layer's pad, two
    all-to-alls of the full tensor each (67 MB each for the enhancer
    head at 2048x1024; found compiling the step for a described v5e:2x2,
    PERF.md section 4, PR 25). Here every shard builds its own rows of
    the padded tensor: its halo'd shard (``parallel.halo.halo_exchange``:
    neighbour rows inside, reflected rows at the image's two edges), cut
    to the ``(H + 2*pad) / spatial`` rows that are its equal share of the
    padded tensor. The result is laid out like ``x``; the convolution that
    follows is GSPMD's, as for the k3 layers."""
    from jax.sharding import PartitionSpec as P

    from p2p_tpu.core.mesh import BATCH_AXES, SPATIAL_AXIS
    from p2p_tpu.parallel.halo import halo_exchange

    grow = 2 * pad // mesh.shape[SPATIAL_AXIS]

    def local(xl):
        rows = xl.shape[1] + grow
        xl = halo_exchange(xl, dim=1, halo=pad, axis_name=SPATIAL_AXIS,
                           edge_mode="reflect")
        return jax.lax.dynamic_slice_in_dim(
            xl, jax.lax.axis_index(SPATIAL_AXIS) * grow, rows, axis=1)

    spec = P(BATCH_AXES, SPATIAL_AXIS, None, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec,),
                         out_specs=spec)(x)


#: how a ``reflect_pad_2d`` call site's backward is built
REFLECT_PAD_BACKWARDS = ("one_pass", "one_pass_w", "autodiff")


def reflect_pad_sites() -> dict:
    """backward -> ``reflect_pad_2d`` call sites traced with it so far in
    this process (``reflect_pad_sites_total{backward=...}``, counted like
    :func:`conv_form_sites`)."""
    from p2p_tpu.obs.registry import get_registry

    reg = get_registry()
    return {b: int(reg.counter("reflect_pad_sites_total", backward=b).value)
            for b in REFLECT_PAD_BACKWARDS}


def _reflect_fold(g: jax.Array, pad: int, axes: tuple) -> jax.Array:
    """The transpose of a reflect pad of ``axes`` by ``pad``, in float32:
    along the first axis the centre band plus the two border strips,
    reversed and zero-filled onto the rows 1..pad and -pad-1..-2 they
    were copied from, every band folded along the remaining axes FIRST.
    So the corners reach the strips (``pad`` rows: small) before the
    strips are placed, and the terms the size of the tensor (the centre
    and, per axis, two placed strips) are independent of each other: one
    sum, where autodiff's fold of each axis reads the fold before it."""
    if not axes:
        return g.astype(jnp.float32)
    axis, rest = axes[0], axes[1:]
    n = g.shape[axis] - 2 * pad

    def band(lo, hi):
        return _reflect_fold(jax.lax.slice_in_dim(g, lo, hi, axis=axis),
                             pad, rest)

    def placed(strip, low):
        config = [(0, 0, 0)] * g.ndim
        config[axis] = (low, n - pad - low, 0)
        return jax.lax.pad(jax.lax.rev(strip, (axis,)),
                           jnp.zeros((), strip.dtype), config)

    return (band(pad, pad + n) + placed(band(0, pad), 1)
            + placed(band(pad + n, n + 2 * pad), n - pad - 1))


def _jnp_reflect_pad(x: jax.Array, pad: int, axes: tuple) -> jax.Array:
    widths = [(pad, pad) if a in axes else (0, 0) for a in range(x.ndim)]
    return jnp.pad(x, widths, mode="reflect")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _reflect_pad(x: jax.Array, pad: int, axes: tuple) -> jax.Array:
    """``jnp.pad(mode="reflect")`` of ``axes`` by ``pad`` (less than the
    extent), with a backward that reads the padded cotangent once
    (:func:`_reflect_fold`: up to four contributions of an element summed
    in float32 and rounded once) where autodiff chains two slice-add
    passes over the whole tensor an axis."""
    return _jnp_reflect_pad(x, pad, axes)


def _reflect_pad_fwd(x, pad, axes):
    return _reflect_pad(x, pad, axes), None


def _reflect_pad_bwd(pad, axes, _, g):
    # traced under the forward's name stack: the scope ``reflect_pad``
    return (_reflect_fold(g, pad, axes).astype(g.dtype),)


_reflect_pad.defvjp(_reflect_pad_fwd, _reflect_pad_bwd)


def reflect_pad_2d(x: jax.Array, pad: int) -> jax.Array:
    """Reflection-pad H and W of an NHWC tensor, under the named scope
    ``reflect_pad``; the values are ``jnp.pad(mode="reflect")``'s.

    The backward is one pass over the padded cotangent
    (:func:`_reflect_pad`) along the axes no mesh shards: H and W, or W
    alone inside a step whose mesh shards H (``core.mesh.current_mesh``
    with ``spatial`` > 1), because the fold REVERSES strips, and a reverse
    along a sharded dimension is what GSPMD answers by re-sharding the
    whole tensor. There H keeps what it had: a pad of two rows or more is
    built shard by shard (:func:`_reflect_pad_h_sharded`) where the padded
    rows split evenly; one row needs no reverse and stays GSPMD's. A pad
    as large as the extent (``jnp.pad`` reflects it again and again) keeps
    autodiff's backward. Which of the three a traced call site took is
    counted in ``reflect_pad_sites_total{backward=...}``."""
    if pad == 0:
        return x
    from p2p_tpu.core.mesh import (
        SPATIAL_AXIS,
        current_mesh,
        spatial_shard_mesh,
    )
    from p2p_tpu.obs.registry import get_registry

    mesh = current_mesh()
    s = mesh.shape.get(SPATIAL_AXIS, 1) if mesh is not None else 1
    if pad >= min(x.shape[1], x.shape[2]):
        backward, axes = "autodiff", ()
    elif s > 1:
        backward, axes = "one_pass_w", (2,)
    else:
        backward, axes = "one_pass", (1, 2)
    get_registry().counter("reflect_pad_sites_total", backward=backward).inc()
    with jax.named_scope("reflect_pad"):
        if 1 not in axes:
            if (s > 1 and pad >= 2 and (2 * pad) % s == 0
                    and x.shape[1] // s > pad
                    and spatial_shard_mesh(x) is not None):
                x = _reflect_pad_h_sharded(x, pad, mesh)
            else:
                x = _jnp_reflect_pad(x, pad, (1,))
        if 2 not in axes:
            x = _jnp_reflect_pad(x, pad, (2,))
        return _reflect_pad(x, pad, axes) if axes else x


def reflect_pad_w(x: jax.Array, pad: int) -> jax.Array:
    """Reflection-pad W alone, inside a ``shard_map`` whose shards hold
    their H halo already (``parallel.spatial.halo_conv``): the one-pass
    backward along W, under the scope ``reflect_pad`` and counted as a
    ``one_pass_w`` site like :func:`reflect_pad_2d`'s under such a mesh."""
    from p2p_tpu.obs.registry import get_registry

    get_registry().counter("reflect_pad_sites_total",
                           backward="one_pass_w").inc()
    with jax.named_scope("reflect_pad"):
        return _reflect_pad(x, pad, (2,))


def normal_init(stddev: float = 0.02):
    """Reference default weight init: N(0, 0.02) (networks.py:131)."""
    return nn.initializers.normal(stddev=stddev)


def _routed_conv(layer, x):
    """The VALID conv of ``ConvLayer`` / ``UpsampleConvLayer`` on their
    padded input: the blocked form where :func:`blocked_conv_block` says
    so, else ``nn.Conv``. Both keep the param tree of the plain
    ``nn.Conv`` under the name ``Conv_0``. Called inside the layer's
    compact ``__call__``.

    A thin layer whose width the block does not divide, and a thin stem
    with k < 7, take the plain conv. Two hand-made forms used to catch
    them on 300k pixels and more (im2col patches + matmul for 3-channel
    stems, kn2row with a hand-written VJP for 3-channel heads); both lost
    on the chip at every shape read (PERF.md section 6, PR 24, one layer
    alone, fwd+bwd ms: HD k7 stem plain 9.41 / patches 6.43 / blocked
    2.02; HD k7 head plain 16.44 / kn2row 18.38 / blocked 4.25; k9 head
    at 256x256 bs32 plain 35.96 / kn2row refused by the compiler at
    44.1 GB / blocked 9.64; k5 3->64 stem plain 4.60 / patches 7.15) and
    went in PR 27. No preset has such a shape: every generator here
    downsamples by 4 to 32, so its width is a multiple of the block."""
    kw = dict(use_bias=layer.use_bias, dtype=layer.dtype,
              kernel_init=layer.kernel_init)
    k, stride = layer.kernel_size, layer.stride
    block = blocked_conv_block(x, layer.features, k, stride)
    if block:
        # the thin k7/k9 image stems and heads (3-16 channels on one side)
        _count_form("blocked")
        return BlockedConv(layer.features, kernel_size=k, block=block,
                           name="Conv_0", **kw)(x)
    return save_conv_out(nn.Conv(
        features=layer.features, kernel_size=(k, k),
        strides=(stride, stride), padding="VALID", **kw)(x))


def _reflect_padded_conv(layer, x):
    """ReflectionPad(k // 2) + the layer's conv on the unpadded ``x``:
    one ``shard_map`` where :func:`halo_conv_mesh` gives a mesh
    (:class:`HaloConv`; the blocked local conv where the padded shape
    asks for it, as without a mesh), else :func:`reflect_pad_2d` and
    :func:`_routed_conv`."""
    k, stride = layer.kernel_size, layer.stride
    mesh = halo_conv_mesh(x, k, stride)
    if mesh is None:
        return _routed_conv(layer, reflect_pad_2d(x, k // 2))
    n, h, w, c = x.shape
    block = blocked_conv_block(
        jax.ShapeDtypeStruct((n, h + k - 1, w + k - 1, c), x.dtype),
        layer.features, k, stride)
    _count_form("halo")
    if block:
        _count_form("blocked")
    return HaloConv(layer.features, kernel_size=k, stride=stride,
                    block=block, use_bias=layer.use_bias, dtype=layer.dtype,
                    kernel_init=layer.kernel_init, name="Conv_0")(x, mesh)


class ConvLayer(nn.Module):
    """ReflectionPad(k//2) + conv. Ref: networks.py:395-405.

    ``int8`` routes the conv through the int8 MXU path (ops/int8.py);
    the reflect pad stays outside (the quantized conv pads with zeros
    only), parameter tree unchanged.
    """

    features: int
    kernel_size: int
    stride: int = 1
    use_bias: bool = True
    int8: bool = False
    int8_delayed: bool = False
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()
    # "zero": Conv2d(padding=k//2) in the layer's place (the SPADE
    # lineage pads with zeros); the conv's form is chosen as for reflect.
    # "zero_after": zeros below and to the right only (the stride-2
    # downsampling of models/vqgan.py: F.pad(x, (0, 1, 0, 1)), padding 0)
    pad_mode: str = "reflect"

    @nn.compact
    def __call__(self, x):
        pad = self.kernel_size // 2
        if self.pad_mode == "reflect" and not self.int8:
            return _reflect_padded_conv(self, x)
        if self.pad_mode == "zero":
            x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        elif self.pad_mode == "zero_after":
            x = jnp.pad(x, ((0, 0), (0, pad), (0, pad), (0, 0)))
        else:
            x = reflect_pad_2d(x, pad)
        if self.int8:
            from p2p_tpu.ops.int8 import QuantConv

            return QuantConv(
                self.features, kernel_size=self.kernel_size,
                strides=self.stride, padding=0, use_bias=self.use_bias,
                dtype=self.dtype, kernel_init=self.kernel_init,
                name="Conv_0", delayed=self.int8_delayed,
            )(x)
        return _routed_conv(self, x)


def kn2row_thin_conv(x: jax.Array, w: jax.Array, pad: int) -> jax.Array:
    """Stride-1 conv for THIN outputs (C_out·k² ≪ C_in) as a 1×1 matmul
    plus shifted slice-adds — the kn2row decomposition.

    A k4 conv from 512 → 1 channel (the PatchGAN head) runs the MXU at
    3–6 TF/s: one output lane of 128 is live, and XLA's conv kernels
    re-read the input window-by-window (profiled ~4 ms/step of the
    256²/bs=128 train step). Rewriting it as

        z[p, t·o] = x[p, :] @ w[t, :, o]        (one 1×1 matmul, one
                                                 HBM pass over x)
        y[i, j, o] = Σ_t z_pad[i+dh_t, j+dw_t, t, o]

    moves the only large-tensor traffic into a plain matmul (bandwidth-
    bound at full HBM rate) and does the k² shift-adds on the tiny tap
    tensor z (k²·C_out channels). The backward that jax derives is just
    as lean: dx = dz @ wᵀ (one pass over dx), dw = xᵀ·dz (one re-read of
    x), slice-transposes on z only.

    x: (N,H,W,C) NHWC; w: (kh,kw,C,O) HWIO; zero padding ``pad`` both
    sides, stride 1. Returns (N, H+2·pad−kh+1, W+2·pad−kw+1, O).
    """
    kh, kw, c, o = w.shape
    n, h, wd, _ = x.shape
    ho, wo = h + 2 * pad - kh + 1, wd + 2 * pad - kw + 1
    wt = w.reshape(kh * kw, c, o).transpose(1, 0, 2).reshape(c, kh * kw * o)
    # 4-D contraction over the channel dim (NO flattening reshape: a
    # (-1, C) reshape of e.g. a concat output forces XLA to materialize
    # layout copies of the big input — profiled +6 ms/step)
    # p2p-lint: disable=jaxpr-f32-leak -- deliberate: z is f32 (MXU accumulation matching the XLA conv this replaces); its backward dots contract the f32 cotangent against the bf16 weight/input, which is the accumulation design, not a leak
    z = jax.lax.dot_general(
        x, wt.astype(x.dtype), (((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,  # f32 MXU accumulation
    ).reshape(n, h, wd, kh * kw, o)
    z = jnp.pad(z, ((0, 0), (pad, pad), (pad, pad), (0, 0), (0, 0)))
    # f32 accumulation of the k² partial sums: the XLA conv this replaces
    # accumulates all kh·kw·C terms in f32 and rounds once — matching
    # that costs nothing (y is the thin output tensor)
    y = jnp.zeros((n, ho, wo, o), jnp.float32)
    for t in range(kh * kw):
        dh, dw = divmod(t, kw)
        y = y + jax.lax.dynamic_slice(
            z, (0, dh, dw, t, 0), (n, ho, wo, 1, o)
        ).reshape(n, ho, wo, o).astype(jnp.float32)
    return y.astype(x.dtype)


def im2col_patches(x: jax.Array, k: int) -> jax.Array:
    """VALID stride-1 im2col: (N, H, W, C) → (N, H−k+1, W−k+1, k²·C),
    feature order (kh, kw, c) — i.e. an HWIO kernel flattens to the
    matching matrix with a plain ``w.reshape(k·k·C, F)``. Used by the
    int8 kn2row backward (ops/int8.py) on the thin cotangent.

    Built from k² static slices + one channel concat (pure HBM movement
    at full rate) — NOT ``lax.conv_general_dilated_patches``, whose
    lowering is itself a thin-input conv (3 TF/s, measured on the
    pix2pixHD enhancer stem).
    """
    n, h, w, c = x.shape
    ho, wo = h - k + 1, w - k + 1
    cols = [
        jax.lax.slice(x, (0, kh, kw, 0), (n, kh + ho, kw + wo, c))
        for kh in range(k) for kw in range(k)
    ]
    return jnp.concatenate(cols, axis=-1)


def blocked_conv(xp: jax.Array, w: jax.Array, s: int) -> jax.Array:
    """VALID stride-1 conv of the pre-padded ``xp`` (N,Hp,Wp,C) with the
    HWIO kernel ``w`` (k,k,C,O), computed on blocks of s pixels along W:
    the same sum of the same products with s times the channels on both
    sides. With ``k' = (s + k - 2) // s + 1`` taps along W:

        xb[n, i, J, (q,c)]     = xp[n, i, s*J + q, c]
        wb[a, B, (q,c), (v,o)] = w[a, s*B + q - v, c, o]  (0 outside [0, k))
        y[n, i, s*J + v, o]    = conv(xb, wb)[n, i, J, (v,o)]

    Both block maps are reshapes (q and c, v and o are neighbours in
    memory); blocks of ROWS as well would cost a transpose each way and
    read slower on the chip at every extent tried (PERF.md section 6,
    PR 24). ``xp`` is zero-filled on the right up to whole blocks (the
    fill only ever meets the zeros of ``wb``). ``wb`` is an einsum of
    ``w`` with a constant 0/1 tensor (as ``_NearestUp2Conv`` builds its
    phase kernels), so autodiff carries its gradient back to ``w``; the
    three convolutions (forward, input and weight gradient) are XLA's own
    on dense operands. The output's width has to divide by s."""
    k, _, cin, cout = w.shape
    n, hp, wp, _ = xp.shape
    wo = wp - k + 1
    if wo % s:
        raise ValueError(f"blocked_conv: the output width {wo} does not "
                         f"divide by the block {s}")
    kb = (s + k - 2) // s + 1
    wb_ = wo // s + kb - 1
    xb = jnp.pad(xp, ((0, 0), (0, 0), (0, s * wb_ - wp), (0, 0)))
    xb = xb.reshape(n, hp, wb_, s * cin)
    # M[B, q, v, b] = 1 where b = s*B + q - v is a tap of w
    m = np.zeros((kb, s, s, k), np.float32)
    for blk in range(kb):
        for q in range(s):
            for v in range(s):
                if 0 <= s * blk + q - v < k:
                    m[blk, q, v, s * blk + q - v] = 1.0
    wb = jnp.einsum("cqvb,abio->acqivo", jnp.asarray(m),
                    w.astype(jnp.float32))
    wb = wb.reshape(k, kb, s * cin, s * cout).astype(xp.dtype)
    yb = jax.lax.conv_general_dilated(
        xb, wb, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return yb.reshape(n, hp - k + 1, wo, cout)


class BlockedConv(nn.Module):
    """Stride-1 conv for thin image-side layers (the k7/k9 stems and
    heads) on blocks of ``block`` pixels along W (see
    :func:`blocked_conv`), under the named scope ``blocked_conv``. Input
    arrives pre-padded (VALID) as with the other ConvLayer branches; param
    tree ("kernel" (k,k,C_in,C_out) float32 + "bias") matches ``nn.Conv``
    and callers name it ``Conv_0``, so checkpoints, the TP rules and the
    optimizer see nothing new."""

    features: int
    kernel_size: int
    block: int
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()

    @nn.compact
    def __call__(self, x):
        k = self.kernel_size
        kernel = self.param("kernel", self.kernel_init,
                            (k, k, x.shape[-1], self.features), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros, (self.features,),
                           jnp.float32) if self.use_bias else None)
        dt = self.dtype or jnp.float32
        with jax.named_scope("blocked_conv"):
            y = blocked_conv(x.astype(dt), kernel, self.block)
            if bias is not None:
                y = y + bias.astype(y.dtype)
        return save_conv_out(y)


class HaloConv(nn.Module):
    """ReflectionPad(k // 2) + conv of an H-sharded input as ONE
    ``shard_map`` over the mesh it is called with
    (``parallel.spatial.halo_conv``: halo rows from the neighbours, W
    padded locally, a local VALID conv, on pixel blocks where ``block``
    says so). Takes the UNPADDED input. Param tree ("kernel"
    (k,k,C_in,C_out) float32 + "bias") matches ``nn.Conv`` and callers
    name it ``Conv_0``, as :class:`BlockedConv`.

    The kernel is cast to the compute dtype out here, so the ``psum`` the
    ``shard_map``'s transpose gives its cotangent (one all-reduce over
    ``data`` and ``spatial`` together) moves bf16 in a bf16 step; the
    bias is added out here too, on the global output, GSPMD's."""

    features: int
    kernel_size: int
    stride: int = 1
    block: int = 0
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()

    @nn.compact
    def __call__(self, x, mesh):
        from p2p_tpu.parallel.spatial import halo_conv

        k = self.kernel_size
        kernel = self.param("kernel", self.kernel_init,
                            (k, k, x.shape[-1], self.features), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros, (self.features,),
                           jnp.float32) if self.use_bias else None)
        dt = self.dtype or jnp.float32
        y = halo_conv(x.astype(dt), kernel.astype(dt), mesh,
                      stride=self.stride, block=self.block)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return save_conv_out(y)


class KN2RowConv(nn.Module):
    """Stride-1 thin-output conv module on the kn2row path.

    Param tree ("kernel" HWIO + optional "bias") matches ``nn.Conv`` so
    checkpoints interchange with the plain path; callers name it
    ``Conv_0`` to mirror an anonymous inner ``nn.Conv``.

    ``int8`` routes the tap decomposition through the s8×s8→s32 form
    (ops/int8.py ``int8_kn2row_conv``: fwd + wgrad on the int8 MXU, the
    tiny-contraction dgrad bf16 per the per-form dispatch table);
    ``int8_delayed`` switches to the stored-scale variant (the caller
    threads the 'quant' collection). Param tree unchanged either way.
    """

    features: int
    kernel_size: int
    padding: int
    use_bias: bool = True
    int8: bool = False
    int8_delayed: bool = False
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()

    @nn.compact
    def __call__(self, x):
        k = self.kernel_size
        kernel = self.param("kernel", self.kernel_init,
                            (k, k, x.shape[-1], self.features), jnp.float32)
        dt = self.dtype or jnp.float32
        if self.int8 and self.int8_delayed:
            from p2p_tpu.ops.int8 import _delayed_scale, int8_kn2row_conv_ds

            sx, update = _delayed_scale(self, x)
            # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 per-form dispatch: the kn2row backward's dgrad contracts over k²·O (16 lanes for the k4→1 head) — below one MXU tile, the int8 rate is unrealizable there; it stays bf16 on the dequantized surrogate while fwd+wgrad run s8×s8→s32 (ops/int8.py kn2row dispatch table; backward eqns attribute to this call site)
            y, amax = int8_kn2row_conv_ds(
                x.astype(dt), kernel.astype(dt), sx, self.padding)
            update(amax)
        elif self.int8:
            from p2p_tpu.ops.int8 import int8_kn2row_conv

            # p2p-lint: disable=perf-int8-coverage-gap -- 2026-08-04 per-form dispatch: see the delayed branch above — the kn2row dgrad stays bf16 by design
            y = int8_kn2row_conv(x.astype(dt), kernel.astype(dt),
                                 self.padding)
        else:
            y = kn2row_thin_conv(x.astype(dt), kernel.astype(dt),
                                 self.padding)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(y.dtype)
        return save_conv_out(y)


def upsample_nearest(x: jax.Array, factor: int) -> jax.Array:
    """Nearest-neighbor ×factor upsample in NHWC via broadcast-reshape."""
    if factor == 1:
        return x
    n, h, w, c = x.shape
    x = x[:, :, None, :, None, :]
    x = jnp.broadcast_to(x, (n, h, factor, w, factor, c))
    return x.reshape(n, h * factor, w * factor, c)


def subpixel_interleave(out: jax.Array, features: int) -> jax.Array:
    """The shifted depth-to-space that makes a ConvTranspose(k4, s2,
    'SAME') out of a conv(k2, s1, pad 1) producing 4F channels: maps that
    conv's output (N, H+1, W+1, 4F) to (N, 2H, 2W, F) via
    ``y[2i+u, 2j+v] = out[i+u, j+v, (u,v)]``. With k=4, s=2 every output
    pixel receives contributions from exactly a 2x2 input window; the
    weight mapping from a flax ConvTranspose kernel is
    ``W'[dh, dw, (u,v)·F] = W[2·dh+u, 2·dw+v]`` (tests/test_ops.py
    holds it against flax). Used by ops/int8.py QuantSubpixelDeconv."""
    n, h1, w1, c4 = out.shape
    h, w, f = h1 - 1, w1 - 1, features
    out = out.reshape(n, h1, w1, 2, 2, f)
    rows = []
    for u in range(2):
        cols = [out[:, u:u + h, v:v + w, u, v] for v in range(2)]
        rows.append(jnp.stack(cols, axis=3))          # (N,H,W,2,F)
    y = jnp.stack(rows, axis=2)                       # (N,H,2,W,2,F)
    return y.reshape(n, 2 * h, 2 * w, f)


def depth_to_space_2x(out: jax.Array, features: int) -> jax.Array:
    """Plain ×2 depth-to-space: (N,H,W,4F) → (N,2H,2W,F) with phase (u,v)
    at channel block u·2+v — ``y[2i+u, 2j+v] = out[i, j, (u·2+v)·F:]``."""
    n, h, w, _ = out.shape
    out = out.reshape(n, h, w, 2, 2, features)
    out = out.transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(n, 2 * h, 2 * w, features)


def nearest_up2_kernel(w: jax.Array) -> jax.Array:
    """The (3,3,ci,co) kernel of a (nearest x2 -> pad 1 -> k3 conv) chain
    folded onto the LOW-RES grid: (3,3,ci,4*co) float32 with
    the output channels in the order (u, v, o), phase (u,v) of the x2
    output at channel block u*2+v. ``Wc[r,c,i,(u,v,o)] = sum_{a,b}
    M[u,r,a] M[v,c,b] W[a,b,i,o]`` with the constant 0/1 folding matrix
    ``M[u, o+1, a+1] = 1 where floor((u+a)/2) == o`` (folded into the
    weights at trace time; autodiff carries the gradient back to ``w``)."""
    m = np.zeros((2, 3, 3), np.float32)
    for u in (0, 1):
        for ia, a in enumerate((-1, 0, 1)):
            m[u, (u + a) // 2 + 1, ia] = 1.0
    m = jnp.asarray(m)
    wc = jnp.einsum("ura,vcb,abio->rciuvo", m, m, w.astype(jnp.float32))
    return wc.reshape(3, 3, w.shape[2], 4 * w.shape[3])


def nearest_up2_conv(x: jax.Array, w: jax.Array, dtype,
                     pad_mode: str) -> jax.Array:
    """(nearest x2 -> pad 1 -> k3 conv) of ``x`` (N,H,W,ci) with the HWIO
    kernel ``w`` (3,3,ci,co), no bias, in the subpixel form: one k3 conv
    ``ci -> 4*co`` of the LOW-RES input padded by one ring with the folded
    kernel, computed in ``dtype``, then :func:`depth_to_space_2x`.
    ``pad_mode`` is UpsampleConvLayer's, the pad of the UPSAMPLED tensor:
    the ring that stands for ``"reflect"`` is an edge pad of the low-res
    input, the one for ``"zero"`` is zeros."""
    wc = nearest_up2_kernel(w)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)),
                 mode={"reflect": "edge", "zero": "constant"}[pad_mode])
    y = jax.lax.conv_general_dilated(
        xp.astype(dtype), wc.astype(dtype), window_strides=(1, 1),
        padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return depth_to_space_2x(save_conv_out(y), w.shape[-1])


class _NearestUp2Conv(nn.Module):
    """EXACT subpixel decomposition of UpsampleConvLayer's
    (nearest ×2 upsample → pad 1 → 3×3 conv) chain, for a reflect pad and
    a zero pad of the upsampled tensor (``pad_mode``) alike.

    With ``up(x)[p,q] = x[p//2, q//2]``, each output phase (u,v)∈{0,1}²
    reads low-res offsets ``o = floor((u+a)/2)`` per tap a∈{-1,0,1}, so

        out[2i+u, 2j+v] = Σ_{o_r,o_c} Wp[u,v][o_r,o_c] · x[i+o_r, j+o_c]

    where the phase kernels Wp are pairwise sums of the original taps
    (e.g. u=0 rows: [W₋₁, W₀+W₁]). All four phases fit a 3×3 support on
    the LOW-RES grid, so the whole layer is ONE 3×3 conv ci→4·co at half
    resolution + :func:`depth_to_space_2x`: the same FLOPs land on full
    128-lane MXU tiles (vs a 32-lane-wide conv over the 4×-materialized
    upsampled tensor) and the activation traffic drops ~4×.
    Boundary: the only taps that leave the image are up[-1] ↔ x[-1]
    (phase 0, offset -1) and up[2H] ↔ x[H] (phase 1, offset +1), so the
    single ring a 3×3 needs is a ring of the LOW-RES input: reflect-padding
    the UPSAMPLED image equals EDGE-padding it (up[-1]=up[1]=x[0],
    up[2H]=up[2H-2]=x[H-1]), zero-padding the upsampled image equals
    zero-padding it; k≥5 needs a second ring where neither identity holds
    — hence the k==3 gate in the dispatcher. Param tree identical to
    ``nn.Conv`` ("kernel" (3,3,ci,co) [+ "bias"]), so checkpoints and the
    TP sharding rules are unchanged.
    """

    features: int
    pad_mode: str
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()

    @nn.compact
    def __call__(self, x):
        ci, co = x.shape[-1], self.features
        kernel = self.param("kernel", self.kernel_init, (3, 3, ci, co),
                            jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros, (co,), jnp.float32)
                if self.use_bias else None)
        # house convention for dispatch targets (cf. _SplitStemConv):
        # dtype=None computes in f32, as the plain nn.Conv path does
        with jax.named_scope("nearest_up2"):
            y = nearest_up2_conv(x, kernel, self.dtype or jnp.float32,
                                 self.pad_mode)
            if bias is not None:
                y = y + bias.astype(y.dtype)
        return y


class UpsampleConvLayer(nn.Module):
    """Optional nearest ×upsample → ReflectionPad → conv.
    Ref: networks.py:408-423."""

    features: int
    kernel_size: int
    stride: int = 1
    upsample: int = 0
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = normal_init()
    # "zero": Conv2d(padding=k//2) after the upsample (models/vqgan.py,
    # models/swinir.py). A k3 upsample=2 site of either mode follows
    # nearest_up2_engages; the subpixel form pads the low-res input with
    # the mode's ring (nearest_up2_conv)
    pad_mode: str = "reflect"

    @nn.compact
    def __call__(self, x):
        if (self.upsample == 2 and self.kernel_size == 3 and self.stride == 1
                and nearest_up2_engages(x, self.features)):
            # subpixel decomposition of upsample→conv (ExpandNetwork's two
            # upsamples, the pix2pixHD enhancer's and G1's last, SwinIR's
            # two — see _NearestUp2Conv)
            _count_form("nearest_up2")
            return _NearestUp2Conv(
                self.features, self.pad_mode, use_bias=self.use_bias,
                dtype=self.dtype, kernel_init=self.kernel_init,
                name="Conv_0",
            )(x)
        if self.upsample:
            x = upsample_nearest(x, self.upsample)
        pad = self.kernel_size // 2
        # ExpandNetwork's k9 head 32→3 lives HERE, not in ConvLayer
        # (networks.py:518-520)
        if self.pad_mode == "zero":
            x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
            return _routed_conv(self, x)
        return _reflect_padded_conv(self, x)
