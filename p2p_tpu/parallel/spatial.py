"""Spatial sharding — large images split along H over the ``spatial``
mesh axis (BASELINE configs[2] Cityscapes 512×256, configs[3] pix2pixHD
1024×512; pix2pixHD at the paper's 2048×1024 on data=2 × spatial=2 is the
one layout that has run on chips, PERF.md section 4).

The batch is laid out ``P(('data', 'fsdp'), 'spatial', None, None)`` and
the whole train step is one ``jit`` (``parallel.dp.make_parallel_train_step``).
Who partitions what inside it, per the scaling-book recipe ("annotate
shardings, let XLA insert collectives, profile, hand-optimize what's left"):

1. **GSPMD** partitions every op whose input and output rows split alike:
   the zero-padded (``SAME``) convolutions of the discriminators and of
   VGG19 with the halo exchanges XLA inserts itself, the pools, every
   elementwise pass, the losses and the optimizers.

2. **One ``shard_map`` a reflect-padded convolution** (:func:`halo_conv`;
   every ``ops/conv.ConvLayer`` / ``UpsampleConvLayer`` site of a generator
   under a mesh whose ``spatial`` axis is above 1, chosen by
   ``ops/conv.halo_conv_mesh`` from the input's shape and the visible
   mesh). The reflect-padded tensor has H + 2p rows, which do not split
   like the activation's H, so GSPMD re-windowed every such layer (pads to
   an even extent, dynamic slices, whole-tensor layout copies, several
   collective-permutes a layer and a two-stage gradient all-reduce;
   PERF.md section 6, PR 35). Here each shard exchanges ``k // 2`` rows
   with its neighbours (``parallel.halo.halo_exchange``: one ``ppermute``
   pair, reflected rows at the image's two edges), pads W itself and runs
   a local ``VALID`` convolution: the shard's output rows are exactly its
   slice of the unsharded layer's, stride 1 or 2, and GSPMD never sees
   the padded tensor. tests/test_spatial4.py holds values, gradients and
   the compiled text's collectives against the unsharded layer.

The Pallas norm kernels wrap themselves in a ``shard_map`` of the same
layout (``ops/pallas/instance_norm``), and a bare ``reflect_pad_2d`` of two
rows or more builds its halo the same way (``ops/conv``).
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2p_tpu.core.mesh import BATCH_AXES, SPATIAL_AXIS
from p2p_tpu.parallel.halo import halo_exchange

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def halo_conv(
    x: jax.Array,
    kernel: jax.Array,
    mesh: Mesh,
    *,
    stride: int = 1,
    block: int = 0,
) -> jax.Array:
    """ReflectionPad(k // 2) + ``VALID`` conv of the global NHWC ``x``,
    laid out ``P((data, fsdp), spatial, None, None)`` on ``mesh``, with
    the replicated HWIO ``kernel`` (k odd): one ``shard_map``. Returns the
    layer's global output in the same layout.

    A shard's local rows have to exceed ``k // 2`` and divide by
    ``stride``: with a symmetric halo of ``k // 2`` rows the local
    ``VALID`` conv then gives ``rows / stride`` output rows, the shard's
    own slice of the unsharded output (for k3 stride 2 an even number of
    local rows). W is not sharded and is padded locally, with the reflect
    pad's one-pass backward (``ops/conv.reflect_pad_w``); H's backward is
    the transpose of the ``ppermute`` pair and the concatenate. ``block``
    > 0 runs the local conv on blocks of pixels (``ops/conv.blocked_conv``:
    the thin k7 / k9 stems and heads), stride 1.

    The kernel enters replicated (``P()``) and in the dtype it is given
    in: the transpose sums its cotangent with ONE ``psum`` over every
    mesh axis the input varies over, in that dtype. Cast it to the
    compute dtype OUTSIDE (``ops/conv.HaloConv`` does), so a bf16 step
    all-reduces bf16 gradients as it does elsewhere."""
    from p2p_tpu.ops.conv import blocked_conv, reflect_pad_w

    pad = kernel.shape[0] // 2

    def local(xl, w):
        xl = halo_exchange(xl, dim=1, halo=pad, axis_name=SPATIAL_AXIS,
                           edge_mode="reflect")
        xl = reflect_pad_w(xl, pad)
        if block:
            with jax.named_scope("blocked_conv"):
                return blocked_conv(xl, w, block)
        return lax.conv_general_dilated(
            xl, w, (stride, stride), "VALID", dimension_numbers=_DIMNUMS)

    spec = P(BATCH_AXES, SPATIAL_AXIS, None, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, P()),
                         out_specs=spec)(x, kernel)


def spatial_activation_sharding(mesh: Mesh) -> NamedSharding:
    """NHWC activations: H over the spatial axis (batch replicated)."""
    return NamedSharding(mesh, P(None, SPATIAL_AXIS, None, None))


def check_spatial_divisible(h: int, mesh: Mesh, n_downsamples: int = 2) -> None:
    """Validate that H stays divisible by the spatial axis through the
    generator's stride-2 encoder (deepest feature map must still split)."""
    n_shards = mesh.shape[SPATIAL_AXIS]
    deepest = h >> n_downsamples
    if deepest % n_shards:
        raise ValueError(
            f"image height {h} → deepest feature height {deepest} is not "
            f"divisible by spatial={n_shards}"
        )
