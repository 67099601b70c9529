"""GSPMD spatial sharding — large images split along H over the ``spatial``
mesh axis (BASELINE configs[2] Cityscapes 512×256, configs[3] pix2pixHD
1024×512; pix2pixHD at the paper's 2048×1024 on data=2 × spatial=2 is the
one layout that has run on chips, PERF.md section 4).

Two complementary paths, per the scaling-book recipe ("annotate shardings,
let XLA insert collectives, profile, hand-optimize what's left"):

1. **GSPMD path (default).** Shard the batch ``P('data', 'spatial', None,
   None)`` and ``jit`` the whole train step. XLA's spatial partitioner
   inserts the conv halo exchanges itself — including for the stride-2
   encoder convs where manual index bookkeeping is error-prone. This is the
   production path; ``p2p_tpu.parallel.dp.make_parallel_train_step`` uses it
   for every preset.

2. **shard_map path (hand-optimized).** For the stride-1 ResidualBlock trunk
   (9 × k3 convs at 128ch — the FLOPs bulk of ExpandNetwork/ResnetGenerator,
   ref networks.py:472-480), :func:`sharded_conv2d` does one explicit
   nearest-neighbor ``ppermute`` halo exchange per conv and computes purely
   locally, guaranteeing no accidental resharding. Verified bitwise against
   the unsharded conv in tests/test_parallel.py.

Halo sizing: a stack of stride-1 convs with kernels k_i needs Σ (k_i // 2)
halo rows if exchanged once up front, or k//2 per conv if exchanged per-conv;
:func:`residual_block_sharded` exchanges once per conv (2 rows/block) which
keeps each message at ~W×128×4 bytes — latency-bound but overlappable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from p2p_tpu.core.mesh import SPATIAL_AXIS
from p2p_tpu.parallel.halo import halo_exchange

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def conv2d_local(
    x: jax.Array,
    kernel: jax.Array,
    *,
    stride: int = 1,
    w_pad_mode: str = "reflect",
) -> jax.Array:
    """Plain local conv, H already halo-padded; W padded locally (unsharded)."""
    pw = kernel.shape[1] // 2
    if pw:
        if w_pad_mode == "reflect":
            x = jnp.pad(x, ((0, 0), (0, 0), (pw, pw), (0, 0)), mode="reflect")
        elif w_pad_mode == "zero":
            x = jnp.pad(x, ((0, 0), (0, 0), (pw, pw), (0, 0)))
        elif w_pad_mode == "wrap":
            x = jnp.pad(x, ((0, 0), (0, 0), (pw, pw), (0, 0)), mode="wrap")
        else:
            raise ValueError(f"unknown w_pad_mode {w_pad_mode!r}")
    dn = lax.conv_dimension_numbers(x.shape, kernel.shape, _DIMNUMS)
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), "VALID", dimension_numbers=dn
    )


def sharded_conv2d(
    x: jax.Array,
    kernel: jax.Array,
    *,
    axis_name: str = SPATIAL_AXIS,
    edge_mode: str = "reflect",
) -> jax.Array:
    """Stride-1 'same' conv on an H-sharded NHWC shard (inside shard_map).

    One bidirectional ppermute of k//2 boundary rows, then a fully local
    VALID conv — the per-shard output rows exactly equal the corresponding
    slice of the unsharded conv output.
    """
    kh = kernel.shape[0]
    halo = kh // 2
    x = halo_exchange(x, dim=1, halo=halo, axis_name=axis_name,
                      edge_mode=edge_mode)
    return conv2d_local(x, kernel, stride=1, w_pad_mode=edge_mode)


def make_sharded_conv(
    mesh: Mesh,
    *,
    axis_name: str = SPATIAL_AXIS,
    edge_mode: str = "reflect",
):
    """Wrap :func:`sharded_conv2d` in shard_map over ``mesh`` for global
    NHWC arrays sharded along H. Returns ``fn(x_global, kernel) -> y_global``.
    """
    spec_x = P(None, axis_name, None, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec_x, P()),
        out_specs=spec_x,
    )
    def _fn(x, kernel):
        return sharded_conv2d(
            x, kernel, axis_name=axis_name, edge_mode=edge_mode
        )

    return _fn


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
