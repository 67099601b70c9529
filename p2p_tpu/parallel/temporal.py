"""Temporal sequence parallelism — video clips sharded over the ``time``
mesh axis (BASELINE configs[4]: vid2vid 8-frame temporal discriminator).

The reference has no video path at all (SURVEY.md §5.7: no attention, no
sequence dim; this config is a requirement on the new framework). Frames are
the "sequence": an NTHWC clip is sharded ``P('data', 'time', 'spatial',
None, None)``, each device holds T/time_shards frames, and the temporal
discriminator's 3-D convs get their neighbor frames through the same
nearest-neighbor ppermute halo exchange ring attention uses for K/V blocks —
here exchanging *frames* instead of attention blocks.

Primitives:

- :func:`sharded_temporal_conv3d` — k_t×k_h×k_w conv on a T-sharded clip;
  one ppermute of k_t//2 boundary frames, then a local VALID conv.
- :func:`temporal_mean` — psum-mean over the time axis for per-clip losses.
- :func:`make_sharded_temporal_conv` — shard_map wrapper for global arrays.

Used by ``p2p_tpu.models.temporal_d.TemporalDiscriminator`` for its
sequence-parallel path.
"""

from __future__ import annotations

import functools

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P
from p2p_tpu.core.mesh import TIME_AXIS
from p2p_tpu.parallel.halo import halo_exchange

_DIMNUMS3D = ("NDHWC", "DHWIO", "NDHWC")


def sharded_temporal_conv3d(
    x: jax.Array,
    kernel: jax.Array,
    *,
    stride_hw: int = 1,
    axis_name: str = TIME_AXIS,
    edge_mode: str = "zero",
) -> jax.Array:
    """'Same'-in-T conv on a local NTHWC shard (inside shard_map).

    ``kernel`` is (kt, kh, kw, Cin, Cout). T gets halo frames from mesh
    neighbors (zero edges, matching torch Conv3d zero padding); H/W are
    zero-padded locally and may be strided.
    """
    kt, kh, kw = kernel.shape[0], kernel.shape[1], kernel.shape[2]
    x = halo_exchange(
        x, dim=1, halo=kt // 2, axis_name=axis_name, edge_mode=edge_mode
    )
    ph, pw = kh // 2, kw // 2
    dn = lax.conv_dimension_numbers(x.shape, kernel.shape, _DIMNUMS3D)
    return lax.conv_general_dilated(
        x,
        kernel,
        (1, stride_hw, stride_hw),
        [(0, 0), (ph, ph), (pw, pw)],
        dimension_numbers=dn,
    )


def temporal_mean(x: jax.Array, axis_name: str = TIME_AXIS) -> jax.Array:
    """Mean of a per-shard scalar over the time axis (inside shard_map)."""
    return lax.pmean(x, axis_name)


def make_sharded_temporal_conv(
    mesh: Mesh,
    *,
    stride_hw: int = 1,
    axis_name: str = TIME_AXIS,
):
    """shard_map wrapper: global NTHWC clip (T sharded) × kernel → global out."""
    spec_x = P(None, axis_name, None, None, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec_x, P()), out_specs=spec_x
    )
    def _fn(x, kernel):
        return sharded_temporal_conv3d(
            x, kernel, stride_hw=stride_hw, axis_name=axis_name
        )

    return _fn


def gather_frames(x: jax.Array, axis_name: str = TIME_AXIS) -> jax.Array:
    """all_gather the full clip onto every time-shard (escape hatch for
    global-T ops, e.g. a clip-level pooling head; O(T) memory)."""
    return lax.all_gather(x, axis_name, axis=1, tiled=True)
