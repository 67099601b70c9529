"""Generic halo exchange over a named mesh axis.

The reference has no distributed layer (SURVEY.md §2.3); its "long-context"
analogue is large spatial extent / video length (SURVEY.md §5.7). The
primitive both need is the same: each shard of a spatially- or
temporally-split tensor must see ``halo`` rows/frames owned by its mesh
neighbors before a convolution can produce its local slice of the output.

This module implements that exchange with a single bidirectional
``jax.lax.ppermute`` pair — nearest-neighbor traffic that rides the ICI
torus links (the mesh is laid out so ``spatial``/``time`` are the innermost
axes — see ``p2p_tpu.core.mesh.make_mesh``). It is meant to be called
*inside* a ``jax.shard_map`` region, where ``x`` is the local shard.

Edge policy matches the conv padding being reproduced:

- ``"reflect"`` — outermost shards reflect their own rows, reproducing the
  framework's ReflectionPad convs (ref networks.py:395-405) exactly.
- ``"zero"``    — zero padding (PatchGAN convs, temporal conv boundaries).
- ``"wrap"``    — periodic; the raw ppermute ring result.

(shard_map outputs must be shape-uniform across shards, so a VALID-style
"no outer padding" mode is not expressible here — callers wanting VALID
convs slice the edge shards' output instead.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _take(x: jax.Array, start: int, size: int, dim: int) -> jax.Array:
    return lax.slice_in_dim(x, start, start + size, axis=dim)


def halo_exchange(
    x: jax.Array,
    *,
    dim: int,
    halo: int,
    axis_name: str,
    edge_mode: str = "reflect",
) -> jax.Array:
    """Pad the local shard with ``halo`` neighbor rows on both sides of ``dim``.

    Must be called inside ``shard_map`` with ``x`` sharded over ``axis_name``
    along ``dim``. Returns the local shard grown by ``2*halo`` along ``dim``
    (edge shards included — their outer halo is synthesized per
    ``edge_mode``).
    """
    if halo == 0:
        return x
    if x.shape[dim] < halo + 1:
        raise ValueError(
            f"local shard extent {x.shape[dim]} along dim {dim} too small for "
            f"halo {halo} (need at least halo+1 rows per shard)"
        )
    # one scope for every explicit exchange: device time is joined to it
    # as to the nets' scopes (docs/OBSERVABILITY.md)
    with jax.named_scope("halo"):
        return _exchange(x, dim, halo, axis_name, edge_mode)


def _exchange(x, dim, halo, axis_name, edge_mode):
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)

    lo_rows = _take(x, 0, halo, dim)                      # my first rows
    hi_rows = _take(x, x.shape[dim] - halo, halo, dim)    # my last rows

    fwd = [(i, (i + 1) % n) for i in range(n)]            # i sends to i+1
    bwd = [(i, (i - 1) % n) for i in range(n)]            # i sends to i-1
    from_prev = lax.ppermute(hi_rows, axis_name, fwd)     # prev's last rows
    from_next = lax.ppermute(lo_rows, axis_name, bwd)     # next's first rows

    if edge_mode == "wrap":
        lo_halo, hi_halo = from_prev, from_next
    elif edge_mode == "zero":
        zeros = jnp.zeros_like(from_prev)
        lo_halo = jnp.where(idx == 0, zeros, from_prev)
        hi_halo = jnp.where(idx == n - 1, zeros, from_next)
    elif edge_mode == "reflect":
        # Global ReflectionPad(p): top halo of the whole image is rows
        # p..1 reversed — fully owned by shard 0, so synthesized locally.
        lo_reflect = jnp.flip(_take(x, 1, halo, dim), axis=dim)
        hi_reflect = jnp.flip(
            _take(x, x.shape[dim] - 1 - halo, halo, dim), axis=dim
        )
        lo_halo = jnp.where(idx == 0, lo_reflect, from_prev)
        hi_halo = jnp.where(idx == n - 1, hi_reflect, from_next)
    else:
        raise ValueError(f"unknown edge_mode {edge_mode!r}")

    return jnp.concatenate([lo_halo, x, hi_halo], axis=dim)


def ring_shift(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Cyclically shift shards around the mesh axis ring (ppermute).

    The building block for ring-style pipelines (the conv-GAN equivalent of
    ring attention's block rotation): after ``axis_size`` shifts every shard
    has seen every block.
    """
    n = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)
