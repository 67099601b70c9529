"""Single-pass BatchNorm moments (Σx, Σx²) as a Pallas TPU kernel.

Why: ``ops/norm.dual_moments`` lowers the two moments as ONE variadic
``lax.reduce`` — but the round-3/4 profiles show XLA's reduce kernel still
READS each operand separately (534 MB moved for a 268 MB activation on the
round-3 BatchNorm_12 kernel; re-measured unchanged in round 4 after the
variadic rewrite). The reference never had this problem to solve — torch's
cuDNN BatchNorm owns its fused stats pass (networks.py:433 BatchNorm2d);
this kernel is the TPU equivalent of that fusion, done by hand because the
compiler won't.

Shape contract: a 2-D ``(M, C)`` view of the activation (callers flatten
all leading axes). The grid streams M in row blocks; both f32 accumulators
live in the same revisited ``(1, C)`` output block — TPU grids execute
sequentially, so first-visit init + accumulate is race-free (same pattern
as instance_norm_kernel.py). The bf16→f32 convert and the square happen
in-register on the VMEM block: ONE read of x total.

Used by ``ops/norm.dual_moments`` when eligible (TPU backend, no >1-device
mesh in scope, M divisible into VMEM-sized blocks); the XLA path remains
the fallback and the numerics are identical (f32 accumulation in both).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pick_m_block(m: int, c: int, budget_bytes: int = 2 << 20) -> int:
    """Largest divisor of M whose padded (mb, C) input block fits VMEM.

    Sized against the PADDED tile (minor dims round up to (8, 128) f32 /
    (16, 128) bf16 tiles — see instance_norm_kernel._pick_h_block, which
    learned this the hard way on the 32-channel pix2pixHD preset)."""
    padded_c = -(-c // 128) * 128
    row_bytes = padded_c * 4  # f32 working copy dominates
    max_mb = max(1, budget_bytes // row_bytes)
    best = 1
    for mb in range(min(m, max_mb), 0, -1):
        if m % mb == 0:
            best = mb
            break
    return best


def _moments_kernel(x_ref, s1_ref, s2_ref):
    i = pl.program_id(0)
    xf = x_ref[...].astype(jnp.float32)
    s1 = jnp.sum(xf, axis=0, keepdims=True)
    s2 = jnp.sum(xf * xf, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = s1
        s2_ref[...] = s2

    @pl.when(i > 0)
    def _acc():
        s1_ref[...] += s1
        s2_ref[...] += s2


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def pallas_dual_moments(x2d: jax.Array, block_m: int,
                        interpret: bool = False):
    """(M, C) → ((C,) Σx, (C,) Σx²) in f32, one pass over x.

    ``interpret=True`` runs the kernel in Pallas interpret mode so the
    CPU test suite can pin its numerics against the XLA path."""
    m, c = x2d.shape
    out = jax.ShapeDtypeStruct((1, c), jnp.float32)
    s1, s2 = pl.pallas_call(
        _moments_kernel,
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[out, out],
        interpret=interpret,
    )(x2d)
    return s1[0], s2[0]


def eligible_block(x: jax.Array) -> int:
    """0 = use the XLA path; otherwise the row-block size to stream with.

    Eligibility: TPU backend, no multi-device mesh in trace scope (a
    pallas_call under GSPMD would force a gather of the sharded
    activation), at least 2 row blocks (otherwise the fusion can't beat
    XLA's single fused kernel), and a big enough tensor that the double
    read is worth saving (small activations are latency-bound either way).
    """
    from p2p_tpu.core.mesh import current_mesh

    if jax.default_backend() != "tpu":
        return 0
    mesh = current_mesh()
    # a Mosaic kernel outside a shard_map is refused in any multi-device
    # program, and with no mesh visible nothing says what this one spans
    if (mesh.size if mesh is not None else jax.device_count()) > 1:
        return 0
    if x.ndim < 2 or x.size < (1 << 20):
        return 0
    m = x.size // x.shape[-1]
    mb = _pick_m_block(m, x.shape[-1])
    if m // mb < 2 or mb < 256:
        return 0
    return mb
