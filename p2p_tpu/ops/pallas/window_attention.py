"""Window attention on windows already split (Swin's ``WMSA`` between its
qkv and proj linear layers), twice: the plain ``jnp`` statement of the
function, and ONE Pallas call a direction that keeps everything of shape
``[.., T, T]`` in VMEM.

    q, k, v = split(qkv), ``heads`` heads of d = C / heads channels
    A = softmax(q k^T * d^-0.5 + B + M),  B = table[index] as [heads, T, T],
        M = mask[window mod nW] (a shifted layer) or 0
    out = merge_heads(A v)                                 [B, T, C]

Products read operands in ``qkv``'s dtype and sum in float32 (float32
operands: at HIGHEST precision in the kernel); the logits with bias and
mask, the row max, exp, sum and quotient are float32; the probabilities
are rounded to ``qkv``'s dtype for A v, and so is the result.

:func:`window_attention` is what XLA runs today (five or six passes over
the float32 ``[B, heads, T, T]`` logits a layer each way, and the head
split as transposing copies). :func:`window_attention_fused` is the
kernel, a ``jax.custom_vjp``, on a layout of its own (:func:`pad_heads`):
q, k, v and the result are groups of whole lane tiles, each head's ``d``
channels followed by zeros up to a power of two, so a lane tile holds
whole heads (four heads of 30 -> 32 in the first tile of a 256-wide
group, two in the second) and XLA hands such tensors over without a
transposing copy. The zero columns are put into the qkv and proj kernels
at apply time (``models/swinir.Dense``); parameters keep their shapes.

- A grid step takes ``Wb`` windows of ``qkv`` (``[Wb, T, 3 Cp]``) and
  loops over them. The heads of a lane tile go through the MXU at once,
  TRANSPOSED: with ``Qm`` = one copy of the tile's q a head, stacked
  along the rows, copy i zeroed outside head i's lanes, ``S^T = k Qm^T``
  is ``[T, heads * T]``: the keys along the sublanes, (head, query) along
  the lanes, 384 = three full lane tiles at the published sizes. No head
  is ever sliced out of the lanes, the softmax's reductions run down the
  sublanes, and a row statistic is a ``[1, heads * T]`` row.
- ``(A^T)^T v`` gives each of a tile's heads its product with ALL of the
  tile's channels, ``[n * T, 128]``; zeroed outside the head's own lanes
  and summed over the row blocks it is the tile's part of the merged
  result. The masked products do as many times the multiply-adds of a
  head-by-head form as a tile has heads, on operands that fill the tiles.
- The backward saves ``qkv`` alone and recomputes the probabilities with
  the forward's own code, then ``dv = A^T dOm`` (``dOm`` the cotangent
  stacked as ``Qm``), ``dA^T = v dOm^T``, ``dS = A (dA - sum_k dA A)`` in
  float32, ``dq = fold((dS^T)^T k)``, ``dk = dS^T Qm``, the logits'
  cotangent times ``d^-0.5`` rounded to the operands' dtype as XLA's
  transposed einsum rounds it, and the bias's cotangent summed over the
  windows across the grid. The mask takes none.

Which of the two a layer runs is :func:`kernel_plan`'s answer, from what
the call site can observe; nothing configures it (docs/PERFORMANCE.md,
"How a form is chosen").
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what a grid step's blocks (both buffers of each) may take of VMEM; the
#: rest of ``_VMEM_LIMIT`` is the loop body's temporaries
_BLOCK_BUDGET = 12 * 2 ** 20
_VMEM_LIMIT = 48 * 2 ** 20
#: windows of the loop body the scheduler sees side by side
_UNROLL = 2

_NT = (((1,), (1,)), ((), ()))      # x y^T
_TN = (((0,), (0,)), ((), ()))      # x^T y


# ------------------------------------------------------ the plain statement


def stored(x: jax.Array, dtype) -> jax.Array:
    """``x`` as a program that keeps this tensor in ``dtype`` reads it back:
    float32 rounded to ``dtype``'s exponent and mantissa bits. By
    ``lax.reduce_precision``, which no compiler pass removes: a convert to
    bfloat16 and back inside a fusion is dropped on the TPU
    (``xla_allow_excess_precision``), so ``astype`` there rounds nothing
    (my chip run 2, PR 38: the bf16 softmax read as the float32 one to
    three digits, on the CPU 4.8x off)."""
    if dtype == jnp.float32:
        return x.astype(jnp.float32)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x.astype(jnp.float32), info.nexp,
                                    info.nmant)


def softmax(logits: jax.Array, dtype) -> jax.Array:
    """Softmax over the last axis in float32, or (a control) with every
    intermediate rounded to ``dtype``."""
    if dtype == jnp.float32:
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    z = stored(logits, dtype)
    z = stored(z - jnp.max(z, axis=-1, keepdims=True), dtype)
    e = stored(jnp.exp(z), dtype)
    return stored(e / stored(jnp.sum(e, axis=-1, keepdims=True), dtype),
                  dtype)


def relative_bias(table: jax.Array, index, heads: int) -> jax.Array:
    """``table[index]`` as ``[heads, T, T]``: the rows of the
    ``[entries, heads]`` table picked by a one-hot product (exact at
    HIGHEST precision; its transpose is a product too, where a gather's is
    a scatter-add). ``index``: ``[T, T]`` ints."""
    t = index.shape[0]
    idx = jnp.asarray(index).reshape(-1)
    onehot = (idx[:, None] == jnp.arange(table.shape[0])[None, :])
    picked = jnp.dot(onehot.astype(jnp.float32), table,
                     precision=jax.lax.Precision.HIGHEST)
    return picked.reshape(t, t, heads).transpose(2, 0, 1)


def window_attention(qkv: jax.Array, table: jax.Array, index,
                     mask: Optional[jax.Array], heads: int,
                     softmax_dtype=jnp.float32) -> jax.Array:
    """``[B, T, 3C]`` -> ``[B, T, C]`` as the module docstring states it,
    in plain ``jnp``; ``mask`` ``[nW, T, T]`` or None, window b reads row
    ``b mod nW``. ``softmax_dtype`` narrower than float32 is a control."""
    b, t, c3 = qkv.shape
    c = c3 // 3
    d = c // heads
    q, k, v = (qkv[..., i * c:(i + 1) * c].reshape(b, t, heads, d)
               for i in range(3))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (float(d) ** -0.5) + relative_bias(table, index,
                                                         heads)[None]
    if mask is not None:
        nw = mask.shape[0]
        logits = (logits.reshape(b // nw, nw, heads, t, t)
                  + mask[None, :, None]).reshape(b, heads, t, t)
    attn = softmax(logits, softmax_dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", attn.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype).reshape(b, t, c)


# ------------------------------------------------------ the kernel's layout


def head_stride(d: int) -> int:
    """Columns from one head to the next in the kernel's layout: ``d``
    channels and zeros up to a power of two, so that a lane tile holds
    whole heads."""
    return 1 << (d - 1).bit_length()


def group_width(heads: int, d: int) -> int:
    """Columns of one of q, k, v (and of the result) in the kernel's
    layout: ``heads`` heads a :func:`head_stride` apart, in whole lane
    tiles."""
    return -(-heads * head_stride(d) // 128) * 128


def pad_heads(x: jax.Array, groups: int, heads: int) -> jax.Array:
    """The last axis of ``x``, ``groups`` groups of ``heads`` heads of
    channels, in the kernel's layout: zeros after every head up to
    :func:`head_stride` and after every group up to :func:`group_width`."""
    d = x.shape[-1] // (groups * heads)
    x = x.reshape(x.shape[:-1] + (groups, heads, d))
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, head_stride(d) - d)])
    x = x.reshape(x.shape[:-2] + (heads * head_stride(d),))
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                + [(0, group_width(heads, d) - x.shape[-1])])
    return x.reshape(x.shape[:-2] + (groups * group_width(heads, d),))


# ------------------------------------------------------- when it engages


def block_windows(b: int, t: int, heads: int, d: int, dtype,
                  mask_windows: Optional[int]) -> int:
    """Windows a grid step of the kernel takes for ``b`` windows of ``t``
    tokens and ``heads`` heads of ``d`` channels in ``dtype``, or 0 for a
    shape it does not take: T a multiple of the dtype's sublane tile, a
    head no wider than a lane tile, whole images of windows, and the
    largest divisor of an image's windows (the mask's block index is then
    a function of the grid index) whose backward blocks fit
    :data:`_BLOCK_BUDGET`."""
    size = jnp.dtype(dtype).itemsize
    if (dtype not in (jnp.bfloat16, jnp.float32) or t % (32 // size)
            or d > 128):
        return 0
    per_image = mask_windows or b
    if b % per_image:
        return 0
    # the backward's blocks: qkv, its cotangent, the output's, a mask row
    window = t * (7 * group_width(heads, d) * size
                  + -(-2 * t // 128) * 128 * 4)
    cap = max(1, _BLOCK_BUDGET // (2 * window))
    return max(w for w in range(1, per_image + 1)
               if per_image % w == 0 and w <= cap)


def kernel_plan(shape, heads: int, dtype, mask: Optional[jax.Array],
                softmax_dtype) -> Tuple[int, bool]:
    """``(windows a block, interpret)`` for a call site whose windows are
    ``shape`` = ``[B, T, C]`` in ``dtype``; 0 windows: the chain stays
    XLA's. The kernel runs where ``ops/pallas.kernel_dispatch``
    says kernels run (the TPU backend; the CPU when a test forces it,
    interpreted), the softmax is float32 (a narrower one is a control and
    keeps the plain statement), the program is one device's
    (``ops/pallas.spans_devices``: over several the chain stays XLA's,
    which GSPMD partitions) and the shape is one :func:`block_windows`
    takes."""
    from p2p_tpu.ops import pallas as rule

    use, interpret = rule.kernel_dispatch()
    if (not use or softmax_dtype != jnp.float32
            or rule.spans_devices(interpret)):
        return 0, False
    b, t, c = shape
    return block_windows(b, t, heads, c // heads, dtype,
                         None if mask is None else mask.shape[0]), interpret


#: call site (a module's path) -> windows a block of its LAST trace in this
#: process, 0 for XLA's chain. A module cannot be handed a run's registry:
#: the Trainer reads this back (``kernel_sites``) right after a dispatch of
#: its own step traced, and at no other time (another trace of the same
#: modules, a float32 check or a control, writes here too)
_SITES: dict = {}


def note_site(site, windows: int) -> None:
    _SITES[tuple(site)] = int(windows)


def kernel_sites() -> dict:
    """How many call sites traced their attention through the kernel the
    last time each was traced in this process, and the windows a block
    they took (0 where none did)."""
    taken = [w for w in _SITES.values() if w]
    return {"layers": len(taken), "windows_per_block": max(taken, default=0)}


# ------------------------------------------------------------- the kernel


def _kept(x, dtype, interpret: bool):
    """A control's rounding of a softmax intermediate (float32: none).
    Mosaic keeps a convert to a narrower float and back; XLA, which runs
    the interpreted kernel, may not (:func:`stored`)."""
    if dtype == jnp.float32:
        return x
    return stored(x, dtype) if interpret else x.astype(dtype).astype(
        jnp.float32)


def _lane_tiles(heads: int, d: int):
    """``(first head, heads, first lane)`` of every lane tile of a group
    of channels."""
    per = 128 // head_stride(d)
    return [(h, min(per, heads - h), 128 * i)
            for i, h in enumerate(range(0, heads, per))]


def _own(n: int, d: int, t: int):
    """``[n * t, 128]`` bool: row block i holds the lanes of the tile's
    i-th head."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, 128), 1)
    dp = head_stride(d)
    return jnp.concatenate(
        [(lane >= i * dp) & (lane < (i + 1) * dp) for i in range(n)], axis=0)


def _stack(x, own):
    """Copies of ``x`` ``[T, 128]`` along the rows, one a head of the
    tile, copy i zeroed outside head i's lanes (exact in any dtype)."""
    rows = jnp.concatenate(
        [x.astype(jnp.float32)] * (own.shape[0] // x.shape[0]), axis=0)
    return jnp.where(own, rows, 0.0).astype(x.dtype)


def _fold(stacked, own, t: int):
    """``[n * T, 128]`` float32 -> ``[T, 128]``: row block i's own lanes."""
    return jnp.where(own, stacked, 0.0).reshape(-1, t, 128).sum(axis=0)


def _probabilities(k, qm, tiles, bias_t, mask_t, d: int, softmax_dtype,
                   interpret: bool):
    """``A^T`` ``[T, heads * T]`` float32 (keys down the sublanes): the
    logits a lane tile of channels at a time (a tile's heads meet no other
    tile's channels), then ``jax.nn.softmax``'s order down axis 0: less
    the max, exp, over the sum."""
    prec = (jax.lax.Precision.HIGHEST if k[0].dtype == jnp.float32
            else None)
    keep = functools.partial(_kept, dtype=softmax_dtype, interpret=interpret)
    s = jnp.concatenate([jax.lax.dot_general(
        k[j], qm[j], _NT, precision=prec,
        preferred_element_type=jnp.float32) for j in range(len(tiles))],
        axis=1)
    s = s * (float(d) ** -0.5) + bias_t
    if mask_t is not None:
        s = s + mask_t
    s = keep(s)
    z = keep(s - jnp.max(s, axis=0, keepdims=True))
    e = keep(jnp.exp(z))
    return keep(e / keep(jnp.sum(e, axis=0, keepdims=True)))


def _tiled_mask(mask_ref, w, width: int):
    if mask_ref is None:
        return None
    m = mask_ref[w]
    return jnp.concatenate([m] * (width // m.shape[-1]), axis=1)


def _each_window(wb: int, window, carry):
    """``window(w, carry)`` for the block's windows, :data:`_UNROLL` of them
    a loop step (Mosaic's own ``unroll`` is all or nothing)."""
    side = _UNROLL if wb % _UNROLL == 0 else 1

    def step(j, carry):
        for i in range(side):
            carry = window(j * side + i, carry)
        return carry

    return jax.lax.fori_loop(0, wb // side, step, carry)


def _forward_kernel(*refs, heads, d, wb, masked, softmax_dtype, interpret):
    qkv_ref, bias_ref = refs[:2]
    mask_ref = refs[2] if masked else None
    out_ref = refs[-1]
    t, c = out_ref.shape[1:]
    tiles = _lane_tiles(heads, d)
    own = [_own(n, d, t) for _, n, _ in tiles]
    bias_t = bias_ref[...]
    prec = (jax.lax.Precision.HIGHEST if qkv_ref.dtype == jnp.float32
            else None)

    def window(w, carry):
        q, k, v = ([qkv_ref[w, :, g * c + l:g * c + l + 128]
                    for _, _, l in tiles] for g in range(3))
        qm = [_stack(q[j], own[j]) for j in range(len(tiles))]
        p = _probabilities(k, qm, tiles, bias_t,
                           _tiled_mask(mask_ref, w, heads * t), d,
                           softmax_dtype, interpret).astype(qkv_ref.dtype)
        for j, (h, n, l) in enumerate(tiles):
            o = jax.lax.dot_general(p[:, h * t:(h + n) * t], v[j], _TN,
                                    precision=prec,
                                    preferred_element_type=jnp.float32)
            out_ref[w, :, l:l + 128] = _fold(o, own[j], t).astype(
                out_ref.dtype)
        return carry

    _each_window(wb, window, 0)


def _backward_kernel(*refs, heads, d, wb, masked, interpret):
    qkv_ref, bias_ref = refs[:2]
    mask_ref = refs[2] if masked else None
    dout_ref, dqkv_ref, dbias_ref = refs[-3:]
    t, c = dout_ref.shape[1:]
    tiles = _lane_tiles(heads, d)
    own = [_own(n, d, t) for _, n, _ in tiles]
    bias_t = bias_ref[...]
    dt = qkv_ref.dtype
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    dot = functools.partial(jax.lax.dot_general, precision=prec,
                            preferred_element_type=jnp.float32)
    nn = (((1,), (0,)), ((), ()))

    def window(w, dbias):
        q, k, v = ([qkv_ref[w, :, g * c + l:g * c + l + 128]
                    for _, _, l in tiles] for g in range(3))
        qm = [_stack(q[j], own[j]) for j in range(len(tiles))]
        p = _probabilities(k, qm, tiles, bias_t,
                           _tiled_mask(mask_ref, w, heads * t), d,
                           jnp.float32, interpret)
        pb = p.astype(dt)
        dom = [_stack(dout_ref[w, :, l:l + 128], own[j])
               for j, (_, _, l) in enumerate(tiles)]
        da = jnp.concatenate([dot(v[j], dom[j], _NT)
                              for j in range(len(tiles))], axis=1)
        ds = p * (da - jnp.sum(da * p, axis=0, keepdims=True))
        dsb = (ds * (float(d) ** -0.5)).astype(dt)
        for j, (h, n, l) in enumerate(tiles):
            cols = slice(h * t, (h + n) * t)
            dq = _fold(dot(dsb[:, cols], k[j], _TN), own[j], t)
            dk = dot(dsb[:, cols], qm[j], nn)
            dv = dot(pb[:, cols], dom[j], nn)
            for g, part in enumerate((dq, dk, dv)):
                dqkv_ref[w, :, g * c + l:g * c + l + 128] = part.astype(dt)
        return dbias + ds

    dbias = _each_window(wb, window,
                         jnp.zeros(dbias_ref.shape, jnp.float32))
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        dbias_ref[...] = dbias

    @pl.when(jnp.logical_not(first))
    def _():
        dbias_ref[...] += dbias


def _specs(qkv, bias_t, mask_t, wb: int):
    """Grid and the block specs of ``qkv``-shaped, bias and mask operands.
    The grid is (blocks of an image, images) with the images innermost, so
    a block of the mask is fetched once for all images."""
    b, t, c3 = qkv.shape
    per_image = b if mask_t is None else mask_t.shape[0]
    blocks = per_image // wb
    windows = lambda width: pl.BlockSpec(  # noqa: E731
        (wb, t, width), lambda j, n: (n * blocks + j, 0, 0))
    bias = pl.BlockSpec(bias_t.shape, lambda j, n: (0, 0))
    mask = [] if mask_t is None else [pl.BlockSpec(
        (wb, t, mask_t.shape[-1]), lambda j, n: (j, 0, 0))]
    return (blocks, b // per_image), windows, bias, mask


def _operands(qkv, bias_t, mask_t):
    return (qkv, bias_t) + (() if mask_t is None else (mask_t,))


# jitted: the layers of a program that call it alike share ONE traced and
# lowered kernel (a pallas_call is lowered again at every call site, 72 of
# them a step: ~10 s of every start, compile cache or not)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _forward(qkv, bias_t, mask_t, heads, d, wb, softmax_dtype, interpret):
    b, t, c3 = qkv.shape
    c = c3 // 3
    grid, windows, bias, mask = _specs(qkv, bias_t, mask_t, wb)
    return pl.pallas_call(
        functools.partial(_forward_kernel, heads=heads, d=d, wb=wb,
                          masked=mask_t is not None,
                          softmax_dtype=softmax_dtype, interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((b, t, c), qkv.dtype),
        grid=grid, in_specs=[windows(c3), bias] + mask,
        out_specs=windows(c),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="window_attention_fwd", interpret=interpret,
    )(*_operands(qkv, bias_t, mask_t))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _backward(qkv, bias_t, mask_t, dout, heads, d, wb, interpret):
    b, t, c3 = qkv.shape
    c = c3 // 3
    grid, windows, bias, mask = _specs(qkv, bias_t, mask_t, wb)
    return pl.pallas_call(
        functools.partial(_backward_kernel, heads=heads, d=d, wb=wb,
                          masked=mask_t is not None, interpret=interpret),
        out_shape=(jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct(bias_t.shape, jnp.float32)),
        grid=grid, in_specs=[windows(c3), bias] + mask + [windows(c)],
        out_specs=(windows(c3), bias),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="window_attention_bwd", interpret=interpret,
    )(*_operands(qkv, bias_t, mask_t), dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused(qkv, bias_t, mask_t, heads, d, wb, softmax_dtype, interpret):
    return _forward(qkv, bias_t, mask_t, heads, d, wb, softmax_dtype,
                    interpret)


def _fused_fwd(qkv, bias_t, mask_t, heads, d, wb, softmax_dtype, interpret):
    out = _forward(qkv, bias_t, mask_t, heads, d, wb, softmax_dtype,
                   interpret)
    return out, (qkv, bias_t, mask_t)


def _fused_bwd(heads, d, wb, softmax_dtype, interpret, saved, dout):
    qkv, bias_t, mask_t = saved
    dqkv, dbias_t = _backward(qkv, bias_t, mask_t, dout.astype(qkv.dtype),
                              heads, d, wb, interpret)
    return dqkv, dbias_t, (None if mask_t is None
                           else jnp.zeros_like(mask_t))


_fused.defvjp(_fused_fwd, _fused_bwd)


def window_attention_fused(qkv: jax.Array, table: jax.Array, index,
                           mask: Optional[jax.Array], heads: int, d: int,
                           wb: int, interpret: bool = False,
                           softmax_dtype=jnp.float32) -> jax.Array:
    """:func:`window_attention` as one Pallas call a direction, on the
    kernel's layout: ``qkv`` ``[B, T, 3 Cp]`` holds q, k and v as three
    groups of ``Cp`` = :func:`group_width` columns, every head's ``d``
    channels followed by zeros up to :func:`head_stride`, and the result
    is ``[B, T, Cp]`` with zeros in the same places (:func:`pad_heads`:
    ``models/swinir.Dense`` puts the zero columns into the two linear
    layers' kernels at apply time). A lane tile then holds whole heads, so
    every stacked product runs a lane tile of channels at a time on the
    heads that live there, and a lane-aligned minor axis is what lets XLA
    hand over and take back these tensors without a transposing copy
    (it lays ``[256, 64, 180]`` out windows-minor).
    ``wb`` windows a grid step (a divisor of the mask's windows, or of B
    without one). ``softmax_dtype`` narrower than float32 builds the
    control ``tests/test_window_attention.py`` holds the kernel's softmax
    against (forward only; :func:`kernel_plan` never asks for it)."""
    t = qkv.shape[1]
    # keys down the rows, (head, query) along the lanes
    bias_t = relative_bias(table, index, heads).transpose(2, 0, 1).reshape(
        t, heads * t)
    mask_t = None
    if mask is not None:
        # as many copies along the lanes as fill a lane tile (the kernel
        # repeats that over the heads), or one a head
        reps = 128 // math.gcd(t, 128)
        reps = reps if heads % reps == 0 else heads
        mask_t = jnp.tile(jnp.swapaxes(mask, 1, 2), (1, 1, reps))
    return _fused(qkv, bias_t, mask_t, heads, d, wb, softmax_dtype,
                  interpret)
