"""The window-attention kernel (``ops/pallas/window_attention.py``),
interpreted on the CPU, against the plain ``jnp`` statement of the same
function, and the rule that sends a call site to one or the other.

Shapes are the published ones for a window: T = 64 tokens, C = 180 = six
heads of 30, on ``2 * nW`` windows (two 16x16 images, nW = 4); parameters
are a seeded ``WindowAttention`` with its qkv kernel and bias table
widened as the cell's check widens them (``benchmark/drivers/train_sr.
WIDEN``: logits spread over units). The kernel is reached as the program
reaches it: through the module, with ``P2P_TPU_FORCE_PALLAS=1`` (what
``ops/pallas.kernel_dispatch`` reads on the CPU), which runs it
interpreted.

Tolerances: float32 operands, both sides float32 on the CPU: 1e-5 of the
scale forward, 1e-4 on gradients (sums in another order). bf16 operands:
the two forms round the same values at the same places except the
backward's ``dA`` (XLA's transposed einsum rounds it to bf16, the kernel
keeps float32): 1e-2 forward (two bf16 ulps of the scale), 2e-2 backward.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.models.swinir import (
    WindowAttention,
    relative_position_index,
    shift_mask,
)
from p2p_tpu.ops.pallas import window_attention as wa

WINDOW, HEADS, EMBED, EXTENT, IMAGES = 8, 6, 180, 16, 2
T = WINDOW * WINDOW
NW = (EXTENT // WINDOW) ** 2
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def force(monkeypatch, on: bool):
    monkeypatch.setenv("P2P_TPU_FORCE_PALLAS", "1" if on else "0")


def max_rel(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape, (got.shape, want.shape)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def widened(params):
    from benchmark.drivers.train_sr import WIDEN

    def wider(path, leaf):
        name = "attn/" + "/".join(str(k.key) for k in path)
        for suffix, factor in WIDEN:
            if name.endswith(suffix):
                return leaf * np.float32(factor)
        return leaf

    return jax.tree_util.tree_map_with_path(wider, params)


@pytest.fixture(scope="module")
def site():
    """A seeded, widened attention module's parameters, its windows and a
    cotangent (float32; a case casts)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((IMAGES * NW, T, EMBED)).astype(
        np.float32))
    params = WindowAttention(heads=HEADS, window=WINDOW).init(
        jax.random.key(0), x)["params"]
    return widened(params), x, jnp.asarray(
        rng.standard_normal(x.shape).astype(np.float32))


def module_outputs(site, dtype, shifted, **fields):
    """The module's output and the cotangents of its parameters and of
    ``x`` for one cotangent of the output."""
    params, x, ct = site
    module = WindowAttention(heads=HEADS, window=WINDOW,
                             dtype=DTYPES[dtype], **fields)
    mask = shift_mask(EXTENT, EXTENT, WINDOW) if shifted else None
    x = x.astype(DTYPES[dtype])
    out, vjp = jax.vjp(lambda p, v: module.apply({"params": p}, v, mask),
                       params, x)
    return out, vjp(ct.astype(out.dtype))


# ------------------------------------------- the kernel against XLA's chain


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shifted", [False, True],
                         ids=["unshifted", "shifted"])
def test_forward_against_the_xla_path(site, monkeypatch, shifted, dtype):
    force(monkeypatch, False)
    want, _ = module_outputs(site, dtype, shifted)
    assert wa.kernel_sites()["layers"] == 0
    force(monkeypatch, True)
    got, _ = module_outputs(site, dtype, shifted)
    # without a mask nothing ties a block to an image
    assert wa.kernel_sites() == {
        "layers": 1, "windows_per_block": NW if shifted else IMAGES * NW}
    assert got.dtype == want.dtype and float(jnp.std(
        want.astype(jnp.float32))) > 0.05
    assert max_rel(got, want) < (1e-5 if dtype == "float32" else 1e-2)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shifted", [False, True],
                         ids=["unshifted", "shifted"])
def test_cotangents_against_the_xla_path(site, monkeypatch, shifted, dtype):
    """qkv's (through its kernel and bias), the bias table's and x's."""
    force(monkeypatch, False)
    _, (want_p, want_x) = module_outputs(site, dtype, shifted)
    force(monkeypatch, True)
    _, (got_p, got_x) = module_outputs(site, dtype, shifted)
    tol = 1e-4 if dtype == "float32" else 2e-2
    assert max_rel(got_x, want_x) < tol
    flat = jax.tree_util.tree_flatten_with_path(want_p)[0]
    assert {jax.tree_util.keystr(p) for p, _ in flat} >= {
        "['qkv']['kernel']", "['relative_position_bias_table']"}
    for (path, want), got in zip(flat, jax.tree_util.tree_leaves(got_p)):
        assert float(jnp.max(jnp.abs(want))) > 0, path
        assert max_rel(got, want) < tol, jax.tree_util.keystr(path)


def function_operands(dtype=jnp.float32, seed=5):
    """qkv in XLA's layout and in the kernel's, a table, the index, the
    mask."""
    rng = np.random.default_rng(seed)
    qkv = rng.standard_normal((IMAGES * NW, T, 3 * EMBED)).astype(np.float32)
    qkv[..., :2 * EMBED] *= 1.6
    qkv = jnp.asarray(qkv).astype(dtype)
    table = jnp.asarray(rng.standard_normal(
        ((2 * WINDOW - 1) ** 2, HEADS)).astype(np.float32))
    return (qkv, wa.pad_heads(qkv, 3, HEADS), table,
            relative_position_index(WINDOW),
            shift_mask(EXTENT, EXTENT, WINDOW))


def merged(out):
    """The kernel's ``[B, T, Cp]`` result as ``[B, T, C]``."""
    d = EMBED // HEADS
    out = np.asarray(out, np.float32)
    assert not out[..., HEADS * wa.head_stride(d):].any()
    heads = out[..., :HEADS * wa.head_stride(d)].reshape(
        out.shape[:2] + (HEADS, wa.head_stride(d)))
    assert not heads[..., d:].any()
    return heads[..., :d].reshape(out.shape[:2] + (EMBED,))


@pytest.mark.parametrize("wb", [1, 2, 4])
def test_windows_a_block(wb):
    """The function itself at several block sizes (the grid's two axes,
    the mask's block index, the bias cotangent summed across the grid)."""
    qkv, padded, table, index, mask = function_operands()
    d = EMBED // HEADS

    def plain(q, tb):
        return wa.window_attention(q, tb, index, mask, HEADS)

    def fused(q, tb):
        return wa.window_attention_fused(q, tb, index, mask, HEADS, d, wb,
                                         interpret=True)

    assert max_rel(merged(fused(padded, table)), plain(qkv, table)) < 1e-5
    w = jnp.asarray(np.random.default_rng(6).standard_normal(
        (IMAGES * NW, T, EMBED)).astype(np.float32))
    want = jax.grad(lambda q, tb: jnp.vdot(plain(q, tb), w), (0, 1))(
        qkv, table)
    got = jax.grad(lambda q, tb: jnp.vdot(
        fused(wa.pad_heads(q, 3, HEADS), tb), wa.pad_heads(w, 1, HEADS)),
        (0, 1))(qkv, table)
    for a, b in zip(got, want):
        assert max_rel(a, b) < 1e-4


def test_the_layout_puts_zeros_after_every_head():
    x = jnp.arange(2 * 3 * 24, dtype=jnp.float32).reshape(2, 72) + 1.0
    y = np.asarray(wa.pad_heads(x, 3, 2))        # 3 groups, 2 heads of 12
    assert wa.head_stride(12) == 16 and wa.group_width(2, 12) == 128
    assert y.shape == (2, 3 * 128)
    for g in range(3):
        for h in range(2):
            at = g * 128 + h * 16
            np.testing.assert_array_equal(
                y[:, at:at + 12], np.asarray(x)[:, (2 * g + h) * 12:][:, :12])
            assert not y[:, at + 12:at + 16].any()
        assert not y[:, g * 128 + 32:(g + 1) * 128].any()
    assert wa.head_stride(30) == 32 and wa.group_width(6, 30) == 256


# ------------------------------------------------------------- precision


def test_the_kernels_softmax_is_float32():
    """One layer's attention output, given bf16-rounded q, k, v, against
    the float32 statement of the function on those same operands: the
    kernel lies within 1.02x of the XLA path's own error (the two read
    1.0000 apart here and compiled on the chip, PERF.md section 6), and
    the kernel body with its softmax's intermediates kept in bfloat16
    (2.4x) is refused by the same limit, 1.5x."""
    qkv, padded, table, index, mask = function_operands(jnp.bfloat16)
    d = EMBED // HEADS
    wb = wa.block_windows(qkv.shape[0], T, HEADS, d, qkv.dtype, NW)
    truth = np.asarray(wa.window_attention(
        qkv.astype(jnp.float32), table, index, mask, HEADS))
    error = lambda out: float(np.abs(  # noqa: E731
        np.asarray(out, np.float32) - truth).mean())
    xla = error(wa.window_attention(qkv, table, index, mask, HEADS))
    kernel = error(merged(wa.window_attention_fused(
        padded, table, index, mask, HEADS, d, wb, interpret=True)))
    narrow = error(merged(wa.window_attention_fused(
        padded, table, index, mask, HEADS, d, wb, interpret=True,
        softmax_dtype=jnp.bfloat16)))
    assert float(np.abs(truth).mean()) > 0.3 and xla > 1e-4
    assert kernel < 1.02 * xla, (kernel, xla)
    assert narrow > 1.5 * xla, (narrow, xla)
    # the XLA path's own control is refused by it too
    assert error(wa.window_attention(qkv, table, index, mask, HEADS,
                                     jnp.bfloat16)) > 1.5 * xla


# ------------------------------------------------------ when it engages


@pytest.mark.parametrize("case", ["tokens_not_a_tile", "float16",
                                  "half_an_image", "head_over_a_tile"])
def test_a_shape_the_kernel_refuses_keeps_xla(monkeypatch, case):
    force(monkeypatch, True)
    window, heads, embed, b, dtype, mask_windows = {
        "tokens_not_a_tile": (3, 2, 24, 4, jnp.float32, None),
        "float16": (4, 2, 24, 4, jnp.float16, None),
        "half_an_image": (4, 2, 24, 6, jnp.float32, 4),
        "head_over_a_tile": (4, 1, 256, 4, jnp.float32, None)}[case]
    t = window * window
    assert wa.block_windows(b, t, heads, embed // heads, dtype,
                            mask_windows) == 0
    if case == "half_an_image":
        return          # no module can be asked for it: its reshape fails
    module = WindowAttention(heads=heads, window=window, dtype=dtype)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (b, t, embed)).astype(np.float32))
    params = module.init(jax.random.key(0), x)["params"]
    jaxpr = str(jax.make_jaxpr(
        lambda p, v: module.apply({"params": p}, v))(params, x))
    assert "pallas_call" not in jaxpr
    assert wa.kernel_sites()["layers"] == 0
    force(monkeypatch, False)
    want = module.apply({"params": params}, x)
    force(monkeypatch, True)
    np.testing.assert_array_equal(
        np.asarray(module.apply({"params": params}, x), np.float32),
        np.asarray(want, np.float32))


@pytest.mark.parametrize("mesh", ["none", "data=1", "data=2"])
def test_a_program_over_several_devices_keeps_xla(mesh, monkeypatch):
    """A Mosaic call is not GSPMD's to partition: under a visible mesh of
    more than one device, or with no mesh in a process of several devices
    (this one has eight), no kernel is taken (``ops/pallas.spans_devices``,
    the rule the norm kernels ask too); the interpreted one is plain XLA
    ops, and only a visible mesh of several keeps it out."""
    from p2p_tpu.core.mesh import make_mesh, mesh_context, parse_mesh_arg
    from p2p_tpu.ops.pallas import spans_devices

    assert jax.device_count() > 1
    shape = (IMAGES * NW, WINDOW * WINDOW, EMBED)
    force(monkeypatch, True)
    plan = lambda: wa.kernel_plan(  # noqa: E731
        shape, HEADS, jnp.bfloat16, None, jnp.float32)
    if mesh == "none":
        assert spans_devices(False) and not spans_devices(True)
        assert plan() == (IMAGES * NW, True)
        return
    spec = parse_mesh_arg(mesh)
    with mesh_context(make_mesh(spec, devices=jax.devices()[:spec.data])):
        several = mesh == "data=2"
        assert spans_devices(False) == spans_devices(True) == several
        assert plan() == ((0, False) if several else (IMAGES * NW, True))


def test_a_narrower_softmax_never_reaches_the_kernel(site, monkeypatch):
    """``softmax_dtype=bfloat16`` and the ``bf16_softmax`` control of
    ``benchmark/tools/control_sr.py`` keep the plain statement, forced or
    not."""
    from benchmark.drivers import train_sr
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.utils.images import dummy_batch

    force(monkeypatch, True)
    params, x, _ = site
    module = WindowAttention(heads=HEADS, window=WINDOW,
                             softmax_dtype=jnp.bfloat16)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda p, v: module.apply({"params": p}, v))(params, x))
    assert wa.kernel_sites()["layers"] == 0
    cfg = get_preset("swinir_realsr_x4")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=60, n_blocks=1, ndf=8),
        data=dataclasses.replace(cfg.data, image_size=64, batch_size=2))
    state = abstract_train_state(cfg)
    batch = dummy_batch(cfg, (2,), abstract=True)
    for control, calls in (("bf16_softmax", 0), ("", 6)):
        jaxpr = str(jax.make_jaxpr(train_sr.program_generator_path(
            cfg, jnp.bfloat16, control))(state, batch))
        # (a call a layer of ONE jitted kernel: its body is printed once)
        assert len(re.findall(r"name=_forward\b", jaxpr)) == calls, control
        assert ("pallas_call" in jaxpr) == bool(calls)
        assert wa.kernel_sites()["layers"] == calls


def test_the_presets_step_on_the_cpu_and_forced(monkeypatch):
    """The published preset's whole train step at the cell's sizes (36
    layers, batch 4, 64x64 -> 256x256; traced, nothing compiled): on the
    CPU its jaxpr holds no ``pallas_call`` and the gauges read 0; with the
    kernel forced every layer's attention is one call forward and one
    backward, 16 windows a block. (That the CPU step's lowered text is
    the parent's: ``scripts/step_program_hash.py --xla_path``, PERF.md
    section 6.)"""
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.models.registry import generator_trace_gauges
    from p2p_tpu.train.step import build_train_step
    from p2p_tpu.utils.images import dummy_batch

    cfg = get_preset("swinir_realsr_x4")
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, batch_size=4),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0))
    assert cfg.train.mixed_precision and cfg.input_hw == (64, 64)
    state = abstract_train_state(cfg)
    batch = dummy_batch(cfg, (4,), abstract=True)
    for on, calls, block in ((False, 0, 0), (True, 36, 16)):
        force(monkeypatch, on)
        jax.clear_caches()     # the environment is no part of a trace's key
        jaxpr = str(jax.make_jaxpr(build_train_step(
            cfg, train_dtype=jnp.bfloat16, jit=False))(state, batch))
        # the layers call ONE jitted kernel a direction and a mask
        assert len(re.findall(r"name=_forward\b", jaxpr)) == calls
        assert len(re.findall(r"name=_backward\b", jaxpr)) == calls
        for name in ("window_attention_fwd", "window_attention_bwd"):
            assert (name in jaxpr) == on
        assert ("pallas_call" in jaxpr) == on
        assert generator_trace_gauges(cfg.model) == {
            "swinir_attn_kernel_layers": float(calls),
            "swinir_attn_kernel_windows_per_block": float(block)}
    assert generator_trace_gauges(get_preset("reference").model) == {}
