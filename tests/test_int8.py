"""int8 QAT conv path: exact parity with the float conv VJP on
integer-valued tensors (where symmetric quantization is lossless), plus
tolerance parity and param-tree compatibility of the flax drop-ins."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.ops.int8 import (
    QuantConv,
    QuantConvTranspose,
    absmax_scale,
    int8_conv,
    quantize_int8,
)

DN = ("NHWC", "HWIO", "NHWC")


def _grid_ints(rng, shape, scale=1.0, channel_axis=None):
    """Integer-valued tensor in [-127,127]·scale with ±127 present, so
    absmax quantization reproduces it exactly. ``channel_axis`` pins
    ±127 in EVERY slice along that axis (equal per-channel scales — the
    condition under which the folded dgrad cotangent stays on the
    integer grid, see ops/int8.py)."""
    v = rng.integers(-127, 128, size=shape).astype(np.float32)
    if channel_axis is None:
        v.flat[0] = 127.0
    else:
        idx = [0] * len(shape)
        idx[channel_axis] = slice(None)
        v[tuple(idx)] = 127.0
    return jnp.asarray(v * scale)


def _float_conv(x, w, strides, padding, lhs_dil=(1, 1)):
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, DN)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        lhs_dilation=lhs_dil, dimension_numbers=dn,
    )


CASES = [
    # (k, strides, padding, lhs_dil, H)
    (3, (1, 1), ((1, 1), (1, 1)), (1, 1), 9),
    (4, (2, 2), ((1, 1), (1, 1)), (1, 1), 12),
    (4, (2, 2), ((2, 2), (2, 2)), (1, 1), 13),   # odd input, ref padw=2
    (4, (1, 1), ((2, 2), (2, 2)), (1, 1), 9),
    (4, (1, 1), ((2, 2), (2, 2)), (2, 2), 6),    # transposed-conv form
    # outputs ≥ 16² positions: exercises the int8 dot_general wgrad
    # branch (ho·wo >= 256 guard in ops/int8.py), s1 and s2
    (3, (1, 1), ((1, 1), (1, 1)), (1, 1), 20),
    (4, (2, 2), ((1, 1), (1, 1)), (1, 1), 36),
]


@pytest.mark.parametrize("k,strides,padding,lhs_dil,H", CASES)
def test_int8_conv_exact_vs_float_on_integer_grids(k, strides, padding,
                                                   lhs_dil, H):
    rng = np.random.default_rng(0)
    x = _grid_ints(rng, (2, H, H, 8), scale=0.5)
    # equal per-channel absmax → the folded dgrad cotangent stays on the
    # integer grid too (see ops/int8.py docstring)
    w = _grid_ints(rng, (k, k, 8, 16), scale=0.25, channel_axis=3)

    y8 = int8_conv(x, w, strides, padding, lhs_dil)
    yf = _float_conv(x, w, strides, padding, lhs_dil)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(yf), rtol=1e-6)

    ct = _grid_ints(rng, yf.shape, scale=2.0)
    _, vjp8 = jax.vjp(lambda a, b: int8_conv(a, b, strides, padding, lhs_dil),
                      x, w)
    _, vjpf = jax.vjp(lambda a, b: _float_conv(a, b, strides, padding,
                                               lhs_dil), x, w)
    dx8, dw8 = vjp8(ct)
    dxf, dwf = vjpf(ct)
    np.testing.assert_allclose(np.asarray(dx8), np.asarray(dxf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dw8), np.asarray(dwf), rtol=1e-5)


def test_int8_conv_tolerance_on_random_normals():
    key = jax.random.key(0)
    x = jax.random.normal(key, (2, 16, 16, 32))
    w = jax.random.normal(jax.random.key(1), (4, 4, 32, 64)) * 0.1
    y8 = int8_conv(x, w, (2, 2), ((1, 1), (1, 1)))
    yf = _float_conv(x, w, (2, 2), ((1, 1), (1, 1)))
    rel = (jnp.linalg.norm(y8 - yf) / jnp.linalg.norm(yf)).item()
    assert rel < 0.02, rel


def test_quantize_roundtrip_and_scale_shapes():
    rng = np.random.default_rng(1)
    x = _grid_ints(rng, (3, 4, 4, 5), scale=0.125)
    s = absmax_scale(x)
    assert s.shape == ()
    np.testing.assert_allclose(
        np.asarray(quantize_int8(x, s), np.float32) * np.asarray(s),
        np.asarray(x), rtol=1e-6)
    sw = absmax_scale(x, axis=(0, 1, 2))
    assert sw.shape == (1, 1, 1, 5)


def test_spectral_conv_int8_close_and_same_power_iteration():
    """SpectralConv(int8=True): σ/u power iteration identical to bf16
    (it runs on the true f32 weight), conv output close."""
    from p2p_tpu.ops.spectral_norm import SpectralConv

    x = jax.random.normal(jax.random.key(0), (2, 16, 16, 32))
    ref = SpectralConv(features=48, kernel_size=4, stride=2, padding=2)
    q = SpectralConv(features=48, kernel_size=4, stride=2, padding=2,
                     int8=True)
    v = ref.init(jax.random.key(1), x)
    yr, sr = ref.apply(v, x, mutable=["spectral"])
    yq, sq = q.apply(v, x, mutable=["spectral"])
    np.testing.assert_allclose(
        np.asarray(sq["spectral"]["u"]), np.asarray(sr["spectral"]["u"]),
        rtol=1e-6)
    rel = (jnp.linalg.norm(yq - yr) / jnp.linalg.norm(yr)).item()
    assert rel < 0.03, rel


def test_quant_subpixel_deconv_matches_subpixel():
    """QuantSubpixelDeconv against its float form written out: conv(k2,
    s1, pad 1) to 4F channels + ``subpixel_interleave``, same params."""
    from flax import linen as nn

    from p2p_tpu.ops.conv import subpixel_interleave
    from p2p_tpu.ops.int8 import QuantSubpixelDeconv

    x = jax.random.normal(jax.random.key(0), (2, 8, 8, 16))
    ref = nn.Conv(4 * 12, (2, 2), padding=1)
    mod = QuantSubpixelDeconv(features=12)
    pr = {"params": {"Conv_0": ref.init(jax.random.key(1), x)["params"]}}
    p = mod.init(jax.random.key(1), x)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(pr)
    y = mod.apply(pr, x)
    yr = subpixel_interleave(ref.apply({"params": pr["params"]["Conv_0"]}, x),
                             12)
    assert y.shape == yr.shape == (2, 16, 16, 12)
    rel = (jnp.linalg.norm(y - yr) / jnp.linalg.norm(yr)).item()
    assert rel < 0.03, rel


@pytest.mark.parametrize("cls", [QuantConv, QuantConvTranspose])
def test_quant_modules_param_compat_and_close(cls):
    from flax import linen as nn

    x = jax.random.normal(jax.random.key(0), (2, 16, 16, 12))
    if cls is QuantConv:
        mod = QuantConv(features=24, kernel_size=4, strides=2, padding=1)
        ref = nn.Conv(24, (4, 4), strides=(2, 2), padding=1)
    else:
        mod = QuantConvTranspose(features=24, kernel_size=4, strides=2)
        ref = nn.ConvTranspose(24, (4, 4), strides=(2, 2), padding="SAME")
    p = mod.init(jax.random.key(1), x)
    pr = ref.init(jax.random.key(1), x)
    # identical param trees (names AND shapes) → checkpoints interchange
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(pr)
    assert [a.shape for a in jax.tree_util.tree_leaves(p)] == \
           [a.shape for a in jax.tree_util.tree_leaves(pr)]
    y = mod.apply(pr, x)          # same weights through both paths
    yr = ref.apply(pr, x)
    assert y.shape == yr.shape
    rel = (jnp.linalg.norm(y - yr) / jnp.linalg.norm(yr)).item()
    assert rel < 0.03, rel


def test_resnet_block_int8_param_compat_and_close():
    """ResnetBlock(int8=True): same param tree as bf16, close output —
    the k3-s1 trunk form used by cityscapes/pix2pixHD int8 generators."""
    from p2p_tpu.models.resnet_gen import ResnetBlock

    x = jax.random.normal(jax.random.key(0), (2, 16, 16, 32))
    ref = ResnetBlock(features=32, norm="instance")
    q = ResnetBlock(features=32, norm="instance", int8=True)
    v = ref.init(jax.random.key(1), x)
    vq = q.init(jax.random.key(1), x)
    assert (jax.tree_util.tree_structure(v) ==
            jax.tree_util.tree_structure(vq))
    yr = ref.apply(v, x)
    yq = q.apply(v, x)
    rel = (jnp.linalg.norm(yq - yr) / jnp.linalg.norm(yr)).item()
    assert rel < 0.03, rel


@pytest.mark.slow
@pytest.mark.parametrize("family", ["expand", "unet", "resnet"])
def test_int8_generator_families_train_one_step(family):
    """Every generator family accepts int8+int8_generator and takes one
    finite training step (the registry threading regression gate)."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = get_preset("reference" if family == "expand" else "facades")
    cfg = cfg.replace(
        model=dataclasses.replace(
            cfg.model, generator=family, int8=True, int8_generator=True,
            ngf=8, n_blocks=2, ndf=8, num_D=2, use_dropout=False,
            norm="instance" if family == "resnet" else cfg.model.norm),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32),
    )
    b = {k: jnp.asarray(v, jnp.float32)
         for k, v in synthetic_batch(2, 32, bits=cfg.model.quant_bits).items()}
    state = create_train_state(cfg, jax.random.key(0), b)
    step = build_train_step(cfg, None)
    state, m = step(state, b)
    assert np.isfinite(float(m["loss_g"])) and np.isfinite(float(m["loss_d"]))


# ------------------------------------------------------- delayed scaling
def test_int8_conv_ds_matches_dynamic_when_scale_agrees():
    """With sx = absmax(x)/127, the stored-scale conv must reproduce the
    dynamic path bitwise (fwd AND both grads), since the quantized
    operands are identical."""
    from p2p_tpu.ops.int8 import int8_conv_ds

    rng = np.random.default_rng(0)
    x = _grid_ints(rng, (2, 8, 8, 8))
    w = _grid_ints(rng, (4, 4, 8, 16), scale=1 / 127.0, channel_axis=3)
    sx = absmax_scale(x)

    def f_dyn(x, w):
        return jnp.sum(int8_conv(x, w, (2, 2), ((1, 1), (1, 1))) ** 2)

    def f_ds(x, w):
        y, amax = int8_conv_ds(x, w, sx, (2, 2), ((1, 1), (1, 1)))
        return jnp.sum(y ** 2), amax

    y_dyn, (gx_dyn, gw_dyn) = jax.value_and_grad(f_dyn, (0, 1))(x, w)
    (y_ds, amax), (gx_ds, gw_ds) = jax.value_and_grad(
        f_ds, (0, 1), has_aux=True)(x, w)
    np.testing.assert_array_equal(np.asarray(y_dyn), np.asarray(y_ds))
    np.testing.assert_array_equal(np.asarray(gx_dyn), np.asarray(gx_ds))
    np.testing.assert_array_equal(np.asarray(gw_dyn), np.asarray(gw_ds))
    assert float(amax) == float(jnp.max(jnp.abs(x)))


def test_quant_conv_delayed_updates_amax_and_clips_transiently():
    """The 'quant' collection carries amax_x: initialized from the init
    batch, decaying-max updated per mutable apply; a larger activation
    raises it immediately, a smaller one decays it by AMAX_DECAY."""
    from p2p_tpu.ops.int8 import AMAX_DECAY

    m = QuantConv(8, kernel_size=4, strides=2, padding=1, delayed=True)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 4)).astype(np.float32))
    v = m.init(jax.random.key(0), x)
    assert float(v["quant"]["amax_x"]) == pytest.approx(
        float(jnp.max(jnp.abs(x))), rel=1e-6)
    # apply on 2x-larger input: amax jumps to the new max
    y, mut = m.apply(
        {"params": v["params"], "quant": v["quant"]}, 2.0 * x,
        mutable=["quant"])
    assert float(mut["quant"]["amax_x"]) == pytest.approx(
        2 * float(jnp.max(jnp.abs(x))), rel=1e-6)
    # apply on tiny input: decays from the stored value, not collapse
    y, mut2 = m.apply(
        {"params": v["params"], "quant": mut["quant"]}, 0.01 * x,
        mutable=["quant"])
    assert float(mut2["quant"]["amax_x"]) == pytest.approx(
        AMAX_DECAY * float(mut["quant"]["amax_x"]), rel=1e-6)
    # read-only apply (eval) works without mutating
    m.apply({"params": v["params"], "quant": mut2["quant"]}, x)


def test_delayed_step_trains_and_threads_quant_state():
    """End-to-end: int8_delayed threads 'quant' through TrainState for G
    and D, scales move across steps, eval + non-delayed paths intact."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_eval_step, build_train_step

    cfg = get_preset("facades_int8")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, int8=True,
                                  int8_generator=True, int8_delayed=True),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )
    b = {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}
    state = create_train_state(cfg, jax.random.key(0), b, 1)
    assert jax.tree_util.tree_leaves(state.quant_d)
    assert jax.tree_util.tree_leaves(state.quant_g)
    amax_before = [float(a) for a in jax.tree_util.tree_leaves(state.quant_d)]
    step = build_train_step(cfg, None, 1, None, jit=True)
    state, m = step(state, b)
    state, m = step(state, {k: 3.0 * v for k, v in b.items()})
    assert np.isfinite(float(m["loss_g"]))
    amax_after = [float(a) for a in jax.tree_util.tree_leaves(state.quant_d)]
    assert amax_before != amax_after
    pred, em = build_eval_step(cfg, None)(state, b)
    assert np.isfinite(float(np.mean(np.asarray(em["psnr"]))))


# --------------------------------------- int8 multiscale discriminator
def _multi_d_cfg(int8=True):
    import dataclasses

    from p2p_tpu.core.config import get_preset

    cfg = get_preset("facades")
    return cfg.replace(
        model=dataclasses.replace(
            cfg.model, ngf=8, ndf=8, num_D=3, n_layers_D=3,
            use_spectral_norm=True, use_dropout=False,
            int8=int8, int8_delayed=int8),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )


def test_int8_multiscale_d_threads_quant_through_all_scales():
    """ISSUE 6 lever 1: the delayed-int8 path covers ALL THREE
    NLayerDiscriminators of the multiscale D — every scale's spectral-norm
    inner convs carry an amax in the 'quant' collection, and one training
    step moves scales on every scale (not just scale0)."""
    import jax.numpy as jnp

    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = _multi_d_cfg()
    b = {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}
    state = create_train_state(cfg, jax.random.key(0), b, 1)
    for s in range(3):
        assert f"scale{s}" in state.quant_d, sorted(state.quant_d)
        # n_layers=3 → 3 spectral inner convs per scale, each with amax_x
        leaves = jax.tree_util.tree_leaves(state.quant_d[f"scale{s}"])
        assert len(leaves) == 3, (s, len(leaves))
    before = {s: [float(a) for a in
                  jax.tree_util.tree_leaves(state.quant_d[f"scale{s}"])]
              for s in range(3)}
    step = build_train_step(cfg, None, 1, None)
    state, m = step(state, b)
    state, m = step(state, {k: 2.5 * v for k, v in b.items()})
    assert np.isfinite(float(m["loss_d"]))
    for s in range(3):
        after = [float(a) for a in
                 jax.tree_util.tree_leaves(state.quant_d[f"scale{s}"])]
        assert after != before[s], f"scale{s} amax never moved"


def test_int8_multiscale_d_frozen_scale_eval_bitwise():
    """The frozen-scale eval pin, D-side twin of the G-trunk/serving ones:
    with the 'quant' collection read-only (eval), the multiscale D forward
    is a pure function of its stored scales — two applies are BITWISE
    equal, and equal to the primal of the mutable (training) apply that
    proposed updates from the same scales."""
    import jax.numpy as jnp

    from p2p_tpu.models.registry import define_D

    cfg = _multi_d_cfg()
    d = define_D(cfg.model)
    rng = np.random.default_rng(3)
    pair = jnp.asarray(rng.uniform(-1, 1, (2, 32, 32, 6)), jnp.float32)
    v = d.init(jax.random.key(1), pair)
    assert "quant" in v and "spectral" in v
    dvars = {"params": v["params"], "spectral": v["spectral"],
             "quant": v["quant"]}

    train_out, mut = d.apply(dvars, pair, mutable=["spectral", "quant"])
    eval1 = d.apply(dvars, pair)
    eval2 = d.apply(dvars, pair)
    for a, b in zip(jax.tree_util.tree_leaves(eval1),
                    jax.tree_util.tree_leaves(eval2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(eval1),
                    jax.tree_util.tree_leaves(train_out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the training apply did propose scale updates (it is the one mutating)
    assert jax.tree_util.tree_leaves(mut["quant"])


def test_reshard_amax_law_pins():
    """The elastic TP-width amax resharding law (ops/int8.reshard_amax,
    driven by the ``tp_amax_recalibrate`` migration): per-tensor scalars
    are width-invariant; a per-shard [W] amax broadcasts on widen and
    max-reduces on narrow; widen-then-narrow round-trips BITWISE."""
    import jax.numpy as jnp

    from p2p_tpu.ops.int8 import reshard_amax

    # per-tensor (scalar) amax — the repo's amax_x form: identity at any
    # width pair (the stored jnp.max is a GLOBAL reduction under GSPMD)
    s = jnp.float32(3.75)
    for w_old, w_new in ((1, 2), (4, 2), (2, 8)):
        np.testing.assert_array_equal(
            np.asarray(reshard_amax(s, w_old, w_new)), np.asarray(s))

    # per-shard vector: widen 2 -> 4 broadcasts each shard to its children
    a2 = jnp.asarray([1.5, 7.25], jnp.float32)
    a4 = reshard_amax(a2, 2, 4)
    np.testing.assert_array_equal(
        np.asarray(a4), np.asarray([1.5, 1.5, 7.25, 7.25], np.float32))
    # ...then narrow 4 -> 2 max-reduces — the widen-then-narrow
    # round-trip reproduces the original per-shard scales bitwise
    np.testing.assert_array_equal(
        np.asarray(reshard_amax(a4, 4, 2)), np.asarray(a2))
    # narrow is an exact max of maxes
    a_uneven = jnp.asarray([2.0, 9.0, 4.0, 3.0], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(reshard_amax(a_uneven, 4, 2)),
        np.asarray([9.0, 4.0], np.float32))
    # indivisible widths fail loudly
    with pytest.raises(ValueError, match="divide"):
        reshard_amax(jnp.zeros((3,)), 3, 2)
    with pytest.raises(ValueError, match="divide"):
        reshard_amax(jnp.zeros((2,)), 2, 3)


def test_frozen_scale_eval_unchanged_by_amax_migration():
    """The TP-migration parity pin: the repo's stored scales are
    per-tensor (global-reduction amax), so the closed-form width remap is
    the identity on them — a frozen-scale eval AFTER a TP-width migration
    is BITWISE the pre-migration eval (strictly inside the existing
    frozen-scale parity band)."""
    import jax.numpy as jnp

    from p2p_tpu.models.registry import define_D
    from p2p_tpu.ops.int8 import reshard_amax

    cfg = _multi_d_cfg()
    d = define_D(cfg.model)
    rng = np.random.default_rng(5)
    pair = jnp.asarray(rng.uniform(-1, 1, (2, 32, 32, 6)), jnp.float32)
    v = d.init(jax.random.key(1), pair)
    migrated = jax.tree_util.tree_map(
        lambda a: reshard_amax(a, 2, 4), v["quant"])
    for a, b in zip(jax.tree_util.tree_leaves(v["quant"]),
                    jax.tree_util.tree_leaves(migrated)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    base = {"params": v["params"], "spectral": v["spectral"]}
    out_before = d.apply({**base, "quant": v["quant"]}, pair)
    out_after = d.apply({**base, "quant": migrated}, pair)
    for a, b in zip(jax.tree_util.tree_leaves(out_before),
                    jax.tree_util.tree_leaves(out_after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_int8_multiscale_d_lsgan_stability_band():
    """The LSGAN-stability parity band, D-side twin of the G-trunk one:
    training with the fully-quantized multiscale D tracks the f32-D run —
    same finite trajectories, D loss within a band of the float oracle
    over the run (quantization noise must not change the game's dynamics
    at this horizon)."""
    import jax.numpy as jnp

    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    def run(int8):
        cfg = _multi_d_cfg(int8=int8)
        b = {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}
        state = create_train_state(cfg, jax.random.key(0), b, 1)
        step = build_train_step(cfg, None, 1, None)
        losses = []
        for i in range(8):
            bi = {k: jnp.roll(v, i, axis=0) for k, v in b.items()}
            state, m = step(state, bi)
            losses.append({k: float(m[k]) for k in ("loss_d", "loss_g")})
        return losses

    qs, fs = run(True), run(False)
    for traj in (qs, fs):
        assert all(np.isfinite(list(r.values())).all() for r in traj), traj
    # parity band over the settled half of the run: mean |Δloss_d| within
    # 35% of the float level (int8 D is a different-but-close game)
    tail_q = np.mean([r["loss_d"] for r in qs[4:]])
    tail_f = np.mean([r["loss_d"] for r in fs[4:]])
    assert abs(tail_q - tail_f) <= 0.35 * max(abs(tail_f), 0.05), (
        tail_q, tail_f)


# ------------------------------------------- tiny-spatial wgrad on TPU
TINY_WGRAD_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from p2p_tpu.ops.int8 import int8_conv
# k4 s2 p1 on 4x4 and 2x2 inputs -> 2x2 and 1x1 outputs: the extents
# whose int8 strided-slice wgrad kernel-faulted an early v5e runtime,
# and that facades_int8_full's U-Net bottom reaches.
for hw in (4, 2):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, hw, hw, 8)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4, 8, 16)),
                    jnp.float32)
    def f(x, w):
        return jnp.sum(int8_conv(x, w, (2, 2), ((1, 1), (1, 1))) ** 2)
    gx, gw = jax.grad(f, (0, 1))(x, w)
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(gw)).all()
    print("OK", hw)
"""


@pytest.mark.slow
def test_tiny_spatial_wgrad_on_tpu():
    """Pins the ops/int8.py tiny-spatial int8 wgrad on REAL TPU hardware
    (invisible on the CPU backend this suite pins): the default dispatch
    sends 2x2- and 1x1-output wgrads down the int8 strided-slice path,
    which an early runtime kernel-faulted. It passed on the attached v5e
    (libtpu 0.0.34, PR 21), so there is no guard and no knob; if a future
    runtime regresses, this fails and ops/int8.py needs a lower bound
    again. Probes the chip from a CHILD of this CPU-pinned process (the
    parent never touches the device, so the child can have it)."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    if "tpu" not in probe.stdout:
        pytest.skip(f"no TPU visible outside the CPU-pinned suite "
                    f"(got {probe.stdout.strip()!r})")
    run = subprocess.run(
        [sys.executable, "-c", TINY_WGRAD_SNIPPET],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert run.returncode == 0, (
        "tiny-spatial int8 wgrad FAILED on this TPU runtime — the early "
        "kernel-fault may be back; restore a lower spatial bound in "
        f"ops/int8.py:\n{run.stderr[-2000:]}"
    )


# ----------------------------------------------- kn2row int8 (ISSUE 14)


KN2ROW_CASES = [
    # (k, pad, cin, cout, H) — cout·k² ≪ cin, the thin-head regime
    (4, 2, 32, 1, 9),       # the PatchGAN logits head's exact form
    (3, 1, 32, 2, 8),
    (2, 0, 16, 4, 6),
]


@pytest.mark.parametrize("k,pad,cin,cout,H", KN2ROW_CASES)
def test_int8_kn2row_exact_vs_float_on_integer_grids(k, pad, cin, cout, H):
    """ISSUE 14 (c): the s8×s8→s32 kn2row tap decomposition — forward
    AND both cotangents exactly reproduce the float kn2row VJP on
    integer-valued tensors (lossless quantization), per-form dispatch
    included (int8 fwd/wgrad, bf16 dgrad)."""
    from p2p_tpu.ops.conv import kn2row_thin_conv
    from p2p_tpu.ops.int8 import int8_kn2row_conv

    rng = np.random.default_rng(0)
    x = _grid_ints(rng, (2, H, H, cin), scale=0.5)
    w = _grid_ints(rng, (k, k, cin, cout), scale=0.25, channel_axis=3)

    y8 = int8_kn2row_conv(x, w, pad)
    yf = kn2row_thin_conv(x, w, pad)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(yf), rtol=1e-5)

    ct = _grid_ints(rng, yf.shape, scale=2.0)
    _, vjp8 = jax.vjp(lambda a, b: int8_kn2row_conv(a, b, pad), x, w)
    _, vjpf = jax.vjp(lambda a, b: kn2row_thin_conv(a, b, pad), x, w)
    dx8, dw8 = vjp8(ct)
    dxf, dwf = vjpf(ct)
    np.testing.assert_allclose(np.asarray(dx8), np.asarray(dxf), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dw8), np.asarray(dwf), rtol=1e-4)


def test_int8_kn2row_ds_matches_dynamic_when_scale_agrees():
    """The delayed kn2row form: with the stored scale set to THIS batch's
    amax/127 (what the dynamic path computes), outputs are bitwise equal
    and the measured amax is the true max|x| (the update proposal)."""
    from p2p_tpu.ops.int8 import int8_kn2row_conv, int8_kn2row_conv_ds

    rng = np.random.default_rng(1)
    x = _grid_ints(rng, (2, 9, 9, 32), scale=0.5)
    w = _grid_ints(rng, (4, 4, 32, 1), scale=0.25, channel_axis=3)
    sx = jnp.max(jnp.abs(x)) / 127.0
    y_dyn = int8_kn2row_conv(x, w, 2)
    y_ds, amax = int8_kn2row_conv_ds(x, w, sx, 2)
    np.testing.assert_array_equal(np.asarray(y_ds), np.asarray(y_dyn))
    assert float(amax) == float(jnp.max(jnp.abs(x)))


def test_kn2row_conv_module_int8_param_compat_and_delayed_amax():
    """KN2RowConv(int8=...): identical param tree to the bf16 kn2row
    module (checkpoints interchange), close output; the delayed form
    creates/updates an amax_x leaf in the 'quant' collection."""
    from p2p_tpu.ops.conv import KN2RowConv

    x = jax.random.normal(jax.random.key(0), (2, 8, 8, 32))
    ref = KN2RowConv(features=1, kernel_size=4, padding=2)
    q = KN2RowConv(features=1, kernel_size=4, padding=2, int8=True)
    v = ref.init(jax.random.key(1), x)
    assert jax.tree_util.tree_structure(
        q.init(jax.random.key(1), x)) == jax.tree_util.tree_structure(v)
    yr = ref.apply(v, x)
    yq = q.apply(v, x)
    rel = (jnp.linalg.norm(yq - yr) / jnp.linalg.norm(yr)).item()
    assert rel < 0.03, rel

    dq = KN2RowConv(features=1, kernel_size=4, padding=2, int8=True,
                    int8_delayed=True)
    vd = dq.init(jax.random.key(1), x)
    assert "quant" in vd and "amax_x" in vd["quant"]
    before = float(vd["quant"]["amax_x"])
    _, mut = dq.apply(vd, 2.0 * x, mutable=["quant"])
    assert float(mut["quant"]["amax_x"]) > before


def test_patchgan_int8_head_routes_kn2row_and_threads_quant():
    """int8_head: the D logits head rides the quantized kn2row path —
    its amax joins the 'quant' collection and moves — with the param
    tree unchanged vs the bf16 head."""
    from p2p_tpu.models.patchgan import NLayerDiscriminator

    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 6))
    kw = dict(ndf=8, n_layers=3, use_spectral_norm=False, int8=True,
              int8_delayed=True)
    ref = NLayerDiscriminator(**kw)
    hq = NLayerDiscriminator(**kw, int8_head=True)
    vr = ref.init(jax.random.key(1), x)
    vh = hq.init(jax.random.key(1), x)
    assert jax.tree_util.tree_structure(
        vr["params"]) == jax.tree_util.tree_structure(vh["params"])
    # the head conv (_PlainConv_4) gains an amax leaf under int8_head
    assert "_PlainConv_4" in vh["quant"]
    assert "_PlainConv_4" not in vr["quant"]
    _, mut = hq.apply(vh, 3.0 * x, mutable=["quant"])
    assert (float(mut["quant"]["_PlainConv_4"]["Conv_0"]["amax_x"])
            > float(vh["quant"]["_PlainConv_4"]["Conv_0"]["amax_x"]))


def test_unet_int8_stem_knob_param_compat():
    """int8_stem quantizes down0 (param tree unchanged); default keeps
    the measured-rejected bf16 stem (no amax leaf for down0)."""
    from p2p_tpu.models.unet import UNetGenerator

    x = jax.random.normal(jax.random.key(0), (1, 32, 32, 3))
    kw = dict(ngf=8, num_downs=5, int8=True, int8_delayed=True)
    ref = UNetGenerator(**kw)
    st = UNetGenerator(**kw, int8_stem=True)
    vr = ref.init(jax.random.key(1), x, train=False)
    vs = st.init(jax.random.key(1), x, train=False)
    assert jax.tree_util.tree_structure(
        vr["params"]) == jax.tree_util.tree_structure(vs["params"])
    assert "down0" in vs["quant"] and "down0" not in vr["quant"]


# ----------------------------------- quantize-fused epilogue (ISSUE 14)


def test_fused_epilogue_matches_unfused_bitwise():
    """int8_fused_epilogue (norm_d instance family + delayed int8): the
    [norm+LeakyReLU+quantize+amax]-fused D == the unfused module chain —
    logits and amax updates BITWISE (the CPU reference path quantizes
    the identical value), gradients within fp-reassociation noise (the
    closed-form norm VJP sums in a different order; the only visible
    divergence is on the norm-cancelled, mathematically-dead conv bias
    gradients)."""
    from p2p_tpu.models.patchgan import NLayerDiscriminator

    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 32, 32, 6)).astype(np.float32))
    kw = dict(ndf=8, n_layers=3, use_spectral_norm=False, int8=True,
              int8_delayed=True, norm="instance", int8_head=True)
    d_u = NLayerDiscriminator(**kw)
    d_f = NLayerDiscriminator(**kw, int8_fused_epilogue=True)
    vu = d_u.init(jax.random.key(0), x)
    vf = d_f.init(jax.random.key(0), x)
    assert jax.tree_util.tree_structure(vu) == \
        jax.tree_util.tree_structure(vf)
    for (pu, lu), (_, lf) in zip(
            jax.tree_util.tree_leaves_with_path(vu),
            jax.tree_util.tree_leaves_with_path(vf)):
        np.testing.assert_array_equal(np.asarray(lu), np.asarray(lf),
                                      err_msg=str(pu))
    ou, mu = d_u.apply(vu, x, mutable=["quant"])
    of, mf = d_f.apply(vf, x, mutable=["quant"])
    np.testing.assert_array_equal(np.asarray(ou[-1]), np.asarray(of[-1]))
    for (pu, lu), (_, lf) in zip(
            jax.tree_util.tree_leaves_with_path(mu),
            jax.tree_util.tree_leaves_with_path(mf)):
        np.testing.assert_array_equal(np.asarray(lu), np.asarray(lf),
                                      err_msg=str(pu))

    def loss(mod, v):
        def f(p):
            out, _ = mod.apply({**v, "params": p}, x, mutable=["quant"])
            return jnp.sum(out[-1].astype(jnp.float32) ** 2)
        return f

    gu = jax.grad(loss(d_u, vu))(vu["params"])
    gf = jax.grad(loss(d_f, vf))(vf["params"])
    for (pu, lu), (_, lf) in zip(
            jax.tree_util.tree_leaves_with_path(gu),
            jax.tree_util.tree_leaves_with_path(gf)):
        np.testing.assert_allclose(np.asarray(lu), np.asarray(lf),
                                   rtol=2e-4, atol=1e-4, err_msg=str(pu))

    # ...and through the FEATURE-MATCHING taps: the fused taps are the
    # dequantized surrogate by VALUE, but their cotangent must reach the
    # epilogue unscaled (ops/int8.surrogate_tap) — a plain q·sx tap
    # silently multiplied the FM gradients by sx (~amax/127 ≈ 0.03×),
    # which only a feats-side loss can see
    def fm_loss(mod, v):
        def f(p):
            out, _ = mod.apply({**v, "params": p}, x, mutable=["quant"])
            return sum(jnp.sum(t.astype(jnp.float32) ** 2) for t in out)
        return f

    gu = jax.grad(fm_loss(d_u, vu))(vu["params"])
    gf = jax.grad(fm_loss(d_f, vf))(vf["params"])
    for (pu, lu), (_, lf) in zip(
            jax.tree_util.tree_leaves_with_path(gu),
            jax.tree_util.tree_leaves_with_path(gf)):
        nu = float(jnp.linalg.norm(lu))
        nf = float(jnp.linalg.norm(lf))
        # skip the norm-cancelled dead-bias leaves: their gradients are
        # identically-zero + fp noise (~1e-3), pure reassociation jitter
        if nu > 1e-2:
            assert 0.9 < nf / nu < 1.1, (str(pu), nf, nu)


def test_fused_epilogue_requires_instance_norm():
    from p2p_tpu.models.patchgan import NLayerDiscriminator

    x = jnp.zeros((1, 16, 16, 6), jnp.float32)
    d = NLayerDiscriminator(ndf=8, use_spectral_norm=False, int8=True,
                            int8_delayed=True, int8_fused_epilogue=True,
                            norm="none")
    with pytest.raises(ValueError, match="instance-family"):
        d.init(jax.random.key(0), x)


def test_fused_epilogue_composes_with_spectral_norm():
    """The spectral-norm D: fused epilogue == unfused, logits bitwise
    (the power iteration runs on the true f32 weight either way)."""
    from p2p_tpu.models.patchgan import NLayerDiscriminator

    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 32, 32, 6)).astype(np.float32))
    kw = dict(ndf=8, n_layers=3, use_spectral_norm=True, int8=True,
              int8_delayed=True, norm="instance")
    d_u = NLayerDiscriminator(**kw)
    d_f = NLayerDiscriminator(**kw, int8_fused_epilogue=True)
    vu = d_u.init(jax.random.key(0), x)
    vf = d_f.init(jax.random.key(0), x)
    assert jax.tree_util.tree_structure(vu) == \
        jax.tree_util.tree_structure(vf)
    ou, _ = d_u.apply(vu, x, mutable=["quant", "spectral"])
    of, _ = d_f.apply(vf, x, mutable=["quant", "spectral"])
    np.testing.assert_array_equal(np.asarray(ou[-1]), np.asarray(of[-1]))


# ----------------------------- net_c on the int8 path (ISSUE 14, d)


def _compression_cfg(**model_kw):
    import dataclasses

    from p2p_tpu.core.config import get_preset

    cfg = get_preset("facades_int8")
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8,
                                  use_compression_net=True,
                                  int8_compression=True, **model_kw),
        data=dataclasses.replace(cfg.data, image_size=16, batch_size=2),
    )


def _u8_batch(seed=0, n=2, size=16):
    rng = np.random.default_rng(seed)
    return {"input": rng.integers(0, 255, (n, size, size, 3)).astype(
                np.uint8),
            "target": rng.integers(0, 255, (n, size, size, 3)).astype(
                np.uint8)}


def test_compression_net_int8_trains_and_frozen_scale_eval_bitwise():
    """net_c on the delayed-int8 path: quant_c exists, threads through
    the train step (amax moves, update stored from the step-1 run), and
    frozen-scale eval is bitwise identical between the trainer's eval
    step and the serving InferState slice."""
    from p2p_tpu.train.state import create_train_state, infer_state_from_train
    from p2p_tpu.train.step import build_eval_step, build_train_step

    cfg = _compression_cfg()
    batch = _u8_batch()
    state = create_train_state(cfg, jax.random.key(0), batch,
                               train_dtype=jnp.bfloat16)
    assert len(jax.tree_util.tree_leaves(state.quant_c)) == 3  # 3 convs
    before = [float(a) for a in jax.tree_util.tree_leaves(state.quant_c)]
    step = build_train_step(cfg, train_dtype=jnp.bfloat16, jit=False)
    state, m = step(state, _u8_batch(seed=1))
    assert np.isfinite(float(m["loss_c"]))
    after = [float(a) for a in jax.tree_util.tree_leaves(state.quant_c)]
    assert after != before, "quant_c never moved through the step"

    ev = build_eval_step(cfg, jnp.bfloat16, jit=False)
    eval_batch = _u8_batch(seed=2)
    p1, _ = ev(state, eval_batch)
    p2, _ = ev(infer_state_from_train(state), eval_batch)
    np.testing.assert_array_equal(np.asarray(p1, np.float32),
                                  np.asarray(p2, np.float32))


# ------------------------- forward-compat restore (ISSUE 14, sat. 3)


def test_pre_drain_checkpoint_restores_with_initialized_amax(tmp_path):
    """A checkpoint saved BEFORE the coverage drain (missing the new
    amax leaves: wider G coverage, the kn2row head, all of quant_c)
    restores under the widened config with those leaves initialized from
    the template — params bitwise from disk, shared amax bitwise from
    disk, NO Orbax structure error — and reports the grafted paths so
    the trainer can arm the --recalibrate_steps warmup. A same-config
    restore stays byte-identical behavior with no graft flags."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.train.checkpoint import CheckpointManager
    from p2p_tpu.train.state import create_train_state

    base = get_preset("facades_int8")

    def tiny(**mk):
        return dataclasses.replace(
            base,
            model=dataclasses.replace(base.model, ngf=8, ndf=8,
                                      use_compression_net=True, **mk),
            data=dataclasses.replace(base.data, image_size=16,
                                     batch_size=2),
        )

    batch = _u8_batch()
    st_old = create_train_state(tiny(), jax.random.key(0), batch,
                                train_dtype=jnp.bfloat16)
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d)
    mgr.save(7, st_old, wait=True)

    cfg_new = tiny(int8_generator=True, int8_head=True,
                   int8_compression=True)
    st_new = create_train_state(cfg_new, jax.random.key(1), batch,
                                train_dtype=jnp.bfloat16)
    m2 = CheckpointManager(d)
    restored = m2.restore(st_new)
    grafted = m2.last_restore_initialized_quant
    assert len(grafted) == 7, grafted      # 3 encoder + head + 3 net_c
    assert any(p.startswith("quant_c/") for p in grafted)
    # params bitwise from disk (the graft touched ONLY quant leaves)
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(st_old.params_g),
            jax.tree_util.tree_leaves_with_path(restored.params_g)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=str(pa))
    # shared quant leaves from disk, new trees match the template
    np.testing.assert_array_equal(
        np.asarray(restored.quant_d["scale0"]["_PlainConv_1"]["Conv_0"]
                   ["amax_x"]),
        np.asarray(st_old.quant_d["scale0"]["_PlainConv_1"]["Conv_0"]
                   ["amax_x"]))
    assert jax.tree_util.tree_structure(restored.quant_g) == \
        jax.tree_util.tree_structure(st_new.quant_g)
    assert jax.tree_util.tree_structure(restored.quant_c) == \
        jax.tree_util.tree_structure(st_new.quant_c)
    # same-config restore: untouched path, no graft flags
    m3 = CheckpointManager(d)
    m3.restore(st_old)
    assert m3.last_restore_initialized_quant == []


def test_quant_init_graft_arms_recalibrate_warmup(tmp_path):
    """arm_quant_init_warmup: a restore that grafted amax leaves logs a
    quant_init record and (with --recalibrate_steps) opens the SAME
    frozen-scale window hold_frozen_quant re-pins — reusing the
    tp_amax_recalibrate machinery."""
    import dataclasses
    from types import SimpleNamespace

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.resilience.reshape import (
        arm_quant_init_warmup,
        hold_frozen_quant,
    )

    cfg = dataclasses.replace(
        get_preset("facades_int8"),
        train=dataclasses.replace(get_preset("facades_int8").train,
                                  recalibrate_steps=2))
    logs = []

    class _State(SimpleNamespace):
        def replace(self, **kw):
            d = dict(self.__dict__)
            d.update(kw)
            return _State(**d)

    state = _State(
        quant_g={"down1": {"amax_x": jnp.float32(3.0)}},
        quant_d=None, quant_c=None, pp_stages=None)
    tr = SimpleNamespace(
        cfg=cfg, state=state, _host_step=0,
        ckpt=SimpleNamespace(
            last_restore_initialized_quant=["quant_g/down1/amax_x"]),
        logger=SimpleNamespace(log=lambda rec, force=False:
                               logs.append(rec)))
    arm_quant_init_warmup(tr, 7)
    assert logs and logs[0]["kind"] == "quant_init"
    assert logs[0]["initialized_leaves"] == 1
    assert tr._quant_freeze_remaining == 2
    assert "quant_g" in tr._quant_frozen
    # the warmup window: each dispatch re-pins the frozen scales
    tr.state.quant_g["down1"]["amax_x"] = jnp.float32(99.0)
    hold_frozen_quant(tr)
    assert float(tr.state.quant_g["down1"]["amax_x"]) == 3.0
    assert tr._quant_freeze_remaining == 1
    # no graft -> no-op
    tr2 = SimpleNamespace(
        cfg=cfg, state=state,
        ckpt=SimpleNamespace(last_restore_initialized_quant=[]),
        logger=SimpleNamespace(log=lambda rec, force=False:
                               logs.append(rec)))
    n_logs = len(logs)
    arm_quant_init_warmup(tr2, 8)
    assert len(logs) == n_logs


def test_int8_full_coverage_overlay():
    """core.config.int8_full_coverage: the ONE shared override set (lint
    traced program == the facades_int8_full sweep row) — coverage knobs on, stems
    deliberately left to their measured-rejected default."""
    from p2p_tpu.core.config import get_preset, int8_full_coverage

    cfg = int8_full_coverage(get_preset("facades_int8"))
    m = cfg.model
    assert m.int8 and m.int8_delayed and m.int8_generator
    assert m.int8_decoder and m.int8_head and m.int8_compression
    assert m.use_compression_net
    assert not m.int8_stem            # measured-rejected, knob stays off
