"""Big LaMa (preset ``big_lama``): the generator of fast Fourier
convolutions, the masked non-saturating loss with its R1 penalty, the
dilated ResNet50 perceptual term, the masks the loader draws, against the
plain reference ``benchmark/reference/big_lama_places256.py`` on seeded
weights at a toy size on the CPU (ngf 8, one or two blocks, D 8 features,
32x32, batch 4).

Through the configuration: the Fourier unit against a DFT by matrix
products; a fast Fourier convolution and the whole generator; D's
features; the dilated ResNet50; the penalty's value, D's gradient under it
(``jax.grad`` of ``jax.grad``) and a finite difference; two whole train
steps; the mask generator; the loader's four-channel batch; ``cli.train``
-> ``cli.infer`` on images and masks of other extents; the published
widths' counts; the other presets' steps, which the new fields must not
reach.

Tolerances. BatchNorm over a few thousand values in float32 on the CPU
leaves a mean 1e-5 off (the backend's running sum), which the division by
a channel's deviation and a dozen such layers carry to ~1e-4 of an
activation, and a ReLU mask that flips under it to percents of a
gradient: so the float32 comparisons ask 2e-3 of a value and a tenth of a
gradient vector, and the mathematics is held EXACTLY by the float64 tests
(the program's modules in float64, BatchNorm's moments too: forward 1e-6,
every loss, gradient, parameter and statistic of two whole steps 1e-6),
where rounding is out of the way.
"""

import dataclasses
import inspect
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness
from p2p_tpu.core.config import get_preset
from p2p_tpu.data import masks as mask_gen

SIZE, BS, NGF, NDF = 32, 4, 8, 8
HYPER = dict(steps=2, lr_g=1e-3, lr_d=1e-4, beta1=0.9, beta2=0.999,
             eps=1e-8, gan_weight=10.0, l1_weight=10.0, fm_weight=100.0,
             hrf_weight=30.0, gp_coef=0.001)
FIELDS = ("params_g", "batch_stats_g", "params_d", "batch_stats_d")


def toy_cfg(n_blocks=1, size=SIZE, bs=BS, mixed=False):
    cfg = get_preset("big_lama")
    return cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=NGF, n_blocks=n_blocks,
                                  ndf=NDF),
        data=dataclasses.replace(cfg.data, image_size=size, batch_size=bs),
        train=dataclasses.replace(cfg.train, mixed_precision=mixed),
        parallel=dataclasses.replace(cfg.parallel, mesh=dataclasses.replace(
            cfg.parallel.mesh, data=1)))


def toy_batch(seed=0, size=SIZE, bs=BS):
    rng = np.random.default_rng(seed)
    target = rng.integers(0, 256, (bs, size, size, 3), dtype=np.uint8)
    inputs = np.stack([
        mask_gen.masked_input(t, mask_gen.draw_mask((seed, i), size, size))
        for i, t in enumerate(target)])
    return {"input": inputs, "target": target}


def unit(x, dtype=jnp.float32):
    return (jnp.asarray(x).astype(dtype) - 127.5) / 127.5


@pytest.fixture(scope="module")
def ref():
    return harness.load_by_path("reference", "big_lama_places256")


@pytest.fixture(scope="module")
def hrf():
    from p2p_tpu.models.resnet_dilated import load_resnet50_dilated_params

    return load_resnet50_dilated_params()


@pytest.fixture(scope="module")
def toy():
    """(cfg, state, flat state) at two blocks."""
    from p2p_tpu.train.state import create_train_state

    cfg = toy_cfg(2)
    state = create_train_state(cfg, jax.random.key(0), toy_batch(), 4, None)
    return cfg, state, check.flatten_state(state, FIELDS)


def flat_with_hrf(flat, hrf):
    out = dict(flat)
    for path, leaf in jax.tree_util.tree_flatten_with_path(hrf)[0]:
        out[check.leaf_key("vgg", path)] = np.asarray(leaf)
    return out


def as_jnp(flat, dtype=jnp.float32):
    return {k: jnp.asarray(v).astype(dtype) for k, v in flat.items()}


# ------------------------------------------------------------ Fourier unit


def dft_pair():
    """``(rfft2, irfft2)`` by matrix products, orthonormal, with the
    signatures ``jnp.fft``'s have: nothing of an FFT library in them."""
    def matrices(h, w, dtype):
        kh = np.arange(h)
        fh = np.exp(-2j * np.pi * np.outer(kh, kh) / h)
        fw = np.exp(-2j * np.pi * np.outer(np.arange(w),
                                           np.arange(w // 2 + 1)) / w)
        return (jnp.asarray(fh, dtype), jnp.asarray(fw, dtype),
                1.0 / np.sqrt(h * w))

    def rfft2(x, axes, norm):
        assert axes == (1, 2) and norm == "ortho"
        cdt = jnp.complex128 if x.dtype == jnp.float64 else jnp.complex64
        fh, fw, scale = matrices(x.shape[1], x.shape[2], cdt)
        z = jnp.einsum("kh,nhwc->nkwc", fh, x.astype(cdt),
                       precision="highest")
        return scale * jnp.einsum("nkwc,wl->nklc", z, fw,
                                  precision="highest")

    def irfft2(z, s, axes, norm):
        assert axes == (1, 2) and norm == "ortho"
        h, w = s
        fh, fw, scale = matrices(h, w, z.dtype)
        # a column l and its mirror w - l are conjugates: twice the real
        # part, but for l = 0 and the Nyquist column
        weight = np.full((w // 2 + 1,), 2.0)
        weight[0] = weight[-1] = 1.0
        cols = jnp.einsum("kh,nklc->nhlc", jnp.conj(fh), z,
                          precision="highest")
        full = jnp.einsum("nhlc,wl->nhwc", cols * jnp.asarray(
            weight, z.real.dtype)[None, None, :, None], jnp.conj(fw),
            precision="highest")
        return scale * full.real

    return rfft2, irfft2


def unit_params(c, seed=3):
    rng = np.random.default_rng(seed)
    path = "block_0/conv1/g2g/fu"
    return {
        f"params_g/{path}/conv/kernel": rng.normal(
            0, 0.2, (1, 1, 2 * c, 2 * c)).astype(np.float32),
        f"params_g/{path}/bn/BatchNorm_0/scale": rng.uniform(
            0.5, 1.5, (2 * c,)).astype(np.float32),
        f"params_g/{path}/bn/BatchNorm_0/bias": rng.normal(
            0, 0.2, (2 * c,)).astype(np.float32),
        f"batch_stats_g/{path}/bn/BatchNorm_0/mean": np.zeros(
            (2 * c,), np.float32),
        f"batch_stats_g/{path}/bn/BatchNorm_0/var": np.ones(
            (2 * c,), np.float32)}, path


@pytest.mark.parametrize("hw", [(8, 8), (6, 10)], ids=["8x8", "6x10"])
@pytest.mark.parametrize("side", ["reference", "program"])
def test_fourier_unit_against_a_dft_by_matrix_products(ref, side, hw):
    """rfft2 -> 1x1 convolution, BatchNorm, ReLU -> irfft2: the
    reference's unit on ``jnp.fft`` and the program's module, each held
    against the reference's unit with both transforms written out as
    matrix products (so neither side's transform is taken on trust)."""
    c = 6
    p, path = unit_params(c)
    h = jnp.asarray(np.random.default_rng(1).normal(
        size=(2,) + hw + (c,)).astype(np.float32))
    want = ref.fourier_unit(as_jnp(p), {}, path, h, True, fft=dft_pair())
    if side == "reference":
        got = ref.fourier_unit(as_jnp(p), {}, path, h, True)
    else:
        from p2p_tpu.models.ffc import FourierUnit

        tree = {"conv": {"kernel": p[f"params_g/{path}/conv/kernel"]},
                "bn": {"BatchNorm_0": {
                    "scale": p[f"params_g/{path}/bn/BatchNorm_0/scale"],
                    "bias": p[f"params_g/{path}/bn/BatchNorm_0/bias"]}}}
        stats = {"bn": {"BatchNorm_0": {"mean": jnp.zeros((2 * c,)),
                                        "var": jnp.ones((2 * c,))}}}
        got, _ = FourierUnit().apply(
            {"params": tree, "batch_stats": stats}, h, True,
            mutable=["batch_stats"])
    assert got.shape == h.shape
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_the_transforms_round_trip_and_keep_the_norm():
    rfft2, irfft2 = dft_pair()
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, 8, 12, 3)).astype(np.float32))
    z = rfft2(x, axes=(1, 2), norm="ortho")
    assert z.shape == (1, 8, 7, 3)
    np.testing.assert_allclose(z, jnp.fft.rfft2(x, axes=(1, 2),
                                                norm="ortho"), atol=1e-5)
    np.testing.assert_allclose(irfft2(z, s=(8, 12), axes=(1, 2),
                                      norm="ortho"), x, atol=1e-5)
    # orthonormal: Parseval over the full spectrum
    full = jnp.fft.fft2(x, axes=(1, 2), norm="ortho")
    assert float(jnp.sum(jnp.abs(full) ** 2)) == pytest.approx(
        float(jnp.sum(x ** 2)), rel=1e-5)


def library_pair():
    """``jnp.fft``'s two transforms with the module's signatures: real and
    imaginary parts as a last axis of 2."""
    def rfft2(x):
        z = jnp.fft.rfft2(x, axes=(1, 2), norm="ortho")
        return jnp.stack([z.real, z.imag], axis=-1)

    def irfft2(z, w):
        return jnp.fft.irfft2(jax.lax.complex(z[..., 0], z[..., 1]),
                              s=(z.shape[1], w), axes=(1, 2), norm="ortho")

    return rfft2, irfft2


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("what", ["rfft2", "irfft2", "rfft2_pullback",
                                  "irfft2_pullback"])
@pytest.mark.parametrize("hw", [(8, 12), (32, 32), (6, 9)],
                         ids=["8x12", "32x32", "6x9"])
def test_the_module_s_transforms_are_the_library_s(hw, what, dtype):
    """``models/ffc.py``'s matrix products against ``jnp.fft`` run in
    float64: the forward transform of a real tensor, the inverse of a
    spectrum that is NOT Hermitian (the unit's comes out of a convolution,
    BatchNorm and a ReLU: the library drops the imaginary parts of column
    0 and of the Nyquist column, and an odd width has no such column), and
    the pullbacks of both. In float64 to 1e-12; in float32 no further from
    the float64 truth than twice the library's own float32 error (root
    mean square: the largest of a few thousand errors is a draw, 0.6-2.5
    of the library's over seeds; this reads 1.4-1.5 at 32x32)."""
    from p2p_tpu.models import ffc

    h, w = hw
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, h, w, 3))
    z = rng.normal(size=(2, h, w // 2 + 1, 3, 2))
    with jax.enable_x64(True):
        def run(pair, dt):
            rfft2, irfft2 = pair
            fn, arg, ct = ((rfft2, x, z) if what.startswith("rfft2") else
                           (lambda v: irfft2(v, w), z, x))
            arg, ct = jnp.asarray(arg, dt), jnp.asarray(ct, dt)
            if what.endswith("pullback"):
                out, = jax.vjp(fn, arg)[1](ct)
            else:
                out = fn(arg)
            assert out.dtype == dt
            return np.asarray(out, np.float64)

        truth = run(library_pair(), jnp.float64)
        got = run((ffc.rfft2, ffc.irfft2), jnp.dtype(dtype))
        assert got.shape == truth.shape
        if dtype == "float64":
            assert np.abs(got - truth).max() < 1e-12 * np.abs(truth).max()
        else:
            rms = lambda a: float(np.sqrt(np.mean(np.square(a))))  # noqa: E731
            assert rms(got - truth) <= 2.0 * rms(
                run(library_pair(), jnp.float32) - truth)


@pytest.mark.parametrize("layer", ["conv", "bn_eval", "bn_train"])
def test_the_unit_s_layers_on_pairs_are_the_layers_on_interleaved_channels(
        layer):
    """Between its transforms the unit keeps (real, imaginary) as a last
    axis of 2 and reads the parameters of the interleaved ``2C`` channels
    as ``[C, 2]``: its 1x1 convolution against ``nn.Conv`` and its
    BatchNorm (``feature_axes=2``) against BatchNorm on ``[..., 2C]``, on
    the SAME variables: outputs, gradients and the running statistics."""
    from flax import linen as nn

    from p2p_tpu.models.ffc import _PairConv, torch_default_init
    from p2p_tpu.ops.norm import BatchNorm

    c = 6
    rng = np.random.default_rng(4)
    z = jnp.asarray(rng.normal(1.0, 2.0, (2, 4, 3, c, 2)).astype(np.float32))
    flat = z.reshape(2, 4, 3, 2 * c)
    if layer == "conv":
        on_pairs = _PairConv()
        plain = nn.Conv(2 * c, (1, 1), use_bias=False,
                        kernel_init=torch_default_init)
    else:
        running = layer == "bn_eval"
        on_pairs = BatchNorm(use_running_average=running, feature_axes=2)
        plain = BatchNorm(use_running_average=running)
    variables = plain.init(jax.random.key(2), flat)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and bool((a == b).all()), variables,
        on_pairs.init(jax.random.key(2), z)))
    if layer != "conv":
        stats = variables["batch_stats"]["BatchNorm_0"]
        variables = {**variables, "batch_stats": {"BatchNorm_0": {
            "mean": stats["mean"] + jnp.arange(2.0 * c) / 10,
            "var": stats["var"] + jnp.arange(2.0 * c) / 7}}}

    def run(module, x):
        def loss(v, xx):
            y, mut = module.apply(v, xx, mutable=["batch_stats"])
            return jnp.sum(jnp.sin(y.reshape(flat.shape))), (y, mut)
        (_, (y, mut)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(variables, x)
        return (y.reshape(flat.shape), mut, grads[0]["params"],
                grads[1].reshape(flat.shape))

    got, want = run(on_pairs, z), run(plain, flat)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- layers and the generator


def test_split_channels_is_the_source_s_arithmetic():
    from p2p_tpu.models.ffc import split_channels

    assert split_channels(512, 0.75) == (128, 384)
    assert split_channels(64, 0.75) == (16, 48)
    assert split_channels(512, 0.0) == (512, 0)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 6e-2)],
                         ids=["float32", "bfloat16"])
def test_fast_fourier_convolution_against_the_reference(ref, toy, dtype,
                                                        tol):
    """One FFC_BN_ACT of the toy state on a random pair."""
    from p2p_tpu.models.ffc import FFCBNAct

    cfg, state, flat = toy
    rng = np.random.default_rng(4)
    x_l = jnp.asarray(rng.normal(size=(BS, 4, 4, 16)).astype(np.float32))
    x_g = jnp.asarray(rng.normal(size=(BS, 4, 4, 48)).astype(np.float32))
    made = {}
    want = ref.ffc_bn_act(as_jnp(flat), made, "block_0/conv1", x_l, x_g,
                          True)
    dt = None if dtype == jnp.float32 else dtype
    got, mut = FFCBNAct(64, 0.75, dtype=dt).apply(
        {"params": state.params_g["block_0"]["conv1"],
         "batch_stats": state.batch_stats_g["block_0"]["conv1"]},
        x_l.astype(dtype), x_g.astype(dtype), True, mutable=["batch_stats"])
    for g, w in zip(got, want):
        assert g.dtype == dtype and g.shape == w.shape
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) < tol * max(
            float(jnp.abs(w).max()), 1.0)
    # the four BatchNorms' running statistics moved as the reference's
    stats = check.flatten_state(
        type("S", (), {"batch_stats_g": {"block_0": {"conv1": mut[
            "batch_stats"]}}})(), ("batch_stats_g",))
    assert set(stats) == set(made) and len(made) == 8
    if dtype == jnp.float32:
        for k, v in made.items():
            np.testing.assert_allclose(stats[k], v, atol=1e-4)


@pytest.mark.parametrize("mode", ["float32", "bfloat16", "evaluation"])
def test_generator_against_the_reference(ref, toy, mode):
    from p2p_tpu.train.state import build_models

    cfg, state, flat = toy
    batch = toy_batch()
    dtype = jnp.bfloat16 if mode == "bfloat16" else None
    train = mode != "evaluation"
    g, _, _ = build_models(cfg, dtype)
    u = unit(batch["input"])
    variables = {"params": state.params_g,
                 "batch_stats": state.batch_stats_g}
    if train:
        got, mut = g.apply(variables, u.astype(dtype or jnp.float32), True,
                           mutable=["batch_stats"])
    else:
        got = g.apply(variables, u, False)
    want, stats = ref.generator(as_jnp(flat), u, train)
    assert got.shape == (BS, SIZE, SIZE, 3)
    gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert gap < (0.08 if mode == "bfloat16" else 2e-3), gap
    assert float(jnp.std(want)) > 0.05      # no constant image
    if not train:
        # the composite: a known pixel is the input's own
        m = batch["input"][..., 3:] > 0
        np.testing.assert_array_equal(
            np.where(m, 0, np.asarray(got)), np.where(m, 0, np.asarray(
                u[..., :3])))
        assert not stats
    elif mode == "float32":
        after = check.flatten_state(
            state.replace(batch_stats_g=mut["batch_stats"]),
            ("batch_stats_g",))
        assert set(after) == set(stats)
        for k, v in stats.items():
            np.testing.assert_allclose(after[k], v, atol=2e-3, err_msg=k)


@pytest.mark.parametrize("train", [False, True], ids=["eval", "train"])
def test_generator_paints_what_it_painted_on_the_library_s_transforms(
        toy, monkeypatch, train):
    """The parameter tree is what it was while the unit called ``jnp.fft``
    (``fu/conv/kernel``, ``fu/bn/...``: a checkpoint from before loads),
    and on the same variables the generator paints the same image on the
    products as on the library's transforms: 1e-3 of a level of 255 in
    float32, at an extent whose spectrum has an odd width (72x64: 9x8 at
    the blocks)."""
    from p2p_tpu.models import ffc
    from p2p_tpu.train.state import build_models

    cfg, state, _ = toy
    g, _, _ = build_models(cfg, jnp.float32)
    unit_leaves = {"/".join(str(getattr(k, "key", k)) for k in path)
                   for path, _ in jax.tree_util.tree_flatten_with_path(
                       state.params_g["block_0"]["conv1"]["g2g"]["fu"])[0]}
    assert unit_leaves == {"conv/kernel", "bn/BatchNorm_0/scale",
                           "bn/BatchNorm_0/bias"}
    rng = np.random.default_rng(11)
    x = rng.uniform(-1, 1, (2, 72, 64, 4)).astype(np.float32)
    x[..., 3] = np.where(x[..., 3] > 0, 1.0, -1.0)
    variables = {"params": state.params_g,
                 "batch_stats": state.batch_stats_g}

    def paint():
        return np.asarray(g.apply(variables, jnp.asarray(x), train,
                                  mutable=["batch_stats"])[0])

    got = paint()
    monkeypatch.setattr(ffc, "rfft2", library_pair()[0])
    monkeypatch.setattr(ffc, "irfft2", library_pair()[1])
    want = paint()
    assert 127.5 * np.abs(got - want).max() < 1e-3


def float64_moments(monkeypatch):
    """BatchNorm's moments in the input's own dtype (ops/norm.py sums in
    float32 whatever comes in): with it the program's modules run in
    float64 throughout."""
    import p2p_tpu.ops.norm as norm

    def dual_moments(xc):
        dims = tuple(range(xc.ndim - 1))
        return jnp.sum(xc, dims), jnp.sum(jnp.square(xc), dims)

    monkeypatch.setattr(norm, "dual_moments", dual_moments)


def wide(tree):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float64)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def test_generator_in_float64_is_the_reference_s(ref, toy, monkeypatch):
    """With rounding out of the way the two forward passes are ONE
    function: 1e-6 of the image (the weights are float32 draws),
    statistics included."""
    from p2p_tpu.train.state import build_models

    cfg, state, flat = toy
    float64_moments(monkeypatch)
    with jax.enable_x64(True):
        g, _, _ = build_models(cfg, jnp.float64)
        u = unit(toy_batch()["input"], jnp.float64)
        got, mut = g.apply(
            {"params": wide(state.params_g),
             "batch_stats": wide(state.batch_stats_g)}, u, True,
            mutable=["batch_stats"])
        want, stats = ref.generator(as_jnp(flat, jnp.float64), u, True)
        assert got.dtype == want.dtype == jnp.float64
        assert float(jnp.abs(got - want).max()) < 1e-6
        after = check.flatten_state(
            state.replace(batch_stats_g=mut["batch_stats"]),
            ("batch_stats_g",))
        assert max(float(np.abs(after[k] - v).max())
                   for k, v in stats.items()) < 1e-6


def test_discriminator_features_against_the_reference(ref, toy):
    from p2p_tpu.train.state import build_models

    cfg, state, flat = toy
    _, d, _ = build_models(cfg, None)
    x = unit(toy_batch()["target"])
    (feats,), mut = d.apply(
        {"params": state.params_d, "spectral": state.spectral_d,
         "batch_stats": state.batch_stats_d}, x,
        mutable=["spectral", "batch_stats"])
    want, stats = ref.discriminator(as_jnp(flat), x)
    # the stem, four BatchNorm layers (three at stride 2), the logits
    assert [f.shape[1] for f in feats] == [17, 9, 5, 3, 4, 5]
    assert [f.shape[-1] for f in feats] == [8, 16, 32, 64, 128, 1]
    for f, w in zip(feats, want):
        np.testing.assert_allclose(f, w, atol=2e-4)
    after = check.flatten_state(
        state.replace(batch_stats_d=mut["batch_stats"]), ("batch_stats_d",))
    for k, v in stats.items():
        np.testing.assert_allclose(after[k], v, atol=1e-4, err_msg=k)


# ------------------------------------------------------- dilated ResNet50


def test_dilated_resnet50_stage_shapes_at_the_published_extent(hrf):
    from p2p_tpu.models.resnet_dilated import (
        ResNet50Dilated,
        resnet50_dilated_gflop_per_image,
    )

    outs = jax.eval_shape(
        lambda p, x: ResNet50Dilated().apply({"params": p}, x), hrf,
        jax.ShapeDtypeStruct((2, 256, 256, 3), jnp.float32))
    assert [o.shape[1:] for o in outs] == [
        (64, 64, 256), (32, 32, 512), (32, 32, 1024), (32, 32, 2048)]
    kernels = sum(v.size for p, v in jax.tree_util.tree_flatten_with_path(
        hrf)[0] if p[-1].key == "kernel")
    # torchvision's ResNet50 without its head, with the deep stem
    assert 23.4e6 < kernels < 23.6e6
    # the issue's count: 27 GMAC a forward pass
    assert 50.0 < resnet50_dilated_gflop_per_image(256, 256) < 58.0


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
def test_dilated_resnet50_against_the_reference(ref, hrf, store):
    from p2p_tpu.models.resnet_dilated import ResNet50Dilated

    x = unit(toy_batch(size=64, bs=2)["target"])
    want = ref.resnet50_dilated(as_jnp(flat_with_hrf({}, hrf)), x)
    dt = None if store == "float32" else jnp.bfloat16
    got = ResNet50Dilated(store_dtype=dt).apply(
        {"params": hrf}, x if dt is None else x.astype(dt))
    assert [g.shape[1] for g in got] == [16, 8, 8, 8]
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        gap = float(jnp.abs(g.astype(jnp.float32) - w).max())
        assert gap < (1e-4 if dt is None else 0.06) * scale
        assert 0.1 < float(jnp.std(w)) < 20.0     # a seeded trunk of size


def test_hrf_loss_is_the_sum_of_the_stages_mean_squares(ref, hrf):
    from p2p_tpu.losses.perceptual import hrf_loss

    a = unit(toy_batch(1, 32, 2)["target"])
    b = unit(toy_batch(2, 32, 2)["target"])
    want = ref.hrf_sum(as_jnp(flat_with_hrf({}, hrf)), a, b) / 2
    assert float(hrf_loss(hrf, a, b)) == pytest.approx(float(want),
                                                       rel=1e-4)
    assert float(hrf_loss(hrf, a, a)) == 0.0
    # the target side carries no gradient
    assert float(jnp.abs(jax.grad(
        lambda y: hrf_loss(hrf, a, y))(b)).max()) == 0.0


# ---------------------------------------------------------------- losses


@pytest.mark.parametrize("case", ["real", "fake", "map", "soft_map"])
def test_nonsaturating_loss(case):
    from p2p_tpu.losses.gan import gan_loss, nonsaturating

    p = jnp.asarray(np.random.default_rng(5).normal(size=(2, 5, 5, 1)),
                    jnp.float32)
    sp = lambda v: np.log1p(np.exp(np.asarray(v, np.float64)))  # noqa: E731
    if case == "real":
        assert float(nonsaturating(p, True)) == pytest.approx(
            sp(-p).mean(), rel=1e-6)
        assert float(gan_loss([[p * 0, p]], True, "nonsaturating")) == \
            pytest.approx(sp(-p).mean(), rel=1e-6)
    elif case == "fake":
        assert float(gan_loss([[p]], False, "nonsaturating")) == \
            pytest.approx(sp(p).mean(), rel=1e-6)
    elif case == "map":
        t = jnp.asarray(np.random.default_rng(6).integers(
            0, 2, p.shape), jnp.float32)
        want = (np.asarray(t) * sp(-p) + (1 - np.asarray(t)) * sp(p)).mean()
        assert float(nonsaturating(p, t)) == pytest.approx(want, rel=1e-6)
    else:
        # a target between 0 and 1 weighs the two pushes per pixel
        t = jnp.asarray(np.random.default_rng(7).uniform(size=p.shape),
                        jnp.float32)
        want = (np.asarray(t) * sp(-p) + (1 - np.asarray(t)) * sp(p)).mean()
        assert float(nonsaturating(p, t)) == pytest.approx(want, rel=1e-6)
        assert float(nonsaturating(p, jnp.ones_like(p))) == pytest.approx(
            float(nonsaturating(p, True)), rel=1e-6)


@pytest.mark.parametrize("hw", [(5, 5), (9, 17), (32, 32)])
def test_mask_resize_is_torch_s_nearest(ref, hw):
    from p2p_tpu.losses.gan import resize_mask_nearest

    m = jnp.asarray(np.random.default_rng(8).integers(
        0, 2, (2, 32, 32, 1)), jnp.float32)
    got = resize_mask_nearest(m, hw)
    assert got.shape == (2,) + hw + (1,)
    rows = np.floor(np.arange(hw[0]) * 32 / hw[0]).astype(int)
    cols = np.floor(np.arange(hw[1]) * 32 / hw[1]).astype(int)
    np.testing.assert_array_equal(got, np.asarray(m)[:, rows][:, :, cols])
    np.testing.assert_array_equal(got, ref.resize_nearest(m, hw))


def test_feature_matching_mse_is_the_mean_over_the_taps():
    from p2p_tpu.losses.feature_matching import feature_matching_mse

    rng = np.random.default_rng(9)
    fake = [[jnp.asarray(rng.normal(size=(2, n, n, 3)), jnp.float32)
             for n in (8, 4, 2)]]
    real = [[f + 1.0 for f in fake[0]]]
    real[0][1] = real[0][1] + 1.0           # a tap 2 away: 4
    # the logits (last) do not count: (1 + 4) / 2
    assert float(feature_matching_mse(fake, real)) == pytest.approx(2.5)
    ct = jax.grad(lambda r: feature_matching_mse(fake, [r]))(real[0])
    assert all(float(jnp.abs(c).max()) == 0.0 for c in ct)


@pytest.mark.parametrize("what", ["value", "d_gradient",
                                  "finite_difference"])
def test_r1_penalty_against_grad_of_grad_of_the_reference(ref, toy, what):
    """The penalty the step computes (``losses.gan.r1_penalty`` on D's
    real call) against the reference's ``jax.grad`` inside ``jax.grad``,
    and D's gradient under it against a central difference at one
    leaf."""
    from p2p_tpu.losses.gan import r1_penalty
    from p2p_tpu.train.state import build_models

    cfg, state, flat = toy
    _, d, _ = build_models(cfg, None)
    x = unit(toy_batch()["target"])
    p = as_jnp(flat)

    def penalty(params_d):
        def logits_sum(v):
            (feats,), _ = d.apply(
                {"params": params_d, "spectral": state.spectral_d,
                 "batch_stats": state.batch_stats_d}, v,
                mutable=["spectral", "batch_stats"])
            return jnp.sum(feats[-1]), None
        return r1_penalty(logits_sum, x)[0]

    d_ref = ref.sub(p, "params_d")
    rest = {k: v for k, v in p.items() if k not in d_ref}
    want_fn = lambda dp: ref.r1({**rest, **dp}, x)[0]  # noqa: E731
    if what == "value":
        got, want = float(penalty(state.params_d)), float(want_fn(d_ref))
        assert want > 1e-3 and got == pytest.approx(want, rel=1e-4)
        # per unit of a [0, 1] image: four times the [-1, 1] tensor's
        assert float(r1_penalty(
            lambda v: (jnp.sum(v ** 2), None), x, 1.0)[0]) * 4 == \
            pytest.approx(float(r1_penalty(
                lambda v: (jnp.sum(v ** 2), None), x)[0]), rel=1e-6)
        return
    grads = jax.grad(penalty)(state.params_d)
    got = check.flatten_state(
        type("S", (), {"params_d": grads})(), ("params_d",))
    if what == "d_gradient":
        want = jax.grad(want_fn)(d_ref)
        norm = lambda a: float(np.linalg.norm(np.asarray(a, np.float64)))  # noqa
        for k, w in want.items():
            assert norm(got[k] - w) < 2e-2 * max(norm(w), 1e-6), k
        # every layer of D is reached by the second-order pass
        assert all(norm(v) > 0 for k, v in got.items()
                   if k.endswith("kernel"))
        return
    leaf = "params_d/scale0/_PlainConv_5/Conv_0/kernel"
    direction = jnp.asarray(np.random.default_rng(10).normal(
        size=flat[leaf].shape), jnp.float32)
    eps = 1e-2
    moved = lambda s: float(want_fn(  # noqa: E731
        {**d_ref, leaf: d_ref[leaf] + s * eps * direction}))
    slope = (moved(1.0) - moved(-1.0)) / (2 * eps)
    assert float(jnp.vdot(got[leaf], direction)) == pytest.approx(
        slope, rel=5e-2)


# ------------------------------------------------------- the whole step


def followed(cfg, state, hrf, batches, dtype, feed):
    from p2p_tpu.train.step import build_train_step

    step = build_train_step(cfg, hrf, 4, dtype)
    # the step donates its state: the fixture's is kept
    state = jax.tree_util.tree_map(jnp.copy, state)
    tap = check.StepTap(step, state, len(batches))
    for b in batches:
        state, _ = tap(state, feed(b))
    return tap, check.flatten_state(state, ("batch_stats_g",
                                            "batch_stats_d"))


def test_two_whole_steps_in_float64_are_the_reference_s(ref, toy, hrf,
                                                        monkeypatch):
    """Every loss, every leaf's first gradient, the parameters after two
    steps of Adam and the running statistics of G and D: the Trainer's
    step in float64 against the ``StepReference`` in float64."""
    from benchmark.reference import nn

    cfg, state, flat = toy
    batches = [toy_batch(0), toy_batch(1)]
    float64_moments(monkeypatch)
    with jax.enable_x64(True):
        monkeypatch.setattr(nn, "to_unit", lambda x: unit(x, jnp.float64))
        hrf64 = wide(hrf)
        tap, after = followed(
            cfg, wide(state), hrf64, batches, jnp.float64,
            lambda b: {k: unit(v, jnp.float64) for k, v in b.items()})
        start = {k: np.asarray(v, np.float64)
                 for k, v in flat_with_hrf(flat, hrf).items()}
        losses, grads, params, stats = ref.StepReference(
            HYPER, rows=2).follow(start, batches)
    assert tap.moments["params_g/stem/Conv_0/kernel"].dtype == np.float64
    for got, want in zip(tap.losses, losses):
        assert set(want) == {"loss_d", "loss_g", "loss_d_r1", "g_gan",
                             "g_feat", "g_hrf", "g_l1_known"}
        for k, v in want.items():
            assert got[k] == pytest.approx(v, rel=1e-5), k
    norm = lambda a: float(np.linalg.norm(a))  # noqa: E731
    for k, g in grads.items():
        assert norm(tap.moments[k] / 0.1 - g) < 1e-5 * max(norm(g), 1e-9), k
    assert set(grads) == set(tap.moments) == set(params)
    assert max(float(np.abs(tap.params[k] - v).max())
               for k, v in params.items()) < 1e-4
    assert max(float(np.abs(after[k] - v).max())
               for k, v in stats.items()) < 1e-5
    assert set(after) == set(stats)


@pytest.mark.parametrize("mode", ["float32", "bfloat16"])
def test_two_whole_steps_against_the_reference(ref, toy, hrf, mode):
    """The same in the precisions that run: float32 within the CPU's
    rounding (module docstring), bf16 within its own."""
    cfg, state, flat = toy
    batches = [toy_batch(0), toy_batch(1)]
    dtype = jnp.bfloat16 if mode == "bfloat16" else None
    tap, after = followed(cfg, state, hrf, batches, dtype,
                          lambda b: {k: jnp.asarray(v)
                                     for k, v in b.items()})
    losses, grads, params, stats = ref.StepReference(
        HYPER, rows=2).follow(flat_with_hrf(flat, hrf), batches)
    loose = mode == "bfloat16"
    for k, v in losses[0].items():
        assert tap.losses[0][k] == pytest.approx(
            v, rel=3e-2 if loose else 2e-4), k
    for k, v in losses[1].items():
        assert tap.losses[1][k] == pytest.approx(
            v, rel=8e-2 if loose else 1e-2), k
    norm = lambda a: float(np.linalg.norm(np.asarray(a, np.float64)))  # noqa
    for net, limit in (("params_g", 0.6 if loose else 0.12),
                       ("params_d", 0.3 if loose else 0.05)):
        keys = [k for k in grads if k.startswith(net)]
        got = np.concatenate([tap.moments[k].ravel() / 0.1 for k in keys])
        want = np.concatenate([grads[k].ravel() for k in keys])
        assert norm(got - want) < limit * norm(want), net
    moved = lambda tree: np.concatenate(  # noqa: E731
        [(tree[k] - flat[k]).ravel() for k in sorted(params)])
    # Adam's first steps move every leaf by about its rate whatever the
    # gradient's size (a sign that flips under rounding moves a leaf the
    # other way): the change's norm is held; a state left unchanged reads 1
    assert abs(norm(moved(tap.params)) - norm(moved(params))) < (
        0.1 * norm(moved(params)))
    for k, v in stats.items():
        scale = (np.sqrt(stats[k[:-4] + "var"]) if k.endswith("mean")
                 else v)
        assert norm(after[k] - v) < (0.1 if loose else 5e-3) * norm(scale), k


def test_compiled_step_names_the_new_scopes(toy, hrf):
    from p2p_tpu.train.step import STEP_SCOPES, build_train_step

    cfg, state, _ = toy
    text = build_train_step(cfg, hrf, 4, None).lower(
        state, toy_batch()).as_text(debug_info=True)
    for scope in ("ffc_local", "ffc_spectral", "ffc_fft", "d_r1",
                  "loss_hrf", "D_real", "D_fake", "loss_fm"):
        assert re.search(rf"[/(\"]{scope}[/)\"]", text), scope
    assert "loss_hrf" in STEP_SCOPES and "d_r1" not in STEP_SCOPES
    # the penalty's passes lie inside D's real call
    assert re.search(r"D_real[^\"\n]*d_r1", text)
    # the transforms lie inside the spectral transform, as matrix products
    # in the forward pass and in the transposed one; nothing of an FFT
    assert "stablehlo.fft" not in text
    products = [ln for ln in text.splitlines() if "dot_general" in ln
                and re.search(r"ffc_spectral[^\"\n]*ffc_fft", ln)]
    assert any("transpose(jvp(" in ln for ln in products)
    assert any("transpose(" not in ln for ln in products)
    # two stages a transform, a forward and an inverse a unit, each once
    # more transposed: 2 blocks x 2 units
    assert len(products) >= 2 * 2 * 2 * 4


@pytest.mark.parametrize("fault", ["conditional_d", "lsgan", "pool",
                                   "penalty_without_a_mask"])
def test_step_refuses_what_the_masked_loss_cannot_take(fault):
    from p2p_tpu.train.step import build_train_step

    cfg = toy_cfg()
    if fault == "conditional_d":
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, d_conditional=True))
    elif fault == "lsgan":
        cfg = cfg.replace(loss=dataclasses.replace(cfg.loss,
                                                   gan_mode="lsgan"))
    elif fault == "pool":
        cfg = cfg.replace(train=dataclasses.replace(cfg.train, pool_size=4))
    else:
        ref_cfg = get_preset("reference")
        cfg = ref_cfg.replace(loss=dataclasses.replace(ref_cfg.loss,
                                                       gp_coef=0.001))
    with pytest.raises(ValueError, match="mask|pool"):
        build_train_step(cfg, None, 4, None)


def test_generator_refuses_an_extent_it_cannot_halve_and_an_empty_branch():
    from p2p_tpu.models.ffc import LamaGenerator

    g = LamaGenerator(ngf=8, n_blocks=1)
    with pytest.raises(ValueError, match="multiple of 8"):
        jax.eval_shape(lambda: g.init(jax.random.key(0),
                                      jnp.zeros((1, 36, 32, 4)), False))
    with pytest.raises(ValueError, match="empty"):
        jax.eval_shape(lambda: LamaGenerator(ngf=8, n_blocks=1, ratio=0.0)
                       .init(jax.random.key(0), jnp.zeros((1, 32, 32, 4)),
                             False))


# ------------------------------------------------------------------ masks


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 2 ** 31 + 5, 2 ** 33])
def test_a_mask_is_a_function_of_seed_epoch_and_index(seed):
    a = mask_gen.draw_mask((seed, 3, 7), 256, 256)
    assert a.shape == (256, 256) and a.dtype == np.uint8
    assert set(np.unique(a)) <= {0, 1} and 0 < a.mean() < 1
    np.testing.assert_array_equal(a, mask_gen.draw_mask((seed, 3, 7),
                                                        256, 256))
    others = [mask_gen.draw_mask(s, 256, 256)
              for s in ((seed + 1, 3, 7), (seed, 4, 7), (seed, 3, 8))]
    assert all((a != o).any() for o in others)


@pytest.mark.parametrize("kind", ["polylines", "boxes"])
def test_mask_kinds_stay_inside_their_stated_ranges(kind):
    rng = np.random.default_rng(11)
    for _ in range(20):
        if kind == "boxes":
            m = mask_gen.box_mask(rng, 256, 256)
            # 1-4 boxes of side 30-149, ten pixels clear of the border
            assert m[:10].sum() == m[-10:].sum() == 0
            assert m[:, :10].sum() == m[:, -10:].sum() == 0
            assert 30 * 30 <= m.sum() <= 4 * 149 * 149
        else:
            m = mask_gen.polyline_mask(rng, 256, 256)
            assert 0 < m.sum() < 256 * 256


def test_masked_share_of_an_epoch_lies_in_the_stated_range():
    shares = np.array([mask_gen.draw_mask((5, epoch, i), 256, 256).mean()
                       for epoch in range(2) for i in range(256)])
    # the configuration file's reading: mean 0.55, quartiles 0.43 / 0.68
    assert 0.45 < shares.mean() < 0.65
    assert 0.3 < np.percentile(shares, 25) < np.percentile(shares, 75) < 0.8
    assert 0.0 < shares.min() and shares.max() < 1.0


@pytest.mark.parametrize("seed", [(5, 0, 0), (5, 1, 3), (2 ** 31 + 5, 2, 9)])
def test_a_mask_is_polylines_and_then_boxes_over_them(seed):
    """Every sample draws BOTH kinds from one stream: the polylines first,
    the boxes over them."""
    rng = np.random.default_rng((mask_gen._TAG,) + seed)
    lines = mask_gen.polyline_mask(rng, 256, 256)
    boxes = mask_gen.box_mask(rng, 256, 256)
    assert lines.any() and boxes.any()
    np.testing.assert_array_equal(mask_gen.draw_mask(seed, 256, 256),
                                  lines | boxes)


@pytest.mark.parametrize("dtype", ["uint8", "float32"])
def test_masked_input_blanks_and_appends(dtype):
    rng = np.random.default_rng(12)
    img = rng.integers(1, 256, (16, 16, 3), dtype=np.uint8)
    if dtype == "float32":
        img = (img.astype(np.float32) - 127.5) / 127.5
    mask = mask_gen.draw_mask((1, 2), 16, 16)
    u = mask_gen.masked_input(img, mask)
    assert u.shape == (16, 16, 4) and u.dtype == img.dtype
    m = mask.astype(bool)
    lo, hi = (0, 255) if dtype == "uint8" else (-1.0, 1.0)
    assert (u[m][:, :3] == lo).all() and (u[m][:, 3] == hi).all()
    assert (u[~m][:, :3] == img[~m]).all() and (u[~m][:, 3] == lo).all()
    # what the generator's first layer makes of it: [0, 1], 0 where missing
    from p2p_tpu.utils.images import ingest

    x01 = np.asarray(ingest(u)) * 0.5 + 0.5
    assert (x01[m][:, :3] == 0).all() and (x01[m][:, 3] == 1).all()


# ----------------------------------------------------------------- loader


@pytest.fixture(scope="module")
def image_root(tmp_path_factory):
    from PIL import Image

    from p2p_tpu.data.synthetic import make_synthetic_dataset

    root = str(tmp_path_factory.mktemp("lama_data"))
    make_synthetic_dataset(root, n_train=8, n_test=2, size=SIZE, bits=8)
    # two test images of other extents with their masks, for cli.infer
    rng = np.random.default_rng(13)
    os.makedirs(os.path.join(root, "test", "mask"))
    for name in os.listdir(os.path.join(root, "test", "a")):
        os.remove(os.path.join(root, "test", "a", name))
    for name, (h, w) in (("odd.png", (44, 60)), ("large.png", (512, 512))):
        Image.fromarray(rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
                        ).save(os.path.join(root, "test", "a", name))
        Image.fromarray(mask_gen.draw_mask((13, h), h, w) * 255).save(
            os.path.join(root, "test", "mask", name))
    return root


@pytest.mark.parametrize("dtype", ["uint8", "float32"])
def test_loader_makes_the_input_from_the_target_and_a_fresh_mask(
        image_root, dtype):
    from p2p_tpu.data.pipeline import PairedImageDataset, make_loader

    ds = PairedImageDataset(image_root, "train", "b2a", SIZE, dtype=dtype,
                            mask_input=True, mask_seed=4)
    assert ds.aug_seed == 4                     # epoch 0 of the run's seed
    epochs = []
    for aug_seed in (5, 6, 5):                  # the trainer: seed + epoch
        ds.aug_seed, ds.mask_shares = aug_seed, {}
        (batch,) = list(make_loader(ds, 8, shuffle=False, num_workers=0))
        assert batch["input"].shape == (8, SIZE, SIZE, 4)
        assert batch["target"].shape == (8, SIZE, SIZE, 3)
        assert batch["input"].dtype == batch["target"].dtype == dtype
        m = batch["input"][..., 3:] > 0
        blank = 0 if dtype == "uint8" else -1.0
        np.testing.assert_array_equal(
            batch["input"][..., :3], np.where(m, blank, batch["target"]))
        assert sorted(ds.mask_shares) == list(range(8))
        assert sum(ds.mask_shares.values()) / 8 == pytest.approx(
            m.mean(), abs=1e-6)
        epochs.append(batch["input"])
    assert (epochs[0] != epochs[1]).any()                  # fresh an epoch
    np.testing.assert_array_equal(epochs[0], epochs[2])    # seeded
    # per (seed, epoch, index), not per seed + epoch: another run's seed
    # at the same sum draws other masks
    other = PairedImageDataset(image_root, "train", "b2a", SIZE, dtype=dtype,
                               mask_input=True, mask_seed=3)
    other.aug_seed = 5                          # seed 3, epoch 2
    for which, seed in ((ds, (4, 1, 1)), (other, (3, 2, 1))):
        np.testing.assert_array_equal(
            np.asarray(which[1]["input"][..., 3] > 0),
            mask_gen.draw_mask(seed, SIZE, SIZE) > 0)
    assert (mask_gen.draw_mask((4, 1, 1), 256, 256)
            != mask_gen.draw_mask((3, 2, 1), 256, 256)).any()
    # the target alone is decoded and memoised; the memo is then full
    assert ds.memo_full and len(ds._memo) == 8
    with pytest.raises(ValueError, match="made from the target"):
        PairedImageDataset(image_root, "train", "b2a", SIZE, augment=True,
                           mask_input=True)


def test_wire_spec_and_dummy_batches_carry_the_mask_channel():
    from p2p_tpu.models.registry import input_mask_channel
    from p2p_tpu.utils.images import dummy_batch, wire_spec

    cfg = toy_cfg()
    assert input_mask_channel(cfg.model) == 3
    assert input_mask_channel(get_preset("reference").model) is None
    assert wire_spec(cfg) == ((SIZE, SIZE, 4), np.dtype(np.uint8))
    assert wire_spec(cfg, "target")[0] == (SIZE, SIZE, 3)
    batch = dummy_batch(cfg, (2,))
    assert batch["input"].shape == (2, SIZE, SIZE, 4)


# ------------------------------------------------------------------- CLIs

CLI = ["--preset", "big_lama", "--name", "toy", "--dataset", "toy",
       "--image_size", str(SIZE), "--ngf", str(NGF), "--n_blocks", "1"]


@pytest.fixture(scope="module")
def trained(image_root, tmp_path_factory):
    from p2p_tpu.cli import train as cli_train

    work = str(tmp_path_factory.mktemp("lama_run"))
    argv = CLI + ["--data_root", image_root, "--workdir", work, "--ndf",
                  str(NDF), "--batch_size", "4", "--nepoch", "1",
                  "--epochsave", "1", "--threads", "0", "--log_every", "1",
                  "--mesh", "data=1"]
    assert cli_train.main(argv) == 0
    return work, argv


def test_cli_train_runs_the_preset_through_the_trainer(trained, image_root):
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.train.loop import Trainer

    work, argv = trained
    stream = [json.loads(x) for x in open(
        os.path.join(work, "metrics_toy.jsonl"))]
    steps = [r for r in stream if r.get("kind") == "train"]
    assert len(steps) == 2
    for name in ("loss_g", "loss_d", "loss_d_r1", "g_gan", "g_feat",
                 "g_l1_known"):
        assert all(np.isfinite(r[name]) for r in steps), name
    assert all(r["loss_d_r1"] > 0 for r in steps)
    # the preset's perceptual term, on the seeded dilated ResNet50
    assert all(r["g_hrf"] > 0 for r in steps)
    # the loader's own counter, in the epoch's record
    (epoch,) = [r for r in stream if r.get("span") == "train_epoch"]
    assert 0.0 < epoch["masked_share_mean"] < 1.0
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    assert (cfg.optim.lr, cfg.optim.lr_d, cfg.optim.beta1) == (
        1e-3, 1e-4, 0.9)
    assert (cfg.loss.gan_mode, cfg.loss.gp_coef, cfg.loss.feat_mode) == (
        "nonsaturating", 0.001, "mse")
    trainer = Trainer(cfg, data_root=image_root, workdir=work)
    try:
        assert trainer.maybe_resume() and int(trainer.state.step) == 2
        assert trainer.train_ds.mask_input
        assert "layer4_2" in trainer.vgg_params     # the dilated ResNet50
        gauges = {k: v["value"] for k, v in trainer.obs.snapshot().items()
                  if k.startswith("ffc_")}
        assert gauges == {"ffc_layers": 6.0, "ffc_fourier_units": 2.0,
                          "ffc_fft_calls_per_step": 8.0,
                          "ffc_dft_transforms_per_step": 8.0,
                          "ffc_global_channels": 48.0}
        # evaluation: the composite against the target
        out = trainer.evaluate()
        assert np.isfinite(out["psnr_mean"])
    finally:
        trainer.close()


def test_the_new_fields_are_configuration_and_no_flag():
    """The penalty's coefficient, the perceptual weight and the FFC ratio
    are fields of the preset; the sizes a toy run changes have flags."""
    from p2p_tpu.cli import train as cli_train

    cfg = cli_train.config_from_flags(cli_train.build_parser().parse_args(
        CLI))
    assert (cfg.loss.gp_coef, cfg.loss.lambda_hrf, cfg.model.ffc_ratio) == (
        0.001, 30.0, 0.75)
    assert (cfg.model.ngf, cfg.model.n_blocks) == (NGF, 1)
    flags = cli_train.build_parser().format_help()
    for name in ("gp_coef", "lambda_hrf", "ffc_ratio"):
        assert f"--{name}" not in flags


def test_cli_infer_fills_masks_at_other_extents(trained, image_root,
                                                tmp_path):
    """44x60 (padded by mirroring to 48x64 and cropped back) and 512x512:
    the model is fully convolutional. Each served image is the composite
    of the generator's own forward pass on the padded input."""
    from PIL import Image

    from p2p_tpu.cli import infer as cli_infer
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.models.registry import define_G
    from p2p_tpu.train.loop import Trainer
    from p2p_tpu.utils.images import to_uint8_img

    work, argv = trained
    out = str(tmp_path / "pred")
    assert cli_infer.main(CLI + [
        "--data_root", image_root, "--workdir", work, "--out", out,
        "--dtype", "f32"]) == 0
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    trainer = Trainer(cfg, data_root=image_root, workdir=work)
    try:
        assert trainer.maybe_resume()
        weights = jax.device_get((trainer.state.params_g,
                                  trainer.state.batch_stats_g))
    finally:
        trainer.close()
    g = define_G(cfg.model)
    for name, (h, w), (ph, pw) in (("odd.png", (44, 60), (48, 64)),
                                   ("large.png", (512, 512), (512, 512))):
        img = np.asarray(Image.open(os.path.join(image_root, "test", "a",
                                                 name)))
        mask = np.asarray(Image.open(os.path.join(
            image_root, "test", "mask", name))) > 0
        pad = lambda a: np.pad(  # noqa: E731
            a, ((0, ph - h), (0, pw - w)) + ((0, 0),) * (a.ndim - 2),
            mode="symmetric")
        u = unit(mask_gen.masked_input(pad(img), pad(mask)))
        want = to_uint8_img(np.asarray(g.apply(
            {"params": weights[0], "batch_stats": weights[1]}, u[None],
            False))[0][:h, :w])
        served = np.asarray(Image.open(os.path.join(out, name)))
        assert served.shape == (h, w, 3)
        assert np.max(np.abs(served.astype(np.int32) - want)) <= 1
        # a known pixel is the image's own, a filled one the generator's
        np.testing.assert_array_equal(served[~mask], img[~mask])
        assert (served[mask] != img[mask]).any()
    # a mask that is missing is said so, not guessed
    os.rename(os.path.join(image_root, "test", "mask", "odd.png"),
              os.path.join(image_root, "test", "mask", "odd.kept"))
    try:
        assert cli_infer.main(CLI + [
            "--data_root", image_root, "--workdir", work, "--out",
            out]) == 1
    finally:
        os.rename(os.path.join(image_root, "test", "mask", "odd.kept"),
                  os.path.join(image_root, "test", "mask", "odd.png"))


# --------------------------------------------------- the published widths


def test_parameter_counts_at_the_published_widths():
    from p2p_tpu.analysis.sharding_audit import abstract_train_state

    cfg = get_preset("big_lama")
    state = abstract_train_state(cfg)
    count = lambda tree, pred=lambda k: True: sum(  # noqa: E731
        int(np.prod(v.shape)) for p, v in
        jax.tree_util.tree_flatten_with_path(tree)[0]
        if pred("/".join(str(getattr(k, "key", k)) for k in p)))
    g = state.params_g
    assert set(g) == ({"stem", "stem_bn", "head"}
                      | {f"down_{i}{s}" for i in range(3)
                         for s in ("", "_bn")}
                      | {f"up_{i}{s}" for i in range(3) for s in ("", "_bn")}
                      | {f"block_{i}" for i in range(18)})
    ffc = g["block_0"]["conv1"]
    assert ffc["l2l"]["kernel"].shape == (3, 3, 128, 128)
    assert ffc["l2g"]["kernel"].shape == (3, 3, 128, 384)
    assert ffc["g2l"]["kernel"].shape == (3, 3, 384, 128)
    assert ffc["g2g"]["conv1"]["kernel"].shape == (1, 1, 384, 192)
    assert ffc["g2g"]["fu"]["conv"]["kernel"].shape == (1, 1, 384, 384)
    assert ffc["g2g"]["conv2"]["kernel"].shape == (1, 1, 192, 384)
    # 36 FFCs of 1.33M, the convolutions around them: the issue's 51M
    assert count(ffc, lambda k: k.endswith("kernel")) == 1_327_104
    assert 50.5e6 < count(g) < 51.5e6
    # D: 4 layers at 64 -> 512, a 512 -> 512 layer at stride 1, the logits
    d = state.params_d["scale0"]
    assert [d[f"_PlainConv_{i}"]["Conv_0"]["kernel"].shape[-1]
            for i in range(6)] == [64, 128, 256, 512, 512, 1]
    assert count(state.batch_stats_d) == 2 * (128 + 256 + 512 + 512)


def test_published_arithmetic_matches_the_issue_s_count():
    from p2p_tpu.models.registry import generator_gauges

    gauges = generator_gauges(get_preset("big_lama").model, 256, 256)
    assert gauges["ffc_layers"] == 40 and gauges["ffc_fourier_units"] == 36
    assert gauges["ffc_fft_calls_per_step"] == 144
    assert gauges["ffc_dft_transforms_per_step"] == 144
    assert gauges["ffc_global_channels"] == 384
    # 54 GMAC an image forward (46 in the blocks, 8 around them)
    assert 104.0 < gauges["generator_gflop_per_image"] < 112.0
    assert 50.0 < gauges["lama_hrf_gflop_per_image"] < 58.0


def test_the_model_axis_leaves_the_new_leaves_whole_by_name():
    from jax.sharding import PartitionSpec as P

    from p2p_tpu.parallel.rules import make_ffc_rules

    rules = make_ffc_rules()
    names = ["params_g/block_7/conv2/l2g/kernel",
             "params_g/block_0/conv1/g2g/fu/conv/kernel",
             "params_g/block_0/conv1/g2g/conv2/kernel",
             "params_g/block_3/conv1/bn_g/BatchNorm_0/scale",
             "params_g/block_3/conv1/g2g/fu/bn/BatchNorm_0/bias",
             "params_g/up_1/kernel"]
    for name in names:
        assert any(re.search(pattern, name) and spec == P()
                   for pattern, spec, *_ in rules), name
    assert not any(re.search(pattern, "params_g/stem/Conv_0/kernel")
                   for pattern, *_ in rules)


# ----------------------------------------------------------- other presets


def _tiny(preset):
    cfg = get_preset(preset)
    size = 64 if cfg.model.generator in ("pix2pixhd", "unet") else 32
    model = dataclasses.replace(cfg.model, ngf=4, ndf=4, n_blocks=1)
    if cfg.model.label_classes:
        model = dataclasses.replace(model, ngf=8, label_classes=3,
                                    input_nc=4)
    if cfg.model.generator == "vqgan":
        model = dataclasses.replace(model, ngf=32, vq_ch_mult=(1, 2),
                                    vq_res_blocks=1, vq_codes=16,
                                    vq_embed_dim=32)
    return cfg.replace(
        model=model,
        data=dataclasses.replace(cfg.data, image_size=size, image_width=size,
                                 batch_size=1),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=dataclasses.replace(
                cfg.parallel.mesh, data=1, spatial=1, time=1)))


@pytest.mark.parametrize("preset", ["reference", "pix2pixhd",
                                    "spade_cityscapes",
                                    "vqgan_imagenet_f16"])
def test_preset_step_unchanged_by_the_new_fields(preset):
    """A preset of another generator traces the step it had: the new
    fields at their defaults, no scope and no second-order pass of this PR
    in its jaxpr (the six accepted cells' lowered programs hash as at the
    parent: ``scripts/step_program_hash.py``, PERF.md section 6)."""
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.models.registry import input_mask_channel
    from p2p_tpu.train.step import build_train_step
    from p2p_tpu.utils.images import wire_spec

    cfg = _tiny(preset)
    assert input_mask_channel(cfg.model) is None
    assert (cfg.loss.gp_coef, cfg.loss.lambda_hrf, cfg.loss.feat_mode) == (
        0.0, 0.0, "l1")
    state = abstract_train_state(cfg)
    batch = {k: jax.ShapeDtypeStruct((1,) + wire_spec(cfg, k)[0],
                                     wire_spec(cfg, k)[1])
             for k in ("input", "target")}
    jaxpr = str(jax.make_jaxpr(build_train_step(cfg, jit=False))(
        state, batch))
    for name in ("ffc_", "d_r1", "loss_hrf", "fft[", "softplus"):
        assert name not in jaxpr, name


def test_the_train_step_still_names_no_generator():
    from p2p_tpu.data import pipeline
    from p2p_tpu.train import loop, step

    for module in (step, loop, pipeline):
        src = inspect.getsource(module)
        for banned in ("model.generator", "models.ffc", "lama", "LaMa"):
            assert banned not in src.replace("LaMa lineage", ""), (
                module.__name__, banned)
