"""SwinIR x4 with its GAN objective (preset ``swinir_realsr_x4``) at toy
sizes, held case by case against the plain reference of its configuration
(``benchmark/reference/swinir_m_realsr_x4_gan.py``: float32, nothing of
the program imported).

Module cases run ``models/swinir.SwinIR`` at embed 24, 2 groups of 2
layers, 2 heads of 12, window 4 on 16x16 -> 64x64, batch 2 (the sizes the
module's constants fix for every preset are fields of the module for
this): LayerNorm, the window partition and its reverse, the shift mask, the
relative-position index and bias, ``WMSA`` unshifted and shifted, ``STL``
with stochastic depth on and off, ``RSTB``, the upsampler, the whole G.
Through the configuration (embed 60 = 2 heads of 30, one group of 6
layers, window 8, D 8 features, 16x16 -> 64x64): the U-Net D with its
spectral vectors, the pre-activation VGG19 taps, three whole train steps,
``cli.train`` -> ``cli.infer`` on an LQ image of another extent; the
published widths' parameter counts; a control (bf16 softmax) the comparison
must refuse; the other presets' steps, which the new fields must not
reach; the loader's joint crop and flip on both extents.

Tolerances: both sides run float32 on the CPU, the program at XLA's
default precision, the reference at ``Precision.HIGHEST``: forward values
agree to ~1e-5 of their scale, 1e-4 is asked; gradients 2e-3 of a leaf's
largest entry.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness
from p2p_tpu.core.config import get_preset

LQ, SCALE, BS = 16, 4, 2
HQ = LQ * SCALE
TOY = dict(embed=24, groups=2, layers_per_group=2, head_dim=12, window=4)
FIELDS = ("params_g", "params_d", "spectral_d", "ema_g")
HYPER = dict(steps=3, lr_g=1e-4, lr_d=1e-4, beta1=0.9, beta2=0.999,
             eps=1e-8, l1_weight=1.0, perceptual_weight=1.0, gan_weight=0.1,
             d_loss_scale=0.5, ema_decay=0.999)
CLI = ["--preset", "swinir_realsr_x4", "--name", "toy", "--dataset", "toy",
       "--image_size", str(HQ), "--ngf", "60", "--n_blocks", "1"]


def toy_cfg(**model):
    cfg = get_preset("swinir_realsr_x4")
    return cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=60, n_blocks=1, ndf=8,
                                  **model),
        data=dataclasses.replace(cfg.data, image_size=HQ, batch_size=BS,
                                 test_batch_size=BS),
        train=dataclasses.replace(cfg.train, mixed_precision=False))


def toy_batch(seed=0):
    rng = np.random.default_rng(seed)
    return {"input": rng.integers(0, 256, (BS, LQ, LQ, 3)).astype(np.uint8),
            "target": rng.integers(0, 256, (BS, HQ, HQ, 3)).astype(np.uint8)}


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        float(np.max(np.abs(got - want))), scale)


def flat_params(tree, prefix, leaf_as=np.asarray):
    return {check.leaf_key(prefix, path): leaf_as(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def traced(tree, prefix):
    return flat_params(tree, prefix, leaf_as=lambda leaf: leaf)


def shaken(params, seed=0, by=0.05):
    """Off the init's ones and zeros, so scales, biases and the bias table
    are compared."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda w: w + by * rng.standard_normal(w.shape).astype(np.float32),
        params)


@pytest.fixture(scope="module")
def ref():
    return harness.load_by_path("reference", "swinir_m_realsr_x4_gan")


@pytest.fixture(scope="module")
def vgg():
    from p2p_tpu.models.vgg import load_vgg19_params

    return load_vgg19_params(arch="vgg19_preact")


@pytest.fixture(scope="module")
def toy_g():
    """The toy generator, its shaken parameters (nested and flat), an LQ
    batch in [-1, 1] and the same in [0, 1]."""
    from p2p_tpu.models.swinir import SwinIR

    g = SwinIR(**TOY)
    x = jnp.asarray(np.random.default_rng(1).uniform(
        -1, 1, (BS, LQ, LQ, 3)).astype(np.float32))
    params = shaken(g.init(jax.random.key(0), x, False)["params"])
    return g, params, flat_params(params, "params_g"), x, (x + 1.0) * 0.5


@pytest.fixture(scope="module")
def toy(vgg):
    """cfg, the seeded state and its flat copy with the frozen VGG19 tree
    under ``vgg/`` (made before a step donates the state)."""
    from p2p_tpu.train.state import create_train_state

    cfg = toy_cfg()
    state = create_train_state(cfg, jax.random.key(0), toy_batch())
    state = state.replace(params_g=shaken(state.params_g, by=0.02),
                          params_d=shaken(state.params_d, 1, by=0.02))
    state = state.replace(ema_g=jax.tree_util.tree_map(jnp.copy,
                                                       state.params_g))
    flat = check.flatten_state(state, FIELDS)
    flat.update(flat_params(vgg, "vgg"))
    return cfg, state, flat


# ------------------------------------------------------- the mechanisms


def test_layer_norm_against_the_reference(ref):
    from p2p_tpu.models.swinir import LayerNorm

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 4, 24)).astype(np.float32))
    params = shaken(LayerNorm().init(jax.random.key(0), x)["params"])
    f = lambda pp, xx: LayerNorm().apply({"params": pp}, xx)  # noqa: E731
    g = lambda pp, xx: ref.layer_norm(traced(pp, "ln"), "ln", xx)  # noqa
    close(f(params, x), g(params, x), 1e-4)
    w = jnp.asarray(rng.standard_normal(x.shape).astype(np.float32))
    got = jax.grad(lambda pp, xx: jnp.vdot(f(pp, xx), w), (0, 1))(params, x)
    want = jax.grad(lambda pp, xx: jnp.vdot(g(pp, xx), w), (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-3)


@pytest.mark.parametrize("case", ["round_trip", "index_arithmetic",
                                  "shifted_index_arithmetic"])
def test_window_partition_and_reverse(ref, case):
    from p2p_tpu.models.swinir import window_partition, window_reverse

    n, h, w, c, win = 2, 8, 12, 3, 4
    x = jnp.arange(n * h * w * c, dtype=jnp.float32).reshape(n, h, w, c)
    if case == "round_trip":
        back = window_reverse(window_partition(x, win), win, h, w)
        assert np.array_equal(np.asarray(back), np.asarray(x))
        return
    shift = 0 if case == "index_arithmetic" else win // 2
    rolled = jnp.roll(x, (-shift, -shift), axis=(1, 2)) if shift else x
    got = np.asarray(window_partition(rolled, win)).reshape(
        n, -1, win * win, c)
    tokens = ref.window_tokens(h, w, win, shift)
    want = np.asarray(x).reshape(n, h * w, c)[:, tokens]
    assert np.array_equal(got, want)


def test_shift_mask_against_region_labels(ref):
    from p2p_tpu.models.swinir import MASK_VALUE, shift_mask

    for h, w, win in ((16, 16, 4), (16, 24, 8)):
        got = np.asarray(shift_mask(h, w, win))
        want = ref.region_mask(h, w, win, win // 2)
        assert np.array_equal(got, want)
        # the first window lies whole in the bulk; the last holds four
        # regions
        assert not got[0].any() and (got[-1] == MASK_VALUE).any()


def test_relative_position_index_and_bias(ref):
    from p2p_tpu.models.swinir import relative_position_index
    from p2p_tpu.ops.pallas.window_attention import relative_bias

    for win in (4, 8):
        assert np.array_equal(relative_position_index(win),
                              ref.relative_index(win))
    idx = relative_position_index(8)
    assert idx.min() == 0 and idx.max() == 224 and idx[0, 0] == 7 * 15 + 7
    table = jnp.asarray(np.random.default_rng(0).standard_normal(
        (49, 2)).astype(np.float32))
    got = relative_bias(table, relative_position_index(4), 2)
    want = np.asarray(table)[ref.relative_index(4)].transpose(2, 0, 1)
    assert np.array_equal(np.asarray(got), want)   # a one-hot pick is exact
    # the table's gradient is the scatter-add of the picked cotangents
    ct = np.random.default_rng(1).standard_normal((2, 16, 16)).astype(
        np.float32)
    grad = jax.grad(lambda t: jnp.vdot(
        relative_bias(t, relative_position_index(4), 2), ct))(table)
    want_grad = np.zeros((49, 2), np.float32)
    np.add.at(want_grad, ref.relative_index(4).reshape(-1),
              ct.transpose(1, 2, 0).reshape(-1, 2))
    close(grad, want_grad, 1e-5)


def _program_layer(g, params, name, t, shift, keep=None):
    from p2p_tpu.models.swinir import SwinLayer

    layer = SwinLayer(heads=g.heads, shift=shift, window=g.window)
    return layer.apply({"params": params[name]}, t, keep)


@pytest.mark.parametrize("shift", [0, 2], ids=["unshifted", "shifted"])
def test_window_attention_against_the_reference(ref, toy_g, shift):
    """``WMSA_s`` alone: the program's roll + partition + attention +
    reverse + roll back against the reference's gather by index
    arithmetic, value and gradients (the bias table's among them)."""
    from p2p_tpu.models.swinir import (WindowAttention, shift_mask,
                                       window_partition, window_reverse)

    g, params, _, _, _ = toy_g
    name = "group_0_layer_1"
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((BS, LQ, LQ, 24)).astype(np.float32))
    attn = WindowAttention(heads=g.heads, window=g.window)

    def program(pp, xx):
        if shift:
            xx = jnp.roll(xx, (-shift, -shift), axis=(1, 2))
        out = attn.apply({"params": pp}, window_partition(xx, g.window),
                         shift_mask(LQ, LQ, g.window) if shift else None)
        out = window_reverse(out, g.window, LQ, LQ)
        return jnp.roll(out, (shift, shift), axis=(1, 2)) if shift else out

    reference = lambda pp, xx: ref.window_attention(  # noqa: E731
        traced(pp, "a"), "a", xx, shift)
    pa = params[name]["attn"]
    close(program(pa, h), reference(pa, h), 1e-4)
    w = jnp.asarray(rng.standard_normal(h.shape).astype(np.float32))
    got = jax.grad(lambda pp, xx: jnp.vdot(program(pp, xx), w), (0, 1))(pa, h)
    want = jax.grad(lambda pp, xx: jnp.vdot(reference(pp, xx), w),
                    (0, 1))(pa, h)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-3)


@pytest.mark.parametrize("depth", ["off", "on"])
def test_swin_layer_against_the_reference(ref, toy_g, depth):
    """``STL``, the shifted layer of the first group; with stochastic depth
    on, image 0 drops its attention branch and image 1 its MLP."""
    g, params, flat, _, _ = toy_g
    name = "group_0_layer_1"
    t = jnp.asarray(np.random.default_rng(3).standard_normal(
        (BS, LQ, LQ, 24)).astype(np.float32))
    keep = None
    if depth == "on":
        keep = jnp.asarray([[0.0, 1.0], [1.0, 0.0]]) / 0.9
    got = _program_layer(g, params, name, t, g.window // 2, keep)
    want = ref.swin_layer(flat, f"params_g/{name}", t, g.window // 2, keep)
    close(got, want, 1e-4)
    if depth == "on":
        plain = _program_layer(g, params, name, t, g.window // 2)
        assert float(jnp.max(jnp.abs(got - plain))) > 1e-3


def test_keep_masks_are_the_forward_s_own_draw(toy_g):
    """The masks ``keep_masks`` hands a reference are the ones a training
    forward with the same rng draws; the first layer always keeps."""
    from p2p_tpu.models.swinir import drop_path_rates

    g, params, _, x, _ = toy_g
    rngs = {"dropout": jax.random.key(11)}
    keep = g.apply({}, BS, method="keep_masks", rngs=rngs)
    assert keep.shape == (2 * g.layers, BS)
    assert set(np.unique(np.asarray(keep))) <= {0.0, 1.0}
    assert np.asarray(keep)[:2].all()
    drawn = g.apply({"params": params}, x, True, rngs=rngs)
    forced = g.apply({"params": params}, x, False, keep)
    assert np.array_equal(np.asarray(drawn), np.asarray(forced))
    rates = drop_path_rates(36)
    assert rates[0] == 0.0 and abs(rates[-1] - 0.1) < 1e-12
    # with rates this small nothing may drop at a toy depth: force one
    dropped = jnp.ones_like(keep).at[3, 0].set(0.0)
    other = g.apply({"params": params}, x, False, dropped)
    assert float(jnp.max(jnp.abs(other[0] - forced[0]))) > 1e-4


@pytest.mark.parametrize("upto", ["patch_norm", "group_0", "body"])
def test_generator_stages_against_the_reference(ref, toy_g, upto):
    """The head + patch norm, ``RSTB_0`` (two layers closed by its
    convolution, added to its input) and the whole body, read off the
    program's intermediates."""
    g, params, flat, x, x01 = toy_g
    _, state = g.apply({"params": params}, x, False,
                       capture_intermediates=True, mutable=["intermediates"])
    seen = {k: v["__call__"][0]
            for k, v in state["intermediates"].items() if k != "__call__"}
    if upto == "patch_norm":
        got = seen["patch_norm"]
    elif upto == "group_0":
        got = seen["patch_norm"] + seen["group_0_conv"]
    else:
        got = seen["conv_first"] + seen["conv_after_body"]
    close(got, ref.generator(flat, x01, upto=upto), 1e-4)


def test_upsampler_against_the_reference(ref, toy_g):
    """conv + LeakyReLU 0.01, two nearest x2 + conv + LeakyReLU 0.2 sites,
    conv_hr, conv_last + mean: the program's image from the body's output
    against the reference's upsampler on the same tensor."""
    g, params, flat, x, x01 = toy_g
    y = g.apply({"params": params}, x, False)
    assert y.shape == (BS, HQ, HQ, 3)
    close((y + 1.0) * 0.5, ref.upsampler(flat, ref.generator(
        flat, x01, upto="body")), 1e-4)


def test_upsampler_in_the_subpixel_form_against_the_reference(
        ref, toy_g, monkeypatch):
    """At the cell's extent ``conv_up1`` and ``conv_up2`` take the subpixel
    form with a ring of ZEROS (PR 43); the toy extent lies under the
    form's pixel floor, so the floor is taken away here: both sites tick
    the counter, read the SAME parameters (a checkpoint of the plain chain
    loads) and the image still equals the reference's upsampler, which
    upsamples, pads with zeros and convolves."""
    from p2p_tpu.ops import conv

    g, params, flat, x, x01 = toy_g
    plain = g.apply({"params": params}, x, False)
    monkeypatch.setattr(conv, "_NEAREST_UP2_MIN_PIXELS", 0)
    before = conv.conv_form_sites()["nearest_up2"]
    y = g.apply({"params": params}, x, False)
    assert conv.conv_form_sites()["nearest_up2"] - before == 2
    close((y + 1.0) * 0.5, ref.upsampler(flat, ref.generator(
        flat, x01, upto="body")), 1e-4)
    close(y, plain, 1e-5)
    # the engaged module builds the tree the plain chain built
    built = jax.eval_shape(lambda: g.init(jax.random.key(0), x, False))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), built["params"]) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), params)


@pytest.mark.parametrize("depth", ["off", "on"])
def test_generator_against_the_reference(ref, toy_g, depth):
    """The whole G, value and the gradient of every leaf, with the keep
    masks off and with one image's branches dropped."""
    g, params, flat, x, x01 = toy_g
    keep = None
    if depth == "on":
        keep = np.ones((2 * g.layers, BS), np.float32)
        keep[2, 0] = keep[5, 1] = keep[7, 0] = 0.0
        keep = jnp.asarray(keep)
    w = jnp.asarray(np.random.default_rng(4).standard_normal(
        (BS, HQ, HQ, 3)).astype(np.float32))
    program = lambda pp: (g.apply({"params": pp}, x, False, keep)  # noqa
                          + 1.0) * 0.5
    reference = lambda pp: ref.generator(  # noqa: E731
        traced(pp, "params_g"), x01, keep)
    close(program(params), reference(params), 1e-4)
    got = jax.grad(lambda pp: jnp.vdot(program(pp), w))(params)
    want = jax.grad(lambda pp: jnp.vdot(reference(pp), w))(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert float(np.max(np.abs(b))) > 0, path
        close(a, b, 2e-3)


def test_parameter_counts_at_the_published_widths():
    """Section 1's counts, from shapes alone."""
    from p2p_tpu.models.registry import define_D, define_G
    from p2p_tpu.utils.images import dummy_batch

    cfg = get_preset("swinir_realsr_x4")
    batch = dummy_batch(cfg, abstract=True)
    assert batch["input"].shape == (1, 64, 64, 3)
    assert batch["target"].shape == (1, 256, 256, 3)
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    g = jax.eval_shape(
        lambda k: define_G(cfg.model).init(
            k, jnp.zeros(batch["input"].shape), False), jax.random.key(0))
    d = jax.eval_shape(
        lambda k: define_D(cfg.model).init(
            k, jnp.zeros(batch["target"].shape)), jax.random.key(0))
    p = g["params"]
    assert count(p["group_0_layer_0"]) == 262530
    assert p["group_0_layer_0"]["attn"]["relative_position_bias_table"
                                        ].shape == (225, 6)
    assert count(p["group_0_conv"]) == 291780
    assert count(p["conv_first"]) + count(p["patch_norm"]) == 5040 + 360
    assert count(p["norm"]) + count(p["conv_after_body"]) == 360 + 291780
    assert (count(p["conv_before_upsample"]), count(p["conv_up1"]),
            count(p["conv_last"])) == (103744, 36928, 1731)
    assert count(p) == 36 * 262530 + 6 * 291780 + 5400 + 292140 + 216259
    assert count(p) == 11715559
    assert count(d["params"]) == 4376897 and count(d["spectral"]) == 1472
    stated = json.load(open(os.path.join(
        harness.BENCH_DIR, "configs", "swinir_m_realsr_x4_gan.json")))[
            "model"]["parameters_trainable"]
    assert stated["generator"] == count(p)
    assert stated["discriminator"] == count(d["params"])


# ------------------------------------------------ D, VGG19 and the step


def test_unet_discriminator_threads_its_spectral_vectors(ref, toy):
    """Logits at the image's extent, value and D's gradient, and the eight
    vectors after a call; bilinear x2 against the 3/4 - 1/4 blend."""
    from p2p_tpu.models.registry import define_D
    from p2p_tpu.models.unet_d import bilinear_up2

    cfg, state, flat = toy
    d = define_D(cfg.model)
    x = jnp.asarray(np.random.default_rng(5).uniform(
        -1, 1, (BS, HQ, HQ, 3)).astype(np.float32))
    small = jnp.asarray(np.random.default_rng(6).standard_normal(
        (1, 3, 5, 2)).astype(np.float32))
    close(bilinear_up2(small), ref.bilinear_up2(small), 1e-5)

    def program(pd):
        out, mut = d.apply({"params": pd, "spectral": state.spectral_d}, x,
                           mutable=["spectral"])
        return out[0][0], mut["spectral"]

    def reference(pd):
        p = {**flat, **traced(pd, "params_d")}
        return ref.discriminator(p, (x + 1.0) * 0.5)

    got, got_u = program(state.params_d)
    want, want_u = reference(state.params_d)
    assert got.shape == (BS, HQ, HQ, 1)
    close(got, want, 1e-4)
    assert len(want_u) == 8
    for key, u in flat_params(got_u, "spectral_d").items():
        close(u, want_u[key], 1e-4)
        assert float(np.max(np.abs(u - flat[key]))) > 1e-3   # it moved
    w = jnp.asarray(np.random.default_rng(7).standard_normal(
        got.shape).astype(np.float32))
    g_got = jax.grad(lambda pd: jnp.vdot(program(pd)[0], w))(state.params_d)
    g_want = jax.grad(lambda pd: jnp.vdot(reference(pd)[0], w))(
        state.params_d)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        close(a, b, 2e-3)
    # two of the ten convolutions carry no spectral norm
    assert set(state.params_d) - set(state.spectral_d) == {"conv0", "conv9"}


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
def test_preactivation_vgg19_taps(ref, vgg, store):
    """conv1_2, conv2_2, conv3_4, conv4_4, conv5_4 BEFORE the ReLU, in the
    float32 trunk against the reference and in the stored-bf16 trunk
    against the float32 one; the first table is what it was."""
    from p2p_tpu.losses.perceptual import VGG_TAPS, vgg_loss
    from p2p_tpu.models.vgg import ARCHS, VGG19Features

    assert ARCHS["vgg19"][1] == ("conv1_1", "conv2_1", "conv3_1", "conv4_1",
                                 "conv5_1")
    assert len(ARCHS["vgg19"][0]) == 17 and len(vgg) == 16
    rng = np.random.default_rng(8)
    x, y = (jnp.asarray(rng.uniform(-1, 1, (1, 32, 32, 3)).astype(
        np.float32)) for _ in range(2))
    flat = flat_params(vgg, "vgg")
    want = ref.vgg_taps(flat, (x + 1.0) * 0.5)
    assert [t.shape[-1] for t in want] == [64, 128, 256, 512, 512]
    assert all(float(t.min()) < 0 for t in want)     # before the ReLU
    if store == "float32":
        got = VGG19Features(imagenet_norm=True, arch="vgg19_preact").apply(
            {"params": vgg}, x)
        for a, b in zip(got, want):
            close(a, b, 1e-4)
        loss = vgg_loss(vgg, x, y, True, taps="preact")
        ref_loss = ref.perceptual(flat, (x + 1.0) * 0.5, (y + 1.0) * 0.5)
        assert abs(float(loss) - float(ref_loss)) <= 1e-4 * float(ref_loss)
        assert VGG_TAPS["preact"][1] == (0.1, 0.1, 1.0, 1.0, 1.0)
    else:
        xb = x.astype(jnp.bfloat16)
        got = VGG19Features(imagenet_norm=True, arch="vgg19_preact",
                            store_dtype=jnp.bfloat16).apply(
                                {"params": vgg}, xb)
        assert all(t.dtype == jnp.bfloat16 for t in got)
        for a, b in zip(got, want):
            close(a.astype(jnp.float32), b, 5e-2)
        ct = jax.grad(lambda im: vgg_loss(vgg, im, y.astype(jnp.bfloat16),
                                          True, taps="preact"))(xb)
        ct32 = jax.grad(lambda im: vgg_loss(vgg, im, y, True,
                                            taps="preact"))(x)
        cos = float(jnp.vdot(ct.astype(jnp.float32), ct32) / (
            jnp.linalg.norm(ct.astype(jnp.float32)) * jnp.linalg.norm(ct32)))
        assert cos > 0.98, cos


def _step_keeps(cfg, state, n):
    from benchmark.drivers import train_sr

    return [train_sr.keep_masks(cfg, None, int(state.noise_seed),
                                int(state.step) + i, BS) for i in range(n)]


def test_three_whole_train_steps_against_the_reference(ref, toy, vgg):
    """Each step's losses term by term, the first gradient as each
    optimizer got it (Adam's first moment over 1 - beta1), D's spectral
    vectors, the parameters and G's EMA after three steps, with the
    stochastic-depth masks the step drew."""
    from benchmark.drivers import train_sr
    from p2p_tpu.train.step import build_train_step

    cfg, state, flat = toy
    batches = [toy_batch(seed) for seed in (0, 1, 2)]
    step = build_train_step(cfg, vgg)
    live, seen, moments = jax.tree_util.tree_map(jnp.copy, state), [], None
    for batch in batches:
        live, metrics = step(live, batch)
        seen.append({k: float(v) for k, v in metrics.items()})
        if moments is None:
            moments = check.first_moments(live)
    losses, grads, params, spectral, ema = ref.StepReference(HYPER).follow(
        flat, batches, _step_keeps(cfg, state, 3))
    for got, want in zip(seen, losses):
        assert set(want) == {"loss_d", "loss_g", "g_l1", "g_vgg", "g_gan"}
        for name, value in want.items():
            assert abs(got[name] - value) <= 2e-3 * max(abs(value), 1e-3), (
                name, got[name], value)
    assert set(grads) == set(moments)
    for leaf in ref.NAMED_LEAVES.values():
        assert leaf in grads, leaf
    for leaf, want in grads.items():
        close(moments[leaf] / (1 - HYPER["beta1"]), want, 5e-3)
    after = check.flatten_state(live, FIELDS)
    for leaf, want in spectral.items():
        close(after[leaf], want, 1e-3)
    worst = check.worst_leaf_gap(
        {k: after[k] - flat[k] for k in params},
        {k: params[k] - flat[k] for k in params})
    assert worst["g"][0] < 0.05 and worst["d"][0] < 0.05, worst
    # a leaf's three-step change is a few ulps of a LayerNorm scale: the
    # values leaf by leaf, the change as one vector over all leaves
    for leaf, want in ema.items():
        close(after[leaf], want, 1e-5)
    assert train_sr.ema_change_gap(after, ema, flat) < 0.02
    # the EMA trails the parameters: 0.999 of itself a step
    p_leaf = "params_g/conv_last/Conv_0/kernel"
    e_leaf = "ema_g/conv_last/Conv_0/kernel"
    assert np.linalg.norm(after[e_leaf] - flat[e_leaf]) < 0.01 * (
        np.linalg.norm(after[p_leaf] - flat[p_leaf]))


def test_compiled_step_names_the_new_scopes(toy, vgg):
    """The scopes survive into the COMPILED step's text, where
    ``benchmark/scope_time.py`` joins a device trace with them, and the
    driver's count of the window split's own ops finds some."""
    from benchmark import scope_time
    from benchmark.drivers import train_sr
    from p2p_tpu.train.step import build_train_step

    cfg, state, _ = toy
    text = build_train_step(cfg, vgg).lower(state, toy_batch()).compile(
    ).as_text()
    owners = scope_time.instruction_scopes(text, train_sr.JOIN)
    found = {s for s in owners.values() if s}
    assert {"swin_attn", "swin_mlp", "swin_ln"} <= found, found
    nets = {s for s in scope_time.instruction_scopes(
        text, scope_time.program_scopes()).values() if s}
    assert {"G", "D_fake", "D_real", "loss_vgg", "loss_gan", "opt_g",
            "opt_d"} <= nets, nets
    ops = train_sr.window_ops(text)
    assert all(isinstance(v, int) and v > 0 for v in ops.values())


def test_bf16_softmax_fails_the_comparison(ref, toy):
    """The variants ``control_sr.py`` runs on the chip, at toy size, from
    the check's state (``train_sr.widened``: the logits spread over units)
    in the float32 program: the sound generator path passes limits that a
    softmax whose intermediates are kept in bfloat16 does not, nor such a
    LayerNorm, nor kernels rounded to 8-bit integers. The rounding is
    ``lax.reduce_precision``'s, which survives compilation."""
    from benchmark.drivers import train_sr
    from p2p_tpu.models.swinir import _stored
    from p2p_tpu.ops.pallas.window_attention import softmax as _softmax

    cfg, state, _ = toy
    wide = train_sr.widened(state)
    leaf = "group_0_layer_1"
    for name, factor in (("qkv", 6.0), ("relative_position_bias_table",
                                        50.0)):
        was = state.params_g[leaf]["attn"][name]
        now = wide.params_g[leaf]["attn"][name]
        was, now = (was["kernel"], now["kernel"]) if name == "qkv" else (
            was, now)
        close(now, factor * np.asarray(was), 1e-6)
    close(wide.ema_g["conv_last"]["Conv_0"]["kernel"],
          20.0 * np.asarray(state.ema_g["conv_last"]["Conv_0"]["kernel"]),
          1e-6)
    close(wide.params_g["conv_hr"]["Conv_0"]["kernel"],
          state.params_g["conv_hr"]["Conv_0"]["kernel"], 0.0)
    want = train_sr.reference_image(ref, wide, toy_batch())
    rows = {control: train_sr.generator_numbers(want, cfg, None, wide,
                                                toy_batch(), control)
            for control in ("",) + train_sr.CONTROLS}
    limits = {"generator_f32_mean_abs_levels": 0.01,
              "generator_f32_max_abs_levels": 0.1}
    quiet = lambda **_: None  # noqa: E731
    pick = lambda row: {k: row[k] for k in limits}  # noqa: E731
    assert rows[""]["generator_spread_levels"] > 5.0
    assert check.verdict(pick(rows[""]), limits, quiet)
    for control in train_sr.CONTROLS:
        assert not check.verdict(pick(rows[control]), limits, quiet), control
    # the rounding itself: bfloat16's eight significant bits, in a jit too
    x = jnp.asarray([1.0 + 2.0 ** -9, 3.0 + 2.0 ** -5], jnp.float32)
    assert jax.jit(lambda v: _stored(v, jnp.bfloat16))(x).tolist() == [
        1.0, 3.03125]
    assert _stored(x, jnp.float32) is x
    logits = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 64)).astype(np.float32) * 3.0)
    narrow = _softmax(logits, jnp.bfloat16)
    close(narrow, jax.nn.softmax(logits, -1), 4e-3)
    assert float(jnp.max(jnp.abs(narrow - jax.nn.softmax(logits, -1)))) > 1e-5


# ------------------------------------------------------------ the loader


@pytest.fixture(scope="module")
def sr_root(tmp_path_factory):
    """LQ / HQ folders: ``a/`` the HQ images, ``b/`` their x4 downsamples;
    a test split whose LQ images have ANOTHER extent (20 x 28)."""
    from PIL import Image

    from benchmark import datagen_sr

    root = str(tmp_path_factory.mktemp("sr_data") / "toy")
    datagen_sr.write_sr_dataset(root, 5, 4, 0, (HQ, HQ), SCALE, workers=1)
    for side in "ab":
        os.makedirs(os.path.join(root, "test", side), exist_ok=True)
    for i in range(2):
        lq, hq = datagen_sr.pair(5, 10 + i, (80, 112), SCALE)
        assert lq.shape == (20, 28, 3)
        Image.fromarray(hq).save(os.path.join(root, "test", "a",
                                              f"odd_{i}.png"))
        Image.fromarray(lq).save(os.path.join(root, "test", "b",
                                              f"odd_{i}.png"))
    return root


#: sha256 (first 16 hex digits) of every batch of one shuffled epoch as the
#: loader of the PARENT commit (94ed4c4, before ``scale``) made them from
#: ``make_synthetic_dataset(root, 6, 1, 48, seed=7)`` at 32x40, batch 2,
#: ``aug_seed`` 5, loader seed 3: keys, dtypes and bytes
PARENT_BATCHES = {
    ("a2b", False, "float32"): "b52ebea1fd8d473d",
    ("a2b", False, "uint8"): "54b1322eedcb08d4",
    ("a2b", True, "float32"): "5891808704fb491c",
    ("a2b", True, "uint8"): "eb311d2c02f2c776",
    ("b2a", False, "float32"): "165683849f77b550",
    ("b2a", False, "uint8"): "52469726b37421f7",
    ("b2a", True, "float32"): "150a347a7b88a8f4",
    ("b2a", True, "uint8"): "e0c69fd8441880a5",
}


@pytest.mark.parametrize("direction, augment, dtype", sorted(PARENT_BATCHES))
def test_loader_at_scale_one_feeds_the_parents_bytes(tmp_path, monkeypatch,
                                                     direction, augment,
                                                     dtype):
    """The host path every accepted cell shares: at ``scale`` 1 the
    rewritten ``PairedImageDataset.__getitem__`` hands the loader the bytes
    the parent's did, whichever side is the input, cropped and flipped or
    not, in either dtype."""
    import hashlib

    from p2p_tpu.data.pipeline import PairedImageDataset, make_loader
    from p2p_tpu.data.synthetic import make_synthetic_dataset

    monkeypatch.setenv("P2P_TPU_NO_GRAIN", "1")
    root = make_synthetic_dataset(str(tmp_path), 6, 1, 48, seed=7)
    ds = PairedImageDataset(root, "train", direction, 32, 40,
                            augment=augment, dtype=dtype, cache=False)
    ds.aug_seed = 5
    digest = hashlib.sha256()
    for batch in make_loader(ds, 2, shuffle=True, seed=3):
        for key in ("input", "target"):
            digest.update(key.encode())
            digest.update(str(batch[key].dtype).encode())
            digest.update(np.ascontiguousarray(batch[key]).tobytes())
    assert digest.hexdigest()[:16] == PARENT_BATCHES[
        direction, augment, dtype]


def test_loader_takes_the_same_crop_and_flip_on_both_extents(tmp_path):
    """An HQ image whose pixel value encodes its position, and its LQ copy
    by the same rule: after the joint crop and flip every LQ pixel still
    names the HQ block above it."""
    from PIL import Image

    from p2p_tpu.data.pipeline import PairedImageDataset

    lh = LQ * 286 // 256
    for side in "ab":
        os.makedirs(tmp_path / "train" / side)
    yy, xx = np.mgrid[0:lh * SCALE, 0:lh * SCALE]
    hq = np.stack([yy // SCALE, xx // SCALE, 0 * yy], -1).astype(np.uint8)
    lq = hq[::SCALE, ::SCALE]
    for i in range(6):
        Image.fromarray(hq).save(tmp_path / "train" / "a" / f"p{i}.png")
        Image.fromarray(lq).save(tmp_path / "train" / "b" / f"p{i}.png")
    ds = PairedImageDataset(str(tmp_path), "train", "b2a", HQ, augment=True,
                            aug_seed=3, dtype="uint8", scale=SCALE)
    offsets, flips = set(), set()
    for i in range(6):
        item = ds[i]
        assert item["input"].shape == (LQ, LQ, 3)
        assert item["target"].shape == (HQ, HQ, 3)
        assert np.array_equal(item["target"][::SCALE, ::SCALE],
                              item["input"])
        offsets.add((int(item["input"][0, 0, 0]), int(item["input"][0, 0, 1])))
        flips.add(bool(item["input"][0, 0, 1] > item["input"][0, -1, 1]))
    assert len(offsets) > 1 and flips == {True, False}
    plain = PairedImageDataset(str(tmp_path), "train", "b2a", lh * SCALE,
                               dtype="uint8", scale=SCALE)[0]
    assert np.array_equal(plain["input"], lq)
    with pytest.raises(ValueError):
        PairedImageDataset(str(tmp_path), "train", "b2a", HQ, scale=SCALE,
                           label_input=True)


# ------------------------------------------------------------- the CLIs


@pytest.fixture(scope="module")
def trained(sr_root, tmp_path_factory):
    from p2p_tpu.cli import train as cli_train

    work = str(tmp_path_factory.mktemp("sr_run"))
    argv = CLI + ["--data_root", sr_root, "--workdir", work, "--ndf", "8",
                  "--batch_size", "2", "--nepoch", "1", "--epochsave", "1",
                  "--threads", "0", "--log_every", "1", "--mesh", "data=1",
                  "--lambda_vgg", "0"]
    assert cli_train.main(argv) == 0
    return work, argv


def test_cli_train_runs_the_preset_through_the_trainer(trained, sr_root):
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.train.loop import Trainer

    work, argv = trained
    stream = [json.loads(x) for x in open(
        os.path.join(work, "metrics_toy.jsonl"))]
    steps = [r for r in stream if r.get("kind") == "train"]
    assert len(steps) == 2
    for name in ("loss_g", "loss_d", "g_gan", "g_l1"):
        assert all(np.isfinite(r[name]) for r in steps), name
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    assert cfg.model.scale == 4 and cfg.input_hw == (LQ, LQ)
    assert cfg.optim.lr_policy == "constant" and cfg.optim.beta1 == 0.9
    assert cfg.health.ema_decay == 0.999 and cfg.loss.gan_weight == 0.1
    trainer = Trainer(cfg, data_root=sr_root, workdir=work)
    try:
        assert trainer.maybe_resume() and int(trainer.state.step) == 2
        gauges = {k: v["value"] for k, v in trainer.obs.snapshot().items()
                  if k.startswith(("swinir_", "generator_gflop"))}
        # a trace of the Trainer's OWN step that took the kernel moves the
        # gauges and leaves a record; no other trace of the modules does
        # (the benchmark's float32 check, a control), before or after
        from p2p_tpu.ops.pallas import window_attention

        sites = dict(window_attention._SITES)
        try:
            window_attention.note_site(("some_layer", "attn"), 8)

            @jax.jit
            def step(state, batch):
                window_attention._SITES.clear()
                window_attention.note_site(("some_layer", "attn"), 16)
                loss = jnp.mean(batch["input"].astype(jnp.float32))
                return (state.replace(step=state.step + 1),
                        {"loss_g": loss, "loss_d": loss * 2.0})

            trainer.train_step = step
            trainer.train_epoch()
            window_attention.note_site(("some_layer", "attn"), 0)
            trainer.train_epoch()
            moved = trainer.obs.snapshot()
        finally:
            window_attention._SITES.clear()
            window_attention._SITES.update(sites)
    finally:
        trainer.close()
    assert moved["swinir_attn_kernel_layers"]["value"] == 1
    assert moved["swinir_attn_kernel_windows_per_block"]["value"] == 16
    said = [json.loads(x) for x in open(
        os.path.join(work, "metrics_toy.jsonl"))]
    assert [r["swinir_attn_kernel_windows_per_block"] for r in said
            if r.get("kind") == "generator_trace"] == [16.0]
    assert gauges["swinir_layers"] == 6
    assert gauges["swinir_windows_per_image"] == (LQ // 8) ** 2
    # no layer's attention ran as the kernel on the CPU, and no record says so
    assert gauges["swinir_attn_kernel_layers"] == 0
    assert gauges["swinir_attn_kernel_windows_per_block"] == 0
    assert not [r for r in stream if r.get("kind") == "generator_trace"]
    parts = ("attn_products", "qkv_proj", "mlp", "group_convs", "upsampler")
    assert abs(sum(gauges[f"swinir_{p}_gflop_per_image"] for p in parts)
               - gauges["generator_gflop_per_image"]) < 1e-9


def test_published_arithmetic_matches_the_issue_s_count():
    """The gauges at the published widths, by hand from the shapes: 50.0
    GMAC in the body (attention products 3.4, qkv / proj 19.1, MLP 19.1,
    group convolutions 8.4), 5.97 in the upsampler, 25.5 a pass of VGG19
    through conv5_4 (ISSUE 38 reckoned 50.4 / 5.6 / 25.5)."""
    from p2p_tpu.models.registry import generator_gauges

    g = generator_gauges(get_preset("swinir_realsr_x4").model, 256, 256)
    body = sum(g[f"swinir_{p}_gflop_per_image"] for p in (
        "attn_products", "qkv_proj", "mlp", "group_convs")) / 2
    assert abs(body - 50.0) < 0.1, body
    assert abs(g["swinir_attn_products_gflop_per_image"] / 2 - 3.4) < 0.05
    assert abs(g["swinir_upsampler_gflop_per_image"] / 2 - 5.97) < 0.05
    assert abs(g["swinir_vgg_gflop_per_image"] / 2 - 25.5) < 0.3


def test_cli_infer_upscales_an_image_of_another_extent(trained, sr_root,
                                                       tmp_path):
    """A 20x28 LQ image is padded to 24x32 by mirroring, run, and its
    80x112 result cropped out: what ``cli.infer`` wrote is the generator's
    output on the padded image, from the checkpoint's EMA weights."""
    from PIL import Image

    from p2p_tpu.cli import infer as cli_infer
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.models.registry import define_G
    from p2p_tpu.train.loop import Trainer
    from p2p_tpu.utils.images import to_uint8_img

    work, argv = trained
    out = str(tmp_path / "pred")
    assert cli_infer.main(CLI + [
        "--data_root", sr_root, "--workdir", work, "--out", out,
        "--dtype", "f32"]) == 0
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    trainer = Trainer(cfg, data_root=sr_root, workdir=work)
    try:
        assert trainer.maybe_resume()
        weights = jax.device_get(trainer.state.ema_g)
    finally:
        trainer.close()
    g = define_G(cfg.model)
    for i in range(2):
        lq = np.asarray(Image.open(os.path.join(
            sr_root, "test", "b", f"odd_{i}.png")))
        padded = np.pad(lq, ((0, 4), (0, 4), (0, 0)), mode="symmetric")
        assert padded.shape == (24, 32, 3)
        assert np.array_equal(padded[20:, :28], lq[:15:-1])
        x = (padded.astype(np.float32) - 127.5) / 127.5
        want = to_uint8_img(np.asarray(g.apply(
            {"params": weights}, x[None], False))[0][:80, :112])
        served = np.asarray(Image.open(os.path.join(out, f"odd_{i}.png")))
        assert served.shape == (80, 112, 3)
        assert np.max(np.abs(served.astype(np.int32) - want)) <= 1


# --------------------------------------------- audits, lint and the engine


def _site_lint_batch(cfg):
    from p2p_tpu.cli.lint import _tiny_batch

    batch = _tiny_batch(cfg)
    assert batch["target"].shape[1] == SCALE * batch["input"].shape[1]
    return None


def _site_memory_audit(cfg):
    from p2p_tpu.analysis.memory_audit import (activation_peak_bytes,
                                               dead_restore_findings,
                                               state_budget)

    assert activation_peak_bytes(cfg, cfg.data.batch_size) > 0
    assert dead_restore_findings(("swinir_realsr_x4",)) == []
    one = state_budget(cfg, {"data": 1})
    assert one["opt"] >= 2 * one["params"] and one["ema"] > 0
    assert 0 < one["other"] < 2048        # D's eight spectral vectors


def _site_sharding_audit(cfg):
    from p2p_tpu.analysis.sharding_audit import (abstract_train_state,
                                                 audit_rules)
    from p2p_tpu.parallel.rules import trainstate_rules

    state = abstract_train_state(cfg)
    table = state.params_g["group_0_layer_0"]["attn"][
        "relative_position_bias_table"]
    assert table.shape == (225, 2)
    sizes = {"data": 2, "fsdp": 2, "spatial": 1, "time": 1, "model": 1,
             "pipe": 1}
    assert audit_rules(trainstate_rules(sizes), state, sizes) == []
    # under a model axis the window transformer's leaves are replicated by
    # rows of their own: none of those rows is dead on this tree
    sizes.update(fsdp=1, model=2)
    dead = [f.message for f in audit_rules(
        trainstate_rules(sizes), state, sizes)]
    assert not [m for m in dead if "attn" in m or "fc1" in m
                or "norm" in m], dead


def _site_engine(cfg):
    from p2p_tpu.serve.engine import InferenceEngine
    from p2p_tpu.serve.tenancy import serving_sample_batch
    from p2p_tpu.train.state import create_infer_state

    sample = serving_sample_batch(cfg)
    assert sample["input"].shape == (1, LQ, LQ, 3)
    state = create_infer_state(cfg, jax.random.key(0), sample)
    engine = InferenceEngine(cfg, state, buckets=(1,), dtype="f32",
                             with_metrics=False)
    (spec,) = engine._abstract_batch(1).values()
    assert spec.shape == (1, LQ, LQ, 3)
    pred, _, _ = engine.infer_batch({"input": toy_batch()["input"][:1]})
    assert np.asarray(pred).shape == (1, HQ, HQ, 3)


def _site_eval_step(cfg):
    """The evaluation: PSNR / SSIM of the x4 output against the HQ target."""
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_eval_step

    state = create_train_state(cfg, jax.random.key(0), toy_batch())
    pred, metrics = build_eval_step(cfg)(state, toy_batch())
    assert pred.shape == (BS, HQ, HQ, 3)
    assert metrics["psnr"].shape == (BS,) and np.isfinite(
        np.asarray(metrics["psnr"])).all()


@pytest.mark.parametrize("site", [
    _site_lint_batch, _site_memory_audit, _site_sharding_audit,
    _site_engine, _site_eval_step], ids=lambda f: f.__name__[6:])
def test_audits_lint_and_the_engine_know_the_two_extents(site):
    """``cli/lint``, the memory and sharding audits, the serving engine and
    the evaluation take the preset as they take every other, with an
    input of the target's extent over four."""
    site(toy_cfg())


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
    fields at their defaults, an input of the target's extent, no scope of
    this PR in its jaxpr (the five accepted cells' lowered programs hash as
    at the parent: ``scripts/step_program_hash.py``, PERF.md section 6)."""
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.train.step import build_train_step
    from p2p_tpu.utils.images import wire_spec

    cfg = _tiny(preset)
    assert cfg.model.scale == 1 and cfg.input_hw == cfg.image_hw
    assert cfg.model.discriminator == "patch"
    assert cfg.loss.gan_weight == 1.0 and cfg.loss.vgg_taps == "relu"
    state = abstract_train_state(cfg)
    batch = {k: jax.ShapeDtypeStruct((1,) + wire_spec(cfg, k)[0],
                                     wire_spec(cfg, k)[1])
             for k in ("input", "target")}
    assert batch["input"].shape[:3] == batch["target"].shape[:3]

    def text(c):
        jaxpr = jax.make_jaxpr(build_train_step(c, jit=False))(state, batch)
        return re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))

    base = text(cfg)
    for name in ("swin_attn", "swin_window", "swin_mlp", "swin_ln"):
        assert name not in base, name


def test_the_compiled_step_holds_no_seed():
    """The seed of a step that draws noise (``ModelConfig.use_dropout``) is
    DATA (``TrainState.noise_seed``): two runs with different ``--seed``
    lower the same step, so one compile-cache entry serves both (on the
    chip a seed baked in cost 180 s and 297 MB a run, PERF.md section 6,
    PR 38). A U-Net dropout preset takes the same path; a state whose step
    draws no noise has no such leaf."""
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.train.step import build_train_step
    from p2p_tpu.utils.images import dummy_batch

    def lowered(cfg, seed):
        cfg = cfg.replace(
            train=dataclasses.replace(cfg.train, seed=seed),
            loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0))
        state = abstract_train_state(cfg)
        batch = dummy_batch(cfg, (cfg.data.batch_size,), abstract=True)
        return state, build_train_step(cfg).lower(state, batch).as_text()

    state, one = lowered(toy_cfg(), 1)
    assert state.noise_seed.shape == () and state.noise_seed.dtype == "uint32"
    assert lowered(toy_cfg(), 2)[1] == one
    # the jitted init neither: its seed is drawn from the rng it is handed
    from p2p_tpu.train.state import _jitted_train_init, create_train_state

    batch = dummy_batch(toy_cfg(), (BS,))
    inits = [_jitted_train_init(
        toy_cfg().replace(train=dataclasses.replace(toy_cfg().train,
                                                    seed=seed)), 1, None
    ).lower(jax.random.key(seed), batch).as_text() for seed in (1, 2)]
    assert inits[0] == inits[1]
    seeds = {int(create_train_state(toy_cfg(), jax.random.key(k),
                                    batch).noise_seed) for k in (1, 2)}
    assert len(seeds) == 2          # and it follows the run's seed
    facades = _tiny("facades")
    state, one = lowered(facades, 1)
    assert facades.model.use_dropout and state.noise_seed.dtype == "uint32"
    assert lowered(facades, 2)[1] == one
    assert lowered(_tiny("reference"), 1)[0].noise_seed is None


def test_the_train_step_still_names_no_generator():
    import inspect

    from p2p_tpu.models.registry import (generator_side,
                                         input_extent_multiple)
    from p2p_tpu.train import step

    src = inspect.getsource(step)
    for banned in ("model.generator", "models.swinir", "swinir", "unet_d"):
        assert banned not in src, banned
    model = get_preset("swinir_realsr_x4").model
    assert generator_side(model) is None
    assert input_extent_multiple(model) == 8
    assert input_extent_multiple(get_preset("reference").model) == 1
