"""VQGAN (preset ``vqgan_imagenet_f16``) at a toy size — ch 32, ch_mult
(1, 2), 1 block a level, 64 codes of width 32, 32x32 images, batch 2,
seeded weights — held case by case against the plain reference of its
configuration (``benchmark/reference/vqgan_imagenet_f16_16384.py``:
float32, nothing of the program imported): GroupNorm + swish, the
residual block with and without its shortcut, attention, the asymmetric
downsampling and the upsampling, the quantizer (distances, indices, the
straight-through gradient, the codebook's gradient, where beta sits),
LPIPS, the BatchNorm discriminator with its threaded statistics, the
adaptive weight against two whole-graph gradients, three whole train
steps, ``cli.train`` -> ``cli.infer``; a control (bf16 distances) that the
comparison must refuse; and the other presets' steps, which the new
fields must not reach.

Tolerances: both sides run float32 on the CPU, the program with one-pass
moments and XLA's default precision, the reference two-pass at
``Precision.HIGHEST``: forward values agree to ~1e-5 of their scale, 1e-4
is asked; gradients 2e-3 of a leaf's largest entry.
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

H = W = 32
BS = 2
CODES, WIDTH = 64, 32
FIELDS = ("params_g", "params_d", "batch_stats_d")
HYPER = dict(steps=3, lr_g=5.4e-5, lr_d=5.4e-5, beta1=0.5, beta2=0.9,
             eps=1e-8, disc_weight=0.75, codebook_weight=1.0,
             perceptual_weight=1.0, vq_beta=0.25)
CLI = ["--preset", "vqgan_imagenet_f16", "--name", "toy", "--dataset", "toy",
       "--image_size", str(H), "--ngf", "32", "--vq_ch_mult", "1,2",
       "--vq_res_blocks", "1", "--vq_codes", str(CODES), "--vq_embed_dim",
       str(WIDTH)]


def toy_cfg(**model):
    cfg = get_preset("vqgan_imagenet_f16")
    return cfg.replace(
        model=dataclasses.replace(
            cfg.model, ngf=32, ndf=8, vq_ch_mult=(1, 2), vq_res_blocks=1,
            vq_codes=CODES, vq_embed_dim=WIDTH, **model),
        data=dataclasses.replace(cfg.data, image_size=H, batch_size=BS,
                                 test_batch_size=BS),
        train=dataclasses.replace(cfg.train, mixed_precision=False))


def toy_batch(seed=0):
    rng = np.random.default_rng(seed)
    img = rng.integers(0, 256, (BS, H, W, 3)).astype(np.uint8)
    return {"input": img, "target": img}


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        float(np.max(np.abs(got - want))), scale)


def flat_params(tree, prefix, leaf_as=np.asarray):
    return {check.leaf_key(prefix, path): leaf_as(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def ref():
    return harness.load_by_path("reference", "vqgan_imagenet_f16_16384")


@pytest.fixture(scope="module")
def lpips_params():
    from p2p_tpu.losses.lpips import load_lpips_params

    return load_lpips_params()


@pytest.fixture(scope="module")
def toy(lpips_params):
    """cfg, batch, the seeded state and its flat copy with the frozen
    LPIPS tree under ``vgg/`` (made before a step donates the state). The
    codebook is spread to the latent's scale: at its seeded +-1/codes
    every row is the same code to four digits and nothing of the search
    is compared."""
    from p2p_tpu.train.state import create_train_state

    cfg, batch = toy_cfg(), toy_batch()
    state = create_train_state(cfg, jax.random.key(0), batch)
    rng = np.random.default_rng(3)
    params_g = jax.tree_util.tree_map(lambda x: x, state.params_g)
    params_g["quantize"]["embedding"] = jnp.asarray(
        rng.standard_normal((CODES, WIDTH)).astype(np.float32) * 0.5)
    state = state.replace(params_g=params_g)
    flat = check.flatten_state(state, FIELDS)
    flat.update(flat_params(lpips_params, "vgg"))
    return cfg, batch, state, flat


def _module_case(module, shape, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    variables = module.init(jax.random.key(seed), x)
    # off the init's ones and zeros, so that scale and bias are compared
    params = jax.tree_util.tree_map(
        lambda w: w + 0.1 * rng.standard_normal(w.shape).astype(np.float32),
        variables["params"])
    return x, params


# ------------------------------------------------------------- the blocks


@pytest.mark.parametrize("swish", [True, False], ids=["swish", "plain"])
def test_group_norm_against_the_reference(ref, swish):
    """Value and both gradients; 64 channels, so a group holds two."""
    from p2p_tpu.ops.norm import GroupNorm, make_norm_act

    module = GroupNorm(swish=swish)
    x, params = _module_case(module, (2, 8, 8, 64))
    p = flat_params(params, "gn")
    f = lambda pp, xx: module.apply({"params": pp}, xx)  # noqa: E731
    g = lambda pp, xx: ref.group_norm(flat_params_j(pp, "gn"), "gn", xx,  # noqa
                                      swish)
    close(f(params, x), ref.group_norm(p, "gn", x, swish), 1e-4)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        x.shape).astype(np.float32))
    got = jax.grad(lambda pp, xx: jnp.vdot(f(pp, xx), w), (0, 1))(params, x)
    want = jax.grad(lambda pp, xx: jnp.vdot(g(pp, xx), w), (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-3)
    if swish:
        # the one definition, through the factory the blocks could call
        module2 = make_norm_act("group_swish")
        import flax.linen as nn

        class Wrap(nn.Module):
            @nn.compact
            def __call__(self, y):
                return module2(y)

        close(Wrap().apply({"params": {"GroupNorm_0": params}}, x),
              f(params, x), 1e-6)


def flat_params_j(tree, prefix):
    """``flat_params`` on traced leaves."""
    return flat_params(tree, prefix, leaf_as=lambda leaf: leaf)


def _block_cases():
    from p2p_tpu.models import vqgan
    from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer

    init = vqgan._KERNEL_INIT
    return {
        "res_identity": (vqgan.ResnetBlock(64), (2, 8, 8, 64),
                         lambda r, p, x: r.res_block(p, "m", x)),
        "res_shortcut": (vqgan.ResnetBlock(64), (2, 8, 8, 32),
                         lambda r, p, x: r.res_block(p, "m", x)),
        "attn": (vqgan.AttnBlock(), (2, 4, 4, 64),
                 lambda r, p, x: r.attn_block(p, "m", x)),
        # odd rows and columns read the pad below and to the right only
        "down": (ConvLayer(32, kernel_size=3, stride=2,
                           pad_mode="zero_after", kernel_init=init),
                 (2, 8, 8, 32),
                 lambda r, p, x: r._conv(
                     p, "m", jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0))),
                     0, 2)),
        "up": (UpsampleConvLayer(32, kernel_size=3, upsample=2,
                                 pad_mode="zero", kernel_init=init),
               (2, 8, 8, 32),
               lambda r, p, x: r._conv(
                   p, "m", r.nn.upsample_nearest(x, 2), 1)),
    }


@pytest.mark.parametrize("case", ["res_identity", "res_shortcut", "attn",
                                  "down", "up"])
def test_block_against_the_reference(ref, case):
    module, shape, reference = _block_cases()[case]
    x, params = _module_case(module, shape)
    got = module.apply({"params": params}, x)
    want = reference(ref, flat_params(params, "m"), x)
    close(got, want, 1e-4)
    if case == "res_shortcut":
        assert "nin_shortcut" in params and got.shape[-1] == 64
    if case == "down":
        assert got.shape == (2, 4, 4, 32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        got.shape).astype(np.float32))
    grads = jax.grad(lambda pp: jnp.vdot(
        module.apply({"params": pp}, x), w))(params)
    wants = jax.grad(lambda pp: jnp.vdot(
        reference(ref, flat_params_j(pp, "m"), x), w))(params)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(wants)):
        if check.leaf_key("m", path) in ref.zero_gradient_leaves(
                flat_params(params, "m")):
            # k's bias shifts every logit of a row alike: softmax does not
            # see it, both sides hand back rounding noise
            assert case == "attn" and float(jnp.max(jnp.abs(a))) < 1e-5
            continue
        close(a, b, 2e-3)


# ----------------------------------------------------------- the quantizer


@pytest.fixture(scope="module")
def quantizer_case():
    from p2p_tpu.models.vqgan import VectorQuantizer

    rng = np.random.default_rng(5)
    z = jnp.asarray(rng.standard_normal((2, 4, 4, WIDTH)).astype(np.float32))
    book = jnp.asarray(rng.standard_normal((CODES, WIDTH)).astype(np.float32))
    return VectorQuantizer(CODES, WIDTH), z, book


@pytest.mark.parametrize("what", ["distances", "indices", "straight_through",
                                  "codebook_gradient", "beta_placement"])
def test_quantizer_against_the_reference(ref, quantizer_case, what):
    module, z, book = quantizer_case
    p = {ref.CODEBOOK: book}
    run = lambda zz, bb: module.apply(  # noqa: E731
        {"params": {"embedding": bb}}, zz)
    out, loss, idx, dist, _ = run(z, book)
    r_out, r_loss, r_idx, r_dist = ref.quantizer(p, z, 0.25)
    if what == "distances":
        close(dist, r_dist, 1e-5)
        # less |z|^2: the full squared distance is that much more
        full = jnp.sum(jnp.square(z.reshape(-1, 1, WIDTH) - book[None]), -1)
        close(dist + jnp.sum(jnp.square(z.reshape(-1, WIDTH)), 1)[:, None],
              full, 1e-4)
    elif what == "indices":
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(r_idx))
        close(out, np.asarray(book)[np.asarray(idx)], 1e-6)
        close(loss, r_loss, 1e-5)
    elif what == "straight_through":
        # the decoder's gradient reaches z unchanged, plus the
        # commitment term's 2 (z - e_k) / size
        w = jnp.ones_like(z)
        got = jax.grad(lambda zz: jnp.vdot(run(zz, book)[0], w)
                       + run(zz, book)[1])(z)
        want = jax.grad(lambda zz: jnp.vdot(
            ref.quantizer(p, zz, 0.25)[0], w)
            + ref.quantizer(p, zz, 0.25)[1])(z)
        close(got, want, 1e-5)
        close(got, 1.0 + 2.0 * (z - out) / z.size, 1e-5)
    elif what == "codebook_gradient":
        got = jax.grad(lambda bb: run(z, bb)[1])(book)
        want = jax.grad(lambda bb: ref.quantizer(
            {ref.CODEBOOK: bb}, z, 0.25)[1])(book)
        close(got, want, 1e-5)
        used = np.unique(np.asarray(idx))
        unused = np.setdiff1d(np.arange(CODES), used)
        assert np.all(np.asarray(got)[unused] == 0) and len(unused) > 0
        assert np.all(np.abs(np.asarray(got)[used]).sum(1) > 0)
    else:
        # legacy: beta weighs the CODEBOOK term (the gradient into e), not
        # the commitment term (the gradient into z)
        from p2p_tpu.models.vqgan import BETA

        assert BETA == 0.25
        pull = 2.0 * (np.asarray(z) - np.asarray(out)) / z.size
        close(jax.grad(lambda zz: run(zz, book)[1])(z), pull, 1e-6)
        want = np.zeros(book.shape, np.float32)
        np.add.at(want, np.asarray(idx).reshape(-1),
                  -BETA * pull.reshape(-1, WIDTH))
        close(jax.grad(lambda bb: run(z, bb)[1])(book), want, 1e-5)


# ------------------------------------------------- losses and discriminator


def test_lpips_against_the_reference(ref, toy):
    from p2p_tpu.losses.lpips import lpips_loss
    from p2p_tpu.models.vgg import ARCHS, vgg_gflop_per_image

    _, batch, _, flat = toy
    rng = np.random.default_rng(7)
    x = ref.nn.to_unit(jnp.asarray(batch["target"]))
    y = jnp.clip(x + 0.3 * rng.standard_normal(x.shape).astype(np.float32),
                 -1, 1)
    tree = _lpips_tree(flat)
    assert len(tree["vgg16"]) == 13 and len(tree["lin"]) == 5
    assert all(np.all(v >= 0) for v in tree["lin"].values())
    assert ARCHS["vgg16"][1] == ("conv1_2", "conv2_2", "conv3_3", "conv4_3",
                                 "conv5_3")
    assert 30 < vgg_gflop_per_image("vgg16", 256, 256) < 45
    got, gx = jax.value_and_grad(lambda a: lpips_loss(tree, a, y))(x)
    want, wx = jax.value_and_grad(
        lambda a: jnp.mean(ref.lpips(flat, a, y)))(x)
    assert float(want) > 1e-3
    close(got, want, 1e-4)
    close(gx, wx, 2e-3)
    assert float(lpips_loss(tree, x, x)) == 0.0


def test_batchnorm_discriminator_threads_its_statistics(ref, toy):
    """The image alone in, k4 pad 1, no bias under the norms, the batch's
    own moments; the running statistics after the fake and the real call
    are the reference's."""
    from p2p_tpu.train.state import build_models

    cfg, batch, state, flat = toy
    _, d, _ = build_models(cfg)
    x = ref.nn.to_unit(jnp.asarray(batch["target"]))
    r = jnp.flip(x, 0) * 0.5
    kernels = state.params_d["scale0"]
    assert kernels["_PlainConv_0"]["Conv_0"]["kernel"].shape == (4, 4, 3, 8)
    assert "bias" not in kernels["_PlainConv_1"]["Conv_0"]
    assert "bias" in kernels["_PlainConv_3"]["Conv_0"]
    stats = state.batch_stats_d
    pred, mut = d.apply({"params": state.params_d, "batch_stats": stats}, r,
                        mutable=["batch_stats"])
    want, new = ref.discriminator(flat, r)
    assert pred[0][-1].shape == want.shape == (BS, 6, 6, 1)
    close(pred[0][-1], want, 1e-4)
    pred2, mut2 = d.apply({"params": state.params_d, **mut}, x,
                          mutable=["batch_stats"])
    want2, new2 = ref.discriminator({**flat, **new}, x)
    close(pred2[0][-1], want2, 1e-4)
    got_stats = flat_params(mut2["batch_stats"], "batch_stats_d")
    assert set(got_stats) == set(new2) and len(new2) == 4
    for k in new2:
        close(got_stats[k], new2[k], 1e-4)
        assert np.linalg.norm(got_stats[k] - flat[k]) > 1e-4


def test_patchgan_says_which_norms_it_takes():
    from p2p_tpu.models.patchgan import NLayerDiscriminator

    x = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(ValueError, match="batch_stats_d"):
        NLayerDiscriminator(norm="layer").init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="plain convolutions"):
        NLayerDiscriminator(norm="batch", use_spectral_norm=True).init(
            jax.random.key(0), x)


# ------------------------------------------------------------ whole steps


def _program_side(cfg, state, batch):
    """The generator's forward as the step sees it: image and the ``vq``
    collection."""
    from p2p_tpu.models.vqgan import side_outputs
    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest

    g, _, _ = build_models(cfg)
    image, mut = g.apply({"params": state.params_g},
                         ingest(jnp.asarray(batch["input"])), True,
                         mutable=["vq"])
    return image, side_outputs(mut["vq"])


def test_autoencoder_forward_against_the_reference(ref, toy):
    cfg, batch, state, flat = toy
    image, side = _program_side(cfg, state, batch)
    want = ref.autoencoder(flat, ref.nn.to_unit(jnp.asarray(batch["target"])))
    np.testing.assert_array_equal(np.asarray(side["indices"]),
                                  np.asarray(want["indices"]))
    assert len(np.unique(np.asarray(side["indices"]))) > 8
    for name in ("image", "codebook_loss", "distances", "latent",
                 "last_input"):
        close(image if name == "image" else side[name], want[name], 1e-4)
    # the house contract: teacher-forced through the program's codes
    pred, dist, moments = ref.generator_path(
        flat, batch["target"], True, code=np.asarray(side["indices"]),
        latent=np.asarray(side["latent"]))
    close(pred, image, 1e-4)
    close(moments["distances_on_latent"], side["distances"], 1e-5)


def test_adaptive_weight_against_two_whole_graph_gradients(ref, toy):
    """lambda of the program's first step (one pull of both cotangents
    through the last convolution) against ``jax.grad`` of nll and of g
    with respect to the last kernel, each through the whole graph, and
    against the reference's image-by-image sums."""
    from p2p_tpu.train.step import build_train_step

    cfg, batch, state, flat = toy
    tree = _lpips_tree(flat)
    _, metrics = build_train_step(cfg, tree)(
        jax.tree_util.tree_map(jnp.copy, state), batch)
    x = ref.nn.to_unit(jnp.asarray(batch["target"]))
    whole = float(ref.adaptive_weight_whole(flat, x))
    assert 1e-3 < whole < 1e3
    assert abs(float(metrics["d_weight"]) - whole) <= 1e-3 * whole
    losses, _, _ = ref.StepReference(HYPER).step(
        {k: jnp.asarray(v) for k, v in flat.items()}, x)
    assert abs(float(losses["d_weight"]) - whole) <= 1e-4 * whole


def _lpips_tree(flat):
    tree = {"vgg16": {}, "lin": {}}
    for k, v in flat.items():
        if k.startswith("vgg/vgg16/"):
            _, _, layer, leaf = k.split("/")
            tree["vgg16"].setdefault(layer, {})[leaf] = jnp.asarray(v)
        elif k.startswith("vgg/lin/"):
            tree["lin"][k.rsplit("/", 1)[1]] = jnp.asarray(v)
    return tree


def test_three_whole_train_steps_against_the_reference(ref, toy):
    """Each step's losses and lambda, the first gradient as each
    optimizer got it (Adam's first moment over 1 - beta1), D's running
    statistics and the parameters after three steps, the codebook's own
    leaf named."""
    from p2p_tpu.train.step import build_train_step

    cfg, _, state, flat = toy
    batches = [toy_batch(seed) for seed in (0, 1, 2)]
    step = build_train_step(cfg, _lpips_tree(flat))
    live, seen, moments = jax.tree_util.tree_map(jnp.copy, state), [], None
    for batch in batches:
        live, metrics = step(live, batch)
        seen.append({k: float(v) for k, v in metrics.items()})
        if moments is None:
            moments = check.first_moments(live)
    losses, grads, params, stats = ref.StepReference(HYPER).follow(
        flat, batches)
    for got, want in zip(seen, losses):
        for name, value in want.items():
            assert abs(got[name] - value) <= 2e-3 * max(abs(value), 1e-3), (
                name, got[name], value)
        assert 1 < got["vq_codes_used"] <= CODES
        assert 1 < got["vq_perplexity"] <= got["vq_codes_used"] + 1e-3
    assert set(grads) == set(moments) and ref.CODEBOOK in grads
    dead = ref.zero_gradient_leaves(flat)
    assert len(dead) == 5 and dead < set(grads)
    # at 32 channels a group holds ONE channel, so the norm after a
    # convolution cancels that convolution's bias (a toy-size artefact:
    # the published widths hold 4 to 16 a group): such leaves hold rounding
    # noise on both sides, like the dead ones
    largest = max(float(np.max(np.abs(g))) for g in grads.values())
    dead |= {k for k, g in grads.items()
             if float(np.max(np.abs(g))) < 1e-5 * largest}
    assert all(k.endswith("/bias") for k in dead) and len(dead) < 20
    for leaf, want in grads.items():
        if leaf not in dead:
            close(moments[leaf] / (1 - HYPER["beta1"]), want, 5e-3)
    params = {k: v for k, v in params.items() if k not in dead}
    after = check.flatten_state(live, FIELDS)
    for leaf, want in stats.items():
        close(after[leaf], want, 1e-3)
    worst = check.worst_leaf_gap(
        {k: after[k] - flat[k] for k in params},
        {k: params[k] - flat[k] for k in params})
    assert worst["g"][0] < 0.05 and worst["d"][0] < 0.05, worst
    moved = np.linalg.norm(after[ref.CODEBOOK] - flat[ref.CODEBOOK])
    want_moved = np.linalg.norm(params[ref.CODEBOOK] - flat[ref.CODEBOOK])
    assert want_moved > 0 and abs(moved - want_moved) <= 0.05 * want_moved


def test_compiled_step_names_the_new_scopes(toy):
    """The scopes of this PR survive into the COMPILED step's text, where
    ``benchmark/scope_time.py`` joins a device trace with them: the three
    step scopes and, inside ``G``, the mechanisms; every convolution and
    product of the lowered step lies under a step scope."""
    from benchmark import scope_time
    from jax._src.lib.mlir import ir
    from p2p_tpu.train.step import STEP_SCOPES, build_train_step

    cfg, batch, state, flat = toy
    lowered = build_train_step(cfg, _lpips_tree(flat)).lower(state, batch)
    text = lowered.compile().as_text()
    for scopes in (("loss_lpips", "loss_adaptive", "loss_codebook"),
                   ("gn_swish", "attn", "vq")):
        owners = scope_time.instruction_scopes(text, scopes)
        assert set(scopes) <= set(owners.values()), scopes
    heavy = []

    def visit(op):
        if op.name in ("stablehlo.convolution", "stablehlo.dot_general"):
            heavy.append(str(op.location).split('"')[1])
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir().operation.walk(visit)
    owners = [scope_time.first_scope(name, STEP_SCOPES) for name in heavy]
    assert len(heavy) > 100
    assert [n for n, o in zip(heavy, owners) if o is None] == []
    assert {"G", "D_fake", "D_real", "loss_lpips",
            "loss_adaptive"} <= set(owners)


def test_bf16_distances_fail_the_comparison(ref, toy):
    """The control: the nearest-code search in bfloat16, on the same
    latent. ``check.verdict`` with the cell's own limits refuses it by the
    distance matrix, and passes the float32 search."""
    from p2p_tpu.models.vqgan import code_distances

    _, batch, state, flat = toy
    cfg = toy[0]
    _, side = _program_side(cfg, state, batch)
    latent = np.asarray(side["latent"])
    book = flat[ref.CODEBOOK]
    want = np.asarray(ref.code_distances(jnp.asarray(latent),
                                         jnp.asarray(book)))
    gap = lambda d: float(np.linalg.norm(np.asarray(d, np.float32) - want)  # noqa
                          / np.linalg.norm(want))
    said = []
    say = lambda **kw: said.append(kw)  # noqa: E731
    limits = {"distance_rel_gap": ref.LIMITS["distance_rel_gap"]}
    sound = {"distance_rel_gap": gap(side["distances"])}
    control = {"distance_rel_gap": gap(code_distances(
        jnp.asarray(latent), jnp.asarray(book), jnp.bfloat16))}
    assert check.verdict(sound, limits, say)
    assert not check.verdict(control, limits, say)
    assert control["distance_rel_gap"] > 30 * sound["distance_rel_gap"]


# ------------------------------------------------------- through the CLIs


@pytest.fixture(scope="module")
def image_root(tmp_path_factory):
    """A paired folder whose two sides are the same images (bits 8: the
    banded copy is the image)."""
    from p2p_tpu.data.synthetic import make_synthetic_dataset

    root = str(tmp_path_factory.mktemp("vq_data"))
    return make_synthetic_dataset(os.path.join(root, "toy"), n_train=4,
                                  n_test=2, size=H, bits=8)


@pytest.fixture(scope="module")
def trained(image_root, tmp_path_factory):
    from p2p_tpu.cli import train as cli_train

    work = str(tmp_path_factory.mktemp("vq_run"))
    argv = CLI + ["--data_root", image_root, "--workdir", work, "--ndf", "8", "--batch_size", "2", "--nepoch", "1",
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
    for name in ("loss_g", "loss_d", "d_weight", "g_codebook", "g_lpips",
                 "vq_codes_used", "vq_perplexity"):
        assert all(np.isfinite(r[name]) for r in steps), name
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    assert cfg.model.vq_ch_mult == (1, 2) and cfg.model.vq_embed_dim == WIDTH
    assert cfg.optim.lr_policy == "constant" and cfg.optim.beta2 == 0.9
    trainer = Trainer(cfg, data_root=image_root, workdir=work)
    try:
        assert trainer.maybe_resume() and int(trainer.state.step) == 2
        fresh = trainer.state.batch_stats_d["scale0"]["BatchNorm_0"][
            "BatchNorm_0"]["var"]
        assert float(jnp.max(jnp.abs(fresh - 1.0))) > 1e-3   # restored
        gauges = {k: v["value"] for k, v in trainer.obs.snapshot().items()
                  if k.startswith(("vqgan_", "generator_gflop"))}
    finally:
        trainer.close()
    assert gauges["vqgan_gn_swish_sites"] == 2 * 10 + 2
    assert gauges["vqgan_attn_blocks"] == 1 + 1 + 1 + 2
    parts = ("encoder", "decoder", "attention", "quantizer")
    assert abs(sum(gauges[f"vqgan_{p}_gflop_per_image"] for p in parts)
               - gauges["generator_gflop_per_image"]) < 1e-9
    assert gauges["vqgan_lpips_gflop_per_image"] > 0


def test_cli_infer_reconstructs_through_the_codes(trained, image_root,
                                                  tmp_path):
    from PIL import Image

    from p2p_tpu.cli import infer as cli_infer
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.data.pipeline import PairedImageDataset
    from p2p_tpu.train.loop import Trainer
    from p2p_tpu.utils.images import to_uint8_img

    work, argv = trained
    out = str(tmp_path / "pred")
    assert cli_infer.main(CLI + [
        "--data_root", image_root, "--workdir", work, "--out", out, "--batch_size", "2", "--dtype", "f32"]) == 0
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    trainer = Trainer(cfg, data_root=image_root, workdir=work)
    try:
        assert trainer.maybe_resume()
        ds = PairedImageDataset(image_root, "test", "b2a", H, W,
                                dtype="uint8")
        batch = {k: np.stack([ds[i][k] for i in range(2)])
                 for k in ("input", "target")}
        image, side = _program_side(cfg, trainer.state, batch)
        book = np.asarray(trainer.state.params_g["quantize"]["embedding"])
    finally:
        trainer.close()
    # what was served went through the codes: decoding the gathered rows
    # of the codebook gives the same image
    assert side["indices"].shape == (2, H // 2, W // 2)
    for i, name in enumerate(ds.names):
        served = np.asarray(Image.open(os.path.join(out, name)), np.int32)
        want = to_uint8_img(np.asarray(image[i])).astype(np.int32)
        assert served.shape == (H, W, 3)
        assert np.max(np.abs(served - want)) <= 1, name
    assert book.shape == (CODES, WIDTH)


# --------------------------------------------- audits, lint and the engine


def _site_lint_batch(cfg):
    from p2p_tpu.cli.lint import _tiny_batch

    return _tiny_batch(cfg)["input"].shape[1:]


def _site_memory_audit(cfg):
    from p2p_tpu.analysis.memory_audit import (activation_peak_bytes,
                                               dead_restore_findings,
                                               state_budget)

    assert activation_peak_bytes(cfg, cfg.data.batch_size) > 0
    # the serving template of the preset itself (at its own size: shapes
    # only, nothing is materialised)
    assert dead_restore_findings(("vqgan_imagenet_f16",)) == []
    one = state_budget(cfg, {"data": 1})
    # the codebook is a parameter (64 x 32 float32 of the ~3 MB), with
    # Adam's two moments like every other
    assert one["params"] > CODES * WIDTH * 4
    assert one["opt"] >= 2 * one["params"]
    # D's running statistics are state that no optimizer owns
    assert 0 < one["other"] < 1024
    assert state_budget(cfg, {"data": 1, "fsdp": 2})["opt"] < one["opt"]


def _site_sharding_audit(cfg):
    from p2p_tpu.analysis.sharding_audit import (abstract_train_state,
                                                 audit_rules)
    from p2p_tpu.parallel.rules import trainstate_rules

    state = abstract_train_state(cfg)
    assert state.params_g["quantize"]["embedding"].shape == (CODES, WIDTH)
    assert set(state.batch_stats_d["scale0"]) == {"BatchNorm_0",
                                                  "BatchNorm_1"}
    sizes = {"data": 2, "fsdp": 2, "spatial": 1, "time": 1, "model": 1,
             "pipe": 1}
    assert audit_rules(trainstate_rules(sizes), state, sizes) == []


def _site_engine(cfg):
    from p2p_tpu.serve.engine import InferenceEngine
    from p2p_tpu.serve.tenancy import serving_sample_batch
    from p2p_tpu.train.state import create_infer_state

    sample = serving_sample_batch(cfg)
    assert sample["target"].shape == (1, H, W, 3)
    state = create_infer_state(cfg, jax.random.key(0), sample)
    engine = InferenceEngine(cfg, state, buckets=(1,), dtype="f32",
                             with_metrics=False)
    (spec,) = engine._abstract_batch(1).values()
    pred, _, _ = engine.infer_batch({"input": toy_batch()["input"][:1]})
    assert np.asarray(pred).shape[-3:] == (H, W, 3)
    return spec.shape[1:]


@pytest.mark.parametrize("site", [
    _site_lint_batch, _site_memory_audit, _site_sharding_audit,
    _site_engine], ids=lambda f: f.__name__[6:])
def test_audits_lint_and_the_engine_know_the_generator(site):
    """``cli/lint``, the memory and sharding audits and the serving
    engine take the preset as they take every other: an image in, the
    codebook among G's parameters, D's running statistics in the state,
    the reconstruction out."""
    assert site(toy_cfg()) in (None, (H, W, 3))


# ----------------------------------------------------------- other presets


def _tiny(preset):
    cfg = get_preset(preset)
    size = 64 if cfg.model.generator in ("pix2pixhd", "unet") else 32
    model = dataclasses.replace(cfg.model, ngf=4, ndf=4, n_blocks=1)
    if cfg.model.label_classes:
        model = dataclasses.replace(model, ngf=8, label_classes=3,
                                    input_nc=4)
    return cfg.replace(
        model=model,
        data=dataclasses.replace(cfg.data, image_size=size, image_width=size,
                                 batch_size=1),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=dataclasses.replace(
                cfg.parallel.mesh, data=1, spatial=1, time=1)))


@pytest.mark.parametrize("preset", ["reference", "pix2pixhd",
                                    "spade_cityscapes"])
def test_preset_step_unchanged_by_the_new_fields(preset):
    """A preset of another generator traces the step it had: no scope,
    metric or state of this PR in it, and the fields that size the
    quantizer, weigh its loss or pad D, set to anything, leave the very
    same jaxpr (the four accepted cells' lowered programs hash as at the
    parent: ``scripts/step_program_hash.py``, PERF.md section 6)."""
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.train.step import build_train_step
    from p2p_tpu.utils.images import wire_spec

    cfg = _tiny(preset)
    assert cfg.model.d_conditional and cfg.model.d_padding == 2
    assert cfg.loss.lambda_lpips == 0 and cfg.loss.adaptive_gan_weight == 0
    state = abstract_train_state(cfg)
    assert state.batch_stats_d is None
    batch = {k: jax.ShapeDtypeStruct((1,) + wire_spec(cfg, k)[0],
                                     wire_spec(cfg, k)[1])
             for k in ("input", "target")}

    def text(c):
        jaxpr, out = jax.make_jaxpr(build_train_step(c, jit=False),
                                    return_shape=True)(state, batch)
        return re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr)), out[1]

    base, metrics = text(cfg)
    for name in ("gn_swish", "attn", "vq", "loss_lpips", "loss_adaptive",
                 "loss_codebook"):
        assert f"{name}/" not in base and f"/{name}" not in base, name
    assert not {"d_weight", "vq_codes_used", "vq_perplexity",
                "g_codebook", "g_lpips"} & set(metrics)
    other = cfg.replace(
        model=dataclasses.replace(cfg.model, vq_codes=5, vq_embed_dim=7,
                                  vq_ch_mult=(3,)))
    assert text(other)[0] == base


def test_the_train_step_names_no_generator():
    """The step is keyed on what ``models/registry.generator_side`` hands
    it (a capability), never on a generator's name or module."""
    import inspect

    from p2p_tpu.train import step

    src = inspect.getsource(step)
    for banned in ("model.generator", "models.vqgan", "models.spade",
                   "models import vqgan"):
        assert banned not in src, banned
    from p2p_tpu.models.registry import generator_side

    assert generator_side(get_preset("reference").model) is None
    assert generator_side(get_preset("spade_cityscapes").model) is None
    side = generator_side(get_preset("vqgan_imagenet_f16").model)
    assert side.collection == "vq" and side.last_kernel[-1] == "kernel"
