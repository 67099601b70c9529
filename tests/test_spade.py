"""SPADE / GauGAN (preset ``spade_cityscapes``) at a toy size — nf 8,
3 classes + the edge channel, 32x64, batch 2, seeded random weights —
held against the plain reference of its configuration
(``benchmark/reference/spade_cityscapes_512x256.py``: float32, nothing of
the program imported): the SPADE layer, a ResBlk, the generator, the
discriminator on classes + edge + image channels, one whole train step;
then the label-map input end to end, the checkpoint round trip of G's
spectral vectors, ``cli.infer``, and every site that builds a dummy input.

Tolerances: both sides run float32 on the CPU, the program through XLA's
default convolution precision and its own one-pass moments, the reference
at ``Precision.HIGHEST`` with two-pass moments; forward values agree to
~1e-5 of their scale, so 1e-4 is asked. Gradients pass through BN0's
rsqrt(var + eps) ~ 300 at these weights (xavier gain 0.02: var << eps),
which amplifies rounding: 2e-3 of a leaf's largest entry is asked of every
leaf whose gradient is not identically zero.
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
from p2p_tpu.core.config import get_preset, list_presets

CLASSES, H, W, BS = 3, 32, 64, 2
FIELDS = ("params_g", "params_d", "spectral_g", "spectral_d",
          "batch_stats_g")
HYPER = dict(lr_g=1e-4, lr_d=4e-4, beta1=0.0, beta2=0.9, eps=1e-8,
             lambda_feat=10.0, lambda_vgg=0.0, n_layers_D=3)


def toy_cfg(**train):
    cfg = get_preset("spade_cityscapes")
    return cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8,
                                  label_classes=CLASSES,
                                  input_nc=CLASSES + 1),
        data=dataclasses.replace(cfg.data, image_size=H, image_width=W,
                                 batch_size=BS, test_batch_size=BS),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        train=dataclasses.replace(cfg.train, mixed_precision=False, **train))


def toy_batch(seed=0):
    rng = np.random.default_rng(seed)
    labels = np.stack([rng.integers(0, CLASSES, (BS, H, W)),
                       rng.integers(0, 2, (BS, H, W))], -1).astype(np.uint8)
    return {"input": labels,
            "target": rng.integers(0, 256, (BS, H, W, 3)).astype(np.uint8)}


@pytest.fixture(scope="module")
def ref():
    return harness.load_by_path("reference", "spade_cityscapes_512x256")


@pytest.fixture(scope="module")
def toy():
    """cfg, batch, the seeded state and its flat copy (made before the
    step donates it)."""
    from p2p_tpu.train.state import create_train_state

    cfg, batch = toy_cfg(), toy_batch()
    state = create_train_state(cfg, jax.random.key(0), batch)
    # the weights as a few training steps leave them: xavier gain 0.02
    # alone gives gamma = beta = 0 to seven digits, which would compare
    # nothing of the modulation
    rng = np.random.default_rng(2)
    params_g = jax.tree_util.tree_map(
        lambda w: w + 0.05 * rng.standard_normal(w.shape, np.float32),
        state.params_g)
    state = state.replace(params_g=params_g)
    return cfg, batch, state, check.flatten_state(state, FIELDS)


def close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = float(np.max(np.abs(want))) + 1e-30
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        float(np.max(np.abs(got - want))), scale)


def test_spade_layer_against_the_reference(ref, toy):
    from p2p_tpu.ops.norm import SPADE
    from p2p_tpu.utils.images import one_hot_labels

    _, batch, state, flat = toy
    m = one_hot_labels(jnp.asarray(batch["input"]), CLASSES, True)
    x = jax.random.normal(jax.random.key(3), (BS, H // 2, W // 2, 16))
    site = "up_3/norm_0"
    variables = {
        "params": state.params_g["up_3"]["norm_0"],
        "batch_stats": state.batch_stats_g["up_3"]["norm_0"]}
    got, _ = jax.jit(lambda v, x, m: SPADE(train=True).apply(
        v, x, m, mutable=["batch_stats"]))(variables, x, m)
    want = jax.jit(lambda p, x, m: ref.spade(p, f"params_g/{site}", x, m))(
        flat, x, m)
    close(got, want, 1e-4)
    # eval: the running statistics, not the batch's
    got_eval = jax.jit(SPADE(train=False).apply)(variables, x, m)
    want_eval = jax.jit(lambda p, x, m: ref.spade(
        p, f"params_g/{site}", x, m, ref._running(p, site)))(flat, x, m)
    close(got_eval, want_eval, 1e-4)
    assert float(jnp.max(jnp.abs(got_eval - got))) > 0.1


@pytest.mark.parametrize("name, fin, fout", [("G_middle_0", 128, 128),
                                             ("up_3", 16, 8)])
def test_res_block_against_the_reference(ref, toy, name, fin, fout):
    """With the identity shortcut and with the learned one."""
    from p2p_tpu.models.spade import SPADEResnetBlock
    from p2p_tpu.utils.images import one_hot_labels

    _, batch, state, flat = toy
    m = one_hot_labels(jnp.asarray(batch["input"]), CLASSES, True)
    x = jax.random.normal(jax.random.key(4), (BS, H // 4, W // 4, fin))
    block = SPADEResnetBlock(fin, fout, out_bias=name == "up_3")
    got, mut = jax.jit(lambda v, x, m: block.apply(
        v, x, m, True, mutable=["batch_stats", "spectral"]))(
        {"params": state.params_g[name],
         "batch_stats": state.batch_stats_g[name],
         "spectral": state.spectral_g[name]}, x, m)
    want, new_u = jax.jit(lambda p, x, m: ref.res_block(p, name, x, m))(
        flat, x, m)
    assert ("conv_s" in state.params_g[name]) == (fin != fout)
    close(got, want, 1e-4)
    for conv, u in mut["spectral"].items():
        close(u["u"], new_u[f"spectral_g/{name}/{conv}/u"], 1e-4)


def test_generator_forward_against_the_reference(ref, toy):
    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest_input

    cfg, batch, state, flat = toy
    g, _, _ = build_models(cfg)
    got, _ = jax.jit(lambda v, x: g.apply(
        v, ingest_input(x, cfg.model), True,
        mutable=["batch_stats", "spectral"]))(
        {"params": state.params_g, "batch_stats": state.batch_stats_g,
         "spectral": state.spectral_g}, jnp.asarray(batch["input"]))
    want = jax.jit(lambda p, x: ref.generator_path(p, x, True)[0])(
        flat, batch["input"])
    assert got.shape == (BS, H, W, 3)
    close(got, want, 1e-4)


def test_discriminator_on_conditioning_plus_image_channels(ref, toy):
    """D's stem sees classes + edge + 3 image channels (39 at the
    cell's size, 7 here), two scales, instance norm + spectral norm on
    the inner convolutions; the split (map, image) pair equals the
    concatenated one."""
    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest, one_hot_labels

    cfg, batch, state, flat = toy
    _, d, _ = build_models(cfg)
    m = one_hot_labels(jnp.asarray(batch["input"]), CLASSES, True)
    image = ingest(jnp.asarray(batch["target"]))
    assert state.params_d["scale1"]["_PlainConv_0"]["Conv_0"][
        "kernel"].shape[2] == CLASSES + 1 + 3
    variables = {"params": state.params_d, "spectral": state.spectral_d}
    apply = jax.jit(lambda v, x: d.apply(v, x, mutable=["spectral"]))
    got, mut = apply(variables, (m, image))
    want, new_u = jax.jit(ref.discriminator)(
        flat, jnp.concatenate([m, image], -1))
    assert len(got) == 2 and all(len(s) == 5 for s in got)
    for scale_g, scale_w in zip(got, want):
        for a, b in zip(scale_g, scale_w):
            close(a, b, 1e-4)
    cat, _ = apply(variables, jnp.concatenate([m, image], -1))
    close(cat[0][-1], got[0][-1], 1e-5)
    u = mut["spectral"]["scale0"]["SpectralConv_1"]["u"]
    close(u, new_u["spectral_d/scale0/SpectralConv_1/u"], 1e-4)


def test_whole_train_step_against_the_reference(ref, toy):
    """Losses, the gradient each optimizer got (Adam's first moment at
    beta1 0), the parameters after the step and both nets' spectral
    vectors. Adam(beta1 0) makes step one ``lr * g / (|g| + eps)``: a
    parameter moves by +-lr wherever |g| >> eps, so the parameters are
    compared where the reference's gradient is clear of rounding."""
    from p2p_tpu.train.step import build_train_step

    cfg, batch, state, flat = toy
    start = {k: v for k, v in flat.items() if not k.startswith("batch_")}
    state1, metrics = build_train_step(cfg)(
        jax.tree_util.tree_map(jnp.copy, state), batch)
    losses, grads, params, vectors = ref.StepReference(HYPER).follow(
        start, [batch])
    for name, want in losses[0].items():
        assert abs(float(metrics[name]) - want) <= 1e-4 * abs(want), name
    moments = check.first_moments(state1)
    after = check.flatten_state(state1, FIELDS)
    dead = ref.zero_gradient_leaves(start)
    assert len(dead) == 3 + 6 and dead <= set(grads)
    compared = 0
    for leaf, want in grads.items():
        if leaf in dead:
            continue
        close(moments[leaf], want, 2e-3)
        clear = np.abs(want) > 1e-3 * np.max(np.abs(want))
        if not clear.any():
            continue
        moved_got = (after[leaf] - flat[leaf])[clear]
        moved_want = (params[leaf] - flat[leaf])[clear]
        lr = HYPER["lr_g" if leaf.startswith("params_g") else "lr_d"]
        assert np.max(np.abs(moved_got - moved_want)) <= 0.02 * lr, leaf
        compared += int(clear.sum())
    assert compared > 100_000
    for leaf, want in vectors.items():
        close(after[leaf], want, 1e-4)
        assert np.linalg.norm(after[leaf] - flat[leaf]) > 1e-3, leaf


# ------------------------------------------------------- label-map input


@pytest.fixture(scope="module")
def label_root(tmp_path_factory):
    from p2p_tpu.data.synthetic import make_synthetic_label_dataset

    root = str(tmp_path_factory.mktemp("labels"))
    return make_synthetic_label_dataset(root, 4, 2, (H, W), CLASSES, seed=5)


def test_label_loader_keeps_ids_bit_for_bit(label_root):
    """Decode, flip and H2D leave every id as written; the one-hot made
    on the device equals ``np.eye``; decodes are counted and spanned."""
    from PIL import Image

    from p2p_tpu.data.pipeline import (PairedImageDataset, device_prefetch,
                                       make_loader)
    from p2p_tpu.obs.registry import get_registry
    from p2p_tpu.utils.images import one_hot_labels

    written = np.asarray(Image.open(
        os.path.join(label_root, "train", "b", "synth_0000.png")))
    assert written.shape == (H, W, 2) and written[..., 0].max() < CLASSES
    assert set(np.unique(written[..., 1])) == {0, 1}
    counter = get_registry().counter("label_maps_decoded_total")
    before = counter.value
    ds = PairedImageDataset(label_root, "train", "b2a", H, W,
                            dtype="uint8", label_input=True)
    item = ds[0]
    assert item["input"].dtype == np.uint8 and item["target"].shape == (
        H, W, 3)
    np.testing.assert_array_equal(item["input"], written)
    assert counter.value == before + 1
    assert get_registry().histogram("label_decode_secs").count >= 1
    # a resize never blends ids: half the size is a subset of the ids
    half = PairedImageDataset(label_root, "train", "b2a", H // 2, W // 2,
                              dtype="uint8", label_input=True)[0]["input"]
    assert set(np.unique(half[..., 0])) <= set(np.unique(written[..., 0]))
    # flip only, both sides together
    aug = PairedImageDataset(label_root, "train", "b2a", H, W, augment=True,
                             dtype="uint8", label_input=True)
    plain = [ds[i] for i in range(4)]
    flipped = 0
    for seed in range(4):
        aug.aug_seed = seed
        for i in range(4):
            got = aug[i]
            if np.array_equal(got["input"], plain[i]["input"]):
                np.testing.assert_array_equal(got["target"],
                                              plain[i]["target"])
            else:
                flipped += 1
                np.testing.assert_array_equal(got["input"],
                                              plain[i]["input"][:, ::-1])
                np.testing.assert_array_equal(got["target"],
                                              plain[i]["target"][:, ::-1])
    assert 0 < flipped < 16
    (batch,) = list(device_prefetch(
        make_loader(ds, 4, shuffle=False, num_workers=0), None))
    np.testing.assert_array_equal(np.asarray(batch["input"][0]), written)
    m = np.asarray(one_hot_labels(batch["input"], CLASSES, True))
    want = np.concatenate([np.eye(CLASSES, dtype=np.float32)[
        np.asarray(batch["input"][..., 0])],
        np.asarray(batch["input"][..., 1:2], np.float32)], -1)
    np.testing.assert_array_equal(m, want)


def test_ingest_is_never_handed_class_ids():
    from p2p_tpu.utils.images import ingest, ingest_input

    cfg = toy_cfg()
    labels = toy_batch()["input"]
    m = ingest_input(jnp.asarray(labels), cfg.model, jnp.bfloat16)
    assert m.shape == (BS, H, W, CLASSES + 1) and m.dtype == jnp.bfloat16
    assert set(np.unique(np.asarray(m, np.float32))) == {0.0, 1.0}
    with pytest.raises(TypeError):
        ingest_input(jnp.zeros((1, 4, 4, 2), jnp.float32), cfg.model)
    image_cfg = get_preset("pix2pixhd")
    x = jnp.zeros((1, 4, 4, 3), jnp.uint8)
    np.testing.assert_array_equal(ingest_input(x, image_cfg.model),
                                  ingest(x))


# ------------------------------------- the normal path: train, save, infer


CLI = ["--preset", "spade_cityscapes", "--name", "toy", "--dataset", "syn",
       "--image_size", str(H), "--image_width", str(W), "--ngf", "8",
       "--label_classes", str(CLASSES)]


@pytest.fixture(scope="module")
def trained(label_root, tmp_path_factory):
    """``cli.train`` for one epoch of 2 steps at the toy size, saved."""
    from p2p_tpu.cli import train as cli_train

    work = str(tmp_path_factory.mktemp("spade_run"))
    argv = CLI + ["--data_root", label_root, "--workdir", work, "--ndf", "8",
                  "--batch_size", "2", "--lambda_vgg", "0", "--nepoch", "1",
                  "--epochsave", "1", "--threads", "0", "--log_every", "1",
                  "--mesh", "data=1"]
    assert cli_train.main(argv) == 0
    return work, argv


def test_cli_train_saves_and_resumes_with_g_vectors(trained, label_root):
    """The checkpoint holds G's spectral vectors and a resumed Trainer
    reads them back bit for bit (and the gauges and scopes are there)."""
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.train.loop import Trainer

    work, argv = trained
    stream = [json.loads(x) for x in open(
        os.path.join(work, "metrics_toy.jsonl"))]
    steps = [r for r in stream if r.get("kind") == "train"]
    assert len(steps) == 2 and all(np.isfinite(r["loss_g"]) for r in steps)
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    assert cfg.optim.lr_d == 4e-4 and cfg.loss.gan_mode == "hinge"
    trainer = Trainer(cfg, data_root=label_root, workdir=work)
    try:
        fresh = check.flatten_state(trainer.state, ("spectral_g",))
        assert trainer.maybe_resume()
        assert int(trainer.state.step) == 2
        resumed = check.flatten_state(trainer.state, ("spectral_g",))
        assert len(resumed) == 7 * 2 + 4
        moved = [k for k in resumed
                 if np.linalg.norm(resumed[k] - fresh[k]) > 1e-3]
        assert len(moved) == len(resumed)
        gauges = trainer.obs.snapshot()
        assert gauges["spade_sites"]["value"] == 18
        assert 0 < gauges["spade_modulation_gflop_per_image"]["value"] < (
            gauges["generator_gflop_per_image"]["value"])
        state = trainer.state
    finally:
        trainer.close()
    # a second save/restore round trip of the very vectors
    from p2p_tpu.train.checkpoint import CheckpointManager

    ckpt = CheckpointManager(os.path.join(work, "again"))
    ckpt.save(7, state)
    ckpt.wait()
    back = ckpt.restore(state, 7)
    for a, b in zip(jax.tree_util.tree_leaves(state.spectral_g),
                    jax.tree_util.tree_leaves(back.spectral_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ckpt.close()


def test_cli_infer_equals_the_eval_generator_of_the_train_state(
        trained, label_root, tmp_path):
    """``cli.infer`` from a label map: the served weight is W / sigma(u)
    with the checkpoint's u, BN0 reads its running statistics."""
    from PIL import Image

    from p2p_tpu.cli import infer as cli_infer
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.data.pipeline import PairedImageDataset
    from p2p_tpu.train.loop import Trainer
    from p2p_tpu.train.step import build_eval_step
    from p2p_tpu.utils.images import to_uint8_img

    work, argv = trained
    out = str(tmp_path / "pred")
    assert cli_infer.main(CLI + [
        "--data_root", label_root, "--workdir", work, "--out", out,
        "--batch_size", "2", "--dtype", "bf16"]) == 0
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    trainer = Trainer(cfg, data_root=label_root, workdir=work)
    try:
        assert trainer.maybe_resume()
        ds = PairedImageDataset(label_root, "test", "b2a", H, W,
                                dtype="uint8", label_input=True)
        batch = {k: np.stack([ds[i][k] for i in range(2)])
                 for k in ("input", "target")}
        pred, _ = build_eval_step(cfg, jnp.bfloat16)(trainer.state, batch)
    finally:
        trainer.close()
    for i, name in enumerate(ds.names):
        served = np.asarray(Image.open(os.path.join(out, name)), np.int32)
        want = to_uint8_img(np.asarray(pred[i])).astype(np.int32)
        assert served.shape == (H, W, 3)
        assert np.max(np.abs(served - want)) <= 1, name


# ------------------------------------------------------------ other presets


def _tiny(preset):
    cfg = get_preset(preset)
    size = 64 if cfg.model.generator in ("pix2pixhd", "unet") else 32
    model = dataclasses.replace(cfg.model, ngf=4, ndf=4, n_blocks=1)
    if cfg.model.generator == "vqgan":
        # GroupNorm's 32 groups need a base width of 32
        model = dataclasses.replace(
            model, ngf=32, vq_ch_mult=(1, 2), vq_res_blocks=1, vq_codes=64,
            vq_embed_dim=32)
    if cfg.model.generator == "swinir":
        # the embedding is a whole number of 30-wide heads
        model = dataclasses.replace(model, ngf=30)
    return cfg.replace(
        model=model,
        data=dataclasses.replace(cfg.data, image_size=size, image_width=size,
                                 batch_size=1),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=dataclasses.replace(
                cfg.parallel.mesh, data=1, spatial=1, time=1)))


@pytest.mark.parametrize("preset", [
    p for p in list_presets()
    # (the two presets that set D's own rate, and the video one)
    if p not in ("spade_cityscapes", "big_lama", "vid2vid_temporal")])
def test_preset_step_unchanged_by_the_new_optim_fields(preset):
    """A preset that sets neither ``lr_d`` nor ``gan_scale_mean`` traces
    the step it had: one learning rate for every net, the scales' SUM,
    no ``spectral_g`` in its state — the very jaxpr of the same preset
    with D's rate spelled out."""
    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.train.step import build_train_step
    from p2p_tpu.utils.images import wire_spec

    cfg = _tiny(preset)
    assert cfg.optim.lr_d is None and not cfg.loss.gan_scale_mean
    assert cfg.model.label_classes == 0
    state = abstract_train_state(cfg)
    assert state.spectral_g is None
    batch = {k: jax.ShapeDtypeStruct((1,) + wire_spec(cfg, k)[0],
                                     wire_spec(cfg, k)[1])
             for k in ("input", "target")}
    def text(c):
        # (function objects print with their addresses)
        return re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
            build_train_step(c, jit=False))(state, batch)))

    assert text(cfg) == text(cfg.replace(optim=dataclasses.replace(
        cfg.optim, lr_d=cfg.optim.lr)))


# ------------------------------------- every site that builds a dummy input


def _site_lint_batch(cfg):
    from p2p_tpu.cli.lint import _tiny_batch

    return _tiny_batch(cfg)["input"].shape[1:]


def _site_memory_step(cfg):
    from p2p_tpu.analysis.memory_audit import (activation_peak_bytes,
                                               dead_restore_findings)

    assert activation_peak_bytes(cfg, cfg.data.batch_size) > 0
    # the serving template of the preset itself (at its own size: shapes
    # only, nothing is materialised)
    assert dead_restore_findings(("spade_cityscapes",)) == []
    return None


def _site_sharding_state(cfg):
    from p2p_tpu.analysis.sharding_audit import abstract_train_state

    state = abstract_train_state(cfg)
    stem = state.params_d["scale1"]["_PlainConv_0"]["Conv_0"]["kernel"]
    assert stem.shape[2] == CLASSES + 1 + 3
    return None


def _site_engine(cfg):
    from p2p_tpu.serve.engine import InferenceEngine
    from p2p_tpu.serve.tenancy import serving_sample_batch
    from p2p_tpu.train.state import create_infer_state

    sample = serving_sample_batch(cfg)
    state = create_infer_state(cfg, jax.random.key(0), sample)
    engine = InferenceEngine(cfg, state, buckets=(1,), dtype="f32",
                             with_metrics=False)
    (spec,) = engine._abstract_batch(1).values()
    pred, _, _ = engine.infer_batch({"input": toy_batch()["input"][:1]})
    assert np.asarray(pred).shape[-3:] == (H, W, 3)
    return spec.shape[1:]


def _site_tenancy(cfg):
    from p2p_tpu.serve.tenancy import serving_sample_batch

    sample = serving_sample_batch(cfg)
    assert sample["target"].shape == (1, H, W, 3)
    return sample["input"].shape[1:]


@pytest.mark.parametrize("site", [
    _site_lint_batch, _site_memory_step, _site_sharding_state, _site_engine,
    _site_tenancy], ids=lambda f: f.__name__[6:])
def test_label_preset_passes_through_the_dummy_input_site(site):
    """``cli/lint``, the two audits, the serving engine and the tenancy
    layer build their dummy inputs from the configuration's input kind
    (``utils.images.wire_spec``): a uint8 (H, W, 2) label map here."""
    shape = site(toy_cfg())
    assert shape in (None, (H, W, 2))
