import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.core.config import ModelConfig
from p2p_tpu.models import (
    CompressionNetwork,
    GlobalGenerator,
    Pix2PixHDGenerator,
    ResnetGenerator,
    UNetGenerator,
    ExpandNetwork,
    MultiscaleDiscriminator,
    NLayerDiscriminator,
    VGG19Features,
)
from p2p_tpu.models.registry import define_C, define_D, define_G, init_variables


def nparams(tree):
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def test_compression_network_shape_and_residual():
    x = jnp.asarray(np.random.default_rng(0).uniform(-1, 1, (2, 32, 32, 3)), jnp.float32)
    net = CompressionNetwork()
    variables = net.init(jax.random.key(0), x)
    y, _ = net.apply(variables, x, mutable=["batch_stats"])
    assert y.shape == x.shape
    # residual is L2-normalized per pixel → ||y-x|| per pixel == 1
    r = np.linalg.norm(np.asarray(y - x), axis=-1)
    np.testing.assert_allclose(r, np.ones_like(r), rtol=1e-4)


@pytest.mark.slow
def test_expand_network_shape_and_range():
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (1, 64, 64, 3)), jnp.float32)
    net = ExpandNetwork()
    variables = net.init(jax.random.key(0), x)
    y, _ = net.apply(variables, x, mutable=["batch_stats"])
    assert y.shape == (1, 64, 64, 3)
    assert float(jnp.max(jnp.abs(y))) <= 1.0  # tanh output
    # Reference conv1 kernel: 12ch in, 32 out, 9x9 (networks.py:460)
    k = variables["params"]["ConvLayer_0"]["Conv_0"]["kernel"]
    assert k.shape == (9, 9, 12, 32)


def test_expand_network_shares_one_prelu():
    x = jnp.zeros((1, 32, 32, 3))
    net = ExpandNetwork(n_blocks=2)
    variables = net.init(jax.random.key(0), x)
    prelu_params = [k for k in variables["params"] if k.startswith("PReLU")]
    assert prelu_params == ["PReLU_0"]  # single shared scalar, ref networks.py:452


def test_nlayer_discriminator_stages():
    x = jnp.zeros((1, 64, 64, 6))
    d = NLayerDiscriminator(ndf=64, n_layers=3)
    variables = d.init(jax.random.key(0), x)
    feats = d.apply(variables, x, mutable=["spectral"])[0]
    assert len(feats) == 5  # n_layers + 2 stages, ref networks.py:789-804
    chans = [f.shape[-1] for f in feats]
    assert chans == [64, 128, 256, 512, 1]
    # stride-2 stages halve (with the k4/pad2 +1 quirk: floor(H/2)+1)
    hs = [f.shape[1] for f in feats]
    assert hs == [33, 17, 9, 10, 11]
    # spectral norm on exactly the 3 inner convs
    assert len(jax.tree_util.tree_leaves(variables["spectral"])) == 3


def test_multiscale_discriminator_orders_finest_first():
    x = jnp.zeros((1, 64, 64, 6))
    d = MultiscaleDiscriminator(ndf=16, num_D=3)
    variables = d.init(jax.random.key(0), x)
    out = d.apply(variables, x, mutable=["spectral"])[0]
    assert len(out) == 3
    # finest scale (full res) first, each subsequent scale halved by avgpool
    assert out[0][0].shape[1] > out[1][0].shape[1] > out[2][0].shape[1]
    assert {f"scale{i}" for i in range(3)} <= set(variables["params"].keys())


def test_vgg19_taps():
    x = jnp.zeros((1, 64, 64, 3))
    m = VGG19Features()
    variables = m.init(jax.random.key(0), x)
    outs = m.apply(variables, x)
    assert [o.shape[-1] for o in outs] == [64, 128, 256, 512, 512]
    assert [o.shape[1] for o in outs] == [64, 32, 16, 8, 4]


@pytest.mark.slow
def test_registry_factories_and_init_types():
    cfg = ModelConfig()
    x = jnp.zeros((1, 32, 32, 3))
    g = define_G(cfg)
    c = define_C(cfg)
    d = define_D(cfg)
    vg = init_variables(g, jax.random.key(0), x)
    vc = init_variables(c, jax.random.key(1), x)
    vd = init_variables(d, jax.random.key(2), jnp.zeros((1, 32, 32, 6)))
    assert nparams(vg["params"]) > 100_000
    assert nparams(vc["params"]) > 10_000
    assert nparams(vd["params"]) > 1_000_000  # 3 PatchGANs

    v_orth = init_variables(g, jax.random.key(0), x, init_type="orthogonal", gain=1.0)
    k = v_orth["params"]["ConvLayer_1"]["Conv_0"]["kernel"]
    m = np.asarray(k).reshape(-1, k.shape[-1])
    np.testing.assert_allclose(m.T @ m, np.eye(k.shape[-1]), atol=1e-4)


def test_vgg_fallback_is_deterministic():
    from p2p_tpu.models.vgg import load_vgg19_params, vgg19_params_source

    assert vgg19_params_source() == "random"
    p1 = load_vgg19_params()
    p2 = load_vgg19_params()
    l1 = jax.tree_util.tree_leaves(p1)
    l2 = jax.tree_util.tree_leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------- new G families

@pytest.mark.slow
def test_unet_generator_shapes_skips_and_grads():
    x = jnp.asarray(
        np.random.default_rng(3).uniform(-1, 1, (2, 64, 64, 3)), jnp.float32
    )
    net = UNetGenerator(ngf=8)
    variables = net.init(jax.random.key(0), x, True)
    y, _ = net.apply(variables, x, True, mutable=["batch_stats"])
    assert y.shape == x.shape
    assert float(jnp.max(jnp.abs(y))) <= 1.0
    # depth clamps to log2(64)=6 levels on a 64px input
    downs = [k for k in variables["params"] if k.startswith("down")]
    assert len(downs) == 6
    # gradients flow through every encoder conv (skip connections intact)
    def loss(p):
        out, _ = net.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, x, True,
            mutable=["batch_stats"],
        )
        return jnp.mean(out**2)
    grads = jax.grad(loss)(variables["params"])
    for name in downs:
        g = np.asarray(grads[name]["kernel"])
        assert np.abs(g).sum() > 0, f"no grad into {name}"


@pytest.mark.slow
def test_unet_inference_mode_no_mutation():
    x = jnp.asarray(
        np.random.default_rng(4).uniform(-1, 1, (1, 32, 32, 3)), jnp.float32
    )
    net = UNetGenerator(ngf=4)
    variables = net.init(jax.random.key(0), x, True)
    y = net.apply(variables, x, False)  # no mutable: eval must not mutate
    assert y.shape == x.shape


@pytest.mark.slow
def test_resnet_generator_shape_block_identity_at_init():
    x = jnp.asarray(
        np.random.default_rng(5).uniform(-1, 1, (1, 32, 48, 3)), jnp.float32
    )
    net = ResnetGenerator(ngf=8, n_blocks=2, norm="instance")
    variables = net.init(jax.random.key(0), x, True)
    y = net.apply(variables, x, True)
    assert y.shape == (1, 32, 48, 3)
    assert float(jnp.max(jnp.abs(y))) <= 1.0


def test_resnet_block_no_post_add_activation():
    # classic ResnetBlock: output can go below the pre-add value (no relu
    # after the residual add, unlike ExpandNetwork's ResidualBlock)
    from p2p_tpu.models import ResnetBlock

    x = jnp.asarray(
        np.random.default_rng(6).normal(size=(1, 8, 8, 4)), jnp.float32
    )
    blk = ResnetBlock(4, norm="instance")
    variables = blk.init(jax.random.key(2), x, True)
    y = blk.apply(variables, x, True)
    assert float(jnp.min(y)) < 0


@pytest.mark.slow
def test_pix2pixhd_generator_shapes_and_param_split():
    x = jnp.asarray(
        np.random.default_rng(7).uniform(-1, 1, (1, 64, 64, 3)), jnp.float32
    )
    net = Pix2PixHDGenerator(ngf=8, n_blocks_global=2, n_blocks_local=1,
                             norm="instance")
    variables = net.init(jax.random.key(0), x, True)
    y = net.apply(variables, x, True)
    assert y.shape == x.shape
    assert "global" in variables["params"]  # G1 is a named submodule
    # G1 alone also runs standalone (coarse-to-fine training schedule)
    g1 = GlobalGenerator(ngf=16, n_blocks=2, norm="instance")
    v1 = g1.init(jax.random.key(1), x, True)
    y1 = g1.apply(v1, x, True)
    assert y1.shape == x.shape


@pytest.mark.slow
def test_registry_builds_all_generator_families():
    x = jnp.zeros((1, 32, 32, 3))
    for gen, norm in [("expand", "batch"), ("unet", "batch"),
                      ("resnet", "instance"), ("pix2pixhd", "instance"),
                      ("pix2pixhd_global", "instance")]:
        cfg = ModelConfig(generator=gen, ngf=8, n_blocks=2, norm=norm)
        g = define_G(cfg)
        variables = init_variables(g, jax.random.key(0), x, train=True)
        out = g.apply(variables, x, True, mutable=["batch_stats"])
        y = out[0] if isinstance(out, tuple) else out
        assert y.shape == x.shape, gen


@pytest.mark.slow
def test_unet_non_power_of_two_sizes():
    # 96 = 2^5*3, 48 = 2^4*3 → depth clamps to 4, odd bottleneck survives
    x = jnp.asarray(
        np.random.default_rng(8).uniform(-1, 1, (1, 96, 48, 3)), jnp.float32
    )
    net = UNetGenerator(ngf=4)
    variables = net.init(jax.random.key(0), x, True)
    y, _ = net.apply(variables, x, True, mutable=["batch_stats"])
    assert y.shape == x.shape
    downs = [k for k in variables["params"] if k.startswith("down")]
    assert len(downs) == 4


@pytest.mark.slow
def test_unet_dropout_needs_rng_and_perturbs_output():
    x = jnp.asarray(
        np.random.default_rng(9).uniform(-1, 1, (1, 32, 32, 3)), jnp.float32
    )
    net = UNetGenerator(ngf=4, use_dropout=True)
    variables = net.init(jax.random.key(0), x, False)  # eval init: no rng
    y1, _ = net.apply(variables, x, True, mutable=["batch_stats"],
                      rngs={"dropout": jax.random.key(1)})
    y2, _ = net.apply(variables, x, True, mutable=["batch_stats"],
                      rngs={"dropout": jax.random.key(2)})
    assert float(jnp.max(jnp.abs(y1 - y2))) > 0
    # eval path is deterministic without an rng
    ye = net.apply(variables, x, False)
    assert ye.shape == x.shape


def test_compression_autoencoder_roundtrip_shapes():
    """Learned-compression AE (reference dead code networks.py:238-392,
    live here): encode → 1/16 spatial latent, decode → input shape."""
    from p2p_tpu.models import CompressionAutoencoder

    x = jnp.asarray(
        np.random.default_rng(11).uniform(-1, 1, (1, 64, 64, 3)), jnp.float32
    )
    ae = CompressionAutoencoder(ngf=4, latent_channels=8, n_blocks=2)
    variables = ae.init(jax.random.key(0), x)
    z = ae.apply(variables, x, method="encode")
    assert z.shape == (1, 4, 4, 8)  # 4 stride-2 downs, latent_channels
    y = ae.apply(variables, x)
    assert y.shape == x.shape


@pytest.mark.slow
def test_compression_autoencoder_quantized_latent_trains():
    from p2p_tpu.models import CompressionAutoencoder

    x = jnp.asarray(
        np.random.default_rng(12).uniform(-1, 1, (1, 32, 32, 3)), jnp.float32
    )
    ae = CompressionAutoencoder(ngf=4, latent_channels=8, n_blocks=1,
                                quant_bits=3)
    variables = ae.init(jax.random.key(0), x)
    z = ae.apply(variables, x, method="encode")
    # quantized-sigmoid latent: at most 2^3 distinct levels in [0,1]
    assert len(np.unique(np.asarray(z))) <= 8
    # STE: gradients reach the encoder through the quantizer
    def loss(p):
        y = ae.apply({"params": p}, x)
        return jnp.mean((y - x) ** 2)
    grads = jax.grad(loss)(variables["params"])
    enc = [np.abs(np.asarray(g)).sum()
           for g in jax.tree_util.tree_leaves(grads["encoder"])]
    assert sum(enc) > 0


@pytest.mark.parametrize("mode", [True, "conv"])
@pytest.mark.slow
def test_resnet_generator_remat_modes_match_no_remat(mode):
    """Both remat modes (full recompute and the conv-residuals-only policy)
    must change memory behavior ONLY — forward values and gradients match
    the un-remat'd generator."""
    from p2p_tpu.models.resnet_gen import ResnetGenerator

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 16, 16, 3)), jnp.float32
    )

    def build(remat):
        g = ResnetGenerator(ngf=8, n_blocks=2, norm="instance", remat=remat)
        v = g.init(jax.random.key(0), x, True)
        return g, v

    g0, v0 = build(False)
    ref = g0.apply(v0, x, True)

    def loss(g, v):
        return lambda p: jnp.sum(g.apply({**v, "params": p}, x, True) ** 2)

    l0, grads0 = jax.value_and_grad(loss(g0, v0))(v0["params"])
    for g1, v1 in [build(mode)]:
        out = g1.apply(v1, x, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        l1, grads1 = jax.value_and_grad(loss(g1, v1))(v1["params"])
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(grads0),
                        jax.tree_util.tree_leaves(grads1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_remat_wrap_rejects_unknown_mode():
    from p2p_tpu.ops.conv import remat_wrap
    from p2p_tpu.models.resnet_gen import ResnetBlock

    with pytest.raises(ValueError):
        remat_wrap(ResnetBlock, "Conv")


def test_dead_bias_removal_forward_exact():
    """Conv biases in front of mean-subtracting norms are exactly dead:
    the default (dropped) layout computes the SAME function as the
    legacy_layout=True layout with its zero-initialized biases, for both
    BatchNorm (unet) and InstanceNorm (resnet) families."""
    import flax
    import jax
    import jax.numpy as jnp

    from p2p_tpu.models.resnet_gen import ResnetGenerator
    from p2p_tpu.models.unet import UNetGenerator

    x = jnp.asarray(
        np.random.default_rng(0).uniform(-1, 1, (2, 32, 32, 3)), jnp.float32
    )
    for make in (
        lambda lb: UNetGenerator(ngf=8, legacy_layout=lb),
        lambda lb: ResnetGenerator(ngf=8, n_blocks=2, legacy_layout=lb),
    ):
        new, old = make(False), make(True)
        vn = new.init(jax.random.PRNGKey(0), x, True)
        vo = old.init(jax.random.PRNGKey(0), x, True)
        fo = flax.traverse_util.flatten_dict(vo["params"])
        fn_keys = flax.traverse_util.flatten_dict(vn["params"]).keys()
        assert set(fn_keys) < set(fo.keys())  # strictly fewer params
        shared = flax.traverse_util.unflatten_dict(
            {k: fo[k] for k in fn_keys})
        kw = {"mutable": ["batch_stats"]} if "batch_stats" in vn else {}
        bs = ({"batch_stats": vn["batch_stats"]}
              if "batch_stats" in vn else {})
        yn = new.apply({"params": shared, **bs}, x, True, **kw)
        yo = old.apply(vo, x, True, **kw)
        if kw:
            yn, yo = yn[0], yo[0]
        np.testing.assert_array_equal(np.asarray(yn), np.asarray(yo))


def test_split_stem_pair_path_equals_concat():
    """_SplitStemConv: D applied to an UNCONCATENATED (a, b) pair equals D
    on concat(a, b) — same params (Conv_0 holds the full 6-ch kernel), all
    scales/stages, and the b-half gradient matches the concat path's
    sliced cotangent (the train step's grad_fake route)."""
    import numpy as np

    from p2p_tpu.models.patchgan import MultiscaleDiscriminator

    a = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    b = jax.random.normal(jax.random.key(2), (2, 32, 32, 3))
    pair = jnp.concatenate([a, b], axis=-1)
    d = MultiscaleDiscriminator(ndf=8, n_layers=2, num_D=2,
                                use_spectral_norm=False)
    vs = d.init(jax.random.key(0), pair)
    outc = d.apply(vs, pair)
    outp = d.apply(vs, (a, b))
    for fc, fp in zip(outc, outp):
        for x, y in zip(fc, fp):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-5, atol=2e-5)

    def loss_concat(bb):
        return sum(jnp.sum(o[-1])
                   for o in d.apply(vs, jnp.concatenate([a, bb], -1)))

    def loss_pair(bb):
        return sum(jnp.sum(o[-1]) for o in d.apply(vs, (a, bb)))

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_concat)(b)),
        np.asarray(jax.grad(loss_pair)(b)),
        rtol=2e-5, atol=2e-5,
    )


def test_discriminator_norm_d_variants():
    """ModelConfig.norm_d (the pix2pixHD-paper D layout): instance /
    pallas_instance norms on the inner convs are affine-free, so the
    param/spectral trees are IDENTICAL to norm='none' (checkpoints
    interchange); the two instance kinds agree numerically (the fused
    Pallas epilogue == module chain); a norm the step cannot thread is
    rejected."""
    x = jnp.asarray(
        np.random.default_rng(5).uniform(-1, 1, (2, 32, 32, 6)), jnp.float32)
    plain = MultiscaleDiscriminator(ndf=8, n_layers=3, num_D=2)
    inst = MultiscaleDiscriminator(ndf=8, n_layers=3, num_D=2,
                                   norm="instance")
    fused = MultiscaleDiscriminator(ndf=8, n_layers=3, num_D=2,
                                    norm="pallas_instance")
    v = plain.init(jax.random.key(0), x)
    v_i = inst.init(jax.random.key(0), x)
    assert (jax.tree_util.tree_structure(v) ==
            jax.tree_util.tree_structure(v_i))

    out_i = inst.apply(v, x)
    out_f = fused.apply(v, x)
    for fi, ff in zip(out_i, out_f):
        for a, b in zip(fi, ff):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)
    # normed D differs from the norm-free one (the option is live)
    out_p = plain.apply(v, x)
    assert not np.allclose(np.asarray(out_p[0][-1]),
                           np.asarray(out_i[0][-1]))

    # (since PR 34 "batch" is taken too, its statistics threaded by the
    # step: tests/test_vqgan.py)
    with pytest.raises(ValueError, match="stateless"):
        NLayerDiscriminator(ndf=8, norm="layer").init(jax.random.key(0), x)


def test_discriminator_norm_d_composes_with_int8():
    """norm_d composes with the delayed-int8 inner convs: the quant
    collection still threads and the forward stays finite/close to the
    un-normed int8 D's structure (one mutable apply)."""
    d = MultiscaleDiscriminator(ndf=8, n_layers=2, num_D=2, int8=True,
                                int8_delayed=True, norm="pallas_instance")
    x = jnp.asarray(
        np.random.default_rng(6).uniform(-1, 1, (2, 32, 32, 6)), jnp.float32)
    v = d.init(jax.random.key(1), x)
    assert "quant" in v
    out, mut = d.apply(v, x, mutable=["spectral", "quant"])
    assert jax.tree_util.tree_leaves(mut["quant"])
    for leaf in jax.tree_util.tree_leaves(out):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.parametrize("net,shape", [
    (ExpandNetwork(dtype=jnp.bfloat16), (2, 256, 256, 3)),
    (Pix2PixHDGenerator(dtype=jnp.bfloat16), (1, 512, 1024, 3)),
], ids=["expand_256", "pix2pixhd_1024x512"])
def test_param_tree_is_the_same_whichever_conv_form(net, shape, monkeypatch):
    """The thin stems and heads take the blocked form (and the enhancer's
    upsample the subpixel form) at the cells' extents and the plain conv
    with every gate shut:
    the variables are the same leaf for leaf (path, shape, dtype), so a
    checkpoint, the TP rules and the optimizer see one tree. Abstract
    evaluation only, no compute."""
    from p2p_tpu.ops import conv

    def tree():
        v = jax.eval_shape(net.init, jax.random.key(0),
                           jax.ShapeDtypeStruct(shape, jnp.float32))
        return {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
                for path, leaf in jax.tree_util.tree_leaves_with_path(v)}

    before = conv.conv_form_sites()
    routed = tree()
    after = conv.conv_form_sites()
    assert sum(after.values()) - sum(before.values()) >= 2   # stem + head
    monkeypatch.setattr(conv, "_BLOCKED_MIN_PIXELS", 10 ** 12)
    monkeypatch.setattr(conv, "_NEAREST_UP2_MIN_PIXELS", 10 ** 12)
    plain = tree()
    assert conv.conv_form_sites() == after      # every site took nn.Conv
    assert routed == plain
    assert routed["['params']['ConvLayer_0']['Conv_0']['kernel']"][1] \
        == jnp.float32
