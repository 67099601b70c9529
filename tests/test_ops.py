import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.core.config import list_presets
from p2p_tpu.ops import (
    angular_loss,
    pixel_shuffle,
    pixel_unshuffle,
    quantize,
    quantize_ste,
    reflect_pad_2d,
    sobel_edges,
    spectral_normalize,
    total_variation_loss,
)
from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer, upsample_nearest
from p2p_tpu.ops.norm import BatchNorm, InstanceNorm
from p2p_tpu.ops.spectral_norm import SpectralConv


def rng(*shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


# ---------------------------------------------------------------- quantizer
def test_quantize_matches_reference_formula():
    x = jnp.asarray(rng(2, 4, 4, 3)) * 2.0
    for bits in (1, 3, 8):
        n = 2**bits - 1
        expected = np.round(np.clip(np.asarray(x), 0, 1) * n) / n
        np.testing.assert_allclose(quantize(x, bits), expected, rtol=1e-6)
        np.testing.assert_allclose(quantize_ste(x, bits), expected, rtol=1e-6)


def test_quantize_grad_zero_but_ste_passes_through():
    x = jnp.asarray([0.3, 0.7, -0.5, 1.5])
    g_plain = jax.grad(lambda v: jnp.sum(quantize(v, 3)))(x)
    np.testing.assert_allclose(g_plain, np.zeros(4))  # SURVEY Q2 semantics
    g_ste = jax.grad(lambda v: jnp.sum(quantize_ste(v, 3)))(x)
    np.testing.assert_allclose(g_ste, [1.0, 1.0, 0.0, 0.0])  # clamp mask


# ----------------------------------------------------- pixel shuffle family
def test_pixel_unshuffle_matches_torch():
    torch = pytest.importorskip("torch")
    x = rng(2, 8, 8, 6)
    ours = pixel_unshuffle(jnp.asarray(x), 2)
    ref = torch.nn.functional.pixel_unshuffle(
        torch.from_numpy(x).permute(0, 3, 1, 2), 2
    ).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-6)


def test_pixel_shuffle_matches_torch_and_roundtrip():
    torch = pytest.importorskip("torch")
    x = rng(2, 4, 4, 12)
    ours = pixel_shuffle(jnp.asarray(x), 2)
    ref = torch.nn.functional.pixel_shuffle(
        torch.from_numpy(x).permute(0, 3, 1, 2), 2
    ).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-6)
    rt = pixel_unshuffle(pixel_shuffle(jnp.asarray(x), 2), 2)
    np.testing.assert_allclose(rt, x, rtol=1e-6)


# ------------------------------------------------------------------- convs
def test_reflect_pad_matches_torch():
    torch = pytest.importorskip("torch")
    x = rng(1, 5, 5, 2)
    ours = reflect_pad_2d(jnp.asarray(x), 2)
    ref = torch.nn.functional.pad(
        torch.from_numpy(x).permute(0, 3, 1, 2), (2, 2, 2, 2), mode="reflect"
    ).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-6)


def _plain_reflect_pad(x, pad):
    return jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                   mode="reflect")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("extent", ["square", "wide", "smallest"])
@pytest.mark.parametrize("pad", [1, 2, 3, 4])
def test_reflect_pad_backward_is_autodiffs(pad, extent, dtype):
    """``reflect_pad_2d``'s one-pass backward (PR 33) against autodiff of
    ``jnp.pad(mode="reflect")``: the forward to the last bit; the
    gradient to 1e-6 in float32, and in bf16 within one rounding of the
    float32 truth and no farther from it than autodiff's chained bf16
    adds; the same under ``jit`` + ``jax.checkpoint``; and a second-order
    gradient through it. ``smallest`` is the least extent a reflect pad
    is defined for, ``pad + 1``."""
    from p2p_tpu.ops.conv import reflect_pad_sites

    h, w = {"square": (12, 12), "wide": (9, 14),
            "smallest": (pad + 1, pad + 1)}[extent]
    x32 = jnp.asarray(rng(2, h, w, 3, seed=pad))
    g32 = jnp.asarray(rng(2, h + 2 * pad, w + 2 * pad, 3, seed=pad + 10))
    x, g = x32.astype(dtype), g32.astype(dtype)
    before = reflect_pad_sites()
    ours, pull = jax.vjp(lambda a: reflect_pad_2d(a, pad), x)
    assert {k: v - before[k] for k, v in reflect_pad_sites().items()} == {
        "one_pass": 1, "one_pass_w": 0, "autodiff": 0}
    plain, pull_plain = jax.vjp(lambda a: _plain_reflect_pad(a, pad), x)
    assert ours.dtype == plain.dtype and bool(jnp.all(ours == plain))
    dx, = pull(g)
    assert dx.dtype == x.dtype
    chain = np.asarray(pull_plain(g)[0], np.float32)
    truth = np.asarray(jax.vjp(lambda a: _plain_reflect_pad(a, pad), x32)[1](
        g.astype(jnp.float32))[0])
    got = np.asarray(dx, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, truth, rtol=1e-6, atol=1e-6)
    else:
        assert np.all(np.abs(got - truth) <= 2.0 ** -8 * np.abs(truth) + 1e-30)
        assert np.abs(got - truth).max() <= np.abs(chain - truth).max()

    def loss(f):
        return lambda a: jnp.sum(jnp.sin(f(a, pad).astype(jnp.float32))
                                 * g32)

    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(
        rtol=0.05, atol=0.05)
    remat = jax.jit(jax.grad(jax.checkpoint(loss(reflect_pad_2d))))(x)
    np.testing.assert_allclose(
        np.asarray(remat, np.float32),
        np.asarray(jax.grad(loss(_plain_reflect_pad))(x), np.float32), **tol)

    def second(f):
        return jax.grad(lambda a: jnp.sum(
            jax.grad(loss(f))(a).astype(jnp.float32) ** 2))

    np.testing.assert_allclose(
        np.asarray(second(reflect_pad_2d)(x), np.float32),
        np.asarray(second(_plain_reflect_pad)(x), np.float32), **tol)


def test_reflect_pad_scope_holds_the_backward_too():
    """The custom backward is traced under the forward's name stack, so
    the scope ``reflect_pad`` names both directions in the lowered text
    (``scripts/conv_layer_trace.py``'s ``reflect_pad_ms`` reads it)."""
    text = jax.jit(jax.grad(lambda a: jnp.sum(reflect_pad_2d(a, 2) ** 2))
                   ).lower(jnp.ones((1, 6, 6, 2))).as_text(debug_info=True)
    assert "jvp(reflect_pad)/jit(_pad)" in text
    assert "transpose(jvp(reflect_pad))/add" in text


def test_reflect_pad_as_large_as_the_extent_keeps_autodiff():
    """``jnp.pad`` reflects a pad of the extent or more again and again;
    the one-pass fold is written for one reflection, so such a site
    keeps autodiff's backward and says so in the counter."""
    from p2p_tpu.ops.conv import reflect_pad_sites

    x = jnp.asarray(rng(1, 3, 6, 2))
    before = reflect_pad_sites()["autodiff"]
    ours, pull = jax.vjp(lambda a: reflect_pad_2d(a, 4), x)
    assert reflect_pad_sites()["autodiff"] == before + 1
    plain, pull_plain = jax.vjp(lambda a: _plain_reflect_pad(a, 4), x)
    np.testing.assert_array_equal(ours, plain)
    np.testing.assert_array_equal(pull(plain)[0], pull_plain(plain)[0])


@pytest.mark.slow
def test_conv_layer_shapes():
    x = jnp.asarray(rng(2, 16, 16, 3))
    layer = ConvLayer(features=8, kernel_size=9, stride=1)
    params = layer.init(jax.random.key(0), x)
    y = layer.apply(params, x)
    assert y.shape == (2, 16, 16, 8)  # reflection pad keeps spatial size
    layer = ConvLayer(features=8, kernel_size=3, stride=2)
    y = layer.apply(layer.init(jax.random.key(0), x), x)
    assert y.shape == (2, 8, 8, 8)


def test_upsample_nearest_matches_numpy():
    x = rng(1, 3, 3, 2)
    ours = upsample_nearest(jnp.asarray(x), 2)
    ref = np.repeat(np.repeat(x, 2, axis=1), 2, axis=2)
    np.testing.assert_allclose(ours, ref)


def test_upsample_conv_layer():
    x = jnp.asarray(rng(2, 8, 8, 4))
    layer = UpsampleConvLayer(features=2, kernel_size=3, upsample=2)
    y = layer.apply(layer.init(jax.random.key(0), x), x)
    assert y.shape == (2, 16, 16, 2)


# ------------------------------------------------------------------- norms
def test_instance_norm_matches_torch():
    torch = pytest.importorskip("torch")
    x = rng(2, 6, 5, 3)
    ours = InstanceNorm().apply({}, jnp.asarray(x))
    ref = torch.nn.functional.instance_norm(
        torch.from_numpy(x).permute(0, 3, 1, 2)
    ).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_batch_norm_train_matches_torch():
    torch = pytest.importorskip("torch")
    x = rng(4, 6, 5, 3)
    bn = BatchNorm(use_running_average=False)
    variables = bn.init(jax.random.key(0), jnp.asarray(x))
    # identity affine for comparison
    variables = {
        "params": {"BatchNorm_0": {"scale": jnp.ones(3), "bias": jnp.zeros(3)}},
        "batch_stats": variables["batch_stats"],
    }
    ours, updated = bn.apply(variables, jnp.asarray(x), mutable=["batch_stats"])
    tbn = torch.nn.BatchNorm2d(3, momentum=0.1)
    tbn.train()
    with torch.no_grad():
        tbn.weight.fill_(1.0)
        tbn.bias.fill_(0.0)
        ref = tbn(torch.from_numpy(x).permute(0, 3, 1, 2)).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)
    # running stats updated toward batch stats with flax momentum 0.9
    rm = updated["batch_stats"]["BatchNorm_0"]["mean"]
    np.testing.assert_allclose(rm, np.asarray(tbn.running_mean), rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------- spectral norm
def test_spectral_normalize_converges_to_top_singular_value():
    w = jnp.asarray(rng(8, 20))
    u = jnp.ones(8) / np.sqrt(8)
    for _ in range(50):
        sigma, u, v = spectral_normalize(w, u)
    true_sigma = np.linalg.svd(np.asarray(w), compute_uv=False)[0]
    np.testing.assert_allclose(float(sigma), true_sigma, rtol=1e-4)


def test_spectral_conv_updates_state_and_normalizes():
    x = jnp.asarray(rng(1, 8, 8, 4))
    layer = SpectralConv(features=8, kernel_size=4, stride=2, padding=1)
    variables = layer.init(jax.random.key(0), x)
    assert "spectral" in variables
    y, mutated = layer.apply(variables, x, mutable=["spectral"])
    assert y.shape == (1, 4, 4, 8)
    u0 = variables["spectral"]["u"]
    u1 = mutated["spectral"]["u"]
    assert not np.allclose(u0, u1)
    # after many applications sigma(W/sigma) -> 1
    vars_i = {"params": variables["params"], "spectral": variables["spectral"]}
    for _ in range(30):
        _, m = layer.apply(vars_i, x, mutable=["spectral"])
        vars_i = {"params": variables["params"], "spectral": m["spectral"]}
    k = variables["params"]["kernel"]
    w_mat = np.asarray(k).transpose(3, 0, 1, 2).reshape(8, -1)
    u = np.asarray(vars_i["spectral"]["u"])
    v = w_mat.T @ u
    v /= np.linalg.norm(v) + 1e-12
    sigma = u @ w_mat @ v
    np.testing.assert_allclose(
        sigma, np.linalg.svd(w_mat, compute_uv=False)[0], rtol=1e-3
    )


# ------------------------------------------------------------------ losses
def test_tv_loss_matches_reference_formula():
    x = rng(2, 5, 6, 3)
    # reference operates NCHW; formula is layout-symmetric (train.py:123-126)
    nchw = np.transpose(x, (0, 3, 1, 2))
    expected = np.mean(np.abs(nchw[:, :, :, :-1] - nchw[:, :, :, 1:])) + np.mean(
        np.abs(nchw[:, :, :-1, :] - nchw[:, :, 1:, :])
    )
    np.testing.assert_allclose(
        float(total_variation_loss(jnp.asarray(x))), expected, rtol=1e-5
    )


def test_sobel_shapes_and_known_edge():
    img = np.zeros((1, 8, 8, 3), np.float32)
    img[:, :, 4:, 0] = 1.0  # vertical step edge
    g = sobel_edges(jnp.asarray(img))
    assert g.shape == (1, 8, 8, 1)
    assert float(jnp.max(g[:, 1:-1, 1:-1])) == pytest.approx(4.0)
    col = np.asarray(g[0, 2:6, :, 0])
    assert col[:, 3].min() > 0  # edge detected at the step
    assert np.allclose(col[:, 1], 0, atol=1e-5)  # flat (eps under sqrt)


def test_sobel_gradient_finite_on_flat_image():
    """d sqrt(gx²+gy²)/dx is 0/0 on flat regions without the eps — this
    op is live in the train loss behind lambda_sobel."""
    flat = jnp.full((1, 8, 8, 3), 0.7)
    g = jax.grad(lambda im: jnp.sum(sobel_edges(im)))(flat)
    assert bool(jnp.isfinite(g).all())


def test_angular_loss_zero_for_identical_and_90deg():
    a = jnp.asarray(rng(2, 4, 4, 3)) ** 2 + 0.1
    loss_same = float(angular_loss(a, a * 2.0))  # scale-invariant
    assert loss_same < 0.3  # acos clamp keeps it near zero, not exactly 0
    x = jnp.zeros((1, 1, 1, 3)).at[..., 0].set(1.0)
    y = jnp.zeros((1, 1, 1, 3)).at[..., 1].set(1.0)
    assert float(angular_loss(x, y)) == pytest.approx(90.0, abs=0.1)


# ---------------------------------------------------------- pallas kernels
def test_pallas_instance_norm_interpret_matches_xla():
    from p2p_tpu.ops.pallas.instance_norm_kernel import instance_norm_fused

    x = jnp.asarray(rng(2, 8, 8, 4))
    scale = jnp.asarray(rng(4, seed=1))
    bias = jnp.asarray(rng(4, seed=2))
    got = instance_norm_fused(x, scale, bias, interpret=True)
    mean = np.mean(np.asarray(x), axis=(1, 2), keepdims=True)
    var = np.var(np.asarray(x), axis=(1, 2), keepdims=True)
    want = (np.asarray(x) - mean) / np.sqrt(var + 1e-5)
    want = want * np.asarray(scale) + np.asarray(bias)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_pallas_instance_norm_gradients_match_oracle():
    """pallas_call has no autodiff rule — the custom VJP must reproduce the
    XLA-native instance-norm gradients (pix2pixHD trains through this)."""
    from p2p_tpu.ops.pallas.instance_norm import pallas_instance_norm

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 8)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)), jnp.float32)

    def loss_pallas(x, s, b):
        y = pallas_instance_norm(x, s, b, force_pallas=True, interpret=True)
        return jnp.mean(y**2)

    def loss_xla(x, s, b):
        mu = jnp.mean(x, axis=(1, 2), keepdims=True)
        var = jnp.var(x, axis=(1, 2), keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + 1e-5)
        return jnp.mean((y * s + b) ** 2)

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, scale, bias)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_batchnorm_shifted_variance_high_mean_channel():
    """One-pass E[x²]−E[x]² variance is catastrophically wrong for
    high-mean/low-std channels; the shifted form Var = E[(x−c)²]−(E[x−c])²
    with c = the running mean must stay accurate once the running mean has
    warmed up (code-review finding on _FastBatchNorm)."""
    import numpy as np
    from p2p_tpu.ops.norm import BatchNorm

    rng = np.random.default_rng(0)
    mean_true, std_true = 100.0, 0.01
    x = jnp.asarray(
        rng.normal(mean_true, std_true, (8, 16, 16, 1)), jnp.float32
    )
    bn = BatchNorm(use_running_average=False, momentum=0.0)
    variables = bn.init(jax.random.key(0), x)
    # Warm the running mean (momentum=0 → running stats = batch stats).
    _, updated = bn.apply(variables, x, mutable=["batch_stats"])
    rm = float(updated["batch_stats"]["BatchNorm_0"]["mean"][0])
    assert abs(rm - mean_true) < 0.01
    # Second pass: shift ≈ true mean → variance must be accurate, so the
    # normalized output has ~unit std (naive one-pass gives var≈0 here and
    # a wildly wrong scale).
    variables = {"params": variables["params"],
                 "batch_stats": updated["batch_stats"]}
    y, updated2 = bn.apply(variables, x, mutable=["batch_stats"])
    var_est = float(updated2["batch_stats"]["BatchNorm_0"]["var"][0])
    var_true = float(np.var(np.asarray(x)))
    assert abs(var_est - var_true) / var_true < 0.05, (var_est, var_true)
    y_std = float(np.std(np.asarray(y)))
    assert 0.9 < y_std < 1.1, y_std


def test_pallas_instance_norm_block_picker_respects_padded_vmem():
    """The H-block picker must size blocks against the PADDED (8,128) VMEM
    tile: with c=32 at w=1024 the lane padding is 4x, and ignoring it
    overflowed scoped vmem on the pix2pixHD 1024x512 preset."""
    from p2p_tpu.ops.pallas.instance_norm_kernel import _pick_h_block

    for (h, w, c) in [(512, 1024, 32), (512, 1024, 64), (256, 512, 3),
                      (1024, 1024, 1024), (7, 13, 5)]:
        hb = _pick_h_block(h, w, c)
        assert h % hb == 0 and 1 <= hb <= h
        padded = hb * (-(-w // 8) * 8) * (-(-c // 128) * 128) * 4
        assert padded <= 1024 * 1024 or hb == 1, (h, w, c, hb, padded)


def test_pallas_instance_norm_narrow_channels_wide_rows():
    """Interpret-mode correctness at the pix2pixHD local-enhancer shape
    class (few channels, wide rows) vs a numpy oracle."""
    import numpy as np
    from p2p_tpu.ops.pallas.instance_norm import _xla_instance_norm
    from p2p_tpu.ops.pallas.instance_norm_kernel import instance_norm_fused

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(2.0, 1.5, (2, 16, 1024, 32)), jnp.float32)
    got = instance_norm_fused(x, interpret=True)
    want = _xla_instance_norm(x, None, None, 1e-5)
    assert jnp.max(jnp.abs(got - want)) < 1e-4


# ------------------------------------------------------- subpixel deconv
def test_subpixel_deconv_matches_conv_transpose():
    """conv(k2, s1, pad 1) to 4F channels + ``subpixel_interleave`` (the
    float form of ops/int8.QuantSubpixelDeconv) is the exact same
    operator as flax ConvTranspose(k4, s2, 'SAME') under the weight mapping
    W'[dh, dw, (u,v)·F] = W[2dh+u, 2dw+v] (subpixel_interleave docstring)."""
    import numpy as np
    from flax import linen as nn

    from p2p_tpu.ops.conv import subpixel_interleave

    rng = np.random.default_rng(0)
    n, h, w, cin, f = 2, 6, 5, 7, 4
    x = jnp.asarray(rng.normal(size=(n, h, w, cin)), jnp.float32)

    deconv = nn.ConvTranspose(f, kernel_size=(4, 4), strides=(2, 2),
                              padding="SAME")
    vd = deconv.init(jax.random.key(0), x)
    want = deconv.apply(vd, x)

    wt = np.asarray(vd["params"]["kernel"])        # (4,4,cin,f)
    w2 = np.zeros((2, 2, 4, cin, f), np.float32)   # (dh,dw,(u,v),cin,f)
    for dh in range(2):
        for dw in range(2):
            for u in range(2):
                for v in range(2):
                    w2[dh, dw, u * 2 + v] = wt[2 * dh + u, 2 * dw + v]
    sub = nn.Conv(4 * f, (2, 2), padding=1)
    # kernel (2,2,cin,4f) with out channel order (u,v,f)
    vs = {"params": {
        "kernel": jnp.asarray(
            np.moveaxis(w2, 2, 3).reshape(2, 2, cin, 4 * f)),
        "bias": jnp.zeros((4 * f,), jnp.float32),
    }}
    got = subpixel_interleave(sub.apply(vs, x), f)
    assert got.shape == want.shape == (n, 2 * h, 2 * w, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------- sharded pallas instance norm
@pytest.mark.slow
def test_sharded_pallas_instance_norm_matches_oracle(devices8):
    """VERDICT r1 #3: the Pallas InstanceNorm under a data×spatial mesh
    (shard_map, interpret mode) matches the XLA oracle, forward and VJP."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2p_tpu.core.mesh import MeshSpec, make_mesh, mesh_context
    from p2p_tpu.ops.pallas.instance_norm import (
        _xla_instance_norm,
        pallas_instance_norm,
    )

    mesh = make_mesh(MeshSpec(data=4, spatial=2), devices=devices8)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(1.5, 2.0, (4, 16, 8, 6)), jnp.float32)
    scale = jnp.asarray(rng.normal(1.0, 0.1, (6,)), jnp.float32)
    bias = jnp.asarray(rng.normal(0.0, 0.1, (6,)), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "spatial", None, None)))

    with mesh_context(mesh):
        got = jax.jit(
            lambda a, s, b: pallas_instance_norm(a, s, b, force_pallas=True)
        )(xs, scale, bias)
    want = _xla_instance_norm(x, scale, bias, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # VJP parity (dx, dscale, dbias) vs the XLA oracle
    def loss_sharded(a, s, b):
        with mesh_context(mesh):
            return jnp.sum(pallas_instance_norm(a, s, b) ** 2)

    def loss_oracle(a, s, b):
        return jnp.sum(_xla_instance_norm(a, s, b, 1e-5) ** 2)

    g_got = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(xs, scale, bias)
    g_want = jax.grad(loss_oracle, argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_sharded_pallas_instance_norm_no_activation_allgather(devices8):
    """The compiled HLO must keep the pallas custom-call on LOCAL shards:
    no all-gather of the (N,H,W,C) activation may surround it (GSPMD's
    default for un-partitioned custom calls) — only the (N,1,1,C) stat
    psums cross devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2p_tpu.analysis.jaxpr_lint import assert_no_collective_as_large_as
    from p2p_tpu.core.mesh import MeshSpec, make_mesh, mesh_context
    from p2p_tpu.ops.pallas.instance_norm import pallas_instance_norm

    mesh = make_mesh(MeshSpec(data=4, spatial=2), devices=devices8)
    n, h, w, c = 4, 16, 8, 6
    x = jnp.zeros((n, h, w, c), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "spatial", None, None)))

    def fn(a):
        with mesh_context(mesh):
            return pallas_instance_norm(a)

    hlo = jax.jit(fn).lower(xs).compile().as_text()
    # local shard is (1, 8, 8, 6) = 384 elements; any all-gather touching
    # >= the full activation element count means the shard was gathered.
    # The library check matches EVERY shape on any all-gather /
    # all-gather-start line (async forms carry tuple shapes — missing
    # those would pass vacuously).
    assert_no_collective_as_large_as(hlo, n * h * w * c)


def test_angular_loss_gradient_finite_on_zero_vectors():
    """d||v||/dv is 0/0 at v=0 (exactly-mid-gray pixels) — live behind
    lambda_angular, so the eps-under-sqrt guard matters."""
    a = jnp.zeros((1, 4, 4, 3))
    b = jnp.ones((1, 4, 4, 3)) * 0.5
    g = jax.grad(lambda x: angular_loss(b, x))(a)
    assert bool(jnp.isfinite(g).all())


def test_kn2row_thin_conv_matches_conv_fwd_and_grad():
    """kn2row decomposition (ops/conv.py) == XLA conv for thin outputs,
    forward and both gradients (it is the PatchGAN head's compute path)."""
    import jax

    from p2p_tpu.ops.conv import kn2row_thin_conv

    rng = np.random.default_rng(0)
    for (h, w, c, o, pad) in [(17, 17, 64, 1, 2), (10, 14, 32, 2, 1)]:
        x = jnp.asarray(rng.normal(size=(2, h, w, c)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(4, 4, c, o)), jnp.float32)
        ref = jax.lax.conv_general_dilated(
            x, k, (1, 1), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        got = kn2row_thin_conv(x, k, pad)
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)

    x = jnp.asarray(rng.normal(size=(2, 12, 12, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(4, 4, 32, 1)), jnp.float32)
    f1 = lambda x, k: jnp.sum(jnp.sin(kn2row_thin_conv(x, k, 2)))
    f2 = lambda x, k: jnp.sum(jnp.sin(jax.lax.conv_general_dilated(
        x, k, (1, 1), ((2, 2), (2, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))))
    for a, b in zip(jax.grad(f1, (0, 1))(x, k), jax.grad(f2, (0, 1))(x, k)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_nearest_up2_conv_matches_upsample_conv():
    """The subpixel decomposition of UpsampleConvLayer (×2 nearest →
    reflect-pad → 3×3 conv ≡ one low-res 3×3 conv ci→4co + depth-to-space,
    edge-padded) is exact: fwd + dx + dw match the plain chain, written
    out here, with the SAME params, boundary rows included."""
    import jax
    from flax import linen as nn

    from p2p_tpu.ops.conv import UpsampleConvLayer

    x = jnp.asarray(rng(1, 300, 256, 8), jnp.float32)
    layer = UpsampleConvLayer(6, kernel_size=3, upsample=2)
    params = layer.init(jax.random.key(0), x)
    conv = nn.Conv(6, (3, 3), padding="VALID")

    def plain(p, xx):
        up = reflect_pad_2d(upsample_nearest(xx, 2), 1)
        return conv.apply({"params": p["params"]["Conv_0"]}, up)

    ref, ref_vjp = jax.vjp(plain, params, x)
    got, got_vjp = jax.vjp(lambda p, xx: layer.apply(p, xx), params, x)
    # the layer really took the subpixel path: it pads the LOW-RES input
    # (300→302 rows) and never materializes a padded upsampled tensor
    # (600→602 rows, the plain chain's reflect pad)
    jaxpr = str(jax.make_jaxpr(lambda p, xx: layer.apply(p, xx))(params, x))
    assert "302" in jaxpr and "602" not in jaxpr
    assert "602" in str(jax.make_jaxpr(plain)(params, x))

    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    ct = jnp.asarray(rng(*ref.shape, seed=1), jnp.float32)
    (dp_ref, dx_ref) = ref_vjp(ct)
    (dp_got, dx_got) = got_vjp(ct)
    np.testing.assert_allclose(np.asarray(dx_got), np.asarray(dx_ref),
                               rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(dp_got),
                    jax.tree_util.tree_leaves(dp_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

    # under the smallest extent read on the chip (one image at 64x64) the
    # plain chain stays: the padded UPSAMPLED tensor (64+2 = 66 rows) is
    # materialized there
    small = jnp.zeros((1, 32, 32, 8), jnp.float32)
    jaxpr_small = str(jax.make_jaxpr(
        lambda p, xx: layer.apply(p, xx))(
            layer.init(jax.random.key(0), small), small))
    assert "66" in jaxpr_small


@pytest.mark.parametrize("batch,f", [(1, 3), (2, 1), (3, 8), (32, 3)])
def test_depth_to_space_2x_is_the_phase_map(batch, f):
    """``depth_to_space_2x`` puts phase (u,v) at channel block u*2+v:
    ``y[2i+u, 2j+v] = out[i, j, (u*2+v)*F:]``, forward and backward (a
    permutation: the cotangent comes back in place), at the batches the
    cells hold."""
    from p2p_tpu.ops.conv import depth_to_space_2x

    out = np.arange(batch * 2 * 5 * 4 * f, dtype=np.float32).reshape(
        batch, 2, 5, 4 * f)
    want = np.zeros((batch, 4, 10, f), np.float32)
    for u in (0, 1):
        for v in (0, 1):
            want[:, u::2, v::2] = out[..., (u * 2 + v) * f:(u * 2 + v + 1) * f]
    got, vjp = jax.vjp(lambda o: depth_to_space_2x(o, f), jnp.asarray(out))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(vjp(jnp.asarray(want))[0]), out)


def _plain_up2_chain(conv, pad_mode):
    """f(params, x): nearest x2 -> reflect or zero pad 1 -> ``conv``
    (VALID), the chain UpsampleConvLayer runs where the subpixel form does
    not engage, written out."""
    def plain(p, xx):
        up = upsample_nearest(xx, 2)
        up = (reflect_pad_2d(up, 1) if pad_mode == "reflect"
              else jnp.pad(up, ((0, 0), (1, 1), (1, 1), (0, 0))))
        return conv.apply(p, up)
    return plain


def _up2_form_matches_plain(shape, co, pad_mode, dtype, tol):
    """``_NearestUp2Conv`` with ``pad_mode``'s ring against the plain
    chain on the same float32 parameters: output, bias gradient, kernel
    gradient and input gradient agree in shape and dtype and to ``tol``
    of each tensor's largest entry."""
    from flax import linen as nn

    from p2p_tpu.ops.conv import _NearestUp2Conv

    r = np.random.default_rng(co)
    x = jnp.asarray(r.normal(size=shape), dtype)
    n, h, w, _ = shape
    ct = jnp.asarray(r.normal(size=(n, 2 * h, 2 * w, co)), dtype)
    form = _NearestUp2Conv(co, pad_mode, dtype=dtype)
    conv = nn.Conv(co, (3, 3), padding="VALID", dtype=dtype)
    params = form.init(jax.random.key(1), x)
    assert set(params["params"]) == {"kernel", "bias"}
    params = jax.tree_util.tree_map(
        lambda p: jnp.asarray(0.1 * r.normal(size=p.shape), p.dtype), params)
    want, want_vjp = jax.vjp(_plain_up2_chain(conv, pad_mode), params, x)
    got, got_vjp = jax.vjp(form.apply, params, x)
    got = jax.tree_util.tree_leaves((got, got_vjp(ct)))
    want = jax.tree_util.tree_leaves((want, want_vjp(ct)))
    assert len(got) == len(want) == 4    # y, d bias, d kernel, dx
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


@pytest.mark.parametrize("pad_mode", ["reflect", "zero"])
@pytest.mark.parametrize("shape,co", [((2, 12, 16, 64), 32),
                                      ((32, 4, 6, 64), 32),
                                      ((2, 8, 8, 128), 64),
                                      ((1, 6, 10, 8), 6),
                                      ((4, 8, 8, 64), 64),
                                      ((2, 4, 4, 256), 256)],
                         ids=["expand_up1_64to32", "expand_up1_64to32_bs32",
                              "expand_up0_128to64", "odd_8to6",
                              "swinir_up_64to64", "vqgan_up_256to256"])
def test_nearest_up2_conv_bf16_matches_plain_chain(shape, co, pad_mode):
    """The subpixel form in bf16, as the presets compute, at
    ExpandNetwork's, SwinIR's and the VQGAN decoder's widths, with the
    edge ring of a reflect-padded site and the zero ring of a zero-padded
    one: forward, input gradient, weight gradient and bias gradient
    against the plain chain (upsample -> pad -> ``nn.Conv``) on the same
    float32 parameters, relative to each tensor's largest entry. The
    folded kernel is rounded to bf16 once, after the float32 sums."""
    _up2_form_matches_plain(shape, co, pad_mode, jnp.bfloat16, 2e-2)


@pytest.mark.parametrize("pad_mode", ["reflect", "zero"])
@pytest.mark.parametrize("shape,co", [((2, 7, 9, 8), 6), ((1, 8, 10, 16), 12),
                                      ((3, 1, 1, 4), 5)],
                         ids=["odd_7x9", "even_8x10", "one_pixel"])
def test_nearest_up2_conv_f32_is_the_plain_chain(shape, co, pad_mode):
    """In float32 the subpixel form IS the plain chain with either ring,
    to 1e-6 of each tensor's largest entry: the output (boundary rows and
    columns included, where the zero ring of the low-res input stands for
    the zero pad of the upsampled one), the input's gradient, the
    kernel's and the bias's; at an odd and an even extent, and at one
    pixel, where every tap but the centre reads the ring."""
    _up2_form_matches_plain(shape, co, pad_mode, jnp.float32, 1e-6)


@pytest.mark.parametrize("pad_mode", ["reflect", "zero"])
def test_a_checkpoint_of_the_plain_chain_restores_into_the_form(
        pad_mode, monkeypatch):
    """The bytes of a site's variables written while it ran the plain
    chain (the parent's program for a zero-padded site: here the form's
    floor is raised over the input) restore into the variables the engaged
    site builds, key for key and shape for shape, and the engaged site
    then computes the plain chain's output from them."""
    from flax import serialization

    from p2p_tpu.ops import conv

    layer = UpsampleConvLayer(12, kernel_size=3, upsample=2,
                              pad_mode=pad_mode)
    x = jnp.asarray(rng(1, 64, 64, 8), jnp.float32)
    with monkeypatch.context() as m:
        m.setattr(conv, "_NEAREST_UP2_MIN_PIXELS", 10 ** 12)
        before = conv.conv_form_sites()
        written = layer.init(jax.random.key(7), x)
        want = layer.apply(written, x)
        assert conv.conv_form_sites() == before      # the plain chain
    blob = serialization.to_bytes(written)
    before = conv.conv_form_sites()["nearest_up2"]
    target = jax.tree.map(jnp.zeros_like, layer.init(jax.random.key(8), x))
    assert conv.conv_form_sites()["nearest_up2"] - before == 1
    restored = serialization.from_bytes(target, blob)
    assert jax.tree.structure(restored) == jax.tree.structure(written)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(written)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(layer.apply(restored, x)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_a_zero_ring_is_not_an_edge_ring():
    """The two rings differ on the border and nowhere else: a form handed
    the wrong ring is caught by the comparisons above (the interior of an
    image cannot tell them apart)."""
    from p2p_tpu.ops.conv import nearest_up2_conv

    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(1, 6, 6, 4)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, 3, 4, 2)), jnp.float32)
    edge = np.asarray(nearest_up2_conv(x, w, jnp.float32, "reflect"))
    zero = np.asarray(nearest_up2_conv(x, w, jnp.float32, "zero"))
    np.testing.assert_array_equal(edge[:, 1:-1, 1:-1], zero[:, 1:-1, 1:-1])
    assert np.abs(edge[:, 0] - zero[:, 0]).max() > 0.1
    assert np.abs(edge[:, :, -1] - zero[:, :, -1]).max() > 0.1


# (k, C_in, C_out, block, H, W): the layers the blocked form is for, at toy
# extents. k9 on 4 and 8 and k5 on 4 meet whole blocks exactly; k7 on 8
# needs the zero fill (the HD enhancer's 1030 columns -> 1032, here cut
# down in proportion)
BLOCKED_CASES = {
    "k9_12to32_s4": (9, 12, 32, 4, 16, 24),
    "k9_32to3_s8": (9, 32, 3, 8, 16, 24),
    "k7_3to32_s8": (7, 3, 32, 8, 16, 16),
    "k7_32to3_s8": (7, 32, 3, 8, 16, 16),
    "k5_3to64_s4": (5, 3, 64, 4, 16, 16),
    "k7_32to3_s8_zero_fill_32x64": (7, 32, 3, 8, 32, 64),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(BLOCKED_CASES))
def test_blocked_conv_equals_conv(case, dtype, tol):
    """The convolution on blocks of pixels along W is the same sum of the same
    products: forward, input gradient and weight gradient against
    ``lax.conv_general_dilated`` on the same (pre-padded) input and HWIO
    kernel, relative to each tensor's largest entry."""
    from p2p_tpu.ops.conv import blocked_conv

    k, cin, cout, block, h, w = BLOCKED_CASES[case]
    r = np.random.default_rng(k * cin + cout)
    xp = jnp.asarray(r.normal(size=(2, h + k - 1, w + k - 1, cin)), dtype)
    wt = jnp.asarray(0.1 * r.normal(size=(k, k, cin, cout)), jnp.float32)
    ct = jnp.asarray(r.normal(size=(2, h, w, cout)), dtype)

    def plain(xp, wt):
        return jax.lax.conv_general_dilated(
            xp, wt.astype(xp.dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    want, want_vjp = jax.vjp(plain, xp, wt)
    got, got_vjp = jax.vjp(lambda a, b: blocked_conv(a, b, block), xp, wt)
    assert got.shape == want.shape and got.dtype == want.dtype
    for a, b in zip((got,) + got_vjp(ct), (want,) + want_vjp(ct)):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


def _walk_convs(jaxpr):
    """(kernel shape, under the ``blocked_conv`` scope, shape of the
    conv's input, window strides) of every ``conv_general_dilated`` in
    ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            yield (eqn.invars[1].aval.shape,
                   "blocked_conv" in str(eqn.source_info.name_stack),
                   eqn.invars[0].aval.shape,
                   tuple(eqn.params["window_strides"]))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_convs(inner)


def _cpu_mesh(axes):
    """A mesh with ``axes`` (name -> size) over the CPU's virtual devices,
    or None for no axes."""
    from p2p_tpu.core.mesh import MeshSpec, make_mesh

    if not axes:
        return None
    return make_mesh(MeshSpec(**axes), devices=jax.devices()[
        :int(np.prod(list(axes.values())))])


def _traced_convs(layer, shape, mesh_axes=None):
    """(kernel shape, under the ``blocked_conv`` scope, rows of the conv's
    input) of every ``conv_general_dilated`` a layer traces to on an input
    of ``shape``, and the ``conv_form_sites_total`` ticks the trace made;
    with ``mesh_axes``, traced inside ``mesh_context`` of such a mesh of
    the CPU's virtual devices, as the parallel step traces its layers.
    Abstract evaluation only, no compute."""
    from p2p_tpu.core.mesh import mesh_context
    from p2p_tpu.ops.conv import conv_form_sites

    mesh = _cpu_mesh(mesh_axes)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    variables = jax.eval_shape(layer.init, jax.random.key(0), x)
    before = conv_form_sites()
    with mesh_context(mesh):
        convs = [(kernel, blocked, lhs[1]) for kernel, blocked, lhs, _ in
                 _walk_convs(jax.make_jaxpr(layer.apply)(variables,
                                                         x).jaxpr)]
    after = conv_form_sites()
    return convs, {f: after[f] - before[f] for f in after if
                   after[f] != before[f]}


# layer, input shape -> (form counted, the one conv's kernel[, the rows of
# that conv's input[, the axes of a mesh the site is traced under]]). The
# shapes of the benchmark cells, routed as they read on the chip (PERF.md
# section 6, PR 24, PR 28 and PR 43), and toy / odd shapes.
ROUTING_CASES = {
    # reference_256.train: ExpandNetwork's stem and head, C's k5 stem
    "ref_stem_k9_12to32": (ConvLayer(32, kernel_size=9), (32, 256, 256, 12),
                           "blocked", (9, 3, 48, 128)),
    "ref_head_k9_32to3": (UpsampleConvLayer(3, kernel_size=9),
                          (32, 256, 256, 32), "blocked", (9, 2, 256, 24)),
    "ref_cstem_k5_3to64": (ConvLayer(64, kernel_size=5), (32, 256, 256, 3),
                           None, (5, 5, 3, 64)),
    # pix2pixhd_1024x512.train: the enhancer's stem and head, G1's stem
    "hd_stem_k7_3to32": (ConvLayer(32, kernel_size=7), (2, 512, 1024, 3),
                         "blocked", (7, 2, 24, 256)),
    "hd_head_k7_32to3": (ConvLayer(3, kernel_size=7), (2, 512, 1024, 32),
                         "blocked", (7, 2, 256, 24)),
    "g1_stem_k7_3to64": (ConvLayer(64, kernel_size=7), (2, 256, 512, 3),
                         "blocked", (7, 2, 24, 512)),
    # pix2pixhd_2048x1024.train_spatial4: the enhancer's stem and head on
    # one image of the paper's extent
    "hd2048_stem_k7_3to32": (ConvLayer(32, kernel_size=7),
                             (1, 1024, 2048, 3), "blocked", (7, 2, 24, 256)),
    "hd2048_head_k7_32to3": (ConvLayer(3, kernel_size=7),
                             (1, 1024, 2048, 32), "blocked",
                             (7, 2, 256, 24)),
    # a width no block of 8 divides: the plain conv (the hand-made kn2row
    # and im2col forms that caught these lost on the chip and went, PR 27)
    "odd_head_k7_64to3": (ConvLayer(3, kernel_size=7), (1, 600, 516, 64),
                          None, (7, 7, 64, 3)),
    "odd_stem_k7_3to32": (ConvLayer(32, kernel_size=7), (1, 600, 516, 3),
                          None, (7, 7, 3, 32)),
    # k < 7 on a large extent: plain too (was im2col patches; 4.60 ms
    # plain against 7.15 on the chip, PR 24)
    "big_stem_k5_3to64": (ConvLayer(64, kernel_size=5), (1, 600, 512, 3),
                          None, (5, 5, 3, 64)),
    # below the smallest extent measured: the plain conv
    "toy_head_k7_64to3": (ConvLayer(3, kernel_size=7), (1, 64, 64, 64),
                          None, (7, 7, 64, 3)),
    "toy_stem_k7_3to32": (ConvLayer(32, kernel_size=7), (1, 64, 64, 3),
                          None, (7, 7, 3, 32)),
    "toy_head_k9_32to3": (UpsampleConvLayer(3, kernel_size=9),
                          (1, 64, 64, 32), None, (9, 9, 32, 3)),
    # a wide trunk conv never leaves the plain path
    "trunk_k3_128to128": (ConvLayer(128, kernel_size=3), (2, 512, 512, 128),
                          None, (3, 3, 128, 128)),
    # UpsampleConvLayer(k3, upsample=2) either side of both bounds of its
    # rule (fewer than 128 output channels, and a batch of at least 16,384
    # post-upsample pixels = 4*64*64, the smallest read on the chip): in
    # the plain chain the conv reads the padded UPSAMPLED tensor (2*H+2
    # rows); in the subpixel form one conv to 4*C_out channels reads the
    # LOW-RES input with its one ring (H+2 rows) -- _NearestUp2Conv
    "up2_k3_under_floor": (UpsampleConvLayer(6, kernel_size=3, upsample=2),
                           (1, 64, 63, 8), None, (3, 3, 8, 6), 130),
    "up2_k3_at_floor": (UpsampleConvLayer(6, kernel_size=3, upsample=2),
                        (1, 64, 64, 8), "nearest_up2", (3, 3, 8, 24), 66),
    # the floor counts the batch, not one image
    "up2_k3_batch_counts": (UpsampleConvLayer(6, kernel_size=3, upsample=2),
                            (4, 32, 32, 8), "nearest_up2", (3, 3, 8, 24),
                            34),
    "up2_k3_127_channels": (UpsampleConvLayer(127, kernel_size=3, upsample=2),
                            (1, 256, 256, 8), "nearest_up2", (3, 3, 8, 508),
                            258),
    "up2_k3_128_channels": (UpsampleConvLayer(128, kernel_size=3, upsample=2),
                            (1, 256, 256, 8), None, (3, 3, 8, 128), 514),
    # a zero-padded site follows the same rule with a ring of zeros (PR
    # 43): either side of the floor, either side of the ceiling
    "up2_k3_zero_under_floor": (UpsampleConvLayer(6, kernel_size=3,
                                                  upsample=2,
                                                  pad_mode="zero"),
                                (1, 64, 63, 8), None, (3, 3, 8, 6), 130),
    "up2_k3_zero_at_floor": (UpsampleConvLayer(6, kernel_size=3, upsample=2,
                                               pad_mode="zero"),
                             (1, 64, 64, 8), "nearest_up2", (3, 3, 8, 24),
                             66),
    "up2_k3_zero_127_channels": (UpsampleConvLayer(127, kernel_size=3,
                                                   upsample=2,
                                                   pad_mode="zero"),
                                 (1, 256, 256, 8), "nearest_up2",
                                 (3, 3, 8, 508), 258),
    "up2_k3_zero_128_channels": (UpsampleConvLayer(128, kernel_size=3,
                                                   upsample=2,
                                                   pad_mode="zero"),
                                 (1, 256, 256, 8), None, (3, 3, 8, 128),
                                 514),
    # the input sharded along H (a mesh with spatial > 1 made visible, as
    # the parallel step traces its layers): the same rule. At the ceiling
    # the plain chain stays, for either pad (its reflect-padded conv is a
    # halo shard_map there, PR 35: a shard's 256 upsampled rows + 2; the
    # zero-padded one GSPMD's); under it the form engages as off a mesh
    "up2_k3_128_channels_h_sharded": (
        UpsampleConvLayer(128, kernel_size=3, upsample=2),
        (2, 256, 256, 8), "halo", (3, 3, 8, 128), 258,
        dict(data=2, spatial=2)),
    "up2_k3_zero_128_channels_h_sharded": (
        UpsampleConvLayer(128, kernel_size=3, upsample=2, pad_mode="zero"),
        (2, 256, 256, 8), None, (3, 3, 8, 128), 514,
        dict(data=2, spatial=2)),
    "up2_k3_127_channels_h_sharded": (
        UpsampleConvLayer(127, kernel_size=3, upsample=2),
        (2, 256, 256, 8), "nearest_up2", (3, 3, 8, 508), 258,
        dict(data=2, spatial=2)),
    "up2_k3_zero_127_channels_h_sharded": (
        UpsampleConvLayer(127, kernel_size=3, upsample=2, pad_mode="zero"),
        (2, 256, 256, 8), "nearest_up2", (3, 3, 8, 508), 258,
        dict(data=2, spatial=2)),
    # the cells' own sites: ExpandNetwork's two at bs32, the pix2pixhd
    # enhancer's and G1's last, third and second at bs2 and G1's third on
    # the paper's extent under the four-chip cell's mesh (PERF.md section
    # 6, PR 28)
    "ref_up1_64to32": (UpsampleConvLayer(32, kernel_size=3, upsample=2),
                       (32, 128, 128, 64), "nearest_up2", (3, 3, 64, 128),
                       130),
    "ref_up0_128to64": (UpsampleConvLayer(64, kernel_size=3, upsample=2),
                        (32, 64, 64, 128), "nearest_up2", (3, 3, 128, 256),
                        66),
    "hd_enh_64to32": (UpsampleConvLayer(32, kernel_size=3, upsample=2),
                      (2, 256, 512, 64), "nearest_up2", (3, 3, 64, 128), 258),
    "hd_g1_last_128to64": (UpsampleConvLayer(64, kernel_size=3, upsample=2),
                           (2, 128, 256, 128), "nearest_up2",
                           (3, 3, 128, 256), 130),
    "hd_g1_third_256to128": (UpsampleConvLayer(128, kernel_size=3,
                                               upsample=2),
                             (2, 64, 128, 256), None, (3, 3, 256, 128), 130),
    "hd_g1_second_512to256": (UpsampleConvLayer(256, kernel_size=3,
                                                upsample=2),
                              (2, 32, 64, 512), None, (3, 3, 512, 256), 66),
    "hd2048_g1_third_256to128": (UpsampleConvLayer(128, kernel_size=3,
                                                   upsample=2),
                                 (2, 128, 256, 256), None,
                                 (3, 3, 256, 128), 258),
    "hd2048_g1_third_256to128_h_sharded": (
        UpsampleConvLayer(128, kernel_size=3, upsample=2),
        (2, 128, 256, 256), "halo", (3, 3, 256, 128), 130,
        dict(data=2, spatial=2)),
    # swinir_m_realsr_x4_gan.train's two zero-padded sites at bs4 (PR 43),
    # and the VQGAN decoder's widest and narrowest at bs12: at the ceiling
    # and over it, plain
    "sr_up1_64to64": (UpsampleConvLayer(64, kernel_size=3, upsample=2,
                                        pad_mode="zero"),
                      (4, 64, 64, 64), "nearest_up2", (3, 3, 64, 256), 66),
    "sr_up2_64to64": (UpsampleConvLayer(64, kernel_size=3, upsample=2,
                                        pad_mode="zero"),
                      (4, 128, 128, 64), "nearest_up2", (3, 3, 64, 256),
                      130),
    "vq_up3_256to256": (UpsampleConvLayer(256, kernel_size=3, upsample=2,
                                          pad_mode="zero"),
                        (12, 32, 32, 256), None, (3, 3, 256, 256), 66),
    "vq_up1_128to128": (UpsampleConvLayer(128, kernel_size=3, upsample=2,
                                          pad_mode="zero"),
                        (12, 128, 128, 128), None, (3, 3, 128, 128), 258),
    # k5 after the upsample: the edge-pad identity holds for one ring only
    "up2_k5_stays_plain": (UpsampleConvLayer(6, kernel_size=5, upsample=2),
                           (1, 256, 256, 8), None, (5, 5, 8, 6), 516),
}


@pytest.mark.parametrize("case", list(ROUTING_CASES))
def test_thin_conv_dispatch_routing(case):
    """Which form ``ConvLayer`` / ``UpsampleConvLayer`` take follows from
    the shapes they see: the blocked form is a ``conv_general_dilated``
    too, so the forms are told apart by the ``blocked_conv`` scope, the
    kernel's shape (s times the channels, k' taps along W) and the
    ``conv_form_sites_total`` counter, which ticks once a traced site;
    the subpixel form of an upsample by the rows its conv reads, its
    four-phase kernel and its own label of the counter."""
    layer, shape, form, kernel, *rest = ROUTING_CASES[case]
    convs, ticks = _traced_convs(layer, shape, *rest[1:])
    assert ticks == ({form: 1} if form else {})
    assert [c[:2] for c in convs] == [(kernel, form == "blocked")]
    if rest:
        assert [c[2] for c in convs] == rest[:1]


@pytest.mark.parametrize("preset", list_presets())
def test_every_preset_sends_its_thin_convs_to_the_blocked_form(preset):
    """G (and C where the preset has one), traced abstractly at the
    preset's own extent: every stride-1 k >= 7 conv with a thin side of
    <= 16 channels on >= 65k padded pixels runs in the blocked form. A
    width the block does not divide would send such a layer to the plain
    conv, which since PR 27 is the only way to lose PR 24's gain
    silently."""
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.ops.conv import _BLOCKED_MIN_PIXELS, conv_form_sites
    from p2p_tpu.train.state import build_models

    cfg = get_preset(preset)
    g, _, c = build_models(cfg, jnp.bfloat16)
    h = cfg.data.image_size
    x = jax.ShapeDtypeStruct(
        (1, h, cfg.data.image_width or h, cfg.model.input_nc), jnp.bfloat16)
    before = conv_form_sites()["blocked"]
    convs = []
    for net in (g, c):
        if net is not None:
            convs += _walk_convs(jax.make_jaxpr(
                lambda x, net=net: net.init(jax.random.key(0), x, False)
            )(x).jaxpr)
    blocked = [c for c in convs if c[1]]
    assert conv_form_sites()["blocked"] - before == len(blocked)
    lost = [
        (kernel, lhs) for kernel, in_blocked, lhs, strides in convs
        if not in_blocked and strides == (1, 1) and kernel[0] >= 7
        and min(kernel[2:]) <= 16 and lhs[1] * lhs[2] >= _BLOCKED_MIN_PIXELS]
    assert lost == []
    # the presets with such layers (U-Nets have none); the LaMa
    # generator's k7 stem (4 -> 64) and head (64 -> 3) at 256x256
    want = {"reference": 2, "pix2pixhd": 3, "cityscapes_spatial": 2,
            "big_lama": 2}
    assert len(blocked) == want.get(preset, 0)


# preset[@variant] -> (batch, (H, W) or None for the preset's own, the
# (C_out, takes the subpixel form) of G's k3-up2 sites in call order[, the
# axes of a mesh G is traced under]). The batch is the benchmark cell's
# where the preset has one (reference 32, pix2pixhd 2, vqgan 12, swinir 4),
# else the preset's own.
UP2_SITE_CASES = {
    # reference_256.train: both of ExpandNetwork's upsamples (PR 28)
    "reference": (32, None, ((64, True), (32, True))),
    # one image of it, as cli.infer feeds it: the same form as in training
    "reference@bs1": (1, None, ((64, True), (32, True))),
    # pix2pixhd_1024x512.train: G1's last (new in PR 28) and the enhancer's
    "pix2pixhd": (2, None, ((512, False), (256, False), (128, False),
                            (64, True), (32, True))),
    # pix2pixhd_2048x1024.train_spatial4, under its mesh (the global batch
    # the step sees): the same two sites
    "pix2pixhd@1024x2048": (2, (1024, 2048), (
        (512, False), (256, False), (128, False), (64, True), (32, True)),
        dict(data=2, spatial=2)),
    # no cell: follows the rule unmeasured
    "cityscapes_spatial": (4, None, ((128, False), (64, True)),
                           dict(data=2, spatial=2)),
    # vqgan_imagenet_f16_16384.train: the decoder's four zero-padded sites
    # are 512, 256, 256 and 128 wide, at the ceiling or over it: plain, at
    # the cell's batch and at one image
    "vqgan_imagenet_f16": (12, None, ((512, False), (256, False),
                                      (256, False), (128, False))),
    "vqgan_imagenet_f16@bs1": (1, None, ((512, False), (256, False),
                                         (256, False), (128, False))),
    # swinir_m_realsr_x4_gan.train (G reads the 64x64 LQ image): the
    # 'nearest+conv' upsampler's two zero-padded sites, 64 wide at 128x128
    # and 256x256, take the form since PR 43; one image's first site holds
    # exactly the floor's 16,384 pixels
    "swinir_realsr_x4": (4, (64, 64), ((64, True), (64, True))),
    "swinir_realsr_x4@bs1": (1, (64, 64), ((64, True), (64, True))),
}


@pytest.mark.parametrize("case", list(UP2_SITE_CASES) + [
    p for p in list_presets() if p not in UP2_SITE_CASES])
def test_every_preset_sends_its_up2_convs_where_the_rule_says(case):
    """G traced abstractly at the preset's own extent and its cell's
    batch (and at one image; under the cell's mesh where that shards H):
    which ``UpsampleConvLayer(k3, upsample=2)`` sites take the subpixel
    form (``conv_form_sites_total{form=nearest_up2}`` ticks inside the
    site's call) is pinned site by site, so PR 28's and PR 43's gains
    cannot be lost silently and no site changes form unnoticed. The
    U-Net presets have no such site."""
    from flax import linen as nn

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.core.mesh import mesh_context
    from p2p_tpu.ops.conv import conv_form_sites
    from p2p_tpu.train.state import build_models

    batch, extent, want, *mesh_axes = UP2_SITE_CASES.get(
        case, (None, None, ()))
    mesh = _cpu_mesh(*mesh_axes or (None,))
    cfg = get_preset(case.split("@")[0])
    g, _, _ = build_models(cfg, jnp.bfloat16)
    h, w = extent or (cfg.data.image_size,
                      cfg.data.image_width or cfg.data.image_size)
    x = jax.ShapeDtypeStruct(
        (batch or cfg.data.batch_size, h, w, cfg.model.input_nc),
        jnp.bfloat16)
    sites = []

    def watch(next_fun, args, kwargs, context):
        m = context.module
        if not (isinstance(m, UpsampleConvLayer) and m.upsample == 2
                and m.kernel_size == 3 and context.method_name == "__call__"):
            return next_fun(*args, **kwargs)
        before = conv_form_sites()["nearest_up2"]
        out = next_fun(*args, **kwargs)
        sites.append((m.features,
                      conv_form_sites()["nearest_up2"] - before == 1))
        return out

    with nn.intercept_methods(watch), mesh_context(mesh):
        jax.eval_shape(lambda x: g.init(jax.random.key(0), x, False), x)
    assert tuple(sites) == want


def test_no_layer_reads_the_environment_to_pick_an_implementation():
    """Which form a conv, an upsample or a norm's moments take follows
    from the shapes the layer sees and nothing else (PR 27): no source of
    ops/conv.py, ops/norm.py, ops/pallas/ or models/ reads the
    environment, but for two reads that choose no implementation."""
    import glob
    import os
    import re

    import p2p_tpu

    allowed = {
        # interpret-mode switch for CPU runs of the kernels (cli/lint)
        os.path.join("ops", "pallas", "__init__.py"): "P2P_TPU_FORCE_PALLAS",
        # a path to the pretrained weights
        os.path.join("models", "vgg.py"): "P2P_TPU_VGG19_NPZ",
    }
    pkg = os.path.dirname(p2p_tpu.__file__)
    files = ([os.path.join(pkg, "ops", "conv.py"),
              os.path.join(pkg, "ops", "norm.py")]
             + sorted(glob.glob(os.path.join(pkg, "ops", "pallas", "*.py")))
             + sorted(glob.glob(os.path.join(pkg, "models", "*.py"))))
    assert len(files) > 10
    reads = {}
    for path in files:
        with open(path) as f:
            src = f.read()
        for m in re.finditer(r"os\.(?:environ|getenv)\b.*", src):
            reads.setdefault(os.path.relpath(path, pkg), []).append(
                m.group(0))
    for rel, lines in reads.items():
        assert rel in allowed, (rel, lines)
        assert all(allowed[rel] in line for line in lines), (rel, lines)
    assert set(reads) == set(allowed)
