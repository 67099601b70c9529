"""CPU interpret-mode pins for EVERY kernel in ops/pallas/ (ISSUE 6
satellite): each Pallas kernel is checked against its lax reference,
forward AND backward, tolerance-banded, with no TPU in the loop — so a
kernel regression (or a Mosaic-facing rewrite that changes numerics) fails
tier-1 before it ever reaches hardware. Deeper per-kernel behavior tests
(block pickers, sharded shard_map variants, module param-tree compat) live
in tests/test_ops.py; this file is the one-stop fwd+bwd numerics gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.ops.pallas.instance_norm import (
    _xla_instance_norm,
    _xla_instance_norm_act,
)


def _rand(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


def _max_rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1.0)


# --------------------------------------------------- instance_norm_kernel
def test_instance_norm_fused_fwd_bwd_vs_lax():
    from p2p_tpu.ops.pallas.instance_norm_kernel import instance_norm_fused

    x = _rand((2, 8, 6, 5), 0)
    s, b = _rand((5,), 1), _rand((5,), 2)

    got = instance_norm_fused(x, s, b, interpret=True)
    want = _xla_instance_norm(x, s, b, 1e-5)
    assert _max_rel(got, want) < 1e-5

    def loss(fn):
        return lambda xx, ss, bb: jnp.sum(jnp.sin(fn(xx, ss, bb)))

    g_got = jax.grad(loss(lambda *a: instance_norm_fused(
        *a, interpret=True)), (0, 1, 2))(x, s, b)
    g_ref = jax.grad(loss(lambda *a: _xla_instance_norm(*a, 1e-5)),
                     (0, 1, 2))(x, s, b)
    for a, r in zip(g_got, g_ref):
        assert _max_rel(a, r) < 1e-4


# --------------------------------------------------------------- norm_act
@pytest.mark.parametrize("act", ["none", "relu", "leaky"])
@pytest.mark.parametrize("residual", [False, True])
def test_norm_act_fused_fwd_bwd_vs_lax(act, residual):
    """The fused InstanceNorm+act(+residual) epilogue == the lax reference
    (the exact op-order twin in ops/pallas/instance_norm.py), fwd and all
    cotangents (x, scale, bias, residual)."""
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_fused

    x = _rand((2, 8, 6, 5), 3)
    s, b = _rand((5,), 4), _rand((5,), 5)
    r = _rand((2, 8, 6, 5), 6) if residual else None

    got = instance_norm_act_fused(x, s, b, r, act=act, interpret=True)
    want = _xla_instance_norm_act(x, s, b, r, act, 0.2, 1e-5)
    assert _max_rel(got, want) < 1e-5

    args = (x, s, b) + ((r,) if residual else ())
    nargs = len(args)

    def wrap(fn):
        def loss(*a):
            rr = a[3] if residual else None
            return jnp.sum(jnp.sin(fn(a[0], a[1], a[2], rr)))
        return loss

    g_got = jax.grad(wrap(lambda xx, ss, bb, rr: instance_norm_act_fused(
        xx, ss, bb, rr, act=act, interpret=True)),
        tuple(range(nargs)))(*args)
    g_ref = jax.grad(wrap(lambda xx, ss, bb, rr: _xla_instance_norm_act(
        xx, ss, bb, rr, act, 0.2, 1e-5)), tuple(range(nargs)))(*args)
    for a, r_ in zip(g_got, g_ref):
        assert _max_rel(a, r_) < 1e-4


# --------------------------------------------------- norm_act_quant (14)
@pytest.mark.parametrize("act", ["none", "relu", "leaky"])
@pytest.mark.parametrize("affine", [False, True])
def test_norm_act_quant_fused_fwd_vs_reference(act, affine):
    """The quantize-fused epilogue kernel (interpret mode) == the lax
    reference: int8-grid output (integer values in [-127,127], carried in
    the compute dtype), identical amax proposal. The two backends compute
    the norm statistics with different (equivalent) formulas, so a value
    EXACTLY on a rounding boundary may flip by one grid step — bounded,
    rare, and asserted as such."""
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_quant

    x = _rand((2, 8, 6, 5), 7)
    s = _rand((5,), 8) if affine else None
    b = _rand((5,), 9) if affine else None
    sx = jnp.float32(0.01234)
    yq_k, amax_k = instance_norm_act_quant(
        x, sx, s, b, act=act, use_kernel=True, interpret=True)
    yq_r, amax_r = instance_norm_act_quant(
        x, sx, s, b, act=act, use_kernel=False)
    assert yq_k.dtype == x.dtype and yq_r.dtype == x.dtype
    got = np.asarray(yq_k, np.float32)
    ref = np.asarray(yq_r, np.float32)
    assert np.all(np.abs(got) <= 127) and np.all(got == np.round(got))
    assert np.max(np.abs(got - ref)) <= 1
    assert (got == ref).mean() > 0.99
    assert abs(float(amax_k) - float(amax_r)) <= 1e-5 * max(
        1.0, abs(float(amax_r)))


@pytest.mark.parametrize("act", ["relu", "leaky"])
def test_norm_act_quant_bwd_is_the_ste_law(act):
    """Backward of the quantize-fused epilogue mirrors the delayed-int8
    STE law. The op's contract (module docstring): the incoming
    cotangent is w.r.t. the DEQUANTIZED surrogate sx·q — exactly what
    ``int8_conv_pq`` hands back — and passes straight through clip/round
    onto the act/norm VJP. So feeding the surrogate cotangent of
    ``L = Σ sin(ŷ)`` must reproduce the gradient of the UNQUANTIZED
    reference chain up to quantization noise in the cotangent itself;
    the stored scale gets a ZERO cotangent exactly (state, not a
    parameter)."""
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_quant

    x = _rand((2, 8, 6, 5), 10)
    s, b = _rand((5,), 11), _rand((5,), 12)
    # a CALIBRATED stored scale (amax/127, what the delayed path
    # converges to) — an undersized scale would clip, and clipping is
    # deliberately outside the STE identity this pin states
    y0 = _xla_instance_norm_act(x, s, b, None, act, 0.2, 1e-5)
    sx = jnp.float32(jnp.max(jnp.abs(y0)) / 127.0)

    def fused(xx, ss, bb):
        return instance_norm_act_quant(
            xx, sx, ss, bb, act=act, use_kernel=True, interpret=True)

    (q, _), vjp_f = jax.vjp(fused, x, s, b)
    ct = jnp.cos(q.astype(jnp.float32) * sx)        # dL/dŷ, L = Σ sin(ŷ)
    g_f = vjp_f((ct.astype(q.dtype), jnp.zeros((), jnp.float32)))

    def ref(xx, ss, bb):
        return _xla_instance_norm_act(xx, ss, bb, None, act, 0.2, 1e-5)

    y_ref, vjp_r = jax.vjp(ref, x, s, b)
    g_r = vjp_r(jnp.cos(y_ref.astype(jnp.float32)).astype(y_ref.dtype))
    for a, r in zip(g_f, g_r):
        # the two cotangents differ only by the quantization error of ŷ
        # (≤ sx/2 per element; cos amplifies it near zero crossings —
        # hence the absolute term)
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=5e-2, atol=0.12)
    # dsx is identically zero by the delayed-scale contract
    dsx = jax.grad(lambda sxx: jnp.sum(instance_norm_act_quant(
        x, sxx, s, b, act=act, use_kernel=True, interpret=True
    )[0].astype(jnp.float32)))(sx)
    assert float(dsx) == 0.0


def test_make_norm_act_quant_seam_routes_and_guards():
    """ops/norm.make_norm_act quant_scale form: the pallas_instance kind
    emits (q, amax); stateful kinds refuse; residual composition
    refuses (no quantized resblock tail in the zoo)."""
    from p2p_tpu.ops.norm import make_norm_act

    x = _rand((2, 8, 6, 5), 13)
    na = make_norm_act("pallas_instance")
    q, amax = na(x, act="leaky", slope=0.2, quant_scale=jnp.float32(0.01))
    qv = np.asarray(q, np.float32)
    assert np.all(np.abs(qv) <= 127) and np.all(qv == np.round(qv))
    assert float(amax) > 0
    with pytest.raises(ValueError):
        na(x, act="leaky", residual=x, quant_scale=jnp.float32(0.01))
    with pytest.raises(ValueError):
        make_norm_act("batch")(x, act="leaky",
                               quant_scale=jnp.float32(0.01))


def test_norm_act_rejects_bad_act_and_slope():
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_fused

    x = _rand((1, 8, 8, 4), 7)
    with pytest.raises(ValueError, match="act must be one of"):
        instance_norm_act_fused(x, act="gelu", interpret=True)
    with pytest.raises(ValueError, match="slope > 0"):
        instance_norm_act_fused(x, act="leaky", slope=-0.1, interpret=True)


def test_pallas_instance_norm_act_dispatch_matches_fallback():
    """The dispatch seam: force_pallas+interpret (the kernel program) ==
    the off-TPU lax fallback the CPU tier-1 runs — so model call sites
    behave identically whichever side of the seam executes."""
    from p2p_tpu.ops.pallas.instance_norm import pallas_instance_norm_act

    x = _rand((2, 8, 8, 6), 8)
    r = _rand((2, 8, 8, 6), 9)
    for act in ("none", "relu", "leaky"):
        fallback = pallas_instance_norm_act(x, residual=r, act=act)
        kernel = pallas_instance_norm_act(x, residual=r, act=act,
                                          force_pallas=True, interpret=True)
        assert _max_rel(kernel, fallback) < 1e-5


def test_sharded_norm_act_matches_oracle(devices8):
    """The spatial-sharded fused epilogue (shard_map + psum'd stat tiles,
    interpret mode) == the unsharded lax oracle, fwd + dx + dresidual."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2p_tpu.core.mesh import MeshSpec, make_mesh, mesh_context
    from p2p_tpu.ops.pallas.instance_norm import (
        sharded_pallas_instance_norm_act,
    )

    mesh = make_mesh(MeshSpec(data=2, spatial=2), devices=devices8[:4])
    x = _rand((4, 8, 8, 6), 10)
    r = _rand((4, 8, 8, 6), 11)
    sh = NamedSharding(mesh, P("data", "spatial", None, None))
    xs, rs = jax.device_put(x, sh), jax.device_put(r, sh)

    with mesh_context(mesh):
        got = jax.jit(lambda a, b: sharded_pallas_instance_norm_act(
            a, None, None, b, "relu", 0.2, 1e-5, mesh, interpret=True)
        )(xs, rs)
    want = _xla_instance_norm_act(x, None, None, r, "relu", 0.2, 1e-5)
    assert _max_rel(got, want) < 1e-5

    def loss_sharded(a, b):
        with mesh_context(mesh):
            return jnp.sum(jnp.sin(sharded_pallas_instance_norm_act(
                a, None, None, b, "relu", 0.2, 1e-5, mesh, interpret=True)))

    def loss_ref(a, b):
        return jnp.sum(jnp.sin(_xla_instance_norm_act(
            a, None, None, b, "relu", 0.2, 1e-5)))

    gx, gr = jax.jit(jax.grad(loss_sharded, (0, 1)))(xs, rs)
    rx, rr = jax.grad(loss_ref, (0, 1))(x, r)
    assert _max_rel(gx, rx) < 1e-4 and _max_rel(gr, rr) < 1e-4


def test_make_norm_act_fused_equals_module_chain():
    """ops/norm.make_norm_act: the pallas_instance fused path == the
    instance module + explicit act + residual add chain the other kinds
    run — the model-seam equivalence that lets norm='pallas_instance'
    swap in without retraining."""
    from flax import linen as nn

    from p2p_tpu.ops.norm import make_norm_act

    class Blk(nn.Module):
        kind: str

        @nn.compact
        def __call__(self, x, r):
            na = make_norm_act(self.kind)
            return na(x, act="leaky", slope=0.2, residual=r)

    x = _rand((2, 8, 8, 6), 12)
    r = _rand((2, 8, 8, 6), 13)
    ref = Blk(kind="instance")
    fused = Blk(kind="pallas_instance")
    v = ref.init(jax.random.key(0), x, r)
    assert v == {}  # affine-free: no params either way
    y_ref = ref.apply({}, x, r)
    y_fused = fused.apply({}, x, r)
    assert _max_rel(y_fused, y_ref) < 1e-5


# ----------------------------------------------------------- dual_moments
def test_dual_moments_bwd_matches_autodiff():
    """dual_moments' custom VJP == autodiff of the explicit reductions."""
    from p2p_tpu.ops.norm import dual_moments

    xc = _rand((4, 6, 5), 15)

    def loss_dm(a):
        s, ss = dual_moments(a)
        return jnp.sum(jnp.sin(s) + jnp.cos(ss))

    def loss_ref(a):
        af = a.astype(jnp.float32)
        dims = tuple(range(a.ndim - 1))
        return jnp.sum(jnp.sin(jnp.sum(af, dims))
                       + jnp.cos(jnp.sum(af * af, dims)))

    g = jax.grad(loss_dm)(xc)
    gr = jax.grad(loss_ref)(xc)
    assert _max_rel(g, gr) < 1e-5
