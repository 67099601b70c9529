"""Parallelism tests on the 8-fake-CPU-device mesh (SURVEY.md §4.3):
halo exchange vs jnp.pad oracles, the halo convolution vs unsharded,
GSPMD stride-2 conv equivalence, and DP train-step == single-device step.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from p2p_tpu.core.config import get_preset
from p2p_tpu.core.mesh import (
    MeshSpec,
    batch_sharding,
    make_mesh,
    replicated,
)
from p2p_tpu.parallel import (
    halo_exchange,
    halo_conv,
    make_parallel_train_step,
    make_sharded_temporal_conv,
    replicate_state,
    ring_shift,
    shard_batch,
)


def _axis_mesh(devices8, n, name):
    return Mesh(np.asarray(devices8[:n]), (name,))


# ---------------------------------------------------------------- halo

@pytest.mark.parametrize("edge_mode,np_mode", [
    ("reflect", "reflect"), ("zero", "constant"), ("wrap", "wrap"),
])
def test_halo_exchange_matches_pad_oracle(devices8, edge_mode, np_mode):
    mesh = _axis_mesh(devices8, 4, "s")
    x = jax.random.normal(jax.random.key(0), (2, 16, 5, 3))
    halo = 2

    fn = shard_map(
        functools.partial(
            halo_exchange, dim=1, halo=halo, axis_name="s", edge_mode=edge_mode
        ),
        mesh=mesh,
        in_specs=P(None, "s", None, None),
        out_specs=P(None, "s", None, None),
        check_vma=False,
    )
    out = np.asarray(fn(x))
    # Each shard independently = its 4-row slice padded with true neighbors.
    ref = np.pad(
        np.asarray(x), ((0, 0), (halo, halo), (0, 0), (0, 0)), mode=np_mode
    )
    for i in range(4):
        lo = i * 4
        expect = ref[:, lo : lo + 4 + 2 * halo]
        got = out[:, i * (4 + 2 * halo) : (i + 1) * (4 + 2 * halo)]
        np.testing.assert_allclose(got, expect, err_msg=f"shard {i}")


def test_ring_shift(devices8):
    mesh = _axis_mesh(devices8, 4, "t")
    x = jnp.arange(8.0).reshape(8, 1)
    fn = shard_map(
        functools.partial(ring_shift, axis_name="t", shift=1),
        mesh=mesh, in_specs=P("t", None), out_specs=P("t", None),
        check_vma=False,
    )
    out = np.asarray(fn(x)).ravel()
    # shard i's block moves to shard i+1
    np.testing.assert_allclose(out, [6, 7, 0, 1, 2, 3, 4, 5])


# ---------------------------------------------------------------- spatial

def _conv_oracle(x, kernel, stride=1, mode="reflect"):
    p = kernel.shape[0] // 2
    if p:
        if mode == "reflect":
            x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect")
        else:
            x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    dn = lax.conv_dimension_numbers(x.shape, kernel.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(x, kernel, (stride, stride), "VALID",
                                    dimension_numbers=dn)


@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
def test_halo_conv_matches_unsharded(devices8, k, stride):
    """``parallel.spatial.halo_conv`` (the one halo convolution in the
    tree; ``ops/conv.HaloConv`` is its caller) on spatial=4, called as a
    function on global arrays: the reflect-padded unsharded conv."""
    mesh = make_mesh(MeshSpec(data=1, spatial=4), devices=devices8[:4])
    x = jax.random.normal(jax.random.key(1), (2, 32, 16, 4))
    kernel = jax.random.normal(jax.random.key(2), (k, k, 4, 8)) * 0.1

    got = halo_conv(x, kernel, mesh, stride=stride)
    want = _conv_oracle(x, kernel, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gspmd_stride2_conv_matches_unsharded(devices8):
    """The GSPMD path: plain jit on an H-sharded input — XLA inserts the
    halo exchange, including for stride 2 where we don't hand-roll it."""
    mesh = _axis_mesh(devices8, 4, "spatial")
    x = jax.random.normal(jax.random.key(3), (2, 32, 16, 4))
    kernel = jax.random.normal(jax.random.key(4), (3, 3, 4, 8)) * 0.1

    f = jax.jit(lambda a, w: _conv_oracle(a, w, stride=2, mode="zero"))
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "spatial", None, None)))
    got = f(xs, kernel)
    want = f(x, kernel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_blocked_conv_layer_under_spatial_sharding_matches_unsharded(devices8):
    """ExpandNetwork's k9 head 32->3 at the smallest extent the blocked
    form takes (256x256; ops/conv.py), H sharded over ``spatial``: the
    rule sees the global extent, the blocks lie along W, and GSPMD's
    result equals the single-device one, forward and both gradients."""
    from p2p_tpu.ops.conv import ConvLayer, conv_form_sites

    mesh = _axis_mesh(devices8, 2, "spatial")
    layer = ConvLayer(3, kernel_size=9)
    x = jax.random.normal(jax.random.key(5), (1, 256, 256, 32))
    before = conv_form_sites()["blocked"]
    variables = layer.init(jax.random.key(6), x)
    assert conv_form_sites()["blocked"] == before + 1

    f = jax.jit(jax.value_and_grad(
        lambda v, a: jnp.sum(jnp.sin(layer.apply(v, a))), argnums=(0, 1)))
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "spatial", None, None)))
    got = jax.tree.leaves(f(variables, xs))
    want = jax.tree.leaves(f(variables, x))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- temporal

@pytest.mark.slow
def test_sharded_temporal_conv3d_matches_unsharded(devices8):
    mesh = _axis_mesh(devices8, 4, "time")
    x = jax.random.normal(jax.random.key(5), (2, 8, 6, 6, 3))
    kernel = jax.random.normal(jax.random.key(6), (3, 3, 3, 3, 4)) * 0.1

    fn = make_sharded_temporal_conv(mesh)
    got = fn(x, kernel)

    dn = lax.conv_dimension_numbers(x.shape, kernel.shape,
                                    ("NDHWC", "DHWIO", "NDHWC"))
    want = lax.conv_general_dilated(
        x, kernel, (1, 1, 1), [(1, 1), (1, 1), (1, 1)], dimension_numbers=dn
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- DP step

def _tiny_cfg(batch):
    import dataclasses

    cfg = get_preset("reference")
    return cfg.replace(
        data=dataclasses.replace(cfg.data, image_size=32, batch_size=batch),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
    )


@pytest.mark.slow
def test_dp_train_step_matches_single_device(devices8):
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = _tiny_cfg(batch=8)
    rng = jax.random.key(0)
    batch = {
        "input": jax.random.normal(jax.random.key(7), (8, 32, 32, 3)),
        "target": jax.random.normal(jax.random.key(8), (8, 32, 32, 3)),
    }

    state_a = create_train_state(cfg, rng, batch)
    state_b = jax.tree_util.tree_map(jnp.copy, state_a)

    step_single = build_train_step(cfg, jit=False)
    new_a, met_a = jax.jit(step_single)(state_a, batch)

    mesh = make_mesh(MeshSpec(data=8), devices=devices8)
    step_dp = make_parallel_train_step(cfg, mesh)
    state_b = replicate_state(state_b, mesh)
    new_b, met_b = step_dp(state_b, shard_batch(batch, mesh))

    for k in met_a:
        np.testing.assert_allclose(
            np.asarray(met_a[k]), np.asarray(met_b[k]),
            rtol=2e-4, atol=2e-4, err_msg=f"metric {k}",
        )
    pa = jax.tree_util.tree_leaves(new_a.params_g)
    pb = jax.tree_util.tree_leaves(new_b.params_g)
    for la, lb in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_data_spatial_mixed_mesh_runs(devices8):
    """data=2 × spatial=2 × time=2 mesh: the full step compiles and runs
    with batch sharded over data AND H over spatial on a 3-axis mesh."""
    from p2p_tpu.train.state import create_train_state

    cfg = _tiny_cfg(batch=4)
    mesh = make_mesh(MeshSpec(data=2, spatial=2, time=2), devices=devices8)
    batch = {
        "input": jax.random.normal(jax.random.key(9), (4, 32, 32, 3)),
        "target": jax.random.normal(jax.random.key(10), (4, 32, 32, 3)),
    }
    state = create_train_state(cfg, jax.random.key(1), batch)
    state = replicate_state(state, mesh)
    step = make_parallel_train_step(cfg, mesh)
    new_state, metrics = step(state, shard_batch(batch, mesh))
    for v in metrics.values():
        assert np.isfinite(np.asarray(v)), metrics
    assert int(new_state.step) == 1


# ------------------------------------------------------- tensor parallel
@pytest.mark.slow
def test_tp_train_step_matches_single_device(devices8):
    """VERDICT r1 missing: Megatron-style channel shards on the ResNet
    trunk's conv pairs (parallel/tp.py) over a data=2 x model=2 mesh match
    the unsharded step to fp tolerance, and the trunk kernels really are
    channel-sharded."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.core.mesh import MeshSpec, make_mesh
    from p2p_tpu.parallel.dp import make_parallel_train_step, shard_batch
    from p2p_tpu.parallel.tp import place_state_tp, tp_sharding_tree
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = get_preset("cityscapes_spatial")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, n_blocks=2,
                                  num_D=2, n_layers_D=2),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 image_width=32),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, spatial=1, time=1, model=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )
    mesh = make_mesh(MeshSpec(data=2, spatial=1, time=1, model=2),
                     devices=devices8[:4])
    rng = np.random.default_rng(0)
    batch = {
        k: jnp.asarray(rng.uniform(-1, 1, (2, 16, 32, 3)), jnp.float32)
        for k in ("input", "target")
    }
    state = create_train_state(cfg, jax.random.key(0), batch)

    # single-device oracle
    ref_step = build_train_step(cfg)
    ref_state, ref_metrics = ref_step(
        jax.tree_util.tree_map(jnp.copy, state), dict(batch))

    # TP: min_ch=16 so the tiny 32-channel trunk (ngf=8 x4) shards
    min_ch = 16
    ssh = tp_sharding_tree(state, mesh, min_ch=min_ch)
    tp_step = make_parallel_train_step(cfg, mesh, state_sharding=ssh)
    tp_state = place_state_tp(state, mesh, min_ch=min_ch)
    # the trunk pair kernels must actually be channel-sharded
    k0 = tp_state.params_g["ResnetBlock_0"]["ConvLayer_0"]["Conv_0"]["kernel"]
    assert "model" in str(k0.sharding.spec), k0.sharding
    tp_state, tp_metrics = tp_step(tp_state, shard_batch(batch, mesh))

    for k in ref_metrics:
        np.testing.assert_allclose(
            float(ref_metrics[k]), float(tp_metrics[k]), rtol=2e-4, atol=2e-4,
        )
    # updated trunk params agree with the oracle
    a = np.asarray(
        ref_state.params_g["ResnetBlock_0"]["ConvLayer_0"]["Conv_0"]["kernel"])
    b = np.asarray(
        tp_state.params_g["ResnetBlock_0"]["ConvLayer_0"]["Conv_0"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def _run_tp_equivalence(cfg, mesh, batch, min_ch, sharded_probes):
    """Shared harness: TP-annotated step == single-device oracle, and the
    named probe kernels really are model-axis-sharded."""
    from p2p_tpu.parallel.dp import make_parallel_train_step, shard_batch
    from p2p_tpu.parallel.tp import place_state_tp, tp_sharding_tree
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    state = create_train_state(cfg, jax.random.key(0), batch)
    ref_step = build_train_step(cfg)
    ref_state, ref_metrics = ref_step(
        jax.tree_util.tree_map(jnp.copy, state), dict(batch))

    ssh = tp_sharding_tree(state, mesh, min_ch=min_ch)
    tp_step = make_parallel_train_step(cfg, mesh, state_sharding=ssh)
    tp_state = place_state_tp(state, mesh, min_ch=min_ch)
    for tree_name, path in sharded_probes:
        leaf = getattr(tp_state, tree_name)
        for k in path:
            leaf = leaf[k]
        assert "model" in str(leaf.sharding.spec), (path, leaf.sharding)
    tp_state, tp_metrics = tp_step(tp_state, shard_batch(batch, mesh))

    for k in ref_metrics:
        # 8e-4: the λ=100-scaled L1 rows sit at ~5e-4 relative on the
        # 0.4.x CPU backend (GSPMD psum reduction order) — observed on
        # the untouched round-5 tree the first time this suite became
        # runnable under that jax; the newer vma-era backend lands ~3e-4
        np.testing.assert_allclose(
            float(ref_metrics[k]), float(tp_metrics[k]),
            rtol=8e-4, atol=8e-4, err_msg=k)
    for tree_name in ("params_g", "params_d"):
        for la, lb in zip(
            jax.tree_util.tree_leaves(getattr(ref_state, tree_name)),
            jax.tree_util.tree_leaves(getattr(tp_state, tree_name)),
        ):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_tp_facades_unet_and_d_chain_match_single_device(devices8):
    """VERDICT r4 #7: the widened TP coverage — U-Net encoder/bottleneck
    pairs (down3→down4, down5→up5) AND the PatchGAN scale's shape-keyed
    channel chain — matches the unsharded facades step, with the probe
    kernels actually model-sharded."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.core.mesh import MeshSpec, make_mesh

    cfg = get_preset("facades")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=64),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, spatial=1, time=1, model=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )
    mesh = make_mesh(MeshSpec(data=2, spatial=1, time=1, model=2),
                     devices=devices8[:4])
    rng = np.random.default_rng(3)
    batch = {
        k: jnp.asarray(rng.uniform(-1, 1, (2, 64, 64, 3)), jnp.float32)
        for k in ("input", "target")
    }
    # ngf=8 U-Net: down3..5/up5 are 64-channel; ndf=8 D chain doubles
    # 8→16→32→64 — log2 parity out-shards 16→32 and in-shards 32→64 at
    # min_ch=16
    _run_tp_equivalence(
        cfg, mesh, batch, min_ch=16,
        sharded_probes=[
            ("params_g", ("down3", "kernel")),       # C_out shard
            ("params_g", ("down4", "kernel")),       # C_in shard
            ("params_g", ("up5", "kernel")),         # bottleneck C_in
            ("params_d", ("scale0", "_PlainConv_2", "Conv_0", "kernel")),
            ("params_d", ("scale0", "_PlainConv_3", "Conv_0", "kernel")),
        ],
    )


@pytest.mark.slow
def test_tp_pix2pixhd_global_and_spectral_d_match_single_device(devices8):
    """VERDICT r4 #7: TP on pix2pixHD's ``global`` encoder/decoder
    transitions and the SpectralConv discriminator chains matches the
    unsharded step (spectral u/v power iteration included)."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.core.mesh import MeshSpec, make_mesh

    cfg = get_preset("pix2pixhd")
    cfg = cfg.replace(
        # norm='instance' (XLA): the Pallas InstanceNorm's manual region
        # covers the spatial axis, not channel shards (tp.py docstring)
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, n_blocks=1,
                                  num_D=2, n_layers_D=2, norm="instance"),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32,
                                 image_width=32),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, spatial=1, time=1, model=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )
    mesh = make_mesh(MeshSpec(data=2, spatial=1, time=1, model=2),
                     devices=devices8[:4])
    rng = np.random.default_rng(4)
    batch = {
        k: jnp.asarray(rng.uniform(-1, 1, (2, 32, 32, 3)), jnp.float32)
        for k in ("input", "target")
    }
    # ndf=8 spectral chain 8→16→32→64: parity shards SpectralConv_1
    # (16→32, C_out) and SpectralConv_2 (32→64, C_in)
    _run_tp_equivalence(
        cfg, mesh, batch, min_ch=16,
        sharded_probes=[
            ("params_g", ("global", "ConvLayer_3", "Conv_0", "kernel")),
            ("params_g", ("global", "ConvLayer_4", "Conv_0", "kernel")),
            ("params_d", ("scale0", "SpectralConv_1", "kernel")),
        ],
    )


@pytest.mark.slow
def test_tp_expand_flagship_trunk_matches_single_device(devices8):
    """Round-5 TP widening, part 2: the flagship ExpandNetwork's
    ``ResidualBlock_i`` trunk (the reference-faithful preset's G —
    networks.py:472-480) channel-shards under the same Megatron pair rule
    as the ResNet family, and the TP step matches the unsharded oracle."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.core.mesh import MeshSpec, make_mesh

    cfg = get_preset("reference")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, n_blocks=2,
                                  num_D=2, n_layers_D=2),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, spatial=1, time=1, model=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )
    mesh = make_mesh(MeshSpec(data=2, spatial=1, time=1, model=2),
                     devices=devices8[:4])
    rng = np.random.default_rng(5)
    batch = {
        k: jnp.asarray(rng.uniform(-1, 1, (2, 32, 32, 3)), jnp.float32)
        for k in ("input", "target")
    }
    # ngf=8 trunk: 32-channel ResidualBlock conv pairs shard at min_ch=16
    _run_tp_equivalence(
        cfg, mesh, batch, min_ch=16,
        sharded_probes=[
            ("params_g", ("ResidualBlock_0", "ConvLayer_0", "Conv_0",
                          "kernel")),
            ("params_g", ("ResidualBlock_1", "ConvLayer_1", "Conv_0",
                          "kernel")),
        ],
    )


# ------------------------------------------------- FSDP / ZeRO sharding

def _fsdp_cfg(ema: bool = True):
    import dataclasses

    cfg = get_preset("facades")
    return cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8,
                                  use_dropout=False),
        data=dataclasses.replace(cfg.data, batch_size=4, image_size=32),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, fsdp=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
        health=dataclasses.replace(
            cfg.health, ema_decay=0.5 if ema else None),
    )


def test_fsdp_rules_shard_moments_and_ema(devices8):
    """Layout pin, no compile: on an fsdp mesh the ONE partitioner
    shards Adam moments and ema_g over the fsdp axis, keeps params/
    batch_stats replicated (fsdp_params off), and the spec builder
    replicates what no dim divides."""
    from p2p_tpu.parallel.rules import state_target_shardings
    from p2p_tpu.train.state import create_train_state

    cfg = _fsdp_cfg()
    mesh = make_mesh(MeshSpec(data=2, fsdp=2), devices=devices8[:4])
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.uniform(-1, 1, (4, 32, 32, 3)), jnp.float32)
             for k in ("input", "target")}
    state = jax.eval_shape(
        lambda: create_train_state(cfg, jax.random.key(0), batch))
    sh = state_target_shardings(state, mesh)

    def specs_of(tree):
        return [tuple(s.spec) for s in jax.tree_util.tree_leaves(tree)]

    # moment/EMA leaves with a divisible dim shard; the indivisible few
    # (the (3,) image-head bias, Adam count scalars) replicate legally
    opt_specs, ema_specs = specs_of(sh.opt_g), specs_of(sh.ema_g)
    assert sum("fsdp" in str(sp) for sp in opt_specs) > len(opt_specs) // 2
    assert sum("fsdp" in str(sp) for sp in ema_specs) > len(ema_specs) // 2
    # params and batch stats stay replicated without --fsdp_params
    assert all(sp == () for sp in specs_of(sh.params_g))
    assert all(sp == () for sp in specs_of(sh.batch_stats_g))
    # ...and shard under the knob
    sh_p = state_target_shardings(state, mesh, fsdp_params=True)
    assert any("fsdp" in str(sp) for sp in specs_of(sh_p.params_g))


@pytest.mark.slow
def test_fsdp_train_step_bitwise_equals_replicated(devices8):
    """THE ZeRO pin (ISSUE 15): on the SAME data=1 x fsdp=2 mesh, the
    train step with rule-sharded optimizer moments + EMA equals the
    fully-replicated placement — every step METRIC bitwise (the loss
    computation is layout-identical), every state leaf within atol 1e-6
    / rtol 2e-4 (the band the TP == single-device pins carry). A true state-bitwise pin is not achievable under GSPMD:
    sharding a kernel's C_out re-tiles its wgrad, which reassociates the
    N·H·W accumulation (measured max |Δ| ~4e-7, CPU backend) —
    layout-only fp noise, well below any real semantic drift (a wrong
    gather or dropped shard lands at the update scale, ~1e-4 relative)."""
    import dataclasses

    from p2p_tpu.parallel.rules import state_target_shardings
    from p2p_tpu.train.state import create_train_state

    cfg = _fsdp_cfg()
    cfg = cfg.replace(parallel=dataclasses.replace(
        cfg.parallel, mesh=MeshSpec(data=1, fsdp=2)))
    mesh = make_mesh(MeshSpec(data=1, fsdp=2), devices=devices8[:2])
    rng = np.random.default_rng(3)
    batch = {k: jnp.asarray(rng.uniform(-1, 1, (4, 32, 32, 3)), jnp.float32)
             for k in ("input", "target")}
    state = create_train_state(cfg, jax.random.key(0), batch)

    # run A: everything replicated over the mesh (the pre-ISSUE-15 law)
    rep_state = replicate_state(
        jax.tree_util.tree_map(jnp.copy, state), mesh)
    rep_step = make_parallel_train_step(cfg, mesh)
    rep_state, rep_metrics = rep_step(rep_state, shard_batch(batch, mesh))

    # run B: ZeRO layout from the ONE partitioner
    ssh = state_target_shardings(state, mesh)
    fsdp_state = jax.device_put(state, ssh)
    mu0 = next(l for l in jax.tree_util.tree_leaves(fsdp_state.opt_g)
               if getattr(l, "ndim", 0) == 4)
    assert "fsdp" in str(mu0.sharding.spec), mu0.sharding
    fsdp_step = make_parallel_train_step(cfg, mesh, state_sharding=ssh)
    fsdp_state, fsdp_metrics = fsdp_step(fsdp_state, shard_batch(batch, mesh))

    for k in rep_metrics:
        assert np.asarray(rep_metrics[k]) == np.asarray(fsdp_metrics[k]), k
    ra, _ = jax.tree_util.tree_flatten(rep_state)
    fa, _ = jax.tree_util.tree_flatten(fsdp_state)
    for la, lb in zip(ra, fa):
        a, b = np.asarray(la), np.asarray(lb)
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_fsdp_train_step_matches_single_device(devices8):
    """fsdp devices consume distinct samples exactly like data devices:
    the data=1 x fsdp=4 step over a global batch of 4 matches the
    single-device oracle to fp reduction tolerance, with params sharded
    too (--fsdp_params, the ZeRO-3-ish gather-on-use path)."""
    import dataclasses

    from p2p_tpu.parallel.rules import state_target_shardings
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = _fsdp_cfg(ema=False)
    cfg = cfg.replace(parallel=dataclasses.replace(
        cfg.parallel, mesh=MeshSpec(data=1, fsdp=4), fsdp_params=True))
    mesh = make_mesh(MeshSpec(data=1, fsdp=4), devices=devices8[:4])
    rng = np.random.default_rng(7)
    batch = {k: jnp.asarray(rng.uniform(-1, 1, (4, 32, 32, 3)), jnp.float32)
             for k in ("input", "target")}
    state = create_train_state(cfg, jax.random.key(0), batch)

    ref_step = build_train_step(cfg)
    ref_state, ref_metrics = ref_step(
        jax.tree_util.tree_map(jnp.copy, state), dict(batch))

    ssh = state_target_shardings(state, mesh, fsdp_params=True)
    fsdp_state = jax.device_put(state, ssh)
    step = make_parallel_train_step(cfg, mesh, state_sharding=ssh)
    fsdp_state, metrics = step(fsdp_state, shard_batch(batch, mesh))

    for k in ref_metrics:
        np.testing.assert_allclose(
            float(ref_metrics[k]), float(metrics[k]), rtol=8e-4, atol=8e-4,
            err_msg=k)
    for la, lb in zip(jax.tree_util.tree_leaves(ref_state.params_g),
                      jax.tree_util.tree_leaves(fsdp_state.params_g)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=5e-4, atol=5e-4)
