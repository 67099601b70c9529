"""pix2pixHD on ``data=2,spatial=2`` (the four-chip cell
``pix2pixhd_2048x1024.train_spatial4``), on four virtual CPU devices at a
small extent: every generator layer form the preset takes at 2048x1024
keeps its H shard (halo ``collective-permute``s, no activation gathered,
no re-shard through an all-to-all around the k7 layers' reflect pad), and
the Trainer's own sharded step agrees with the plain float32 reference of
the configuration (``benchmark/reference/pix2pixhd_2048x1024.py`` followed
by ``benchmark/reference/train_step.py``) on seeded weights.

Extents: 256x256 is the smallest at which the k7 stem and head take the
blocked form (``ops/conv.blocked_conv_block``); below ~64 rows a shard
holds one row and spatial sharding is not faithful (docs/PARALLELISM.md).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from p2p_tpu.analysis.jaxpr_lint import (
    collect_collectives,
    hlo_collective_bytes,
    hlo_collective_shapes,
)
from p2p_tpu.core.mesh import (
    MeshSpec,
    batch_sharding,
    make_mesh,
    mesh_context,
    replicated,
)


def _mesh(devices8, **axes):
    spec = MeshSpec(**{"data": 1, **axes})
    n = int(np.prod([getattr(spec, a) for a in (
        "data", "fsdp", "spatial", "time", "model", "pipe")]))
    return make_mesh(spec, devices=devices8[:n])


def _largest(text, kind):
    return max((n for n, _ in hlo_collective_shapes(text, kind)), default=0)


# ------------------------------------------------------- the reflect pad


@pytest.mark.parametrize("axes, pad, sharded_path", [
    (dict(data=2, spatial=2), 2, True),
    (dict(data=2, spatial=2), 3, True),     # the k7 layers
    (dict(data=2, spatial=2), 4, True),     # a k9 layer
    (dict(data=1, spatial=2), 3, True),
    (dict(data=2, spatial=4), 2, True),
    (dict(data=2, spatial=4), 3, False),    # 6 rows do not split in 4
    (dict(data=2, spatial=2), 1, False),    # one row: no reverse, GSPMD's
], ids=lambda v: str(v).replace(" ", "") if not isinstance(v, bool) else
    ("shard_map" if v else "gspmd"))
def test_reflect_pad_of_a_sharded_height(devices8, axes, pad, sharded_path):
    """``reflect_pad_2d`` inside a step whose mesh shards H: the values
    and the gradient are ``jnp.pad``'s to the last bit, and where the
    shard-by-shard path is taken (two rows or more, and they split
    evenly) the program moves halo rows only: collective-permutes, no
    all-to-all (GSPMD's answer to the pad's reverse, which re-sharded
    the whole tensor from H to W and back) and no all-gather."""
    from p2p_tpu.ops.conv import reflect_pad_2d

    mesh = _mesh(devices8, **axes)
    x = jax.random.normal(jax.random.key(0), (2, 64, 48, 8))
    weight = jnp.arange(x.shape[1] + 2 * pad, dtype=jnp.float32)[
        None, :, None, None]

    def plain(a):
        return jnp.pad(a, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                       mode="reflect")

    def in_mesh(a):
        with mesh_context(mesh):
            return reflect_pad_2d(a, pad)

    def both(f):
        return lambda a: (f(a), jax.grad(
            lambda b: jnp.sum(jnp.sin(f(b)) * weight))(a))

    jitted = jax.jit(both(in_mesh), in_shardings=batch_sharding(mesh))
    for got, want in zip(jitted(x), both(plain)(x)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    census = collect_collectives(jitted.lower(x).compile().as_text())
    if sharded_path:
        assert census["collective-permute"] and not census["all-to-all"] \
            and not census["all-gather"], dict(census)
    # no collective census is pinned for GSPMD's own path: what it makes
    # of a reverse depends on the backend's simplifier


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("axes, pad, sharded_path", [
    (dict(data=2, spatial=2), 3, True),     # the k7 layers
    (dict(data=2, spatial=2), 4, True),     # a k9 layer
    (dict(data=1, spatial=2), 3, True),
    (dict(data=2, spatial=4), 2, True),
    (dict(data=2, spatial=4), 3, False),    # 6 rows do not split in 4
    (dict(data=2, spatial=2), 1, False),    # one row: every k3 layer
], ids=lambda v: str(v).replace(" ", "") if not isinstance(v, bool) else
    ("shard_map" if v else "gspmd"))
def test_reflect_pad_gradient_of_a_sharded_height(devices8, axes, pad,
                                                  sharded_path, dtype):
    """The backward of ``reflect_pad_2d`` under a mesh that shards H
    (PR 33): the site counts as ``one_pass_w`` (the one-pass fold along W
    alone: a fold reverses strips, and H is sharded), value and gradient
    equal the unsharded ones (the one-pass fold of both axes on one
    device, and autodiff of ``jnp.pad``), and with the shard-by-shard H
    half the compiled value-and-grad still moves halo rows only."""
    from p2p_tpu.ops.conv import reflect_pad_2d, reflect_pad_sites

    mesh = _mesh(devices8, **axes)
    x = jax.random.normal(jax.random.key(0), (2, 64, 48, 8)).astype(dtype)
    weight = jax.random.normal(
        jax.random.key(1), (2, 64 + 2 * pad, 48 + 2 * pad, 8))

    def loss(f):
        return lambda a: jnp.sum(jnp.sin(f(a).astype(jnp.float32)) * weight)

    def plain(a):
        return jnp.pad(a, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                       mode="reflect")

    def in_mesh(a):
        with mesh_context(mesh):
            return reflect_pad_2d(a, pad)

    jitted = jax.jit(jax.value_and_grad(loss(in_mesh)),
                     in_shardings=batch_sharding(mesh))
    before = reflect_pad_sites()
    value, grad = jitted(x)
    assert {k: v - before[k] for k, v in reflect_pad_sites().items()} == {
        "one_pass": 0, "one_pass_w": 1, "autodiff": 0}
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" else dict(
        rtol=2.0 ** -6, atol=2.0 ** -6)    # H keeps chained bf16 adds
    for unsharded in (lambda a: reflect_pad_2d(a, pad), plain):
        want_value, want = jax.value_and_grad(loss(unsharded))(x)
        # a float32 sum of 70k terms in another order
        np.testing.assert_allclose(float(value), float(want_value),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(grad, np.float32),
                                   np.asarray(want, np.float32), **tol)
    if sharded_path:
        census = collect_collectives(jitted.lower(x).compile().as_text())
        assert census["collective-permute"] and not census["all-to-all"] \
            and not census["all-gather"], dict(census)


# ------------------- reflect-padded convolutions as one shard_map (PR 35)


def _value_and_grads(layer):
    def fn(v, a):
        return jax.value_and_grad(
            lambda vv, aa: jnp.sum(jnp.sin(
                layer.apply(vv, aa).astype(jnp.float32))),
            argnums=(0, 1))(v, a)
    return fn


def _in_mesh(fn, mesh):
    def wrapped(*args):
        with mesh_context(mesh):
            return fn(*args)
    return jax.jit(wrapped, in_shardings=(replicated(mesh),
                                          batch_sharding(mesh)))


def _halo_sites():
    from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer

    # name -> (layer, input shape, mesh axes, engages, blocked inside)
    return {
        "k3_stride1": (ConvLayer(16, 3), (2, 32, 24, 8),
                       dict(data=2, spatial=2), True, False),
        "k3_stride2": (ConvLayer(16, 3, stride=2), (2, 32, 24, 8),
                       dict(data=2, spatial=2), True, False),
        "k5_stride1_spatial4": (ConvLayer(8, 5), (2, 32, 16, 4),
                                dict(data=1, spatial=4), True, False),
        "k5_stride2_spatial4": (ConvLayer(8, 5, stride=2), (2, 32, 16, 4),
                                dict(data=1, spatial=4), True, False),
        "k7_stem_blocked": (ConvLayer(32, 7), (2, 256, 256, 3),
                            dict(data=2, spatial=2), True, True),
        "up2_plain_chain": (UpsampleConvLayer(128, 3, upsample=2),
                            (2, 16, 16, 8), dict(data=2, spatial=2),
                            True, False),
        "no_bias_fsdp": (ConvLayer(16, 3, use_bias=False), (2, 32, 24, 8),
                         dict(fsdp=2, spatial=2), True, False),
        # what keeps GSPMD's path
        "spatial1": (ConvLayer(16, 3), (2, 32, 24, 8),
                     dict(data=2), False, False),
        "odd_local_rows_stride2": (ConvLayer(16, 3, stride=2),
                                   (2, 30, 24, 8),
                                   dict(data=2, spatial=2), False, False),
        "zero_pad": (ConvLayer(16, 3, pad_mode="zero"), (2, 32, 24, 8),
                     dict(data=2, spatial=2), False, False),
    }


@pytest.mark.parametrize("site", list(_halo_sites()))
def test_reflect_padded_conv_as_one_shard_map(devices8, site):
    """A reflect-padded ``ConvLayer`` / ``UpsampleConvLayer`` site under
    ``mesh_context`` (PR 35): where ``ops/conv.halo_conv_mesh`` engages
    (``spatial`` > 1, an odd kernel, local rows above the pad and a
    multiple of the stride, no axis beyond data / fsdp / spatial) the
    site is ONE ``shard_map`` (``parallel.spatial.halo_conv``), counted
    in ``conv_form_sites()["halo"]``; forward, input gradient and kernel
    / bias gradient equal the unsharded layer's, the parameter tree is
    ``Conv_0/{kernel,bias}``, and the compiled text moves halo rows only
    (collective-permutes; no all-gather, no all-to-all). Everything else
    keeps the pad-then-conv chain, counts nothing and is still right."""
    from p2p_tpu.ops.conv import conv_form_sites

    layer, shape, axes, engages, blocked = _halo_sites()[site]
    mesh = _mesh(devices8, **axes)
    x = jax.random.normal(jax.random.key(1), shape)
    before = conv_form_sites()
    variables = layer.init(jax.random.key(2), x)
    assert conv_form_sites()["halo"] == before["halo"]      # no mesh: 0
    assert set(variables["params"]["Conv_0"]) == (
        {"kernel", "bias"} if layer.use_bias else {"kernel"})
    fn = _value_and_grads(layer)
    want = jax.jit(fn)(variables, x)
    before = conv_form_sites()
    sharded = _in_mesh(fn, mesh)
    got = sharded(variables, x)
    took = {k: v - before[k] for k, v in conv_form_sites().items()}
    assert took == {"halo": int(engages), "blocked": int(blocked),
                    "nearest_up2": 0}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4,
            atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)
    if engages:
        census = collect_collectives(
            sharded.lower(variables, x).compile().as_text())
        assert census["collective-permute"] and not census["all-gather"] \
            and not census["all-to-all"], dict(census)


@pytest.mark.parametrize("axes, kwargs", [
    (dict(spatial=2, model=2), {}),     # a tensor-parallel kernel: P() would
    (dict(spatial=2, pipe=2), {}),      # gather it at every site
    (dict(spatial=2, time=2), {}),
    (dict(data=2, spatial=2), dict(int8=True)),
    (dict(data=2, spatial=2), dict(pad_mode="zero_after", stride=2)),
], ids=lambda v: "-".join(f"{k}{n}" for k, n in v.items()) or "reflect")
def test_halo_form_declines(devices8, axes, kwargs):
    """Traced only (GSPMD's partition of such programs is not this
    test's, and the CPU backend refuses an int8 pad along sharded rows):
    a mesh with an axis beyond data / fsdp / spatial above 1, the int8
    path and a zero pad trace no ``shard_map`` and count no ``halo``
    site; the same layer on data=2 x spatial=2 traces one."""
    from p2p_tpu.ops.conv import ConvLayer, conv_form_sites

    layer = ConvLayer(16, 3, **kwargs)
    x = jnp.ones((2, 32, 24, 8))
    variables = layer.init(jax.random.key(0), x)

    def traced(mesh):
        with mesh_context(mesh):
            return str(jax.make_jaxpr(
                lambda a: layer.apply(variables, a))(x))

    before = conv_form_sites()["halo"]
    assert "shard_map" not in traced(_mesh(devices8, **axes))
    assert conv_form_sites()["halo"] == before
    if not kwargs:
        assert "shard_map" in traced(_mesh(devices8, data=2, spatial=2))
        assert conv_form_sites()["halo"] == before + 1


class _TwoLayers(nn.Module):
    dtype: object = None

    @nn.compact
    def __call__(self, x):
        from p2p_tpu.ops.conv import ConvLayer

        x = ConvLayer(16, 3, stride=2, dtype=self.dtype)(x)
        return ConvLayer(16, 3, dtype=self.dtype)(nn.relu(x))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_two_halo_layers_move_halo_rows_and_one_gradient_sum(devices8,
                                                             dtype):
    """A stride-2 and a stride-1 reflect-padded layer with a ReLU
    between, forward and both gradients on data=2 x spatial=2: the
    compiled text holds no all-gather and no all-to-all, and no more
    collective-permutes than one pair a layer and direction (GSPMD's
    partition of the padded tensor made 18 of two such layers on the
    described chips, PERF.md section 6, PR 35). In the traced program
    every kernel's cotangent is summed by ONE psum over data, fsdp and
    spatial together, in the compute dtype (the kernel is cast outside
    the shard_map): a bf16 step all-reduces bf16."""
    from p2p_tpu.ops.conv import conv_form_sites

    mesh = _mesh(devices8, data=2, spatial=2)
    block = _TwoLayers(dtype=jnp.dtype(dtype))
    x = jax.random.normal(jax.random.key(1), (2, 32, 24, 8)).astype(dtype)
    variables = block.init(jax.random.key(2), x)
    fn = _value_and_grads(block)
    before = conv_form_sites()["halo"]
    sharded = _in_mesh(fn, mesh)
    census = collect_collectives(
        sharded.lower(variables, x).compile().as_text())
    assert conv_form_sites()["halo"] == before + 2
    assert not census["all-gather"] and not census["all-to-all"], dict(census)
    assert 0 < census["collective-permute"] <= 8, dict(census)

    from p2p_tpu.analysis.jaxpr_lint import iter_eqns

    psums = [e for e in iter_eqns(jax.make_jaxpr(sharded)(variables, x).jaxpr)
             if e.primitive.name.startswith("psum")
             and e.invars[0].aval.ndim == 4]
    assert len(psums) == 2, psums
    for e in psums:
        assert set(e.params["axes"]) == {"data", "fsdp", "spatial"}
        assert e.invars[0].aval.dtype == jnp.dtype(dtype)
    if dtype == "float32":
        for a, b in zip(jax.tree.leaves(sharded(variables, x)),
                        jax.tree.leaves(jax.jit(fn)(variables, x))):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4,
                atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)


# --------------------------------------------- the generator's layer forms


class _Pool(nn.Module):
    @nn.compact
    def __call__(self, x):
        from p2p_tpu.models.patchgan import avg_pool_downsample

        return avg_pool_downsample(x)


class _Block(nn.Module):
    @nn.compact
    def __call__(self, x):
        from p2p_tpu.models.resnet_gen import ResnetBlock

        return ResnetBlock(16, norm="pallas_instance")(x, True)


def _layer_forms():
    from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer

    # name -> (layer, input shape, may a reflect pad of GSPMD's own show:
    # none does since the reflect-padded convolutions run as one
    # shard_map, PR 35); the extents are the smallest at which each form
    # is the one the preset takes at 2048x1024
    return {
        "enhancer_stem_k7_blocked": (
            ConvLayer(32, kernel_size=7), (2, 256, 256, 3), False),
        "enhancer_head_k7_blocked": (
            ConvLayer(3, kernel_size=7), (2, 256, 256, 32), False),
        "down_k3_stride2": (
            ConvLayer(16, kernel_size=3, stride=2), (2, 128, 128, 8), False),
        "resblock_k3_fused_norm_act_residual": (
            _Block(), (2, 64, 64, 16), False),
        "up2_conv_subpixel": (
            UpsampleConvLayer(8, kernel_size=3, upsample=2),
            (2, 256, 320, 16), False),
        "avg_pool_3s2": (_Pool(), (2, 128, 128, 3), False),
    }


@pytest.mark.parametrize("form", list(_layer_forms()))
def test_generator_layer_form_keeps_its_h_shard(devices8, monkeypatch, form):
    """One layer of each form, forward and both gradients, under the
    batch's own layout ``P((data, fsdp), spatial)``: the result is the
    single-device one, rows cross the shard boundary as halo
    ``collective-permute``s, and no all-gather reaches a quarter of the
    layer's input (the smoke's bound is the step's smallest normed
    activation; a gathered activation is the whole of one), and no
    all-to-all (a reflect pad left to GSPMD shows as one on the CPU
    backend: its reverse along the sharded rows)."""
    from p2p_tpu.ops.conv import conv_form_sites

    # the Pallas norm kernels (interpreted) inside their shard_map, as on
    # the chip, not the XLA stand-in
    monkeypatch.setenv("P2P_TPU_FORCE_PALLAS", "1")
    layer, shape, gspmd_pad = _layer_forms()[form]
    mesh = _mesh(devices8, data=2, spatial=2)
    x = jax.random.normal(jax.random.key(1), shape)
    before = conv_form_sites()
    variables = layer.init(jax.random.key(2), x)
    assert (conv_form_sites()["blocked"] > before["blocked"]) == (
        "blocked" in form)
    assert conv_form_sites()["halo"] == before["halo"]      # no mesh

    value_and_grads = _value_and_grads(layer)
    sharded = _in_mesh(value_and_grads, mesh)
    got = jax.tree.leaves(sharded(variables, x))
    want = jax.tree.leaves(jax.jit(value_and_grads)(variables, x))
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4,
            atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)
    text = sharded.lower(variables, x).compile().as_text()
    census = collect_collectives(text)
    assert census["collective-permute"], dict(census)
    assert _largest(text, "all-gather") < x.size // 4
    if not gspmd_pad:
        assert not census["all-to-all"], dict(census)


def test_fused_norm_act_shards_the_batch_over_data_and_fsdp(devices8,
                                                            monkeypatch):
    """The fused norm+act(+residual) ``shard_map`` lays N over (data,
    fsdp) like the plain norm's and like the divisibility test both use
    (it named ``data`` alone): on data=1 x fsdp=2 x spatial=2 the rows of
    the batch stay where they are (no all-gather of the activation) and
    the values are the unsharded ones."""
    from p2p_tpu.ops.pallas.instance_norm import pallas_instance_norm_act

    monkeypatch.setenv("P2P_TPU_FORCE_PALLAS", "1")
    mesh = _mesh(devices8, fsdp=2, spatial=2)
    x = jax.random.normal(jax.random.key(3), (2, 32, 16, 8))
    res = jax.random.normal(jax.random.key(4), x.shape)

    def fn(a, r):
        return jax.value_and_grad(lambda aa: jnp.sum(jnp.sin(
            pallas_instance_norm_act(aa, residual=r, act="relu"))))(a)

    def in_mesh(a, r):
        with mesh_context(mesh):
            return fn(a, r)

    bsh = batch_sharding(mesh)
    sharded = jax.jit(in_mesh, in_shardings=(bsh, bsh))
    for got, want in zip(jax.tree.leaves(sharded(x, res)),
                         jax.tree.leaves(fn(x, res))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
    text = sharded.lower(x, res).compile().as_text()
    assert _largest(text, "all-gather") < x.size // 4
    assert collect_collectives(text)["all-reduce"]      # the moments' psum


# ------------------------------------------------------------- the census


def test_collective_bytes_of_a_compiled_text():
    """``hlo_collective_bytes`` on lines as the v5e compiler writes them
    (layouts with their own parentheses; async tuples): a
    collective-permute's start counts its RESULT once, an all-reduce its
    results, ``-done`` lines nothing."""
    text = "\n".join([
        "  %cp.1 = (bf16[1,2,2048,3]{2,1,3,0:T(2,128)(2,1)}, bf16[1,2,2048,3]"
        "{2,1,3,0:T(2,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
        "collective-permute-start(%x), channel_id=9",
        "  %cp.2 = bf16[1,2,2048,3]{2,1,3,0} collective-permute-done(%cp.1)",
        "  %ar = (f32[8,8]{1,0}, bf16[4]{0}) all-reduce-start(%a, %b)",
        "  %ard = (f32[8,8]{1,0}, bf16[4]{0}) all-reduce-done(%ar)",
        "  %a2a = bf16[1,512,2,1024,32]{3,4,1,0,2:T(8,128)(2,1)} "
        "all-to-all(%copy.1), dimensions={2}",
        "  %ag = (f32[2,4]{1,0}, f32[4,4]{1,0}) all-gather-start(%c)",
    ])
    assert dict(hlo_collective_bytes(text)) == {
        "collective-permute": 2 * 2048 * 3 * 2,
        "all-reduce": 8 * 8 * 4 + 4 * 2,
        "all-to-all": 512 * 2 * 1024 * 32 * 2,
        "all-gather": 4 * 4 * 4,
    }
    assert dict(collect_collectives(text)) == {
        "collective-permute": 1, "all-reduce": 1, "all-to-all": 1,
        "all-gather": 1}


# ------------------------- the Trainer's step against the plain reference


def test_sharded_trainer_step_against_the_plain_reference(devices8, capsys,
                                                          monkeypatch):
    """The benchmark's own driver on the rehearsal twin of the four-chip
    cell (``benchmark/tests/cells/SPATIAL4.json``: preset pix2pixhd at
    ngf 8, 256x256, global batch 2, ``--mesh data=2,spatial=2``, the
    configuration's reference module ``pix2pixhd_2048x1024``): the
    Trainer's compiled step, tapped for its first three steps, against
    ``TrainReference`` from the same seeded state — each loss at step one
    and its widest gap later, the worst leaf's gap of the first gradient
    and of the parameters' change, per net. Tolerances are the
    configuration file's ``limits``: three times the largest of three CPU
    seeds at this size (bf16 against float32; the sharded step reads
    within a few percent of what the one-device rehearsal cell reads, so
    they stayed that cell's but for D's two loss gaps, which read 3.7e-5
    and 6.9e-5 here against limits of 1e-4 and 2e-4 and were doubled).

    The same run shows what the program records of its collectives: one
    ``kind="collectives"`` record from the compiled text and the gauge of
    the largest gather or re-shard, which the benchmark's reader reads."""
    from benchmark import harness

    # the Pallas kernels (interpreted) inside their shard_map in the step;
    # the check's own jit of the generator sees no mesh and takes the XLA
    # norm, as on the chips
    monkeypatch.setenv("P2P_TPU_FORCE_PALLAS", "1")
    # the driver points this process's environment at the cell's compile
    # cache (harness.prepare_jax_env): given back at teardown, or a later
    # test of this worker that names its own cache directory is refused
    # (tests/test_serve.py, core/cache.resolve_cache_dir)
    for name in ("JAX_COMPILATION_CACHE_DIR",
                 "JAX_COMPILATION_CACHE_MAX_SIZE"):
        monkeypatch.setenv(name, os.environ.get(name, ""))
    bench = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "SPATIAL4.json")
    cell = harness.load_cell(
        "tiny_pix2pixhd_spatial4.train_spatial4", 2 ** 31 + 29, 1.0, False,
        time.perf_counter(), bench_file=bench, require_tpu=False)
    driver = harness.load_by_path("drivers", cell.workload["driver"])
    line = json.loads(driver.run(cell))
    out = capsys.readouterr().out
    assert line["correct"] is True, out[-4000:]
    rows = next(json.loads(ln) for ln in out.splitlines()
                if ln.startswith('{"check": "correct"'))["rows"]
    judged = {r["number"] for r in rows if r["limit"] is not None}
    assert {"step1_loss_d_rel_gap", "later_loss_g_rel_gap",
            "first_grad_g_worst_leaf_gap", "first_grad_d_worst_leaf_gap",
            "params_change_g_worst_leaf_gap",
            "params_change_d_worst_leaf_gap"} <= judged

    # the program's own record of the step's collectives
    stream = os.path.join(cell.work, "train",
                          f"metrics_{cell.config_name}.jsonl")
    records = [json.loads(x) for x in open(stream)]
    (rec,) = [r for r in records if r.get("kind") == "collectives"]
    assert rec["mesh"] == {"data": 2, "spatial": 2}
    assert rec["collective-permute.count"] > 0
    assert rec["collective-permute.bytes"] > 0 and rec["all-reduce.bytes"] > 0
    # a gathered activation would be 2*256*256*4 elements or more
    assert rec["largest_all_gather_elements"] < 2 * 256 * 256
    # the gauge takes the larger of the two ways a shard is undone; on the
    # CPU backend GSPMD answers a k3 layer's one-row reverse with an
    # all-to-all of the activation, the chip's compiler with
    # collective-permutes (PERF.md section 4), so only the gather is
    # bounded here
    from benchmark import epoch_records

    gauge = epoch_records.live_trainer().obs.snapshot()[
        "step_largest_all_gather_elements"]["value"]
    assert gauge == max(rec["largest_all_gather_elements"],
                        rec["largest_all_to_all_elements"])
    reader = harness.load_by_path("layer_metrics",
                                  "comm.largest_all_gather_elems")
    assert reader.read({"steps": line["attempted"]}) == gauge


def test_kernel_dispatch_without_a_visible_mesh(monkeypatch):
    """Which norm a program takes where the compiled kernel is asked for
    (``use_kernel`` true, not interpreted: the TPU's case, steered here in
    the test). Inside ``mesh_context`` over several devices: the
    ``shard_map`` variant, batch-only meshes (``spatial`` = 1) too, and
    the XLA norm for a batch the mesh cannot split. With NO mesh visible
    in a process of several devices nothing says what the program spans
    (the benchmark's generator check jits on a mesh Trainer's replicated
    state from outside its mesh): the XLA norm, never a bare kernel that
    Mosaic would refuse to partition. One device: the bare kernel."""
    from p2p_tpu.core.mesh import mesh_context
    from p2p_tpu.ops.pallas import instance_norm as pin

    monkeypatch.setattr(pin, "kernel_dispatch",
                        lambda force=False, interpret=False: (True, False))
    def traced(op, a):
        # a fresh function each time: make_jaxpr keeps a trace by function
        return str(jax.make_jaxpr(lambda v: op(v))(a))

    norm = pin.pallas_instance_norm
    fused = lambda a: pin.pallas_instance_norm_act(a, act="relu")  # noqa
    x = jnp.ones((2, 16, 16, 8))
    assert jax.device_count() > 1
    for op in (norm, fused):
        outside = traced(op, x)
        assert "pallas_call" not in outside and "shard_map" not in outside
        with mesh_context(make_mesh(MeshSpec(data=2),
                                    devices=jax.devices()[:2])):
            assert "shard_map" in traced(op, x)
            assert "pallas_call" not in traced(op, x[:1])
        with mesh_context(make_mesh(MeshSpec(data=1),
                                    devices=jax.devices()[:1])):
            bare = traced(op, x)
            assert "pallas_call" in bare and "shard_map" not in bare
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert "pallas_call" in traced(norm, x)


def test_mesh_trainer_notes_its_collectives(devices8, tmp_path):
    """``note_step_collectives`` lowers the step from the avals and
    shardings it ran with: jax hands back the running executable, nothing
    compiles. A one-device mesh notes nothing."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import make_synthetic_dataset
    from p2p_tpu.train import loop

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=4, n_test=1, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 threads=0),
        parallel=dataclasses.replace(cfg.parallel, mesh=MeshSpec(data=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False))
    tr = loop.Trainer(cfg, data_root=root, workdir=str(tmp_path))
    try:
        assert tr._collectives_of is tr.train_step
        tr.train_epoch()
        assert tr._collectives_of is None
        assert tr.obs.gauge("step_collective_ops", op="all-reduce").value > 0
        assert tr.obs.gauge("step_largest_all_gather_elements").value >= 0
        compiles = tr.retrace.compiles
        batch = jax.device_put(
            {k: np.stack([tr.train_ds[i][k] for i in range(2)])
             for k in ("input", "target")}, tr.batch_sharding)
        tr._collectives_of = tr.train_step
        loop.note_step_collectives(tr, batch)
        assert tr.retrace.compiles == compiles
    finally:
        tr.close()
    one = loop.Trainer(
        cfg.replace(parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=1))),
        data_root=root, workdir=str(tmp_path / "one"))
    try:
        assert one._collectives_of is None
    finally:
        one.close()
