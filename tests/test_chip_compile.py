"""The Pallas kernels of the main path, compiled for a DESCRIBED TPU v5e.

No chip is attached in CI, but the TPU compiler is installed: it compiles
for a topology that is described (``v5e:2x2``), and refuses what the chip
would refuse — a block shape Mosaic cannot tile, a scalar store to VMEM,
a scoped-VMEM overflow — which interpret mode never sees. One real shape
per kernel family, forward and grad, plus one ``shard_map`` variant on the
2x2 mesh. About a second each; nothing runs, so nothing here is a time.

The topology is described inside a module-scoped fixture of THIS file (one
process may hold libtpu; see the on-chip-measurement guide) and the
persistent compile cache is off around the compiles — an entry written
for a described device cannot be read back without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2x2(topo):
    from p2p_tpu.core.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=2, spatial=2), devices=topo.devices)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _sum_grad(fn, argnums=0):
    return jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=argnums)


def _assert_kernel(text):
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


# pix2pixHD local-enhancer activation at 1024x512 (narrow C: the padded
# tile is what _pick_h_block must budget) and the PatchGAN's odd pad-2
# inner extent at 256^2
HD_SHAPE = (1, 512, 1024, 32)
PATCH_SHAPE = (1, 65, 65, 256)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_instance_norm_fused_compiles(one_chip, grad):
    from p2p_tpu.ops.pallas.instance_norm_kernel import instance_norm_fused

    def fn(x):
        return instance_norm_fused(x, None, None, 1e-5)

    x = jax.ShapeDtypeStruct(HD_SHAPE, jnp.bfloat16, sharding=one_chip)
    _assert_kernel(_compiled_text(_sum_grad(fn) if grad else fn, x))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_instance_norm_act_fused_with_residual_compiles(one_chip, grad):
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_fused

    def fn(x, r):
        return instance_norm_act_fused(x, None, None, r, act="relu")

    x = jax.ShapeDtypeStruct((1, 128, 256, 128), jnp.bfloat16,
                             sharding=one_chip)
    _assert_kernel(_compiled_text(
        _sum_grad(fn, argnums=(0, 1)) if grad else fn, x, x))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_instance_norm_act_quant_kernel_compiles(one_chip, grad):
    """The quantizing epilogue (--norm_d pallas_instance
    --int8_fused_epilogue) at a PatchGAN inner shape. Its forward was
    refused outright before PR 21 (scalar store to VMEM, (1,1) block)."""
    from p2p_tpu.ops.pallas.norm_act import instance_norm_act_quant

    def fn(x, sx):
        return instance_norm_act_quant(x, sx, act="leaky", slope=0.2,
                                       use_kernel=True)[0]

    x = jax.ShapeDtypeStruct(PATCH_SHAPE, jnp.bfloat16, sharding=one_chip)
    sx = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    _assert_kernel(_compiled_text(_sum_grad(fn) if grad else fn, x, sx))


def test_sharded_instance_norm_keeps_the_shard(mesh2x2):
    """The shard_map variant on data=2 x spatial=2 (H split in two): the
    kernel runs on local shards and no all-gather of the activation
    surrounds it."""
    from p2p_tpu.analysis.jaxpr_lint import assert_no_collective_as_large_as
    from p2p_tpu.ops.pallas.instance_norm import sharded_pallas_instance_norm

    shape = (2, 128, 256, 128)
    x = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=NamedSharding(
            mesh2x2, P(("data", "fsdp"), "spatial", None, None)))

    def fn(a):
        return sharded_pallas_instance_norm(a, None, None, 1e-5, mesh2x2)

    text = _compiled_text(_sum_grad(fn), x)
    _assert_kernel(text)
    n, h, w, c = shape
    assert_no_collective_as_large_as(text, n * h * w * c // 4)


# a layer's attention of the SwinIR cell: 256 windows (4 images x 64) of 64
# tokens, six heads of 30, in the kernel's layout (heads 32 apart, groups
# of 256 columns)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_window_attention_kernel_compiles(one_chip, grad, dtype):
    """Forward and backward at the cell's shape, shifted (the mask path),
    at the block size the rule picks; float32 operands too (the check's
    float32 program, products at HIGHEST). No ``[.., T, T]`` tensor is an
    operand or a result of anything in the compiled program: the logits
    and the probabilities live in the kernel."""
    from p2p_tpu.models.swinir import relative_position_index, shift_mask
    from p2p_tpu.ops.pallas import window_attention as wa

    heads, d, images, extent = 6, 30, 4, 64
    wb = wa.block_windows(256, 64, heads, d, dtype, 64)
    assert wb == (16 if dtype == jnp.bfloat16 else 8)

    def fn(qkv, table):
        return wa.window_attention_fused(
            qkv, table, relative_position_index(8),
            shift_mask(extent, extent, 8), heads, d, wb)

    qkv = jax.ShapeDtypeStruct(
        (images * 64, 64, 3 * wa.group_width(heads, d)), dtype,
        sharding=one_chip)
    table = jax.ShapeDtypeStruct((225, heads), jnp.float32,
                                 sharding=one_chip)
    text = _compiled_text(_sum_grad(fn, argnums=(0, 1)) if grad else fn,
                          qkv, table)
    _assert_kernel(text)
    assert text.count("window_attention_bwd" if grad
                      else "window_attention_fwd") >= 1
    assert not re.search(r"\[256,6,64,64\]|\[256,64,6,64\]", text)


# the thin image-side layers of the two benchmark cells, at their own
# extents and batch: ExpandNetwork's k9 head 32->3 (bs32 256x256) and the
# pix2pixHD enhancer's k7 stem 3->32 (bs2 1024x512)
@pytest.mark.parametrize("shape,features,k", [
    ((32, 256, 256, 32), 3, 9), ((2, 512, 1024, 3), 32, 7),
], ids=["expand_head_k9_32to3", "hd_stem_k7_3to32"])
def test_blocked_conv_layer_compiles_dense(one_chip, shape, features, k):
    """``ConvLayer`` on pixel blocks, forward and both gradients, through
    the chip's compiler: the program convolves with the blocked kernel's
    k x k' window, never with the thin layer's own k x k, and fits the
    chip."""
    from p2p_tpu.ops.conv import ConvLayer, blocked_conv_block

    layer = ConvLayer(features, kernel_size=k, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    variables = jax.eval_shape(layer.init, jax.random.key(0), x)
    variables = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), variables)
    pad = k // 2
    padded = (shape[0], shape[1] + 2 * pad, shape[2] + 2 * pad, shape[3])
    s = blocked_conv_block(jax.ShapeDtypeStruct(padded, jnp.bfloat16),
                           features, k, 1)

    def loss(v, xx):
        return jnp.sum(layer.apply(v, xx).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables, x).compile()
    text = compiled.as_text()
    kb = (s + k - 2) // s + 1
    windows = re.findall(r"convolution\([^)]*\), window=\{size=(\d+x\d+)",
                         text)
    # forward, input gradient and weight gradient: k x k' taps, or the
    # feature map as the window; never the thin layer's own k x k
    assert f"{k}x{kb}" in windows and f"{k}x{k}" not in windows, windows
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 * 2 ** 30


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_fourier_unit_transforms_compile_as_products(one_chip, grad):
    """``models/ffc.py``'s ``rfft2`` and ``irfft2`` at the shape the cell
    ``big_lama_places256.train`` runs a unit (bf16 activations, float32
    transforms), forward and backward, through the chip's compiler: two
    products a transform (the compiler's ``convolution``), nothing of an
    ``fft``, and a few dozen instructions where XLA's expansion of the
    ``fft`` ops held thousands."""
    from p2p_tpu.models import ffc

    def pair(x):
        z = ffc.rfft2(x.astype(jnp.float32)).astype(x.dtype)
        return ffc.irfft2(jax.nn.relu(z).astype(jnp.float32),
                          x.shape[2]).astype(x.dtype)

    x = jax.ShapeDtypeStruct((16, 32, 32, 192), jnp.bfloat16,
                             sharding=one_chip)

    def both(v, ct):
        # a cotangent of its own: the compiler folds a constant one away
        y, pullback = jax.vjp(pair, v)
        return y, pullback(ct)[0]

    text = _compiled_text(both, x, x) if grad else _compiled_text(pair, x)
    assert not re.search(r"\bfft\(", text)
    products = re.findall(r" convolution\(", text)
    assert len(products) == (8 if grad else 4), len(products)
    entry, _ = _entry_and_types(text)
    assert len(entry) < 60, len(entry)


def _entry_and_types(text):
    """The ENTRY computation's lines, and name -> result type of every
    instruction of the module."""
    types = dict(re.findall(r"%([\w.\-]+) = \(?(\w+\[[\d,]*\])", text))
    return text[text.index("ENTRY"):].splitlines(), types


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_vgg_loss_stores_what_its_convolutions_read(
        one_chip, parent_vgg_loss, dtype):
    """``vgg_loss`` and its image gradient at 256x256 through the chip's
    compiler. bf16 images: no float32 tensor of conv1's extent outside a
    fusion body (the parent keeps some) and every convolution reads a
    bf16 activation; float32 images: the compiler is handed the parent's
    program, text for text."""
    from p2p_tpu.losses import vgg_loss
    from p2p_tpu.models.vgg import load_vgg19_params

    params = load_vgg19_params()
    x = jax.ShapeDtypeStruct((2, 256, 256, 3), dtype, sharding=one_chip)

    def lowered(loss):
        return jax.jit(jax.value_and_grad(
            lambda a, b: loss(params, a, b))).lower(x, x)

    if dtype == "float32":
        # (the compiled text carries file names and line numbers)
        assert lowered(vgg_loss).as_text() == lowered(
            parent_vgg_loss).as_text()
        return
    new, old = (lowered(f).compile().as_text()
                for f in (vgg_loss, parent_vgg_loss))
    wide = r"= \(?f32\[2,256,256,64\]"
    entry, types = _entry_and_types(new)
    assert [ln for ln in entry if re.search(wide, ln)] == []
    assert [ln for ln in _entry_and_types(old)[0] if re.search(wide, ln)]
    reads = [types[a] for a in re.findall(
        r" convolution\(%([\w.\-]+), ", new)]
    assert len(reads) >= 16 + 15     # 16 forward x 2 images, 15 backward
    assert all(t.startswith("bf16[") for t in reads), reads
