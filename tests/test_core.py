import dataclasses

import jax
import pytest

from p2p_tpu.core import MeshSpec, get_preset, list_presets, make_mesh
from p2p_tpu.core.mesh import batch_sharding, video_sharding
from p2p_tpu.core.rng import RngStream


def test_mesh_shapes(devices8):
    mesh = make_mesh(MeshSpec(data=-1, spatial=2), devices=devices8)
    assert mesh.shape == {"data": 4, "fsdp": 1, "spatial": 2, "time": 1,
                          "model": 1, "pipe": 1}
    mesh = make_mesh(MeshSpec(data=2, spatial=2, time=2), devices=devices8)
    assert mesh.shape == {"data": 2, "fsdp": 1, "spatial": 2, "time": 2,
                          "model": 1, "pipe": 1}


def test_mesh_bad_shape(devices8):
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(data=3, spatial=3), devices=devices8)  # 9 > 8
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(data=-1, spatial=3), devices=devices8)  # 8 % 3
    # explicit sub-mesh is allowed: uses the first d*s*t devices
    m = make_mesh(MeshSpec(data=2, spatial=2), devices=devices8)
    assert m.shape == {"data": 2, "fsdp": 1, "spatial": 2, "time": 1,
                       "model": 1, "pipe": 1}


def test_shardings_build(devices8):
    mesh = make_mesh(MeshSpec(data=2, spatial=2, time=2), devices=devices8)
    import jax.numpy as jnp

    x = jnp.zeros((4, 8, 8, 3))
    xs = jax.device_put(x, batch_sharding(mesh))
    assert xs.sharding.is_equivalent_to(batch_sharding(mesh), ndim=4)
    v = jnp.zeros((2, 8, 8, 8, 3))
    vs = jax.device_put(v, video_sharding(mesh))
    assert vs.shape == v.shape


def test_presets_complete():
    names = list_presets()
    # The five BASELINE.json configs plus the reference-faithful config.
    for required in ("facades", "edges2shoes_dp", "cityscapes_spatial",
                     "pix2pixhd", "vid2vid_temporal", "reference"):
        assert required in names
    cfg = get_preset("pix2pixhd")
    assert cfg.image_hw == (512, 1024)
    assert cfg.parallel.mesh.spatial == 2
    cfg2 = cfg.replace(name="x")
    assert cfg2.name == "x" and cfg.name == "pix2pixhd"
    assert dataclasses.is_dataclass(cfg)


def test_rng_stream_deterministic():
    s = RngStream.from_seed(0)
    k1 = s.at_step(3).key("dropout")
    k2 = s.at_step(3).key("dropout")
    k3 = s.at_step(4).key("dropout")
    k4 = s.at_step(3).key("noise")
    import numpy as np

    assert np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k3))
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k4))


def test_facades_int8_preset_ships_delayed_scaling():
    """The preset pins the round-3 measured-fastest path with no flag set;
    --no-int8_delayed is the documented escape for resuming pre-round-3
    checkpoints."""
    cfg = get_preset("facades_int8")
    assert cfg.model.int8 and cfg.model.int8_delayed
    assert not cfg.model.legacy_layout  # dead-bias layout is the default


def test_parse_mesh_arg_positional_and_named():
    from p2p_tpu.core.mesh import parse_mesh_arg

    spec = parse_mesh_arg("2,1,1,2")
    assert (spec.data, spec.spatial, spec.time, spec.model, spec.pipe,
            spec.fsdp) == (2, 1, 1, 2, 1, 1)
    spec = parse_mesh_arg("data=4,fsdp=2,model=2")
    assert (spec.data, spec.fsdp, spec.model) == (4, 2, 2)
    assert (spec.spatial, spec.time, spec.pipe) == (1, 1, 1)
    # data defaults to -1 (all remaining devices) when unnamed
    spec = parse_mesh_arg("fsdp=2")
    assert spec.data == -1 and spec.fsdp == 2


@pytest.mark.parametrize("bad", [
    "4,2",             # too few positional axes
    "1,1,1,1,1,2",     # fsdp has no positional slot
    "data=2,data=2",   # duplicate axis
    "zeta=2",          # unknown axis
    "data=0",          # zero size
    "fsdp=-1",         # -1 is data-only
])
def test_parse_mesh_arg_rejects(bad):
    from p2p_tpu.core.mesh import parse_mesh_arg

    with pytest.raises(ValueError):
        parse_mesh_arg(bad)


def test_fsdp_mesh_batch_sharding(devices8):
    """Batches shard over BOTH data and fsdp (core/mesh.BATCH_AXES): on a
    data=2 x fsdp=2 mesh a batch of 4 lands one sample per device."""
    import jax.numpy as jnp

    mesh = make_mesh(MeshSpec(data=2, fsdp=2), devices=devices8[:4])
    x = jax.device_put(jnp.zeros((4, 8, 8, 3)), batch_sharding(mesh))
    assert len(x.addressable_shards) == 4
    assert all(s.data.shape[0] == 1 for s in x.addressable_shards)
