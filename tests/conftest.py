"""Test fixture: force an 8-device CPU mesh so every sharding / collective /
halo-exchange path is CI-able without TPU hardware (SURVEY.md §4.3)."""

import os

# The suite always runs on the CPU backend, whatever the session exports:
# set the env BEFORE jax is imported (subprocess workers inherit it), and
# the live config too in case a plugin imported jax first.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs[:8]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy test (>~10 s on CPU); quick gate: -m 'not slow'",
    )


@pytest.fixture(scope="session")
def parent_vgg_loss():
    """``vgg_loss`` as it stood before VGG19 stored bf16 activations for
    bf16 images: the plain trunk on whatever it is handed, the taps' L1 in
    float32. What float32 images must still lower to, and the program the
    bf16 path is compared with."""
    import jax.numpy as jnp

    from p2p_tpu.losses import VGG_SLICE_WEIGHTS
    from p2p_tpu.models.vgg import VGG19Features

    def loss(params, x, y):
        model = VGG19Features()
        feats_x = model.apply({"params": params}, x)
        feats_y = model.apply({"params": params}, jax.lax.stop_gradient(y))
        total = jnp.zeros((), jnp.float32)
        for w, fx, fy in zip(VGG_SLICE_WEIGHTS, feats_x, feats_y):
            fy = jax.lax.stop_gradient(fy)
            total = total + w * jnp.mean(
                jnp.abs(fx.astype(jnp.float32) - fy.astype(jnp.float32)))
        return total

    return loss
