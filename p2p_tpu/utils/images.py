"""Host-side image IO.

Parity with /root/reference/utils.py: ``save_img`` maps [-1,1] → uint8 via
(x+1)/2·255 (utils.py:15-22 — the CORRECT mapping, which the reference's
train-time ``tensor2img`` disagrees with, SURVEY Q8). Arrays here are NHWC
or HWC numpy/JAX; no CHW anywhere.
"""

from __future__ import annotations

import numpy as np
from PIL import Image


def ingest(x, train_dtype=None):
    """Batch-image entry contract for the jitted steps: uint8 [0,255]
    (the uint8 input pipeline, DataConfig.uint8_pipeline) or float [-1,1].

    The device-side normalize ``(f32(u8) − 127.5)·(1/127.5)`` uses the
    SAME f32 expression as both host decode paths (fastimage.cpp
    normalize_f32 and data/pipeline.load_image): the subtraction is exact
    in f32, leaving ONE rounding step and no mul+add pattern a backend
    could FMA-contract — so the uint8 and f32 pipelines round through
    identical f32 values on every backend. Verified bit-exact in
    tests/test_train.py::test_train_step_uint8_batch_matches_f32; the
    cast chain fuses into the first consumer under jit. Works on jax and
    numpy arrays alike (returns jnp on jnp input).
    """
    import jax.numpy as jnp

    if x.dtype == np.uint8:
        x = ((x.astype(jnp.float32) - np.float32(127.5))
             * np.float32(1.0 / 127.5))
    if train_dtype is not None:
        x = x.astype(train_dtype)
    return x


def one_hot_labels(x, classes: int, edge: bool, dtype=None):
    """A label map as the loader ships it — integer ``(..., H, W, 2)``:
    class id, instance-edge bit — to the conditioning map the nets see:
    ``classes`` one-hot channels (+ the edge bit as one more), exact 0/1
    in ``dtype`` (float32 when None). An id outside ``[0, classes)`` gives
    an all-zero pixel. Works on jax and numpy arrays (returns jnp)."""
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    ids = jnp.asarray(x[..., :1])
    m = (ids == jnp.arange(classes, dtype=ids.dtype)).astype(dtype)
    if edge:
        m = jnp.concatenate([m, (x[..., 1:2] != 0).astype(dtype)], axis=-1)
    return m


def ingest_input(x, model, train_dtype=None):
    """``batch["input"]`` as the configuration's nets take it: a label-map
    configuration (``ModelConfig.label_classes`` > 0) one-hots its integer
    map on the device — class ids are never centred at 127.5 —, every
    other goes through :func:`ingest`."""
    if model.label_classes:
        if not np.issubdtype(np.dtype(x.dtype), np.integer):
            raise TypeError("a label-map input is an integer map of class "
                            f"ids, got {x.dtype}")
        return one_hot_labels(x, model.label_classes, model.label_edge,
                              train_dtype)
    return ingest(x, train_dtype)


def wire_spec(cfg, key: str = "input"):
    """``((H, W, C), dtype)`` of one item of ``batch[key]`` as the loader
    of ``cfg`` ships it: what every dummy batch (lint, the audits, the
    serving templates and buckets) has to be built from. A label-map
    input is uint8 ``(H, W, 2)`` whatever ``uint8_pipeline`` says; the
    input's extent is the target's over ``cfg.model.scale``."""
    h, w = cfg.input_hw if key == "input" else cfg.image_hw
    if key == "input" and cfg.model.label_classes:
        return (h, w, 2), np.dtype(np.uint8)
    nc = cfg.model.input_nc if key == "input" else cfg.model.output_nc
    return (h, w, nc), np.dtype(
        np.uint8 if cfg.data.uint8_pipeline else np.float32)


def dummy_batch(cfg, lead=(1,), dtype=None, abstract: bool = False):
    """``{"input", "target"}`` in the shapes and dtypes the loader of
    ``cfg`` ships (:func:`wire_spec`) behind the leading axes ``lead``:
    zeros, or ``jax.ShapeDtypeStruct``s with ``abstract``. ``dtype``
    overrides the wire dtype (the sites that always trace uint8). The
    one place lint, the audits and the serving templates get a stand-in
    batch from, so a label-map configuration is never handed an image."""
    out = {}
    for key in ("input", "target"):
        hwc, wire = wire_spec(cfg, key)
        shape, dt = tuple(lead) + hwc, np.dtype(dtype or wire)
        if abstract:
            import jax

            out[key] = jax.ShapeDtypeStruct(shape, dt)
        else:
            out[key] = np.zeros(shape, dt)
    return out


def label_preview(x) -> np.ndarray:
    """A label map ``(H, W, 2)`` as an RGB uint8 picture (a fixed colour a
    class, edges white) for the sample dumps."""
    ids = np.asarray(x[..., 0], np.uint32)
    rgb = np.stack([(ids * 67 + 29) % 256, (ids * 131 + 71) % 256,
                    (ids * 197 + 113) % 256], axis=-1).astype(np.uint8)
    rgb[np.asarray(x[..., 1]) != 0] = 255
    return rgb


def to_uint8_img(x) -> np.ndarray:
    """[-1,1] float HWC → uint8 HWC. uint8 input passes through unscaled
    (already-converted images, e.g. the masking experiment's AND output)."""
    if isinstance(x, np.ndarray) and x.dtype == np.uint8:
        if x.ndim == 4:
            if x.shape[0] != 1:
                raise ValueError(f"expected single image, got batch {x.shape}")
            return x[0]
        return x
    arr = np.asarray(x, np.float32)
    if arr.ndim == 4:
        if arr.shape[0] != 1:
            raise ValueError(f"expected single image, got batch {arr.shape}")
        arr = arr[0]
    arr = (arr + 1.0) * 0.5 * 255.0
    return np.clip(np.round(arr), 0, 255).astype(np.uint8)


def save_img(x, path: str) -> None:
    Image.fromarray(to_uint8_img(x)).save(path)
