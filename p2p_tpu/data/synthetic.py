"""Synthetic paired data for tests and smoke runs.

Procedurally generated RGB images (smooth gradients + random rectangles and
disks — enough structure that quantization visibly banding-degrades them),
run through the same quantizer as real data. Used by the integration tests
(SURVEY §4.4: tiny synthetic set driven N steps), ``train/graft.py`` and
``chip_smoke.py``.
"""

from __future__ import annotations

import os
from typing import Tuple, Optional

import numpy as np
from PIL import Image

from p2p_tpu.data.generate import compress_uint8


def _synthetic_image(rng: np.random.Generator, size: Tuple[int, int]) -> np.ndarray:
    h, w = size
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    img = np.zeros((h, w, 3), np.float32)
    # smooth background gradient with random orientation/phase per channel
    for c in range(3):
        fx, fy = rng.uniform(0.5, 3.0, 2)
        phase = rng.uniform(0, 2 * np.pi)
        img[:, :, c] = 0.5 + 0.5 * np.sin(
            2 * np.pi * (fx * xx / w + fy * yy / h) + phase
        )
    # random rectangles
    for _ in range(rng.integers(3, 8)):
        y0, x0 = rng.integers(0, h // 2), rng.integers(0, w // 2)
        y1, x1 = y0 + rng.integers(4, h // 2), x0 + rng.integers(4, w // 2)
        img[y0:y1, x0:x1] = rng.uniform(0, 1, 3)
    # random disks
    for _ in range(rng.integers(2, 6)):
        cy, cx = rng.integers(0, h), rng.integers(0, w)
        r = rng.integers(3, max(4, h // 6))
        mask = (yy - cy) ** 2 + (xx - cx) ** 2 < r**2
        img[mask] = rng.uniform(0, 1, 3)
    return (img * 255).astype(np.uint8)


def make_synthetic_dataset(
    out_dir: str,
    n_train: int = 8,
    n_test: int = 4,
    size: int = 64,
    bits: int = 3,
    seed: int = 0,
) -> str:
    """Write a/ + b/ splits of procedural images; returns out_dir."""
    rng = np.random.default_rng(seed)
    for split, n in (("train", n_train), ("test", n_test)):
        a_dir = os.path.join(out_dir, split, "a")
        b_dir = os.path.join(out_dir, split, "b")
        os.makedirs(a_dir, exist_ok=True)
        os.makedirs(b_dir, exist_ok=True)
        for i in range(n):
            img = _synthetic_image(rng, (size, size))
            name = f"synth_{i:04d}.png"
            Image.fromarray(img).save(os.path.join(a_dir, name))
            Image.fromarray(compress_uint8(img, bits)).save(
                os.path.join(b_dir, name)
            )
    return out_dir


def synthetic_batch(
    batch_size: int = 1, size: int = 64, bits: int = 3, seed: int = 0,
    width: Optional[int] = None, dtype: str = "float32",
):
    """In-memory batch dict {'input','target'}, b2a direction — float32
    [-1,1] by default, raw uint8 with ``dtype='uint8'`` (the uint8 input
    pipeline contract; the steps normalize on device via ingest).

    ``size`` is the height; ``width`` defaults to square (the wide presets —
    Cityscapes 512×256, pix2pixHD 1024×512 — pass it explicitly)."""
    rng = np.random.default_rng(seed)
    targets = np.stack(
        [_synthetic_image(rng, (size, width or size))
         for _ in range(batch_size)]
    )
    inputs = np.stack([compress_uint8(t, bits) for t in targets])
    if dtype == "uint8":
        return {"input": inputs, "target": targets}
    # the canonical normalize (see utils/images.ingest) so the f32 and
    # uint8 synthetic batches are bit-identical after device ingest
    to_f = lambda x: ((x.astype(np.float32) - np.float32(127.5))
                      * np.float32(1.0 / 127.5))
    return {"input": to_f(inputs), "target": to_f(targets)}
