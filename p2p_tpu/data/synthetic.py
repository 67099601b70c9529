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


def _synthetic_label_pair(rng: np.random.Generator, size: Tuple[int, int],
                          classes: int):
    """One seeded label/photo pair: piecewise-constant regions of
    ``classes`` ids (a background, rectangles, disks; each region an
    instance), the edge bit where a pixel's instance differs from a
    4-neighbour's (the SPADE lineage's ``get_edges``), and a photo whose
    colours follow the ids over a smooth gradient."""
    h, w = size
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    inst = np.zeros((h, w), np.int32)
    ids = np.full((h, w), rng.integers(0, classes), np.uint8)
    for k in range(1, int(rng.integers(6, 14))):
        if rng.random() < 0.5:
            y0, x0 = rng.integers(0, h - 2), rng.integers(0, w - 2)
            y1 = y0 + rng.integers(2, max(3, h // 2))
            x1 = x0 + rng.integers(2, max(3, w // 2))
            mask = np.zeros((h, w), bool)
            mask[y0:y1, x0:x1] = True
        else:
            cy, cx = rng.integers(0, h), rng.integers(0, w)
            r = rng.integers(2, max(3, h // 4))
            mask = (yy - cy) ** 2 + (xx - cx) ** 2 < r ** 2
        inst[mask] = k
        ids[mask] = rng.integers(0, classes)
    edge = np.zeros((h, w), bool)
    dx, dy = inst[:, 1:] != inst[:, :-1], inst[1:] != inst[:-1]
    edge[:, 1:] |= dx
    edge[:, :-1] |= dx
    edge[1:] |= dy
    edge[:-1] |= dy
    palette = rng.uniform(0.1, 0.9, (classes, 3)).astype(np.float32)
    shade = 0.85 + 0.15 * np.sin(
        2 * np.pi * (rng.uniform(0.5, 3.0) * xx / w
                     + rng.uniform(0.5, 3.0) * yy / h))
    photo = palette[ids] * shade[..., None]
    labels = np.stack([ids, edge.astype(np.uint8)], axis=-1)
    return labels, (photo * 255).astype(np.uint8)


def make_synthetic_label_dataset(
    out_dir: str,
    n_train: int = 8,
    n_test: int = 4,
    size: Tuple[int, int] = (64, 128),
    classes: int = 35,
    seed: int = 0,
) -> str:
    """Write a seeded label->photo dataset in the paired layout: ``a/``
    the photos (RGB PNG), ``b/`` the label maps as two-channel ``LA``
    PNGs (class id, instance-edge bit), which
    ``PairedImageDataset(label_input=True, direction="b2a")`` reads back
    bit for bit. Returns ``out_dir``."""
    rng = np.random.default_rng(seed)
    for split, n in (("train", n_train), ("test", n_test)):
        a_dir = os.path.join(out_dir, split, "a")
        b_dir = os.path.join(out_dir, split, "b")
        os.makedirs(a_dir, exist_ok=True)
        os.makedirs(b_dir, exist_ok=True)
        for i in range(n):
            labels, photo = _synthetic_label_pair(rng, size, classes)
            name = f"synth_{i:04d}.png"
            Image.fromarray(photo).save(os.path.join(a_dir, name))
            Image.fromarray(labels, "LA").save(os.path.join(b_dir, name))
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
