"""Seeded inputs: procedural RGB images (smooth sinusoid gradients plus
random rectangles and disks — enough structure that a 3-bit quantizer
visibly bands them), written as the paired a/ b/ PNG splits the trainer
reads, or handed out as arrays and PNG payloads.

Copied from ``p2p_tpu/data/synthetic.py`` so that the inputs belong to the
yardstick; the same seed gives the same bytes.
"""

from __future__ import annotations

import io
import os
from typing import List, Tuple

import numpy as np
from PIL import Image


def synthetic_image(rng: np.random.Generator, hw: Tuple[int, int]) -> np.ndarray:
    h, w = hw
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    img = np.zeros((h, w, 3), np.float32)
    for c in range(3):
        fx, fy = rng.uniform(0.5, 3.0, 2)
        phase = rng.uniform(0, 2 * np.pi)
        img[:, :, c] = 0.5 + 0.5 * np.sin(
            2 * np.pi * (fx * xx / w + fy * yy / h) + phase)
    for _ in range(rng.integers(3, 8)):
        y0, x0 = rng.integers(0, h // 2), rng.integers(0, w // 2)
        y1, x1 = y0 + rng.integers(4, h // 2), x0 + rng.integers(4, w // 2)
        img[y0:y1, x0:x1] = rng.uniform(0, 1, 3)
    for _ in range(rng.integers(2, 6)):
        cy, cx = rng.integers(0, h), rng.integers(0, w)
        r = rng.integers(3, max(4, h // 6))
        img[(yy - cy) ** 2 + (xx - cx) ** 2 < r ** 2] = rng.uniform(0, 1, 3)
    return (img * 255).astype(np.uint8)


def compress_uint8(img: np.ndarray, bits: int) -> np.ndarray:
    """round(x / 255 * (2^b - 1)) / (2^b - 1) * 255, the banded half."""
    n = float(2 ** bits - 1)
    return np.round(np.round(img.astype(np.float32) / 255.0 * n) / n
                    * 255.0).astype(np.uint8)


def image(seed: int, index: int, hw: Tuple[int, int]) -> np.ndarray:
    """Image ``index`` of ``seed``: its own generator, so any one image can
    be made without the others (and in another process)."""
    return synthetic_image(np.random.default_rng((int(seed), int(index))), hw)


def images(seed: int, n: int, hw: Tuple[int, int]) -> List[np.ndarray]:
    return [image(seed, i, hw) for i in range(n)]


def png_bytes(img: np.ndarray) -> bytes:
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="PNG", compress_level=1)
    return buf.getvalue()


def _write_pair(job) -> None:
    seed, index, hw, bits, a_path, b_path = job
    img = image(seed, index, hw)
    for path, arr in ((a_path, img), (b_path, compress_uint8(img, bits))):
        with open(path, "wb") as f:
            f.write(png_bytes(arr))


def write_paired_dataset(root: str, seed: int, n_train: int, n_test: int,
                         hw: Tuple[int, int], bits: int = 3,
                         workers: int = 8) -> None:
    """``root/{train,test}/{a,b}/pair_NNNN.png``: a = the image, b = its
    ``bits``-bit banded copy; image ``i`` of the seed is train pair ``i``.
    Written by a few spawned workers (set-up is paid by every run); found
    again if a finished marker is there."""
    done = os.path.join(root, ".complete")
    if os.path.exists(done):
        return
    jobs = []
    for split, lo, n in (("train", 0, n_train), ("test", n_train, n_test)):
        for side in "ab":
            os.makedirs(os.path.join(root, split, side), exist_ok=True)
        for i in range(n):
            name = f"pair_{i:04d}.png"
            jobs.append((seed, lo + i, hw, bits,
                         os.path.join(root, split, "a", name),
                         os.path.join(root, split, "b", name)))
    if workers > 1 and len(jobs) >= 32:
        import multiprocessing

        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            pool.map(_write_pair, jobs, chunksize=8)
    else:
        for job in jobs:
            _write_pair(job)
    with open(done, "w") as f:
        f.write("ok\n")
