"""Seeded super-resolution pairs: ``benchmark/datagen.py``'s procedural
images as the HQ side and, as the LQ side, their ``scale`` x ``scale``
AREA downsample (the mean of each block, in float64) rounded to 8 bits,
written as the paired a/ b/ PNG splits the trainer reads (``a/`` the HQ
image, ``b/`` the LQ one, direction ``b2a``) or handed out as arrays.

The SwinIR authors degrade on the host with BSRGAN's random blur / noise /
JPEG pipeline; that is data, not model, and the configuration file lists
this generator as a departure. The same seed gives the same bytes.
"""

from __future__ import annotations

import os
from typing import List, Tuple

import numpy as np

from benchmark import datagen


def area_downsample(img: np.ndarray, scale: int) -> np.ndarray:
    """uint8 ``(H, W, 3)`` -> uint8 ``(H / scale, W / scale, 3)``."""
    h, w, c = img.shape
    blocks = img.reshape(h // scale, scale, w // scale, scale, c)
    return np.round(blocks.astype(np.float64).mean(axis=(1, 3))).astype(
        np.uint8)


def pair(seed: int, index: int, hw: Tuple[int, int],
         scale: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(lq, hq)`` number ``index`` of ``seed``; ``hw`` is the HQ extent."""
    hq = datagen.image(seed, index, hw)
    return area_downsample(hq, scale), hq


def pairs(seed: int, n: int, hw: Tuple[int, int],
          scale: int) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    lqs, hqs = zip(*(pair(seed, i, hw, scale) for i in range(n)))
    return list(lqs), list(hqs)


def _write_pair(job) -> None:
    seed, index, hw, scale, a_path, b_path = job
    lq, hq = pair(seed, index, hw, scale)
    for path, arr in ((a_path, hq), (b_path, lq)):
        with open(path, "wb") as f:
            f.write(datagen.png_bytes(arr))


def write_sr_dataset(root: str, seed: int, n_train: int, n_test: int,
                     hw: Tuple[int, int], scale: int,
                     workers: int = 8) -> None:
    """``root/{train,test}/{a,b}/pair_NNNN.png``: a = the HQ image, b = its
    LQ copy; pair ``i`` of the seed is train pair ``i``. Written by a few
    spawned workers; found again if a finished marker is there."""
    done = os.path.join(root, ".complete")
    if os.path.exists(done):
        return
    jobs = []
    for split, lo, n in (("train", 0, n_train), ("test", n_train, n_test)):
        for side in "ab":
            os.makedirs(os.path.join(root, split, side), exist_ok=True)
        for i in range(n):
            name = f"pair_{i:04d}.png"
            jobs.append((seed, lo + i, hw, scale,
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
