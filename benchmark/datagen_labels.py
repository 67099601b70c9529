"""Seeded label-map inputs: a piecewise-constant map of class ids, the
instance-edge bit derived from an instance map, and a photo whose colours
follow the ids, written as the paired a/ b/ PNG splits a label-map
Trainer reads (``a/`` the photo, RGB; ``b/`` the label map, a two-channel
``LA`` PNG: class id, edge bit) or handed out as arrays.

The yardstick's own generator, independent of
``p2p_tpu/data/synthetic.py``; the same seed gives the same bytes.
"""

from __future__ import annotations

import os
from typing import List, Tuple

import numpy as np
from PIL import Image

from benchmark.datagen import png_bytes


def synthetic_pair(rng: np.random.Generator, hw: Tuple[int, int],
                   classes: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(labels, photo)``: uint8 ``(H, W, 2)`` — class id, edge bit —
    and uint8 ``(H, W, 3)``. A street-like layout: horizontal bands (sky,
    buildings, road) of one class each, then rectangles and disks, each
    its own instance with a class of its own; a pixel is an edge where
    its instance differs from a 4-neighbour's."""
    h, w = hw
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    ids = np.zeros((h, w), np.uint8)
    inst = np.zeros((h, w), np.int32)
    cuts = np.sort(rng.integers(h // 8, h - h // 8, 2))
    for k, (lo, hi) in enumerate(zip((0, *cuts), (*cuts, h))):
        wave = (rng.uniform(0, h / 16) * np.sin(
            2 * np.pi * rng.uniform(0.5, 2.0) * xx[0] / w)).astype(np.int32)
        band = (yy >= lo + wave[None]) & (yy < hi + wave[None] + (hi == h))
        ids[band], inst[band] = rng.integers(0, classes), k
    n_inst = 3
    for _ in range(int(rng.integers(8, 20))):
        if rng.random() < 0.6:
            y0, x0 = rng.integers(0, h - 4), rng.integers(0, w - 4)
            y1 = y0 + rng.integers(4, max(5, h // 3))
            x1 = x0 + rng.integers(4, max(5, w // 4))
            mask = (yy >= y0) & (yy < y1) & (xx >= x0) & (xx < x1)
        else:
            cy, cx = rng.integers(0, h), rng.integers(0, w)
            r = rng.integers(3, max(4, h // 6))
            mask = (yy - cy) ** 2 + (xx - cx) ** 2 < r ** 2
        ids[mask], inst[mask] = rng.integers(0, classes), n_inst
        n_inst += 1
    edge = np.zeros((h, w), bool)
    dx, dy = inst[:, 1:] != inst[:, :-1], inst[1:] != inst[:-1]
    edge[:, 1:] |= dx
    edge[:, :-1] |= dx
    edge[1:] |= dy
    edge[:-1] |= dy
    palette = rng.uniform(0.05, 0.95, (classes, 3)).astype(np.float32)
    shade = 0.8 + 0.2 * np.sin(2 * np.pi * (
        rng.uniform(0.5, 3.0) * xx / w + rng.uniform(0.5, 3.0) * yy / h
    ) + rng.uniform(0, 2 * np.pi))
    photo = palette[ids] * shade[..., None] * np.where(
        edge, 0.6, 1.0)[..., None]
    labels = np.stack([ids, edge.astype(np.uint8)], axis=-1)
    return labels, (photo * 255).astype(np.uint8)


def pair(seed: int, index: int, hw: Tuple[int, int], classes: int):
    """Pair ``index`` of ``seed``: its own generator, so any one pair can
    be made without the others (and in another process)."""
    return synthetic_pair(np.random.default_rng((int(seed), int(index))),
                          hw, classes)


def pairs(seed: int, n: int, hw: Tuple[int, int], classes: int) -> List:
    return [pair(seed, i, hw, classes) for i in range(n)]


def _write_pair(job) -> None:
    seed, index, hw, classes, a_path, b_path = job
    labels, photo = pair(seed, index, hw, classes)
    with open(a_path, "wb") as f:
        f.write(png_bytes(photo))
    Image.fromarray(labels, "LA").save(b_path, format="PNG",
                                       compress_level=1)


def write_label_dataset(root: str, seed: int, n_train: int, n_test: int,
                        hw: Tuple[int, int], classes: int,
                        workers: int = 8) -> None:
    """``root/{train,test}/{a,b}/pair_NNNN.png``; pair ``i`` of the seed
    is train pair ``i``. Written by a few spawned workers (set-up is paid
    by every run); found again if a finished marker is there."""
    done = os.path.join(root, ".complete")
    if os.path.exists(done):
        return
    jobs = []
    for split, lo, n in (("train", 0, n_train), ("test", n_train, n_test)):
        for side in "ab":
            os.makedirs(os.path.join(root, split, side), exist_ok=True)
        for i in range(n):
            name = f"pair_{i:04d}.png"
            jobs.append((seed, lo + i, hw, classes,
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
