"""Random inpainting masks, drawn on the host for every sample anew.

The LaMa lineage's training masks (``saicinpainting/training/data/
masks.py`` under ``big-lama``'s data configuration, as recalled): every
sample gets wide polylines (``make_random_irregular_mask``) and THEN boxes
(``make_random_rectangle_mask``) over the same mask. (The source's
``MixedMaskGenerator`` with ``irregular_proba`` 1, ``box_proba`` 1,
``segm_proba`` 0 may well choose ONE kind a sample instead; no file of it
can be read here, so the overlay that this system's configuration names
stands, and the one-kind form is noted in ``benchmark/configs/
big_lama_places256.json`` under ``assumed``.)

  polylines: 1-5 of them, each from a uniform start through 1-5 segments
    of direction 0.01 + U{0..3} radians (mirrored on every second
    polyline), length 10 + U{0..199} and width 5 + U{0..99} pixels, the
    end points clipped to the image; ``cv2.line``.
  boxes: 1-4 of them, sides U{30..149} pixels, at least 10 from the edge.

The ranges are the 256x256 configuration's in PIXELS, as the source states
them; at another extent every length scales with the shorter side over
256 (the draws stay the same), so a toy extent sees masks of the same
shares and not one blanket.

A mask is a pure function of the integers it is seeded by: the loader
hands (the run's seed, the epoch, the sample's index), so same-seed runs
see the same masks and every epoch fresh ones.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

POLYLINES = dict(min_times=1, max_times=5, max_angle=4, max_len=200,
                 max_width=100)
BOXES = dict(min_times=1, max_times=4, margin=10, min_size=30, max_size=150)
_TAG = 0x4D41534B
#: the extent the ranges are stated for
_EXTENT = 256


def _pixels(length: int, h: int, w: int) -> int:
    """``length`` pixels of the 256x256 configuration at ``h`` x ``w``."""
    return max(1, round(length * min(h, w) / _EXTENT))


def polyline_mask(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    import cv2

    p = POLYLINES
    mask = np.zeros((h, w), np.uint8)
    times = int(rng.integers(p["min_times"], p["max_times"] + 1))
    for i in range(times):
        x0, y0 = int(rng.integers(w)), int(rng.integers(h))
        for _ in range(1 + int(rng.integers(5))):
            angle = 0.01 + int(rng.integers(p["max_angle"]))
            if i % 2 == 0:
                angle = 2 * np.pi - angle
            length = _pixels(10 + int(rng.integers(p["max_len"])), h, w)
            width = _pixels(5 + int(rng.integers(p["max_width"])), h, w)
            x1 = int(np.clip(x0 + length * np.sin(angle), 0, w))
            y1 = int(np.clip(y0 + length * np.cos(angle), 0, h))
            cv2.line(mask, (x0, y0), (x1, y1), 1, width)
            x0, y0 = x1, y1
    return mask


def box_mask(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    p = BOXES
    mask = np.zeros((h, w), np.uint8)
    margin = _pixels(p["margin"], h, w)
    for _ in range(int(rng.integers(p["min_times"], p["max_times"] + 1))):
        bw = _pixels(int(rng.integers(p["min_size"], p["max_size"])), h, w)
        bh = _pixels(int(rng.integers(p["min_size"], p["max_size"])), h, w)
        x0 = int(rng.integers(margin, w - margin - bw + 1))
        y0 = int(rng.integers(margin, h - margin - bh + 1))
        mask[y0:y0 + bh, x0:x0 + bw] = 1
    return mask


def draw_mask(seed: Sequence[int], h: int, w: int) -> np.ndarray:
    """uint8 ``(h, w)``, 1 = missing, from the integers ``seed`` (the
    loader's ``(seed, epoch, index)``): polylines, then boxes over them."""
    rng = np.random.default_rng((_TAG,) + tuple(int(s) for s in seed))
    mask = polyline_mask(rng, h, w)
    return np.maximum(mask, box_mask(rng, h, w))


def masked_input(image: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """``(H, W, 4)``: the image with its missing pixels blanked to the
    authors' 0 (uint8 0; -1 of a float image in [-1, 1]) and the mask as
    a fourth channel (uint8 0 / 255; float -1 / 1), in the image's dtype:
    what the generator reads once ``utils/images.ingest`` has normalised
    it."""
    m = mask.astype(bool)[..., None]
    if image.dtype == np.uint8:
        blank, lo, hi = np.uint8(0), np.uint8(0), np.uint8(255)
    else:
        blank, lo, hi = (image.dtype.type(-1), image.dtype.type(-1),
                         image.dtype.type(1))
    return np.concatenate(
        [np.where(m, blank, image), np.where(m, hi, lo)], axis=-1)
