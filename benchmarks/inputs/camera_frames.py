"""Seeded camera frames: ``frames_per_request`` uint8 RGB frames of the
configuration's input size a request, as a camera driver sends them
(786 KB a 512x512 frame; the served pipeline widens on the device).

Content is a coarse random field, held over blocks and dithered, so
that neighbouring anchors see related pixels and NMS has overlapping
boxes to suppress; it has no bearing on speed. The dither comes from
the generator's raw bytes through a table, in slices drawn side by side:
NumPy's bounded uint8 draw took some 9 s of every set-up for the 604 MB
of a 768-frame request."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


DITHER = ((np.arange(256) * 49) >> 8).astype(np.uint8)  # a raw byte -> 0..48, each value 5 or 6 bytes of 256
SLICE = 64  # frames drawn at a time, each slice from a generator of its own, a few slices side by side


def make(rng: np.random.Generator, n: int, params: dict, cfg: dict) -> list[dict]:
    h, w = cfg["model"]["input_hw"]
    b, block = int(params["frames_per_request"]), int(params.get("block", 32))

    def fill(images: np.ndarray, sub: np.random.Generator) -> None:
        coarse = sub.integers(24, 232, (len(images), h // block, w // block, 3), dtype=np.uint8)
        field = coarse.repeat(block, axis=1).repeat(block, axis=2)
        raw = np.frombuffer(sub.bytes(field.size), np.uint8).reshape(field.shape)
        np.add(field - np.uint8(24), DITHER[raw], out=images)

    out = []
    with ThreadPoolExecutor(max_workers=6) as pool:
        for _ in range(n):
            images = np.empty((b, h, w, 3), np.uint8)
            slices = [images[i : i + SLICE] for i in range(0, b, SLICE)]
            list(pool.map(fill, slices, rng.spawn(len(slices))))
            out.append({"images": images})
    return out
