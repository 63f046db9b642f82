"""Seeded camera STREAMS for a ``sessions`` mix: ``requests_per_stream``
requests of ``frames_per_request`` frames each (``camera_frames``'
content), sent in order under one sequence id as a camera sends its
frames. A stream is drawn whole from the seed: no request's input
depends on an answer."""

from __future__ import annotations

import numpy as np

from . import camera_frames


def make(rng: np.random.Generator, n: int, params: dict, cfg: dict) -> list[list[dict]]:
    k = int(params.get("requests_per_stream", 1))
    frames = camera_frames.make(rng, n * k, params, cfg)
    return [frames[i * k : (i + 1) * k] for i in range(n)]
