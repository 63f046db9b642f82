"""Seeded lidar scans: one scan a request, ``points`` padded to the
configuration's point bucket with the live count in ``num_points``,
the contract ``drivers/driver.channel_infer3d`` sends.

A scan has ``points_min``..``points_max`` returns that fall on
``pillars_min``..``pillars_max`` distinct pillars, laid along straight
segments as returns lie along walls, kerbs and vehicles, more of them
near the sensor. Returns sit within 0.3 of a cell from its centre, so
no point lies on a cell edge where two float32 evaluations of the cell
index could differ. The occupied-pillar count stays under the entry's
``max_voxels``: the capped (OpenPCDet) and uncapped (served scatter)
voxelizers then agree on which pillars exist."""

from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, n: int, params: dict, cfg: dict) -> list[dict]:
    voxel = cfg["model"]["voxel"]
    lo = np.asarray(voxel["point_cloud_range"][:3], np.float64)
    hi = np.asarray(voxel["point_cloud_range"][3:], np.float64)
    size = np.asarray(voxel["voxel_size"], np.float64)
    nx, ny = (int(v) for v in np.round((hi - lo) / size)[:2])
    bucket = int(cfg["model"]["point_bucket"])
    if params["pillars_max"] > voxel["max_voxels"] or params["points_max"] > bucket:
        raise ValueError("traffic exceeds the entry's pillar budget or point bucket")
    out = []
    for _ in range(n):
        want = int(rng.integers(params["pillars_min"], params["pillars_max"] + 1))
        cells = np.zeros((0, 2), np.int64)
        while len(cells) < want:
            k = 256
            start = rng.uniform([0, 0], [nx, ny], (k, 2))
            angle = rng.uniform(0, np.pi, k)
            length = rng.integers(4, 80, k)
            t = np.arange(80)[None, :, None]
            step = np.stack([np.cos(angle), np.sin(angle)], 1)[:, None, :]
            line = np.floor(start[:, None, :] + t * step).astype(np.int64)
            keep = (t[..., 0] < length[:, None]) & (line >= 0).all(-1) & (line < [nx, ny]).all(-1)
            cells = np.unique(np.concatenate([cells, line[keep]]), axis=0)
        cells = cells[rng.permutation(len(cells))[:want]]
        m = int(rng.integers(params["points_min"], params["points_max"] + 1))
        centre_xy = lo[:2] + (cells + 0.5) * size[:2]
        weight = 1.0 / (4.0 + np.hypot(centre_xy[:, 0], centre_xy[:, 1]))
        # every chosen pillar holds at least one return
        pick = np.concatenate([np.arange(want), rng.choice(want, m - want, p=weight / weight.sum())])
        xy = centre_xy[pick] + rng.uniform(-0.3, 0.3, (m, 2)) * size[:2]
        z = rng.uniform(lo[2] + 0.3, hi[2] - 0.3, m)
        cloud = np.zeros((bucket, 4), np.float32)
        cloud[:m, 0:2], cloud[:m, 2], cloud[:m, 3] = xy, z, rng.uniform(0, 1, m)
        cloud[:m] = cloud[rng.permutation(m)]
        out.append({"points": cloud, "num_points": np.asarray(m, np.int32)})
    return out
