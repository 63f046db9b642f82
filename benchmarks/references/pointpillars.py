"""Plain float32 reference of PointPillars as the entry serves it.

Written from the published description (Lang et al. 2019; OpenPCDet
``pointpillar.yaml``: PillarVFE with absolute xyz and the two offset
triples, scatter to the BEV canvas, three-block backbone with deconv
concat, single-stage anchor head with residual box coding and
direction bins) in straightforward ``jax.numpy``: float32 throughout,
every contraction at ``Precision.HIGHEST``, no kernels, no batcher.

Departure, stated by the entry as served (``vfe: auto`` resolves to
the scatter path, ``pipelines/detect3d.py``): every point of a pillar
and every occupied pillar is kept, where OpenPCDet's voxelizer cuts at
32 points a pillar and ``max_voxels`` pillars. The cell's clouds stay
under the pillar budget, so the two contracts see the same pillars;
pillars with more than 32 returns keep them all here and there.

The reference stops at its decoded candidates above the score
threshold (rotated BEV IoU would be more code than the forward pass):
``COMPARE = "candidates"`` tells the comparison that every served box
needs a partner among them and that each scan's best candidate, which
greedy NMS always keeps, must be served.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .scope import GAIN, HIGHEST, Scope

EPS = 1e-3
ROTATIONS = (0.0, math.pi / 2)
MAX_CANDIDATES = 2048  # decoded per scan; more above the gate is reported


def _grid(model: dict):
    lo = np.asarray(model["voxel"]["point_cloud_range"][:3], np.float32)
    hi = np.asarray(model["voxel"]["point_cloud_range"][3:], np.float32)
    size = np.asarray(model["voxel"]["voxel_size"], np.float32)
    n = np.round((hi - lo) / size).astype(int)
    return lo, size, int(n[0]), int(n[1])


def _conv_bn_relu(s: Scope, name, x, features, stride):
    x = s.conv(("backbone", name), x, features, 3, stride, 1, GAIN["relu"])
    bn = name.replace("_down", "_down_bn").replace("_conv", "_bn")
    return jax.nn.relu(s.batch_norm(("backbone", bn), x, EPS))


def _deconv_bn_relu(s: Scope, name, x, features, k):
    """Transposed conv with kernel = stride = k: every input cell
    writes its own k x k output block. The checkpoint layout stores
    the kernel as the gradient-of-conv form reads it (flax
    ``ConvTranspose``, ``transpose_kernel=False``): block position
    (dy, dx) uses kernel tap (k-1-dy, k-1-dx)."""
    w = s.kernel(("backbone", name), (k, k, x.shape[-1], features), GAIN["relu"] * k * k)
    b, h, wd, _ = x.shape
    y = jnp.einsum("bhwi,yxio->bhywxo", x, w[::-1, ::-1], precision=HIGHEST)
    s.flops += 2.0 * k * k * x.shape[-1] * features * b * h * wd
    y = y.reshape(b, h * k, wd * k, features)
    return jax.nn.relu(s.batch_norm(("backbone", name + "_bn"), y, EPS))


def _canvas(s: Scope, points, count, model: dict):
    """One scan (P, 4) with ``count`` live rows -> (ny, nx, C) canvas."""
    lo, size, nx, ny = _grid(model)
    xyz = points[:, :3]
    ijk = jnp.floor((xyz - lo) / size).astype(jnp.int32)
    inside = jnp.all((ijk >= 0) & (ijk < jnp.asarray([nx, ny, 1])), axis=1)
    live = inside & (jnp.arange(points.shape[0]) < count)
    dump = nx * ny
    pillar = jnp.where(live, ijk[:, 1] * nx + ijk[:, 0], dump)
    w = live.astype(jnp.float32)[:, None]
    sums = jax.ops.segment_sum(
        jnp.concatenate([xyz, jnp.ones_like(w)], 1) * w, pillar, dump + 1
    )
    mean = sums[pillar, :3] / jnp.maximum(sums[pillar, 3:], 1.0)
    centre = (ijk.astype(jnp.float32) + 0.5) * size + lo
    feats = jnp.concatenate([points[:, :4], xyz - mean, xyz - centre], 1) * w
    kernel = s.kernel(("vfe", "linear"), (feats.shape[1], model["vfe_filters"]), GAIN["relu"])
    x = jnp.einsum("pi,io->po", feats, kernel, precision=HIGHEST)
    s.flops += 2.0 * feats.shape[0] * feats.shape[1] * model["vfe_filters"]
    x = jax.nn.relu(s.batch_norm(("vfe", "bn"), x, EPS, live=live))
    top = jax.ops.segment_max(x, pillar, dump + 1)[:dump]
    top = jnp.where(sums[:dump, 3:] > 0, top, 0.0)
    return top.reshape(ny, nx, -1)


def _head_draws(cfg: dict):
    w, model = cfg["weights"], cfg["model"]
    a = len(model["anchors"]) * len(ROTATIONS)
    nc = len(model["anchors"])
    return {
        "cls_head": ([w["cls_std"]] * (a * nc), [0.0] * (a * nc)),
        "box_head": ([w["box_std"]] * (a * 7), [0.0] * (a * 7)),
        "dir_head": ([w["dir_std"]] * (a * 2), [0.0] * (a * 2)),
    }


def heads(s: Scope, points, counts, cfg: dict):
    """points (B, P, 4), counts (B,) -> raw head maps in float32."""
    m = cfg["model"]
    if s.drawing:  # the encoder's parameters are drawn on the first scan,
        _canvas(s, points[0], counts[0], m)  # outside any vmap, then read
    encoder = Scope(tree=s.tree)
    canvas = jax.vmap(lambda p, c: _canvas(encoder, p, c, m))(points, counts)
    s.flops += encoder.flops * points.shape[0]
    if s.drawing:
        s.flops = encoder.flops * points.shape[0]
    x, ups = canvas, []
    for bi, (n, stride, f, us, uf) in enumerate(
        zip(m["backbone_layers"], m["backbone_strides"], m["backbone_filters"],
            m["upsample_strides"], m["upsample_filters"])
    ):
        x = _conv_bn_relu(s, f"block{bi}_down", x, f, stride)
        for li in range(n):
            x = _conv_bn_relu(s, f"block{bi}_conv{li}", x, f, 1)
        ups.append(_deconv_bn_relu(s, f"up{bi}", x, uf, us))
    spatial = jnp.concatenate(ups, -1)
    draws = _head_draws(cfg)
    a = len(m["anchors"]) * len(ROTATIONS)
    out = {}
    # class logits: per calibration scan, ``cls_above_gate`` of them
    # above the entry's own score threshold
    gate = math.log(cfg["pipeline"]["score_thresh"] / (1.0 - cfg["pipeline"]["score_thresh"]))
    tail = {"cls_head": (int(cfg["weights"]["cls_above_gate"]) * spatial.shape[0], gate)}
    for name, key in (("cls_head", "cls"), ("box_head", "box"), ("dir_head", "dir")):
        h = s.head((name,), spatial, *draws[name], above=tail.get(name))
        out[key] = h.reshape(*h.shape[:3], a, h.shape[-1] // a)
    return out


def anchors(model: dict) -> np.ndarray:
    """(h, w, A, 7) [x, y, z, dx, dy, dz, rot]: one anchor per class
    and rotation, centred on each head cell, z at the class centre."""
    lo, size, nx, ny = _grid(model)
    stride = model["backbone_strides"][0] // model["upsample_strides"][0]
    h, w = ny // stride, nx // stride
    r = model["voxel"]["point_cloud_range"]
    xs = r[0] + (np.arange(w) + 0.5) * (r[3] - r[0]) / w
    ys = r[1] + (np.arange(h) + 0.5) * (r[4] - r[1]) / h
    gx, gy = np.meshgrid(xs, ys)
    out = []
    for cls in model["anchors"]:
        for rot in ROTATIONS:
            a = np.zeros((h, w, 7), np.float32)
            a[..., 0], a[..., 1] = gx, gy
            a[..., 2] = cls["bottom_z"] + cls["size"][2] / 2
            a[..., 3:6] = cls["size"]
            a[..., 6] = rot
            out.append(a)
    return np.stack(out, 2)


def candidates(raw: dict, cfg: dict):
    """Raw head maps -> the MAX_CANDIDATES best anchors of each scan,
    decoded (ResidualCoder, direction bins): rows [x, y, z, dx, dy, dz,
    heading, score, label] and the count above the score threshold."""
    m, pipe = cfg["model"], cfg["pipeline"]
    b = raw["cls"].shape[0]
    nc = raw["cls"].shape[-1]
    cls = raw["cls"].reshape(b, -1, nc)
    box = raw["box"].reshape(b, -1, 7)
    dirs = raw["dir"].reshape(b, -1, 2)
    anc = jnp.asarray(anchors(m).reshape(-1, 7))
    score = jax.nn.sigmoid(cls.max(-1))
    label = cls.argmax(-1) + 1
    above = (score > pipe["score_thresh"]).sum(-1)
    top, idx = jax.lax.top_k(score, min(MAX_CANDIDATES, score.shape[1]))
    t = jnp.take_along_axis(box, idx[..., None], 1)
    a = anc[idx]
    diag = jnp.sqrt(a[..., 3] ** 2 + a[..., 4] ** 2)
    size = jnp.exp(jnp.clip(t[..., 3:6], -10, 10)) * a[..., 3:6]
    rot = t[..., 6] + a[..., 6]
    period = 2 * jnp.pi / m["num_dir_bins"]
    rot = rot - m["dir_offset"]
    rot = rot - jnp.floor(rot / period) * period + m["dir_offset"]
    bin_ = jnp.take_along_axis(dirs, idx[..., None], 1).argmax(-1)
    rows = jnp.concatenate(
        [
            (t[..., 0] * diag + a[..., 0])[..., None],
            (t[..., 1] * diag + a[..., 1])[..., None],
            (t[..., 2] * a[..., 5] + a[..., 2])[..., None],
            size,
            (rot + period * bin_)[..., None],
            top[..., None],
            jnp.take_along_axis(label, idx, 1).astype(jnp.float32)[..., None],
        ],
        -1,
    )
    return {"rows": rows, "above": above}


def init_params(key, calibration: dict, cfg: dict):
    """The benchmark's seeded weights in the served family's checkpoint
    layout; ``calibration`` is a stack of seeded scans of the cell's own
    inputs (see scope.py)."""
    s = Scope(key=key, bn_bias=cfg["weights"]["bn_bias"])
    heads(s, calibration["points"], calibration["num_points"], cfg)
    return s.tree


def forward(tree, inputs: dict, cfg: dict):
    raw = heads(Scope(tree=tree), inputs["points"], inputs["num_points"], cfg)
    return candidates(raw, cfg)


def flops_per_item(cfg: dict) -> float:
    s = Scope(key=jax.random.PRNGKey(0))
    p = cfg["model"]["point_bucket"]
    jax.eval_shape(
        lambda pts, n: heads(s, pts, n, cfg),
        jax.ShapeDtypeStruct((1, p, 4), jnp.float32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
    )
    return s.flops


# -- host half ----------------------------------------------------------------

COMPARE = "candidates"
BOX_COLS = 7


def detections(outputs: dict, cfg: dict) -> list[dict]:
    thresh = np.float32(cfg["pipeline"]["score_thresh"])
    scans = []
    for rows, above in zip(np.asarray(outputs["rows"], np.float32), np.asarray(outputs["above"])):
        scans.append({"rows": rows[rows[:, 7] > thresh], "gated": int(above)})
    return scans
