"""Plain float32 reference of YOLOv5 (v6.0 layout) as the entry serves it.

Written from the published description (ultralytics ``yolov5n.yaml``:
CSP backbone, SPPF, PANet neck, anchor Detect head at strides 8/16/32,
``depth_multiple``/``width_multiple`` scaling; ultralytics
``non_max_suppression``: conf = obj * cls, best class only, per-class
greedy NMS) in straightforward ``jax.numpy``: float32 throughout,
every contraction at ``Precision.HIGHEST``, no kernels, no batcher.

Departures from the published model, both stated by the served entry
and lossless against upstream weights: the stem is the space-to-depth
form of the 6x6 stride-2 conv (``s2d``), and every stage width is
raised to ``ch_floor`` channels.

Host side: ``detections`` gates, converts and runs greedy per-class
NMS in plain NumPy loops with an IoU of its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .scope import GAIN, Scope

EPS = 1e-3  # ultralytics BatchNorm2d(eps=1e-3)


def _width(model: dict, ch: int) -> int:
    base = max(8, int(round(ch * model["width_multiple"] / 8) * 8))
    return max(base, model.get("ch_floor", 0))


def _depth(model: dict, n: int) -> int:
    return max(1, round(n * model["depth_multiple"]))


def _cba(s: Scope, path, x, features, k=1, stride=1):
    x = s.conv(path + ("conv",), x, features, k, stride, k // 2, GAIN["silu"])
    x = s.batch_norm(path + ("bn",), x, EPS)
    return x * jax.nn.sigmoid(x)


def _c3(s: Scope, path, x, features, depth, shortcut=True):
    hidden = features // 2
    a = _cba(s, path + ("cv1",), x, hidden)
    b = _cba(s, path + ("cv2",), x, hidden)
    for i in range(depth):
        y = _cba(s, path + (f"m{i}", "cv1"), a, hidden)
        y = _cba(s, path + (f"m{i}", "cv2"), y, hidden, 3)
        a = a + y if shortcut else y
    return _cba(s, path + ("cv3",), jnp.concatenate([a, b], -1), features)


def _sppf(s: Scope, path, x, features):
    x = _cba(s, path + ("cv1",), x, x.shape[-1] // 2)
    pools = [x]
    for _ in range(3):
        pools.append(
            lax.reduce_window(
                pools[-1], -jnp.inf, lax.max, (1, 5, 5, 1), (1, 1, 1, 1),
                ((0, 0), (2, 2), (2, 2), (0, 0)),
            )
        )
    return _cba(s, path + ("cv2",), jnp.concatenate(pools, -1), features)


def _up(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def _head_draws(cfg: dict):
    """Per output channel of a Detect conv: kernel std and bias, from
    the configuration's ``weights`` block, anchor-major as the head is
    reshaped ``(a, 5 + nc)``."""
    w, model = cfg["weights"], cfg["model"]
    nc, na = model["num_classes"], len(model["anchors"][0])
    std = [w["box_std"]] * 4 + [w["obj_std"]] + [w["cls_std"]] * nc
    bias = [0.0] * 4 + [w["obj_bias"]] + [w["cls_bias"]] * nc
    return std * na, bias * na


def heads(s: Scope, frames, cfg: dict):
    """frames (B, H, W, 3) uint8 or float in [0, 255] -> raw head maps
    [(B, H/8, W/8, a, 5 + nc), /16, /32] in float32."""
    m = cfg["model"]
    c, d = (lambda ch: _width(m, ch)), (lambda n: _depth(m, n))
    x = frames.astype(jnp.float32) / 255.0
    if m["s2d"]:
        b, h, w, ch = x.shape
        x = x.reshape(b, h // 2, 2, w // 2, 2, ch).transpose(0, 1, 3, 2, 4, 5)
        x = _cba(s, ("stem",), x.reshape(b, h // 2, w // 2, 4 * ch), c(64), 3)
    else:
        x = s.conv(("stem", "conv"), x, c(64), 6, 2, 2, GAIN["silu"])
        x = s.batch_norm(("stem", "bn"), x, EPS)
        x = x * jax.nn.sigmoid(x)
    x = _cba(s, ("down2",), x, c(128), 3, 2)
    x = _c3(s, ("c3_2",), x, c(128), d(3))
    x = _cba(s, ("down3",), x, c(256), 3, 2)
    p3 = _c3(s, ("c3_3",), x, c(256), d(6))
    x = _cba(s, ("down4",), p3, c(512), 3, 2)
    p4 = _c3(s, ("c3_4",), x, c(512), d(9))
    x = _cba(s, ("down5",), p4, c(1024), 3, 2)
    x = _c3(s, ("c3_5",), x, c(1024), d(3))
    p5 = _sppf(s, ("sppf",), x, c(1024))

    t5 = _cba(s, ("lat5",), p5, c(512))
    n4 = _c3(s, ("c3_up4",), jnp.concatenate([_up(t5), p4], -1), c(512), d(3), False)
    t4 = _cba(s, ("lat4",), n4, c(256))
    out3 = _c3(s, ("c3_up3",), jnp.concatenate([_up(t4), p3], -1), c(256), d(3), False)
    x = _cba(s, ("pan3",), out3, c(256), 3, 2)
    out4 = _c3(s, ("c3_pan4",), jnp.concatenate([x, t4], -1), c(512), d(3), False)
    x = _cba(s, ("pan4",), out4, c(512), 3, 2)
    out5 = _c3(s, ("c3_pan5",), jnp.concatenate([x, t5], -1), c(1024), d(3), False)

    std, bias = _head_draws(cfg)
    na = len(m["anchors"][0])
    out = []
    for i, feat in enumerate((out3, out4, out5)):
        h = s.head((f"detect{i}",), feat, std, bias)
        out.append(h.reshape(*h.shape[:3], na, h.shape[-1] // na))
    return out


def decode(raw, cfg: dict):
    """Raw head maps -> (B, N, 5 + nc) [cx, cy, w, h, obj, cls...] in
    input pixels: xy = (2 sig(t) - 0.5 + cell) * stride,
    wh = (2 sig(t))^2 * anchor, obj/cls = sig(t)."""
    m = cfg["model"]
    out = []
    for head, anchors, stride in zip(raw, m["anchors"], m["strides"]):
        b, h, w, a, no = head.shape
        gy, gx = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
        cell = jnp.stack([gx, gy], -1).astype(jnp.float32)[None, :, :, None, :]
        sig = jax.nn.sigmoid(head)
        xy = (sig[..., :2] * 2.0 - 0.5 + cell) * stride
        wh = (sig[..., 2:4] * 2.0) ** 2 * jnp.asarray(anchors, jnp.float32)
        out.append(
            jnp.concatenate([xy, wh, sig[..., 4:]], -1).reshape(b, h * w * a, no)
        )
    return jnp.concatenate(out, 1)


def init_params(key, calibration: dict, cfg: dict):
    """The benchmark's seeded weights in the served family's checkpoint
    layout; one traced call, made on the device. ``calibration`` is one
    seeded request of the cell's own inputs (see scope.py)."""
    s = Scope(key=key, bn_bias=cfg["weights"]["bn_bias"])
    heads(s, calibration["images"], cfg)
    return s.tree


def forward(tree, inputs: dict, cfg: dict):
    """The reference's device half: decoded predictions for a batch."""
    return {"pred": decode(heads(Scope(tree=tree), inputs["images"], cfg), cfg)}


def flops_per_item(cfg: dict) -> float:
    """Multiply-adds x 2 of every conv of one frame's forward pass."""
    s = Scope(key=jax.random.PRNGKey(0))
    hw = cfg["model"]["input_hw"]
    jax.eval_shape(lambda x: heads(s, x, cfg), jax.ShapeDtypeStruct((1, hw[0], hw[1], 3), jnp.uint8))
    return s.flops


# -- host half ----------------------------------------------------------------

COMPARE = "boxes"  # the reference runs its own NMS: boxes match boxes
BOX_COLS = 4


def _iou(box, boxes):
    lt = np.maximum(box[:2], boxes[:, :2])
    rb = np.minimum(box[2:], boxes[:, 2:])
    wh = np.clip(rb - lt, 0.0, None)
    inter = wh[:, 0] * wh[:, 1]
    area = lambda b: (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1])
    return inter / np.maximum(area(box) + area(boxes) - inter, 1e-9)


def detections(outputs: dict, cfg: dict) -> list[dict]:
    """Per frame: rows [x1, y1, x2, y2, conf, class] the published
    postprocess keeps, and how many candidates passed the gate."""
    pipe = cfg["pipeline"]
    frames = []
    for pred in np.asarray(outputs["pred"], np.float32):
        conf_all = pred[:, 5:] * pred[:, 4:5]
        label = conf_all.argmax(1)
        conf = conf_all.max(1)
        keep = conf > np.float32(pipe["conf_thresh"])
        xywh, conf, label = pred[keep, :4], conf[keep], label[keep]
        half = xywh[:, 2:] / 2
        boxes = np.concatenate([xywh[:, :2] - half, xywh[:, :2] + half], 1)
        order = np.argsort(-conf, kind="stable")
        alive = np.ones(len(order), bool)
        rows = []
        for pos, i in enumerate(order):
            if not alive[pos]:
                continue
            rows.append([*boxes[i], conf[i], label[i]])
            if len(rows) == pipe["max_det"]:
                break
            rest = order[pos + 1 :]
            same = label[rest] == label[i]
            alive[pos + 1 :] &= ~(same & (_iou(boxes[i], boxes[rest]) > pipe["iou_thresh"]))
        frames.append(
            {"rows": np.asarray(rows, np.float32).reshape(-1, 6), "gated": int(keep.sum())}
        )
    return frames
