"""Device-true stage profile: rep-loop INSIDE one jit so the
per-dispatch host overhead amortizes away (perf/_harness.py). NOTE:
isolated stages don't sum to the full pipeline (XLA loop-invariant
hoisting) — treat per-stage numbers as bounds, A/B whole pipelines."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from _harness import timed


from triton_client_tpu.models.yolov5 import init_yolov5
from triton_client_tpu.ops.detect_postprocess import extract_boxes
from triton_client_tpu.ops.nms import _nms_fixpoint
from triton_client_tpu.ops.preprocess import normalize_image

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 8
print(f"== yolov5n 512 batch {BATCH}, device-true (in-jit loop) ==",
      file=sys.stderr)
model, variables = init_yolov5(
    jax.random.PRNGKey(0), num_classes=2, variant="n", input_hw=(512, 512)
)
rng = np.random.default_rng(0)
frames = jnp.asarray(
    rng.integers(0, 255, (BATCH, 512, 512, 3)).astype(np.float32)
)


from _harness import tokify


def backbone_one(tok):
    x = normalize_image(frames + tok * 0.0, "yolo")
    return tokify(model.apply(variables, x, train=False))


def decode_one(tok):
    x = normalize_image(frames + tok * 0.0, "yolo")
    return tokify(model.decode(model.apply(variables, x, train=False)))


def full_one(tok):
    x = normalize_image(frames + tok * 0.0, "yolo")
    pred = model.decode(model.apply(variables, x, train=False))
    return tokify(extract_boxes(pred, conf_thresh=0.3, iou_thresh=0.45))


pred0 = jax.block_until_ready(
    jax.jit(
        lambda: model.decode(
            model.apply(variables, normalize_image(frames, "yolo"), train=False)
        )
    )()
)


def post_one(tok):
    return tokify(
        extract_boxes(pred0 + tok * 0.0, conf_thresh=0.3, iou_thresh=0.45)
    )


def gate_topk_one(tok):
    p = pred0 + tok * 0.0
    conf = p[..., 4:5] * p[..., 5:]
    scores = jnp.max(conf, axis=-1)
    gated = jnp.where(scores > 0.3, scores, -jnp.inf)
    ts, ti = jax.lax.top_k(gated, 1024)
    return tokify(ts, ti)


def gate_topk256_one(tok):
    p = pred0 + tok * 0.0
    conf = p[..., 4:5] * p[..., 5:]
    scores = jnp.max(conf, axis=-1)
    gated = jnp.where(scores > 0.3, scores, -jnp.inf)
    ts, ti = jax.lax.top_k(gated, 256)
    return tokify(ts, ti)


def sort_one(tok):
    p = pred0 + tok * 0.0
    conf = p[..., 4:5] * p[..., 5:]
    scores = jnp.max(conf, axis=-1)
    s = jnp.sort(scores, axis=-1)
    return tokify(s)


cb = jnp.asarray(rng.uniform(0, 512, (BATCH, 1024, 4)).astype(np.float32))
cb = cb.at[..., 2:].set(cb[..., :2] + 50)
cs = jnp.asarray(rng.uniform(0, 1, (BATCH, 1024)).astype(np.float32))


def nms_one(tok):
    idx, valid = jax.vmap(
        lambda b, s: _nms_fixpoint(b + tok * 0.0, s, 0.45, max_det=300)
    )(cb, cs)
    return tokify(idx, valid)


t_back = timed("pre+backbone (raw heads)", backbone_one)
t_dec = timed("pre+backbone+decode", decode_one)
timed("gate+topk 1024 (on fixed pred)", gate_topk_one)
timed("gate+topk 256 (on fixed pred)", gate_topk256_one)
timed("gate+full sort (on fixed pred)", sort_one)
timed("nms fixpoint 8x1024 isolated", nms_one)
t_post = timed("extract_boxes full (on fixed pred)", post_one)
t_full = timed("FULL pipeline", full_one)
print(
    f"accounting: backbone {t_back:.2f} + decode {t_dec - t_back:.2f} "
    f"+ post {t_post:.2f} vs full {t_full:.2f}",
    file=sys.stderr,
)
print(f"fps at batch {BATCH}: {BATCH / t_full * 1000:.0f}", file=sys.stderr)
