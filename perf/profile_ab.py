"""Whole-pipeline A/B on the live chip (in-jit rep loop, interleaved
trials): batch size, top-k width, NMS formulation. The full pipeline is
the only trustworthy unit — stage isolation gets
confounded by XLA loop-invariant hoisting."""
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

INNER, OUTER, TRIALS = 10, 2, 6

import _harness  # noqa: F401  (sys.path bootstrap)
from triton_client_tpu.models.yolov5 import init_yolov5
from triton_client_tpu.ops.detect_postprocess import extract_boxes
from triton_client_tpu.ops.preprocess import normalize_image

model, variables = init_yolov5(
    jax.random.PRNGKey(0), num_classes=2, variant="n", input_hw=(512, 512)
)
rng = np.random.default_rng(0)


def make_step(batch, max_nms=1024, nms_env=None):
    frames = jnp.asarray(
        rng.integers(0, 255, (batch, 512, 512, 3)).astype(np.float32)
    )
    saved_env = os.environ.get("TRITON_CLIENT_TPU_NMS")
    if nms_env:
        os.environ["TRITON_CLIENT_TPU_NMS"] = nms_env

    def one(tok):
        x = normalize_image(frames + tok * 0.0, "yolo")
        pred = model.decode(model.apply(variables, x, train=False))
        dets, valid = extract_boxes(
            pred, conf_thresh=0.3, iou_thresh=0.45, max_nms=max_nms
        )
        return (jnp.sum(valid) + jnp.sum(dets) * 1e-12).astype(jnp.float32)

    @jax.jit
    def looped(tok):
        return jax.lax.fori_loop(0, INNER, lambda i, t: one(t), tok)

    tok = jnp.float32(0.0)
    for _ in range(2):
        tok = looped(tok)
    float(tok)
    if nms_env:  # restore the operator's setting, don't clobber it
        if saved_env is None:
            os.environ.pop("TRITON_CLIENT_TPU_NMS", None)
        else:
            os.environ["TRITON_CLIENT_TPU_NMS"] = saved_env
    return looped


CASES = [
    ("b8  k1024 fixpoint", dict(batch=8)),
    ("b8  k256  fixpoint", dict(batch=8, max_nms=256)),
    ("b8  k1024 xla-loop", dict(batch=8, nms_env="xla")),
    ("b8  k1024 pallas  ", dict(batch=8, nms_env="pallas")),
    ("b16 k1024 fixpoint", dict(batch=16)),
    ("b32 k1024 fixpoint", dict(batch=32)),
    ("b64 k1024 fixpoint", dict(batch=64)),
]

steps = []
for name, kw in CASES:
    t0 = time.perf_counter()
    steps.append((name, kw, make_step(**kw)))
    print(f"compiled {name} in {time.perf_counter() - t0:.0f}s", file=sys.stderr)

acc = {name: [] for name, _, _ in steps}
for _ in range(TRIALS):
    for name, kw, step in steps:  # interleaved
        tok = jnp.float32(0.0)
        t0 = time.perf_counter()
        for _ in range(OUTER):
            tok = step(tok)
        float(tok)
        acc[name].append((time.perf_counter() - t0) * 1e3 / (OUTER * INNER))

for name, kw, _ in steps:
    ms = statistics.median(acc[name])
    fps = kw["batch"] / ms * 1000
    print(f"{name}  {ms:8.3f} ms/call  {fps:7.0f} fps", file=sys.stderr)
