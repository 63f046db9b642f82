"""2D primary MFU attack (VERDICT r2 #4): structural A/B variants of
the YOLOv5n b8 pipeline on the live chip.

r2 established the b8 primary is fixed-overhead-bound (1.4% MFU,
batch amortizes 4x, NMS formulation irrelevant). The untried levers:

  * s2d      — space-to-depth stem (now the model's own s2d option);
  * minch32  — >= 32-channel width floor (the model's ch_floor option);
  measured: s2d -8%, minch32 -13%, together -16% at b8 — shipped as
  YoloV5(s2d=..., ch_floor=...) / detect2d --mxu-opt;
  * headless — backbone only (no decode/NMS): the head+decode share of
               the 7.8 ms;
  * b1/b16   — the batch curve endpoints for context.

All variants run the full fused pipeline (pre+forward+decode+NMS unless
noted), chained-token in-jit reps, interleaved trials (perf/_harness).
"""

import _harness  # noqa: F401

import sys

import numpy as np

import jax
import jax.numpy as jnp
from _harness import compile_looped, run_trials

from triton_client_tpu.models.yolov5 import YoloV5
from triton_client_tpu.obs.roofline import classify, peak_flops
from triton_client_tpu.ops.detect_postprocess import extract_boxes
from triton_client_tpu.ops.preprocess import normalize_image

BATCH = 8
HW = (512, 512)


def make_case(model_cls, batch=BATCH, with_post=True, variant="n",
              **model_kw):
    model = model_cls(num_classes=2, variant=variant, **model_kw)
    rng = np.random.default_rng(0)
    frames = jnp.asarray(
        rng.integers(0, 255, (batch, *HW, 3)).astype(np.float32)
    )
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *HW, 3)))

    def step(tok):
        x = normalize_image(frames + tok * 0.0, "yolo")
        heads = model.apply(variables, x, train=False)
        if not with_post:
            return (
                tok * 0.5
                + sum(jnp.sum(h) for h in heads).astype(jnp.float32) * 1e-9
            )
        pred = YoloV5.decode(model, heads)
        dets, valid = extract_boxes(pred, conf_thresh=0.3, iou_thresh=0.45)
        return (jnp.sum(valid) + jnp.sum(dets) * 1e-12).astype(jnp.float32)

    return step, batch


def main():
    inner = 25
    wanted = sys.argv[1:] or [
        "base", "s2d", "minch32", "headless", "b16",
    ]
    factories = {
        "base": lambda: make_case(YoloV5),
        "s2d": lambda: make_case(YoloV5, s2d=True),
        "minch32": lambda: make_case(YoloV5, ch_floor=32),
        "s2d_minch32": lambda: make_case(YoloV5, s2d=True, ch_floor=32),
        "headless": lambda: make_case(YoloV5, with_post=False),
        "b1": lambda: make_case(YoloV5, batch=1),
        "b16": lambda: make_case(YoloV5, batch=16),
        # model-size MFU scaling (the "95% idle" diagnosis): the n
        # variant is 21 GFLOP/b8-call against a 197 TFLOP/s MXU — if
        # MFU rises with s/m/l at the same batch, the idle time is the
        # MODEL's arithmetic intensity, not the framework's dispatch
        "v5s": lambda: make_case(YoloV5, variant="s", dtype=jnp.bfloat16),
        "v5m": lambda: make_case(YoloV5, variant="m", dtype=jnp.bfloat16),
        "v5l": lambda: make_case(YoloV5, variant="l", dtype=jnp.bfloat16),
        "v5m_b32": lambda: make_case(
            YoloV5, variant="m", batch=32, dtype=jnp.bfloat16
        ),
        # the peak-per-chip A/B: run `... b64 b64_mxu_bf16`
        "b64": lambda: make_case(YoloV5, batch=64),
        "b64_mxu_bf16": lambda: make_case(
            YoloV5, batch=64, s2d=True, ch_floor=32, dtype=jnp.bfloat16
        ),
    }
    cases = []
    units = {}
    flops = {}
    nbytes = {}
    for name in wanted:
        step, batch = factories[name]()
        print(f"compiling {name} ...", flush=True)
        looped = compile_looped(step, inner)
        cases.append((name, looped))
        units[name] = batch
        try:
            cost = looped.lower(jnp.float32(0.0)).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            # XLA's cost model counts the fori_loop BODY once (verified
            # against the bench's single-step flops for the base
            # config), so no division by the trip count
            flops[name] = float(cost.get("flops", 0.0))
            nbytes[name] = float(cost.get("bytes accessed", 0.0))
        except Exception:
            flops[name] = 0.0
            nbytes[name] = 0.0
    out = run_trials(cases, inner=inner, trials=8)
    # the live device's bf16 MXU peak (fp32 runs the MXU at bf16 rate
    # under jax's default precision) — single source of truth in
    # obs.roofline; a device it does not list has no MFU
    peak = peak_flops("bf16")
    if peak is None:
        raise SystemExit("no peaks listed for this device: nothing to divide by")
    print("\n== results ==")
    for name, ms in out.items():
        fps = units[name] / (ms / 1e3)
        mfu = flops[name] / (ms / 1e3) / peak if flops.get(name) else 0.0
        roof = classify(
            flops.get(name, 0.0), nbytes.get(name, 0.0),
            precision="bf16", batch=units[name],
        )
        ceiling = (
            f"  {roof.bound:9s} ceil={roof.attainable_fps:9.1f} fps"
            f"  I={roof.intensity:6.1f} flop/B"
            if roof.bound != "unknown" else ""
        )
        print(
            f"{name:10s} {ms:7.3f} ms/call  {fps:8.1f} fps  mfu={mfu:.4f}"
            f"{ceiling}",
            flush=True,
        )


if __name__ == "__main__":
    main()
