"""Precision-policy sweep for the serving stack (round 10).

The served models are HBM-bandwidth-bound (a few percent MFU), so
runtime/precision.py moves fewer bytes per call: bf16
params+wire, int8 weight-only, int8 weights+activations. This harness
is the policy x batch grid over ONE pipeline (yolov5n by default):

  * ``per_chip_frames_per_sec`` — the chip-side device program (the
    jitted device_fn, batched input resident in HBM in the WIRE dtype,
    int8 wire dequantized in-body exactly like the serving launcher),
    the number the BENCH ``*_per_chip`` rows carry. Measured with the
    perf/_harness token-chained looped jit, which amortizes the
    per-dispatch host cost a bare per-call fence would add;
  * ``e2e_frames_per_sec`` — through the serving channel from host
    numpy (stage -> launch -> readback), so the bf16/int8 WIRE savings
    show up (the wire cast halves/quarters the H2D bytes);
  * ``param_bytes`` / ``hbm_param_mb`` — post-cast parameter footprint
    (the collector's param_bytes gauge; bf16 halves it, int8 quarters);
  * ``flops_per_frame`` / ``mfu`` — from the compiled executable's own
    cost analysis, against the PEAK OF THE POLICY DTYPE (f32/bf16/int8w
    share the bf16 MXU peak — int8w dequantizes to f32 compute — and
    full int8 runs the 2x int8 MAC path);
  * ``map_vs_f32`` / ``parity_ok`` — synthetic-set detection parity:
    the f32 pipeline's detections become ground truth and every policy
    must hold mAP@0.5:0.95 >= 1 - its declared budget
    (runtime/precision.py _MAP_BUDGETS; tests/test_precision.py
    enforces the same contract in CI);
  * ``speedup_vs_f32`` — per-chip fps over the same-batch f32 row (the
    acceptance check: bf16 must land measurably above the f32 row
    on real hardware).

int8 rows run the full calibration pass first (policy.calibrated over
the synthetic frames) so activation wire-quantization is live, exactly
like a production registration.

Usage: python perf/profile_precision.py [--hw 512] [--batches 8,32]
       [--policies f32,bf16,int8w,int8] [--frames 8] [--conf 0.05]
"""

from __future__ import annotations

import argparse
import json
import statistics
import time


def _median_ms(fn, trials: int = 5) -> float:
    fn()  # warm
    acc = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fn()
        acc.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(acc)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--hw", type=int, default=512,
                   help="square input size for yolov5n")
    p.add_argument("--batches", default="8",
                   help="comma-separated device batch sizes")
    p.add_argument("--policies", default="f32,bf16,int8w,int8")
    p.add_argument("--frames", type=int, default=8,
                   help="synthetic eval frames for calibration + parity")
    p.add_argument("--conf", type=float, default=0.05,
                   help="detection confidence threshold (low: random or "
                   "lightly-trained weights must still emit boxes for "
                   "the parity check to bite)")
    p.add_argument("--rounds", type=int, default=4,
                   help="e2e requests per timed trial")
    p.add_argument("--inner", type=int, default=8,
                   help="device_fn iterations per looped-jit dispatch "
                   "(amortizes the per-dispatch host cost)")
    args = p.parse_args(argv)

    from _harness import timed  # repo-path + compilation-cache bootstrap

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_client_tpu.channel import InferRequest, TPUChannel
    from triton_client_tpu.eval.detection_map import DetectionEvaluator
    from triton_client_tpu.pipelines.detect2d import (
        Detect2DConfig,
        build_yolov5_pipeline,
    )
    from triton_client_tpu.runtime.precision import (
        POLICIES,
        PrecisionPolicy,
    )
    from triton_client_tpu.runtime.repository import ModelRepository

    # the live device's peak per policy (obs.roofline: f32/bf16/int8w
    # run the MXU at the bf16 rate, full int8 at 2x); None off-table
    from triton_client_tpu.obs.roofline import peak_flops

    peak = {pol: peak_flops(pol) for pol in ("f32", "bf16", "int8w", "int8")}

    hw = (args.hw, args.hw)
    batches = [int(b) for b in args.batches.split(",") if b]
    policies = [s.strip() for s in args.policies.split(",") if s.strip()]
    unknown = set(policies) - set(POLICIES)
    if unknown:
        raise SystemExit(f"unknown policies {sorted(unknown)}")

    rng = np.random.default_rng(0)
    eval_frames = rng.integers(
        0, 255, (args.frames, *hw, 3)
    ).astype(np.float32)

    cfg = Detect2DConfig(
        model_name="yolov5_prec", input_hw=hw, num_classes=2,
        conf_thresh=args.conf,
    )

    def build(policy):
        return build_yolov5_pipeline(
            jax.random.PRNGKey(0), variant="n", num_classes=2,
            input_hw=hw, config=cfg, precision=policy,
        )

    # f32 reference: its detections on the synthetic set ARE the ground
    # truth every other policy is scored against
    ref_pipe, _, _ = build("f32")
    ref_dets, ref_valid = ref_pipe.infer(eval_frames)
    gts = [
        d[v.astype(bool)][:, [0, 1, 2, 3, 5]]
        for d, v in zip(ref_dets, ref_valid)
    ]
    n_ref = int(np.asarray(ref_valid).sum())
    # the attainable ceiling: f32 scored against its own detections
    # lands slightly under 1.0 (AP interpolation over tied
    # confidences), so budget floors are RELATIVE to this self-score,
    # not an absolute 1.0 (tests/test_precision.py uses the same form)
    self_eval = DetectionEvaluator()
    for d, v, gt in zip(ref_dets, ref_valid, gts):
        self_eval.add_frame(d, v, gt)
    ref_map = self_eval.summary()["map"]
    print(json.dumps({
        "note": "f32 reference detections as synthetic ground truth",
        "frames": args.frames, "boxes": n_ref, "conf_thresh": args.conf,
        "f32_self_map": round(float(ref_map), 4),
    }), flush=True)

    base_fps: dict[int, float] = {}
    for name in policies:
        policy = PrecisionPolicy.parse(name)
        if policy.quantize_acts:
            # the production registration order: calibrate activation
            # scales over the synthetic set, then build with the
            # calibrated policy so the int8 wire path is live
            policy = policy.calibrated({"images": eval_frames})
        pipe, spec, _ = build(policy)

        # accuracy parity first (cheap; the budget gate)
        evaluator = DetectionEvaluator()
        dets, valid = pipe.infer(eval_frames)
        for d, v, gt in zip(dets, valid, gts):
            evaluator.add_frame(d, v, gt)
        mean_ap = evaluator.summary()["map"]
        budget = pipe.precision.map_budget
        parity_ok = mean_ap >= ref_map - budget if n_ref else None

        repo = ModelRepository()
        repo.register(
            spec, pipe.infer_fn(), device_fn=pipe.device_fn(),
            precision=pipe.precision,
        )
        chan = TPUChannel(repo)
        raw_fn = pipe.device_fn()
        wire_policy = pipe.precision
        # the serving launcher's body: int8 wire inputs dequantize
        # inside the jit (channel/staged.py _device_body)
        body = (
            (lambda inputs: raw_fn(wire_policy.ingest(inputs)))
            if wire_policy.wire_ingest_needed
            else raw_fn
        )

        for batch in batches:
            frames = rng.integers(0, 255, (batch, *hw, 3)).astype(
                np.float32
            )
            # HBM-resident input in the wire dtype, as the channel
            # would have staged it (bf16 halves it, int8 quarters it)
            dev_in = {
                "images": jnp.asarray(
                    wire_policy.wire_cast("images", frames)
                )
            }

            def one(tok):
                # zero-valued token add: keeps every iteration
                # data-dependent on the loop so XLA cannot hoist the
                # model call, without changing the input values
                staged = {
                    k: v + (tok * 0).astype(v.dtype)
                    for k, v in dev_in.items()
                }
                out = body(staged)
                acc = jnp.float32(0)
                for v in out.values():
                    acc = acc + jnp.sum(v).astype(jnp.float32) * 1e-9
                return tok * 0.5 + acc

            t_dev_ms = timed(
                f"{name}_b{batch} device_fn", one,
                inner=args.inner, trials=5,
            )
            per_chip = batch / (t_dev_ms / 1e3)

            req = InferRequest(spec.name, {"images": frames})

            def e2e():
                futs = [
                    chan.do_inference_async(
                        InferRequest(spec.name, {"images": frames})
                    )
                    for _ in range(args.rounds)
                ]
                for f in futs:
                    f.result()

            chan.do_inference(req)  # warm the wire shape
            wall_ms = _median_ms(e2e, trials=3)

            flops = None
            try:
                cost = (
                    jax.jit(body)
                    .lower(dev_in).compile().cost_analysis()
                )
                if cost and cost.get("flops"):
                    flops = float(cost["flops"]) / batch
            except Exception:
                pass
            base_fps.setdefault(batch, per_chip if name == "f32" else 0.0)
            row = {
                "case": f"yolov5n_{args.hw}_{name}_b{batch}",
                "precision": name,
                "batch": batch,
                "per_chip_frames_per_sec": round(per_chip, 2),
                "e2e_frames_per_sec": round(
                    args.rounds * batch / (wall_ms / 1e3), 2
                ),
                "device_exec_ms": round(t_dev_ms, 2),
                "param_bytes": spec.extra.get("param_bytes"),
                "hbm_param_mb": round(
                    (spec.extra.get("param_bytes") or 0) / 1e6, 2
                ),
                "map_vs_f32": round(float(mean_ap), 4),
                "map_budget": budget,
                "parity_ok": parity_ok,
                "speedup_vs_f32": (
                    round(per_chip / base_fps[batch], 3)
                    if base_fps.get(batch) else None
                ),
            }
            if flops:
                row["flops_per_frame"] = flops
                if peak[name]:  # no listed peak (CPU smoke): no MFU
                    row["mfu"] = round(flops * per_chip / peak[name], 4)
            print(json.dumps(row), flush=True)
            if parity_ok is False:
                raise SystemExit(
                    f"{name}: mAP {mean_ap:.4f} under the declared "
                    f"budget floor {ref_map - budget:.4f} vs f32"
                )


if __name__ == "__main__":
    main()
