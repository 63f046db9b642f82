"""SLO capacity search: max offered qps at p99 <= SLO, per precision.

The MLPerf-Inference server-scenario headline for this serving stack
(ROADMAP item 3's "millions of users" turned into a measured number):
an OPEN-LOOP, seeded-Poisson, coordinated-omission-safe load drive
(`utils/loadgen.run_open_loop`) binary-searched over offered rate
until p99 sits at the SLO boundary. The closed-loop perf scripts
(profile_serving.py) answer "how fast can N polite clients go"; this
one answers the production question — "how much traffic can I accept
and still keep my latency promise" — which is the denominator every
later scaling PR (ragged batching, router, multi-host) is judged by.

Alongside the capacity number the script cross-checks the SLO
observability ring itself:

  * server-side p50/p99 per stage read from the collector's histogram
    snapshot (the same path /metrics exports) next to the client-side
    open-loop percentiles;
  * histogram-vs-span reconciliation: the (model, e2e) histogram count
    must equal the traces finished, and mean span coverage must hold
    the >=95% PR-2 gate — the "histogram stage sums reconcile with
    span wall-coverage" acceptance check.

Usage:
    python perf/profile_slo.py                   # yolov5n f32, auto SLO
    python perf/profile_slo.py --slo-ms 250
    python perf/profile_slo.py --precision bf16 --duration 4
"""

import argparse
import json
import sys

import _harness  # noqa: F401  (sys.path bootstrap)
import numpy as np

import jax

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer
from triton_client_tpu.utils.loadgen import run_open_loop, slo_capacity_search

HW = (512, 512)
MAX_BATCH = 8


def build_repo(precision: str):
    policy = None
    if precision and precision != "f32":
        from triton_client_tpu.runtime.precision import PrecisionPolicy

        policy = PrecisionPolicy.parse(precision)
        if policy.quantize_acts:
            # production registration order: calibrate activation
            # scales before building, so the int8 wire path is live
            rng = np.random.default_rng(0)
            calib = rng.integers(0, 255, (8, *HW, 3)).astype(np.float32)
            policy = policy.calibrated({"images": calib})
    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=HW,
        precision=policy,
    )
    repo = ModelRepository()
    repo.register(
        spec, pipe.infer_fn(), device_fn=pipe.device_fn(),
        precision=getattr(pipe, "precision", None),
    )
    return repo, spec


def serve_and_search(args) -> dict:
    repo, spec = build_repo(args.precision)
    inner = TPUChannel(repo)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, (1, *HW, 3)).astype(np.uint8)
    for k in (1, 2, 4, MAX_BATCH):
        print(f"precompile b{k}", file=sys.stderr, flush=True)
        inner.do_inference(
            InferRequest(
                model_name=spec.name,
                inputs={"images": np.repeat(frame, k, axis=0)},
            )
        )
    batching = ContinuousBatchingChannel(inner, max_batch=MAX_BATCH)
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", max_workers=16,
        metrics_port="auto", slo_ms=args.slo_ms or 0.0,
    )
    server.start()
    addr = f"127.0.0.1:{server.port}"
    scenarios = [(spec.name, {"images": frame})]
    try:
        slo_ms = args.slo_ms
        if not slo_ms:
            # auto-SLO: 3x the lightly-loaded p50 — honest on any rig
            # (a fixed wall-clock SLO would read 0 capacity over a
            # ~100 ms WAN link and hide regressions on fast hosts)
            calib = run_open_loop(
                addr, scenarios, rate_qps=4.0, duration_s=3.0,
                seed=args.seed, deadline_s=120.0,
            )
            p50 = calib.percentile(50.0)
            if p50 == float("inf"):
                raise RuntimeError(
                    f"calibration window served nothing: {calib.errors[:3]}"
                )
            slo_ms = max(10.0, 3.0 * p50)
            print(f"auto SLO: p50={p50:.1f} ms -> slo={slo_ms:.1f} ms",
                  file=sys.stderr, flush=True)
            # arm the live tracker so the server-side attainment view
            # in the report scores the search traffic too
            if server.slo is not None:
                server.slo.set_budget(slo_ms)
        result = slo_capacity_search(
            addr, scenarios, slo_ms=slo_ms, duration_s=args.duration,
            seed=args.seed, qps_lo=args.qps_lo, qps_hi=args.qps_hi,
        )
        # server-side view through the SAME snapshot path /metrics uses
        snap = server.collector.snapshot()
        from triton_client_tpu.obs.histogram import quantile_from_snapshot

        hists = snap.get("histograms") or {}
        stage_view = {}
        for key, h in hists.items():
            model, _, stage = key.partition("|")
            if model != spec.name:
                continue
            stage_view[stage] = {
                "count": h["count"],
                "sum_s": round(h["sum"], 3),
                "p50_ms": round(quantile_from_snapshot(h, 0.5) * 1e3, 3),
                "p99_ms": round(quantile_from_snapshot(h, 0.99) * 1e3, 3),
            }
        # reconciliation: every finished trace must have landed one e2e
        # histogram sample, and span coverage must hold the PR-2 gate
        finished = (snap.get("tracer") or {}).get("finished", 0)
        e2e_count = stage_view.get("e2e", {}).get("count", 0)
        coverage = [
            t.span_coverage() for t in server.tracer.recent(0)
        ] if server.tracer is not None else []
        mean_cov = float(np.mean(coverage)) if coverage else 0.0
        result.update(
            model=spec.name,
            precision=args.precision or "f32",
            server_stages=stage_view,
            traces_finished=finished,
            e2e_histogram_count=e2e_count,
            histogram_trace_reconciled=bool(finished == e2e_count),
            mean_span_coverage=round(mean_cov, 4),
            slo=snap.get("slo"),
        )
        return result
    finally:
        server.stop()
        batching.close()


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--precision", default="", choices=["", "f32", "bf16", "int8w", "int8"])
    p.add_argument("--slo-ms", type=float, default=0.0,
                   help="latency SLO (0 = auto: 3x lightly-loaded p50)")
    p.add_argument("--duration", type=float, default=5.0,
                   help="seconds per search probe")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--qps-lo", type=float, default=1.0)
    p.add_argument("--qps-hi", type=float, default=512.0)
    args = p.parse_args()
    result = serve_and_search(args)
    print(json.dumps(result, indent=2, default=str), flush=True)
    if not result["histogram_trace_reconciled"]:
        print("WARN: e2e histogram count != traces finished",
              file=sys.stderr, flush=True)
    if result["mean_span_coverage"] < 0.95:
        print(f"WARN: mean span coverage "
              f"{result['mean_span_coverage']:.3f} < 0.95",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
