"""Benchmark: fused perception pipelines on one TPU chip.

Prints ONE JSON line (the driver's contract): the primary metric is the
YOLOv5n 512x512 fused end-to-end pipeline. Secondary metrics (bf16,
batch-64, PointPillars, SECOND-IoU, CenterPoint 10-sweep) go to stderr
and BENCH_LOCAL.json.

Methodology:

* Every timed call is CHAINED through a scalar token computed from the
  full output, so successive calls cannot overlap or be elided, and a
  float() readback forces completion.
* Throughput trials run the chained rep-loop INSIDE one jit
  (lax.fori_loop): one dispatch and one readback per trial, so the
  per-dispatch host cost is amortized; per-request latency (which
  legitimately pays the dispatch) is reported separately from
  single-dispatch calls.
* Configs are INTERLEAVED round-robin (A/B/A/B...) and the reported
  value is the median across trials, so a slow phase of the host hits
  all configs equally instead of biasing one.
* MFU is derived from the compiled executable's own FLOP count
  (cost_analysis) against the peak obs/roofline.py lists for the
  device_kind jax reports. NOTE: jax's default matmul precision on TPU
  feeds the MXU bf16 inputs with f32 accumulation even for f32 arrays,
  so fp32 and bf16 model dtypes run the MXU at the same rate — the
  honest peak for both is the bf16 peak.

This file runs on a TPU or not at all: ``main`` prints the device as
its first stdout line and exits non-zero on any other backend, and a
phase that fails is reported again at the end and makes the exit code
1 — later phases still run, but a run with a failed phase never ends
in 0. Which of the fences above a machine that holds its own TPU still
needs is the benchmark PR's question (ROADMAP A0/C6), not settled here.

The reference publishes no numbers; its serving path is one blocking
gRPC round-trip per frame to a remote Triton GPU. vs_baseline remains
anchored to the real-time sensor rates its ROS pipelines must sustain
(30 fps camera / 10 Hz lidar, SURVEY.md section 3.1) — a deployment
headroom ratio, not a hardware comparison; p50/p99/MFU are the
hardware-meaningful numbers.

Budget discipline: a run once timed out (rc=124) with zero rows
because all emission waited for the full run. Now the run schedules
itself against ``BENCH_BUDGET_S`` wall-clock: configs build
and warm lazily in value order and are SKIPPED (stderr note) when
their estimated warmup no longer fits; trials stop early at
>= MIN_TRIALS rounds; every row prints the moment it exists; a SIGTERM
flushes whatever has >= 3 trial samples. The persistent compilation
cache (utils/compilation_cache.py) turns the fresh warmup bill into
seconds for every later run that finds the same cache directory.
"""

import json
import os
import signal
import statistics
import sys
import time

from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

enable_persistent_cache()  # before any jax compile

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 8
TRIALS = 10          # interleaved rounds per config (r1-r4: 12 — the
                     # per-call medians moved <0.5% between 10 and 12
                     # rounds at r4 spreads of 0.005-0.03, and the two
                     # rounds buy ~25 s for the serving stage)
MIN_TRIALS = 6       # fewest rounds a budget squeeze may cut to
REPS = 25            # chained dispatches per trial
LAT_CALLS = 20       # single-call latency samples (readback per call)
# warmup-scheduler reserve for the serving stage (VERDICT r3 #2):
# every secondary admission, the trial loop's early stop, and the
# primary-extras gate all leave this much for the serving rows (r5:
# when only the b64 tails carried the reserve at admission, the delta
# rows and trial rounds ran right through it and serving starved).
# Admission stays value-ordered greedy: the b64 peak is considered
# before the delta rows and degrades to a shortened provisional block
# when the full protocol no longer fits (that block still costs its
# warmup, which can squeeze later admissions — the deliberate trade:
# the peak row outranks everything below it); a config shed OUTRIGHT
# never blocks later, cheaper rows. 280 (not 170): with 170 the delta
# rows were admitted on optimistic warmup estimates and left the
# serving gate ~40 s short twice — the reserve must absorb one
# mis-estimated warmup, not just the serving windows themselves.
SERVING_RESERVE_S = 280.0

# The serving stage's own envelope — the thing SERVING_RESERVE_S exists
# to protect. The start gate and the window sizing both derive from
# these (the gate used to hardcode 170, the OLD reserve value, and
# silently drifted when the reserve was retuned to 280):
SERVING_TAIL_S = 120.0      # merge-size precompiles + row-flush slack
SERVING_MIN_WINDOW_S = 15.0  # floor per transport window (~20 batches)
SERVING_MAX_WINDOW_S = 60.0
# cheapest viable stage: the tail plus one minimum window per transport
# row (5 rows: grpc/shm/uds/stream_b8 + the 3D row) — below this the
# window formula would bottom out under its own floor, so don't start
# at all
SERVING_FLOOR_S = SERVING_TAIL_S + 5 * SERVING_MIN_WINDOW_S
assert SERVING_FLOOR_S < SERVING_RESERVE_S

# Wall-clock budget (VERDICT r3 #1): one run shows the driver's
# clock ran out with 902 s of warmups + 8 trial rounds + a setup phase
# (10 config builds + NMS gate) on the books — i.e. the external cap
# is at least ~1,050 s but its exact value is unknown. 1,020 stays
# BELOW that observed floor while still fitting the full warm-cache
# run with shortened serving windows; every headline row is out by
# ~T+700 regardless, and the SIGTERM flush covers a cap landing in
# the serving tail. Everything after setup is scheduled against it:
# warmups are ordered by value-per-second and skipped (with a stderr
# note) when they no longer fit, trials stop early at >= MIN_TRIALS,
# and rows are emitted the moment they exist.
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1020"))
T_START = time.perf_counter()


def _remaining() -> float:
    return BUDGET_S - (time.perf_counter() - T_START)


def _load_flops_sidecar() -> dict:
    try:
        with open("BENCH_FLOPS.json") as f:
            return dict(json.load(f))
    except Exception:
        return {}


# metric -> {"flops", "bytes"} per call, persisted across runs (see
# Config.warmup). Entries were plain flops floats before the roofline
# round; _sidecar_cost loads both forms.
_FLOPS_SIDEBAR = _load_flops_sidecar()


def _sidecar_cost(key: str) -> tuple[float, float]:
    """(flops, bytes) per call from a sidecar entry (0.0 = unknown)."""
    entry = _FLOPS_SIDEBAR.get(key)
    if isinstance(entry, dict):
        return (
            float(entry.get("flops", 0.0) or 0.0),
            float(entry.get("bytes", 0.0) or 0.0),
        )
    if entry:
        return float(entry), 0.0
    return 0.0, 0.0


def _save_flops_sidecar() -> None:
    try:
        with open("BENCH_FLOPS.json", "w") as f:
            json.dump(_FLOPS_SIDEBAR, f, indent=1, sort_keys=True)
    except OSError as e:
        print(f"could not write BENCH_FLOPS.json: {e}", file=sys.stderr)
CAMERA_FPS_BASELINE = 30.0
LIDAR_HZ_BASELINE = 10.0  # KITTI/nuScenes lidar scan rate
# Per-chip peaks live in obs/roofline.py — ONE table for bench MFU,
# served MFU, and the roofline ceiling (it keeps the per-policy MXU
# rationale: f32/bf16/int8w execute matmuls at the bf16 peak under
# jax's default precision, full int8 runs the int8 MAC path at 2x).
from triton_client_tpu.obs.roofline import (  # noqa: E402
    classify as roofline_classify,
    device_info,
    peak_flops,
)


class Config:
    """One benchmarked pipeline: ``one(tok) -> tok`` chains the full
    pipeline through a scalar token. Throughput runs ``reps`` chained
    iterations inside ONE jitted fori_loop dispatch; latency uses the
    single-step jit (a real per-request dispatch).

    ``reps`` scales with the pipeline so every trial's timed compute is
    ~1 s: with the default 25, a fast config's 0.2 s trial was the same
    order as the host's dispatch jitter, and the trial spread measured
    the host, not the chip — amortizing each dispatch over ~1 s of
    chip work pushes that noise down an order of magnitude."""

    def __init__(self, name, metric, one, unit_per_call, baseline_hz,
                 reps=REPS, precision="f32", fused_stages=()):
        self.name = name
        self.metric = metric
        self.one = one
        self.precision = precision  # serving policy the row ran under
        # which Pallas fusions the row's pipeline routed (ops/fused
        # resolution at build time; [] = pure XLA reference path) —
        # bench_diff readers need the column to know WHICH route a
        # round's number measured
        self.fused_stages = tuple(fused_stages)
        self.reps = reps
        self.step = jax.jit(one)          # single-dispatch form (latency)
        self.looped = jax.jit(
            lambda tok: jax.lax.fori_loop(0, reps, lambda i, t: one(t), tok)
        )
        self.unit_per_call = unit_per_call  # frames (batch) or scans per call
        self.baseline_hz = baseline_hz
        self.trial_ms = []                # per-call ms, one entry per trial
        self.flops_per_call = None
        self.bytes_per_call = None

    def warmup(self):
        tok = jnp.float32(0.0)
        float(self.looped(tok))
        float(self.step(jnp.float32(0.0)))
        # FLOP count: the sidecar (BENCH_FLOPS.json, keyed by metric)
        # spares the cost_analysis retrace+compile (~10-30 s/config of
        # pure warmup bill) on every run after the first; a config
        # whose flops change (model edit) just needs the sidecar entry
        # deleted — or delete the file to re-derive everything
        cached_flops, cached_bytes = _sidecar_cost(self.metric)
        if cached_flops and cached_bytes:
            self.flops_per_call = cached_flops
            self.bytes_per_call = cached_bytes
            return
        try:
            cost = self.step.lower(jnp.float32(0.0)).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost and cost.get("flops"):
                self.flops_per_call = float(cost["flops"])
                self.bytes_per_call = float(
                    cost.get("bytes accessed", 0.0) or 0.0
                )
                _FLOPS_SIDEBAR[self.metric] = {
                    "flops": self.flops_per_call,
                    "bytes": self.bytes_per_call,
                }
                # persist per-config: a timeout mid-warmup (the exact
                # failure this cache targets) must not lose the
                # entries already derived
                _save_flops_sidecar()
        except Exception:
            pass  # cost analysis is best-effort
        if self.flops_per_call is None and cached_flops:
            # legacy flops-only sidecar entry and no fresh measurement:
            # MFU still computes, the roofline columns wait for bytes
            self.flops_per_call = cached_flops

    def run_trial(self):
        tok = jnp.float32(0.0)
        t0 = time.perf_counter()
        tok = self.looped(tok)  # self.reps chained calls, ONE dispatch
        float(tok)
        self.trial_ms.append((time.perf_counter() - t0) * 1e3 / self.reps)

    def latency_profile(self):
        """Per-request e2e latency: one forced readback per call."""
        samples = []
        tok = jnp.float32(0.0)
        for _ in range(LAT_CALLS):
            t0 = time.perf_counter()
            tok = self.step(tok)
            float(tok)
            samples.append((time.perf_counter() - t0) * 1e3)
        return samples

    def result(self, with_latency: bool = True) -> dict:
        """``with_latency=False`` computes the row from trial samples
        alone (pure numpy, no device calls) — the form the SIGTERM
        flush uses, where a jax dispatch could deadlock."""
        per_call_ms = statistics.median(self.trial_ms)
        # trimmed spread (p90-p10)/median: a host stall lands in a
        # single trial and makes the max-min spread useless for run-
        # over-run comparison; the median value itself is robust
        spread = (
            float(np.percentile(self.trial_ms, 90))
            - float(np.percentile(self.trial_ms, 10))
        ) / per_call_ms
        rate = self.unit_per_call / (per_call_ms / 1e3)
        lat = self.latency_profile() if with_latency else []
        out = {
            "metric": self.metric,
            "value": round(rate, 2),
            "unit": ("frames/sec" if self.unit_per_call > 1 else "scans/sec"),
            "vs_baseline": round(rate / self.baseline_hz, 2),
            "per_call_ms": round(per_call_ms, 4),
            "p50_e2e_ms": (
                round(float(np.percentile(lat, 50)), 3) if lat else None
            ),
            "p99_e2e_ms": (
                round(float(np.percentile(lat, 99)), 3) if lat else None
            ),
            "trial_spread": round(spread, 3),
            "trials": len(self.trial_ms),
            "precision": self.precision,
            "fused_stages": list(self.fused_stages),
        }
        if self.flops_per_call:
            # MFU against the peak of the dtype the row actually ran
            # on THIS device_kind (main refuses a device with no peak)
            out["flops_per_call"] = self.flops_per_call
            out["mfu"] = round(
                self.flops_per_call
                / (per_call_ms / 1e3)
                / peak_flops(self.precision),
                4,
            )
            if self.bytes_per_call:
                # roofline placement: measured intensity vs the machine
                # knee, the binding ceiling, and the attainable rate if
                # only that ceiling bound (obs/roofline.py)
                roof = roofline_classify(
                    self.flops_per_call, self.bytes_per_call,
                    self.precision, batch=int(self.unit_per_call),
                )
                out["bytes_per_call"] = self.bytes_per_call
                out["arithmetic_intensity"] = round(roof.intensity, 2)
                out["roofline_bound"] = roof.bound
                out["attainable_fps"] = round(roof.attainable_fps, 2)
                if roof.attainable_fps > 0:
                    out["roofline_attained_ratio"] = round(
                        rate / roof.attainable_fps, 6
                    )
        return out


def make_yolov5(dtype=None, batch=BATCH, mxu=False) -> Config:
    from triton_client_tpu.models.yolov5 import init_yolov5
    from triton_client_tpu.ops.detect_postprocess import extract_boxes
    from triton_client_tpu.ops.fused import fused_interpret, resolve_fused_stages
    from triton_client_tpu.ops.preprocess import normalize_image

    input_hw = (512, 512)
    model, variables = init_yolov5(
        jax.random.PRNGKey(0), num_classes=2, variant="n", input_hw=input_hw,
        dtype=dtype or jnp.float32,
        s2d=mxu, ch_floor=32 if mxu else 0,
    )
    rng = np.random.default_rng(0)
    frames = jnp.asarray(
        rng.integers(0, 255, (batch, *input_hw, 3)).astype(np.float32)
    )
    # same trace-time routing the served pipeline uses: fused decode+NMS
    # tail on a real TPU (ISSUE 16), reference chain elsewhere — the
    # row's fused_stages column records which route the number measured
    fused_stages = resolve_fused_stages("auto", ("decode_nms",))

    def step(tok):
        x = normalize_image(frames + tok * 0.0, "yolo")
        pred = model.decode(model.apply(variables, x, train=False))
        dets, valid = extract_boxes(
            pred, conf_thresh=0.3, iou_thresh=0.45,
            fused="decode_nms" in fused_stages,
            interpret=fused_interpret(),
        )
        # token depends on every output row -> readback fences the call
        return (jnp.sum(valid) + jnp.sum(dets) * 1e-12).astype(jnp.float32)

    suffix = (
        ("_mxu" if mxu else "")
        + ("_bf16" if dtype == jnp.bfloat16 else "")
        + (f"_b{batch}" if batch != BATCH else "")
    )
    return Config(
        f"yolov5n{suffix}",
        f"yolov5n_512{suffix}_e2e_frames_per_sec_per_chip",
        step, batch, CAMERA_FPS_BASELINE,
        # ~5-8 ms/call at b8: 120 chained reps ≈ 1 s of chip work per
        # dispatch; b64 runs ~18 ms/call so 50 reps lands in the same
        # regime
        reps=120 if batch == BATCH else 50,
        precision="bf16" if dtype == jnp.bfloat16 else "f32",
        fused_stages=fused_stages,
    )


def _structured_cloud(pc_range, n_target=120_000) -> np.ndarray:
    """Realistic-density synthetic scan (io/synthdata.py scene model):
    ground-plane clutter + surface-sampled objects with 1/r^2 return
    falloff. Real lidar concentrates returns near the sensor and on
    surfaces — uniform-random clouds have occupancy/collision patterns
    nothing like a scan, so 3D numbers are pinned on structured scenes
    (VERDICT r2 #6; the uniform config stays as a delta secondary)."""
    from triton_client_tpu.io.synthdata import synth_scene_frame

    rng = np.random.default_rng(0)
    pts, _ = synth_scene_frame(
        rng,
        pc_range=tuple(pc_range),
        n_objects=10,
        n_clutter=n_target - 4_000,
    )
    if len(pts) < n_target:
        # top up with extra ground clutter so structured-vs-uniform
        # configs compare the SAME point count, purely different
        # distributions
        extra = n_target - len(pts)
        x0, y0, _z0, x1, y1, _z1 = pc_range
        fill = np.stack(
            [
                rng.uniform(x0, x1, extra),
                rng.uniform(y0, y1, extra),
                rng.normal(-1.9, 0.05, extra),
                rng.uniform(0, 1, extra),
            ],
            axis=1,
        ).astype(np.float32)
        pts = np.concatenate([pts, fill])
    # shuffle before truncating: the object points are concatenated
    # last, and a tail cut must not preferentially delete objects
    return pts[rng.permutation(len(pts))[:n_target]]


def _make_3d(pipeline, point_budget, name, metric, cloud=None,
             structured=True, reps=REPS, fused_stages=()) -> Config:
    """Shared 3D config builder; ``cloud`` overrides the default
    synthetic KITTI-sized scan (CenterPoint passes its aggregated
    multi-sweep cloud) so the fencing-token step exists in ONE place."""
    from triton_client_tpu.ops.voxelize import pad_points

    if cloud is None and structured:
        cloud = _structured_cloud(pipeline.model.cfg.voxel.point_cloud_range)
    if cloud is None:
        rng = np.random.default_rng(0)
        n_pts = 120_000  # ~KITTI velodyne scan
        pc_range = pipeline.model.cfg.voxel.point_cloud_range
        cloud = np.empty((n_pts, 4), np.float32)
        cloud[:, 0] = rng.uniform(pc_range[0], pc_range[3], n_pts)
        cloud[:, 1] = rng.uniform(pc_range[1], pc_range[4], n_pts)
        cloud[:, 2] = rng.uniform(pc_range[2], pc_range[5], n_pts)
        cloud[:, 3] = rng.uniform(0, 1, n_pts)
    padded, m = pad_points(cloud, point_budget)
    pj, mj = jnp.asarray(padded), jnp.asarray(m)

    inner = pipeline._jit

    def step(tok):
        dets, valid = inner(pj + tok * 0.0, mj)
        return (jnp.sum(valid) + jnp.sum(dets) * 1e-12).astype(jnp.float32)

    return Config(name, metric, step, 1, LIDAR_HZ_BASELINE, reps=reps,
                  fused_stages=fused_stages)


def make_pointpillars(structured=True) -> Config:
    from triton_client_tpu.dataset_config import detect3d_from_yaml
    from triton_client_tpu.pipelines.detect3d import build_pointpillars_pipeline

    _, model_cfg, pipe_cfg = detect3d_from_yaml("data/kitti_pointpillars.yaml")
    pipeline, spec, _ = build_pointpillars_pipeline(
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=pipe_cfg
    )
    suffix = "" if structured else "_uniform"
    return _make_3d(
        pipeline, max(pipe_cfg.point_buckets), f"pointpillars{suffix}",
        f"pointpillars_kitti{suffix}_e2e_scans_per_sec_per_chip",
        structured=structured,
        reps=75,  # ~11 ms/scan -> ~0.8 s per dispatch
        fused_stages=spec.extra.get("fused_stages", []),
    )


def make_centerpoint() -> Config:
    """CenterPoint-pillar, nuScenes 10-sweep config
    (data/nusc_centerpoint.yaml): a 5-feature aggregated cloud
    (x, y, z, i, Δt) through the velocity-head pipeline."""
    import dataclasses

    from triton_client_tpu.dataset_config import detect3d_from_yaml
    from triton_client_tpu.ops.sweeps import aggregate_sweeps
    from triton_client_tpu.ops.voxelize import pad_points
    from triton_client_tpu.pipelines.detect3d import build_centerpoint_pipeline

    _, model_cfg, pipe_cfg = detect3d_from_yaml("data/nusc_centerpoint.yaml")
    pipe_cfg = dataclasses.replace(pipe_cfg, point_buckets=(131072,))
    pipeline, spec, _ = build_centerpoint_pipeline(
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=pipe_cfg
    )
    r = model_cfg.voxel.point_cloud_range
    sweeps, times = [], []
    for i in range(10):  # ~13k points/sweep -> ~131k aggregated
        # every sweep is a structured scene too (same rationale as
        # _structured_cloud; a static platform repeats the scene)
        sweeps.append(_structured_cloud(r, 13_000))
        times.append(-0.05 * i)
    cloud = aggregate_sweeps(sweeps, times=times)
    return _make_3d(
        pipeline, 131072, "centerpoint",
        "centerpoint_nusc_10sweep_e2e_scans_per_sec_per_chip",
        cloud=cloud,
        reps=75,  # ~11 ms/scan -> ~0.8 s per dispatch
        fused_stages=spec.extra.get("fused_stages", []),
    )


def make_second() -> Config:
    from triton_client_tpu.pipelines.detect3d import (
        Detect3DConfig,
        build_second_pipeline,
    )

    cfg = Detect3DConfig(model_name="second_iou")
    pipeline, spec, _ = build_second_pipeline(jax.random.PRNGKey(0), config=cfg)
    return _make_3d(
        pipeline, max(cfg.point_buckets), "second_iou",
        "second_iou_kitti_e2e_scans_per_sec_per_chip",
        reps=50,  # ~16 ms/scan -> ~0.8 s per dispatch
        fused_stages=spec.extra.get("fused_stages", []),
    )


def make_second_sparse() -> Config:
    """SECOND at the REFERENCE's 0.05 m spconv grid via the sparse
    submanifold encoder (ops/sparse_conv.py) — the grid the dense
    emulation cannot compile (5.4 GB volume)."""
    from triton_client_tpu.dataset_config import detect3d_from_yaml
    from triton_client_tpu.pipelines.detect3d import build_second_pipeline

    _, model_cfg, pipe_cfg = detect3d_from_yaml(
        "data/kitti_second_sparse005.yaml"
    )
    pipeline, spec, _ = build_second_pipeline(
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=pipe_cfg
    )
    return _make_3d(
        pipeline, max(pipe_cfg.point_buckets), "second_sparse005",
        "second_iou_sparse005_e2e_scans_per_sec_per_chip",
        fused_stages=spec.extra.get("fused_stages", []),
    )


def measure_serving(
    duration_s: float = 60.0,
    clients: int = 16,
    max_batch: int = 8,
    max_merge: int = 16,
    input_hw: tuple = (512, 512),
    on_row=None,
    precision: str = "f32",
) -> list:
    """Serving-path benchmark (VERDICT r2 #3): N concurrent gRPC
    clients on localhost against the KServe server + micro-batcher —
    the Triton-equivalent surface whose metrics ARE the reference's
    perf story (README.md:88-95). Four transports, one row each:

      * grpc      — stock KServe raw tensors over loopback TCP (what a
        remote client pays);
      * shm       — the system shared-memory extension (the same-host
        auto-negotiated default): request tensors travel as region
        coordinates and the 786 KB frame payload is one memcpy instead
        of a protobuf serialize/copy/deserialize in each process;
      * uds       — shm tensors with the control plane on a unix
        socket instead of loopback TCP;
      * stream_b8 — uds+shm through ModelStreamInfer with 8-frame
        groups: one message carries 8 packed frames, so the
        per-message protocol cost is paid once per group.

    The gap between any row and the in-process primary is the serving
    overhead; the gaps BETWEEN the rows decompose it (codec vs TCP vs
    per-message cost). Each row reports served fps, ``host_gap_ratio``
    (served fps / device ceiling — the headline the tentpole moves),
    request p50/p99, and the batcher's merge-size histogram, alongside
    the two environment probes (upload_mbps, direct_batch_ms) that
    dominate this rig. A mode that completes zero requests degrades to
    a value-0 row with the error note — the decomposition fields stay
    meaningful.

    Round 4 (VERDICT r3 #2): the batcher forms device batches at slot
    time with ``max_merge`` > admission size, power-of-two bucket
    padding, and a merge hold for burst coalescing; with the
    device-host-device bounce fixed the path serves ~15 fps on this
    rig, so even the budget-floor 15 s window resolves ~20 device
    batches (a 60 s window ~80). Each transport's row is surfaced via
    ``on_row`` the moment its window closes."""
    import collections
    import threading

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime.continuous import (
        ContinuousBatchingChannel,
    )
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer

    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=input_hw,
        precision=precision,
    )
    repo = ModelRepository()
    # multi-device rig: serve the whole mesh through the sharded
    # channel (batches split over the data axis, params replicated) so
    # the row carries a real aggregate_frames_per_sec; single-device
    # keeps the historical eager TPUChannel path so served rows stay
    # comparable across rounds
    data_axis = len(jax.devices())
    if data_axis > 1:
        from triton_client_tpu.channel.sharded_channel import (
            ShardedTPUChannel,
        )
        from triton_client_tpu.parallel.mesh import MeshConfig

        repo.register(
            spec, pipe.infer_fn(), device_fn=pipe.device_fn(),
            precision=pipe.precision,
        )
        inner = ShardedTPUChannel(repo, MeshConfig(data=data_axis, model=1))
    else:
        repo.register(spec, pipe.infer_fn(), precision=pipe.precision)
        inner = TPUChannel(repo)

    occupancy: collections.Counter = collections.Counter()
    occ_lock = threading.Lock()
    inner_infer = inner.do_inference

    device_call_s = []  # per-device-call wall (stall forensics)
    window_t0 = [0.0]   # calls STARTED before the current window are
                        # not its forensics: a wire-mode stall that
                        # finishes inside the shm window must not be
                        # attributed to shm (run_pool's straggler join
                        # means in-window stalls do land before the
                        # row is built; only a stall outliving the
                        # join deadline escapes the row entirely)

    def tapped(req):
        # batch forensics are leading-dim semantics for every request
        # shape: the first input tensor's leading dim is the batch (a
        # 3D single-scan request's (N, pf) points then count the
        # cloud-size bucket, not a silent 1 — r5's hard "images"
        # lookup KeyError'd the whole 3D row; a flat b=1 fallback
        # would misattribute a future batched-points request)
        arr = req.inputs.get("images")
        if arr is None and req.inputs:
            arr = next(iter(req.inputs.values()))
        shape = np.shape(arr) if arr is not None else ()
        b = int(shape[0]) if shape else 1
        with occ_lock:
            occupancy[b] += 1
        t0 = time.perf_counter()
        try:
            return inner_infer(req)
        finally:
            with occ_lock:
                if t0 >= window_t0[0]:
                    device_call_s.append(time.perf_counter() - t0)

    inner.do_inference = tapped

    rng = np.random.default_rng(0)
    # uint8 wire frames: the pipeline normalizes on device, so shipping
    # raw bytes quarters the wire + host->device upload vs the
    # reference's float32 tensors (its clients convert BEFORE the wire,
    # utils/preprocess.py image_adjust) — on this rig upload bandwidth
    # IS the serving ceiling (see upload_mbps in the result)
    frame = rng.integers(0, 255, (1, *input_hw, 3)).astype(np.uint8)
    # pre-compile every batch size the bucket-padding dispatcher can
    # produce: log2(max_merge)+1 power-of-two sizes, not every integer
    # (each compile is seconds to tens of seconds and must not land
    # inside the timed window)
    k = 1
    while k <= max_merge:
        inner_infer(
            InferRequest(
                model_name=spec.name,
                inputs={"images": np.repeat(frame, k, axis=0)},
            )
        )
        k *= 2

    # reference device-path cost for the SAME work: one max_merge
    # batch through the pipeline from host memory (pays the upload the
    # in-process configs don't) — the gap between this and the served
    # rate is the wire/codec/host-CPU stack
    direct = np.repeat(frame, max_merge, axis=0)
    pipe.infer(direct)  # warm
    t0 = time.perf_counter()
    for _ in range(3):
        pipe.infer(direct)
    direct_batch_ms = (time.perf_counter() - t0) / 3 * 1e3

    # dtype-correct FLOP accounting for the served rows (round 10):
    # derive per-frame FLOPs once from the compiled executable (sidecar
    # cached, same methodology as the e2e configs) so served mfu stops
    # being as-if-f32
    flops_key = f"served_yolov5n_{input_hw[0]}_{precision}_b{max_merge}"
    flops_per_frame, bytes_per_frame = _sidecar_cost(flops_key)
    if not (flops_per_frame and bytes_per_frame):
        try:
            cost = (
                pipe._jit.lower(jnp.asarray(direct), tuple(input_hw))
                .compile()
                .cost_analysis()
            )
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost and cost.get("flops"):
                flops_per_frame = float(cost["flops"]) / max_merge
                bytes_per_frame = (
                    float(cost.get("bytes accessed", 0.0) or 0.0) / max_merge
                )
                _FLOPS_SIDEBAR[flops_key] = {
                    "flops": flops_per_frame,
                    "bytes": bytes_per_frame,
                }
                _save_flops_sidecar()
        except Exception:
            pass  # best-effort

    # host->device upload bandwidth probe: the per-request transfer the
    # in-process configs never pay (device-resident inputs): PCIe on a
    # machine that holds its TPU
    blob = np.zeros((8, *input_hw, 3), np.uint8)
    jnp.asarray(blob).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        jnp.asarray(blob).block_until_ready()
        blob[0, 0, 0, 0] += 1  # defeat any caching
    up_s = (time.perf_counter() - t0) / 3
    upload_mbps = blob.nbytes / 1e6 / up_s

    # per-request deadline sized from the measured device path: the
    # whole client pool behind one dispatch queue, with 20x headroom
    # for host CPU contention (the r3 driver rig hit 120 s deadlines
    # at p50 17 s) — deadlines firing inside the window turn the row
    # into an error count instead of a rate
    deadline_s = max(180.0, direct_batch_ms / 1e3 * clients * 20)

    # windowless EDF admission, dense merges padded to live-occupancy
    # buckets (arrivals pool while device work is in flight)
    batching = ContinuousBatchingChannel(
        inner, max_batch=max_batch, max_merge=max_merge
    )
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", uds_address="auto",
        max_workers=clients + 8,
    )
    server.start()
    addr = f"127.0.0.1:{server.port}"
    replica_servers: list = []  # BENCH_REPLICAS extra front-door targets

    # per-transport serving rows (ISSUE 13): the host-gap story needs
    # one row per transport the host path offers, not just wire-vs-shm
    #   grpc      — loopback TCP, raw protobuf tensors (remote-client
    #               cost model)
    #   shm       — loopback TCP control + shared-memory tensors (the
    #               same-host default)
    #   uds       — unix socket control + shared-memory tensors
    #   stream_b8 — uds+shm with 8-frame stream groups: the per-message
    #               protocol cost paid once per 8 frames
    _TRANSPORT_MODES = {
        "grpc": dict(use_shm=False, uds=False, group=1),
        "shm": dict(use_shm=True, uds=False, group=1),
        "uds": dict(use_shm=True, uds=True, group=1),
        "stream_b8": dict(use_shm=True, uds=True, group=8),
    }

    def run_mode(transport: str) -> dict:
        from triton_client_tpu.utils.loadgen import run_pool

        mode = _TRANSPORT_MODES[transport]
        stats0 = {}

        def window_start():
            # timed window starts here: drop warm-phase accounting
            with occ_lock:
                occupancy.clear()
                device_call_s.clear()
                window_t0[0] = time.perf_counter()
            stats0.update(batching.stats())

        res = run_pool(
            server.uds_address if mode["uds"] else addr,
            spec.name,
            {"images": frame},
            clients=clients,
            duration_s=duration_s,
            deadline_s=deadline_s,
            use_shared_memory=mode["use_shm"],
            mode="stream" if mode["group"] > 1 else "unary",
            inflight=mode["group"],
            stream_group=mode["group"],
            on_window_start=window_start,
        )
        stats = batching.stats()
        if res.errors:
            print(
                f"serving bench ({transport}) client "
                f"errors: {res.errors[:3]}",
                file=sys.stderr,
            )

        total = res.served_frames
        latencies = res.latencies_ms
        d_frames = stats.get("merged_frames", 0) - stats0.get(
            "merged_frames", 0
        )
        d_merges = stats.get("merges", 0) - stats0.get("merges", 0)
        d_padded = stats.get("padded_frames", 0) - stats0.get(
            "padded_frames", 0
        )
        d_ragged_rows = stats.get("ragged_rows", 0) - stats0.get(
            "ragged_rows", 0
        )
        d_ragged_pad = stats.get("ragged_pad_rows", 0) - stats0.get(
            "ragged_pad_rows", 0
        )
        mean_batch = (d_frames / d_merges) if d_merges else 0.0
        # the wire row keeps its historical unsuffixed metric name so
        # bench_diff comparisons line up across rounds
        suffix = "" if transport == "grpc" else f"_{transport}"
        row = {
            "metric": f"yolov5n_512_served{suffix}_frames_per_sec",
            "transport": transport,
            "value": round(res.fps, 2),
            "unit": "frames/sec",
            "vs_baseline": round(res.fps / CAMERA_FPS_BASELINE, 2),
            # whole-server rate over every device the channel drives;
            # per-chip divides it back out for the BENCH_LOCAL-style
            # single-chip comparison
            "data_axis": data_axis,
            "aggregate_frames_per_sec": round(res.fps, 2),
            "frames_per_sec_per_chip": round(res.fps / data_axis, 2),
            "clients": clients,
            "served_frames": total,
            "request_p50_ms": (
                round(float(np.percentile(latencies, 50)), 2)
                if latencies else None
            ),
            "request_p99_ms": (
                round(float(np.percentile(latencies, 99)), 2)
                if latencies else None
            ),
            "request_p999_ms": (
                round(float(np.percentile(latencies, 99.9)), 2)
                if latencies else None
            ),
            # filled on the wire row by the open-loop SLO search below
            # (None = not searched: shm/3d rows, or budget ran out).
            # goodput = SLO-met completions/sec AT capacity and
            # shed_rate = deliberate RESOURCE_EXHAUSTED rejections /
            # scheduled — the capacity story reports what was served
            # within SLO, not just offered load survived
            "slo_capacity_qps": None,
            "goodput_qps": None,
            "shed_rate": None,
            "slo_ms": None,
            # fleet row (ISSUE 10): with BENCH_REPLICAS=N > 1 the wire
            # row also searches capacity through a FrontDoorRouter over
            # N endpoints (extra servers share this rig's device, so
            # the number measures the front door + failover machinery,
            # not N devices' worth of compute)
            "replicas": 1,
            "fleet_goodput_qps": None,
            "upload_mbps": round(upload_mbps, 1),
            "direct_batch_ms": round(direct_batch_ms, 1),
            # what the device leg alone supports at the same max_merge
            # batch: every served batch pays one un-amortized dispatch
            # — served/ceiling is the serving stack's share, ceiling
            # is the environment's
            "device_ceiling_fps": round(
                max_merge / (direct_batch_ms / 1e3), 2
            ),
            # the host-gap headline: served rate as a fraction of what
            # the device leg alone supports on this rig — 1.0 means the
            # host transport costs nothing, the seed's shm row sat at
            # ~0.01 where the device leg dominates
            "host_gap_ratio": round(
                res.fps / max(1e-9, max_merge / (direct_batch_ms / 1e3)),
                4,
            ),
            "client_errors": len(res.errors),
            "device_batches": d_merges,
            "mean_batch": round(float(mean_batch), 2),
            "padded_frames": stats.get("padded_frames", 0)
            - stats0.get("padded_frames", 0),
            # padding-tax headline for the window: pad rows (dense
            # bucket pad + ragged alignment slack) over all device rows
            "pad_fraction": round(
                (d_padded + d_ragged_pad)
                / max(1, d_frames + d_padded + d_ragged_rows + d_ragged_pad),
                4,
            ),
            "ragged_batches": stats.get("ragged_batches", 0)
            - stats0.get("ragged_batches", 0),
            "ragged_rows": d_ragged_rows,
            "ragged_pad_rows": d_ragged_pad,
            "batch_occupancy": {
                str(k): occupancy[k] for k in sorted(occupancy)
            },
            # stall forensics: a window with max >> median is
            # environment-stalled and its fps is not a framework number
            "max_device_call_s": (
                round(max(device_call_s), 2) if device_call_s else None
            ),
            "p50_device_call_s": (
                round(float(np.percentile(device_call_s, 50)), 2)
                if device_call_s else None
            ),
            "precision": precision,
            "fused_stages": spec.extra.get("fused_stages", []),
        }
        if flops_per_frame:
            row["flops_per_frame"] = flops_per_frame
            row["mfu"] = round(
                res.fps * flops_per_frame
                / peak_flops(precision),
                4,
            )
            if bytes_per_frame:
                roof = roofline_classify(
                    flops_per_frame * max_merge,
                    bytes_per_frame * max_merge,
                    precision, batch=max_merge,
                )
                row["bytes_per_frame"] = bytes_per_frame
                row["arithmetic_intensity"] = round(roof.intensity, 2)
                row["roofline_bound"] = roof.bound
                row["attainable_fps"] = round(roof.attainable_fps, 2)
                if roof.attainable_fps > 0:
                    row["roofline_attained_ratio"] = round(
                        res.fps / roof.attainable_fps, 6
                    )
        if total == 0:
            row["degraded"] = (
                f"no request completed in the {duration_s:.0f}s window; "
                f"first error: {res.errors[:1]}"
            )
        return row

    rows = []
    try:
        for transport in ("grpc", "shm", "uds", "stream_b8"):
            if transport != "grpc" and _remaining() < 100.0:
                # the wire row is already captured; further transports
                # must not drag the run past the external cap
                print(
                    f"serving {transport} mode skipped: "
                    f"{_remaining():.0f}s left", file=sys.stderr,
                )
                break
            try:
                row = run_mode(transport)
                if (
                    transport == "grpc"
                    and row["request_p50_ms"]
                    and _remaining() > 240.0
                ):
                    # open-loop SLO capacity on the wire transport: the
                    # MLPerf server-scenario number (max offered qps at
                    # p99 <= SLO) next to the closed-loop fps. SLO =
                    # 3x a lightly-loaded OPEN-loop p50 — closed-loop
                    # p50 hides the batcher's merge hold (clients
                    # arrive together and fill batches; a lone Poisson
                    # arrival waits the hold out), so deriving from it
                    # reads capacity 0 on any held config; and a fixed
                    # wall SLO would read 0 on a slow link.
                    # Short probes + a hard straggler deadline keep the
                    # whole search bounded (~12 probes x ~15 s worst
                    # case) so it can never eat the rows that follow.
                    try:
                        from triton_client_tpu.utils.loadgen import (
                            run_open_loop,
                            slo_capacity_search,
                        )

                        calib = run_open_loop(
                            addr, [(spec.name, {"images": frame})],
                            rate_qps=4.0, duration_s=3.0,
                            deadline_s=60.0,
                        )
                        p50 = calib.percentile(50.0)
                        slo_ms = max(
                            10.0,
                            3.0 * (row["request_p50_ms"] or 0.0),
                            3.0 * (0.0 if p50 == float("inf") else p50),
                        )
                        cap = slo_capacity_search(
                            addr, [(spec.name, {"images": frame})],
                            slo_ms=slo_ms, duration_s=3.0,
                            qps_lo=0.5,
                            qps_hi=max(8.0, 4.0 * (row["value"] or 1.0)),
                            deadline_s=12.0,
                        )
                        row["slo_capacity_qps"] = cap["slo_capacity_qps"]
                        row["goodput_qps"] = cap.get("goodput_qps")
                        row["shed_rate"] = cap.get("shed_rate")
                        row["slo_ms"] = round(slo_ms, 2)
                        row["slo_p99_ms"] = cap["p99_ms"]
                        # fleet capacity through the front door: extra
                        # replica servers over the SAME repo + batcher
                        # (one host, shared device — the delta vs the
                        # single-endpoint number is the router's cost
                        # or win, not extra hardware)
                        n_replicas = int(
                            os.environ.get("BENCH_REPLICAS", "1")
                        )
                        if n_replicas > 1 and _remaining() > 180.0:
                            for _ in range(n_replicas - 1):
                                extra = InferenceServer(
                                    repo, batching,
                                    address="127.0.0.1:0",
                                    max_workers=clients + 8,
                                )
                                extra.start()
                                replica_servers.append(extra)
                            fleet = [addr] + [
                                f"127.0.0.1:{s.port}"
                                for s in replica_servers
                            ]
                            cap_fleet = slo_capacity_search(
                                fleet, [(spec.name, {"images": frame})],
                                slo_ms=slo_ms, duration_s=3.0,
                                qps_lo=0.5,
                                qps_hi=max(8.0, 4.0 * (row["value"] or 1.0)),
                                deadline_s=12.0,
                            )
                            row["replicas"] = n_replicas
                            row["fleet_goodput_qps"] = cap_fleet.get(
                                "goodput_qps"
                            )
                            row["fleet_slo_capacity_qps"] = cap_fleet[
                                "slo_capacity_qps"
                            ]
                    except Exception as e:
                        _failed("slo capacity search", e)
                rows.append(row)
                if on_row is not None:
                    on_row(row)  # emitted the moment it exists
            except Exception as e:
                _failed(f"serving mode {transport}", e)
        # 3D served row (VERDICT r4 Weak #2: serving evidence was
        # 2D-unary only): PointPillars through the SAME server +
        # batcher. 3D requests are single-scan (no leading batch dim —
        # the reference's 3D client contract), so they ride the
        # batcher's oversized-solo path; the row measures the serving
        # stack on the 3D pipeline, not merge behavior.
        if _remaining() > 110.0:
            try:
                row = _serve_3d_row(
                    repo, batching, server,
                    duration_s=min(25.0, max(12.0, _remaining() - 90.0)),
                )
                rows.append(row)
                if on_row is not None:
                    on_row(row)
            except Exception as e:
                _failed("serving 3d mode", e)
        else:
            print(
                f"serving 3d row skipped: {_remaining():.0f}s left",
                file=sys.stderr,
            )
    finally:
        for extra in replica_servers:
            try:
                extra.stop()
            except Exception:
                pass
        server.stop()
        batching.close()
    return rows


def _serve_3d_row(repo, batching, server, duration_s: float) -> dict:
    """PointPillars served over the live KServe server: 8 closed-loop
    clients sending single scans (~20k-point uniform clouds, the
    pointpillars_uniform distribution)."""
    from triton_client_tpu.pipelines.detect3d import (
        build_pointpillars_pipeline,
    )
    from triton_client_tpu.utils.loadgen import run_pool

    pipe3, spec3, _ = build_pointpillars_pipeline(jax.random.PRNGKey(0))
    repo.register(spec3, pipe3.infer_fn())

    rng = np.random.default_rng(3)
    n_pts = 20000
    pts = np.stack(
        [
            rng.uniform(0.0, 69.12, n_pts),
            rng.uniform(-39.68, 39.68, n_pts),
            rng.uniform(-3.0, 1.0, n_pts),
            rng.uniform(0, 1, n_pts),
        ],
        axis=1,
    ).astype(np.float32)
    feed = {"points": pts, "num_points": np.asarray(n_pts, np.int32)}
    # warm the scan shape through the inner channel before the window,
    # then time one warm dispatch (the per-scan device-path cost)
    from triton_client_tpu.channel.base import InferRequest

    batching.do_inference(InferRequest(model_name=spec3.name, inputs=feed))
    t0 = time.perf_counter()
    batching.do_inference(InferRequest(model_name=spec3.name, inputs=feed))
    direct_ms = (time.perf_counter() - t0) * 1e3

    res = run_pool(
        f"127.0.0.1:{server.port}",
        spec3.name,
        feed,
        clients=8,
        duration_s=duration_s,
        deadline_s=240.0,
    )
    latencies = res.latencies_ms
    row = {
        "metric": "pointpillars_served_scans_per_sec",
        "value": round(res.fps, 2),
        "unit": "scans/sec",
        "vs_baseline": round(res.fps / LIDAR_HZ_BASELINE, 2),
        "clients": 8,
        "served_scans": res.served_frames,
        "request_p50_ms": (
            round(float(np.percentile(latencies, 50)), 2) if latencies else None
        ),
        "request_p99_ms": (
            round(float(np.percentile(latencies, 99)), 2) if latencies else None
        ),
        "request_p999_ms": (
            round(float(np.percentile(latencies, 99.9)), 2)
            if latencies else None
        ),
        "slo_capacity_qps": None,
        "goodput_qps": None,
        "shed_rate": None,
        "slo_ms": None,
        "direct_scan_ms": round(direct_ms, 1),
        # single-scan dispatches: the ceiling is one scan per device
        # call on this rig (no batch amortization on the 3D wire)
        "device_ceiling_fps": round(1e3 / direct_ms, 2) if direct_ms else None,
        "client_errors": len(res.errors),
        "precision": "f32",
        "fused_stages": spec3.extra.get("fused_stages", []),
    }
    if res.served_frames == 0:
        row["degraded"] = f"no request completed; first error: {res.errors[:1]}"
    return row


def _serve_streaming_sessions_row(duration_s: float) -> dict:
    """ISSUE 15 streaming sessions at replay pace: 8 concurrent
    synthetic streams, each a scripted multi-object scene replayed at
    recorded fps through its own ``sequence_id`` against one in-process
    server with device-resident tracking. The row's ``value`` is the
    total sustained frames/sec across streams — gated by
    perf/bench_diff.py like every throughput row; the tracking-quality
    counters (id switches, fragmentation, aliases) ride along so a
    regression in EITHER pace or identity stability shows up in the
    diff. Echo detector on purpose: the row measures the session layer
    (slot pool + on-device tracker step + sequence plumbing), not
    detector math."""
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.ops.tracking import TrackerConfig
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.runtime.sessions import SessionManager
    from triton_client_tpu.utils.loadgen import run_streams, synthetic_stream

    n_streams, fps = 8, 10.0
    det_dim = 11
    spec = ModelSpec(
        name="stream_echo",
        version="1",
        platform="jax",
        inputs=(
            TensorSpec("detections", (-1, det_dim), "FP32"),
            TensorSpec("valid", (-1,), "BOOL"),
        ),
        outputs=(
            TensorSpec("detections", (-1, det_dim), "FP32"),
            TensorSpec("valid", (-1,), "BOOL"),
        ),
    )
    repo = ModelRepository()
    repo.register(
        spec,
        lambda inputs: {
            "detections": inputs["detections"],
            "valid": inputs["valid"],
        },
    )
    chan = TPUChannel(repo)
    manager = SessionManager(
        max_sessions=n_streams * 2, ttl_s=300.0,
        tracker=TrackerConfig(max_tracks=32),
    )
    chan.attach_sessions(manager)
    server = InferenceServer(
        repo, chan, address="127.0.0.1:0", uds_address="auto",
        max_workers=n_streams + 2,
    )
    server.start()
    try:
        # warm: compile the tracker step before the paced window
        run_streams(
            server.uds_address, spec.name, n_streams=1,
            source=lambda i: synthetic_stream(n_frames=3, fps=100.0),
            deadline_s=60.0, stream_id_prefix="warm",
        )
        n_frames = max(10, int(duration_s * fps))
        res = run_streams(
            server.uds_address, spec.name, n_streams=n_streams,
            source=lambda i: synthetic_stream(
                n_frames=n_frames, fps=fps, n_objects=4, seed=i
            ),
            deadline_s=duration_s + 120.0,
        )
        summary = res.summary()
        total_fps = sum(s.sustained_fps for s in res.streams)
        row = {
            "metric": "streaming_sessions",
            "value": round(total_fps, 2),
            "unit": "frames/sec",
            "streams": n_streams,
            "requested_fps_per_stream": fps,
            "min_sustained_fps": summary["min_sustained_fps"],
            "worst_inter_frame_p99_ms": summary["worst_inter_frame_p99_ms"],
            "goodput": summary["goodput"],
            "id_switches": summary["id_switches"],
            "fragmentation": summary["fragmentation"],
            "track_id_aliases": summary["track_id_aliases"],
            "session_frames": manager.stats()["frames_total"],
            "precision": "f32",
        }
        if res.frames_ok == 0:
            row["degraded"] = "no stream frame completed"
        return row
    finally:
        server.stop()


def _serve_quality_plane_row(duration_s: float) -> dict:
    """ISSUE 17 continuous quality plane: one in-process server with a
    detection echo model, its ``_int8`` twin armed as a canary, and the
    shadow sampler at the serve CLI's canary-default 25%. Two paced
    open-loop windows, sampling OFF then ON, same seed; the row's
    ``value`` is scored frames/sec off the mirror's own counter, and
    ``quality_overhead_headroom`` (p99 off / p99 on) is gated by
    perf/bench_diff.py: a >10% drop means the sampler started taxing
    the primary path. Echo detector on purpose — the row measures the
    route/observe/mirror machinery, not detector math."""
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.eval.quality_plane import QualityPlane
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.loadgen import run_open_loop

    det = np.zeros((6, 6), np.float32)
    det[:, 0] = np.arange(6) * 30.0
    det[:, 1] = np.arange(6) * 20.0
    det[:, 2] = det[:, 0] + 24.0
    det[:, 3] = det[:, 1] + 16.0
    det[:, 4] = 0.9
    det[:, 5] = np.arange(6) % 3

    def _det_fn(inputs):
        return {
            "detections": det + np.float32(0.0) * inputs["x"][0, 0],
            "valid": np.ones((6,), bool),
        }

    repo = ModelRepository()
    for name in ("qp_det", "qp_det_int8"):
        repo.register(
            ModelSpec(
                name=name, version="1", platform="jax",
                inputs=(TensorSpec("x", (-1, 4), "FP32"),),
                outputs=(
                    TensorSpec("detections", (-1, 6), "FP32"),
                    TensorSpec("valid", (-1,), "BOOL"),
                ),
            ),
            _det_fn,
        )
    quality = QualityPlane(sample_rate=0.0, window_frames=16)
    quality.set_canary("qp_det", "qp_det_int8", 0.25)
    server = InferenceServer(
        repo, TPUChannel(repo), address="127.0.0.1:0",
        max_workers=8, quality=quality,
    )
    server.start()
    try:
        import dataclasses as _dc

        from triton_client_tpu.channel.base import InferRequest
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        window = max(2.0, duration_s / 2.0)
        scenarios = [("qp_det", {"x": np.ones((2, 4), np.float32)})]
        addr = f"127.0.0.1:{server.port}"
        rate = 120.0
        # compile BOTH registrations (the canary slice routes to the
        # variant mid-window otherwise) and the shadow dispatch path
        # before any timed window
        warm_chan = GRPCChannel(addr, timeout_s=30.0)
        try:
            for name in ("qp_det", "qp_det_int8"):
                for i in range(3):
                    warm_chan.do_inference(InferRequest(
                        name, scenarios[0][1], request_id=f"warm-{name}-{i}"
                    ))
        finally:
            warm_chan.close()
        # deterministic per-arrival identity: the hash-sampled canary
        # slice and shadow sample are then identical across runs
        factory = lambda req, i: _dc.replace(req, request_id=f"qp-{i}")
        off = run_open_loop(
            addr, scenarios, rate_qps=rate, duration_s=window, seed=11,
            deadline_s=30.0, request_factory=factory,
        )
        quality.set_sample_rate(0.25)
        t0 = time.perf_counter()
        on = run_open_loop(
            addr, scenarios, rate_qps=rate, duration_s=window, seed=11,
            deadline_s=30.0, request_factory=factory,
        )
        quality.drain(20.0)
        wall = time.perf_counter() - t0
        mirror = quality.snapshot()["mirror"]
        p99_off = off.percentile(99.0)
        p99_on = on.percentile(99.0)
        # the gated ratio uses p95: the same signal (sidecar tax on the
        # primary path) with far less single-sample jitter than p99
        p95_off = off.percentile(95.0)
        p95_on = on.percentile(95.0)
        row = {
            "metric": "quality_plane",
            "value": round(mirror["scored"] / max(wall, 1e-9), 2),
            "unit": "scored_frames/sec",
            "sample_rate": 0.25,
            "scored_frames": mirror["scored"],
            "mirror_dropped": mirror["dropped"],
            "shadow_lag_ms": round(mirror["mean_lag_s"] * 1e3, 3),
            "p99_off_ms": round(p99_off, 3),
            "p99_on_ms": round(p99_on, 3),
            "p99_delta_ms": round(p99_on - p99_off, 3),
            "p95_off_ms": round(p95_off, 3),
            "p95_on_ms": round(p95_on, 3),
            "shadow_overhead_ratio": round(p95_on / max(p95_off, 1e-9), 4),
            "quality_overhead_headroom": round(
                p95_off / max(p95_on, 1e-9), 4
            ),
            "canary": quality.canary.stats()["models"]
            .get("qp_det", {}).get("state", "none"),
            "precision": "f32",
        }
        if on.completed == 0 or off.completed == 0:
            row["degraded"] = (
                f"window incomplete; first error: {(off.errors or on.errors)[:1]}"
            )
        return row
    finally:
        server.stop()


def _serve_temporal_reuse_row(duration_s: float) -> dict:
    """ISSUE 19 temporal compute reuse: the same synthetic stream set
    replayed twice against an in-process server with device-resident
    tracking — reuse OFF (full detector every frame) then reuse ON
    (adaptive keyframe scheduling, static scene so K opens wide and
    coast dominates). The echo detector carries a fixed simulated
    device cost so the per-stream device-seconds ledger (the PR 11
    scoreboard) has something to save; the row's ``value`` is
    streams-per-chip at the replay fps with reuse on, and
    ``temporal_speedup`` (streams-per-chip on / off) is gated by
    perf/bench_diff.py. ID switches ride along so a cheaper schedule
    that costs identity stability shows up in the diff."""
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.ops.tracking import TrackerConfig
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.runtime.sessions import SessionManager
    from triton_client_tpu.runtime.temporal import (
        TemporalReuseConfig,
        TemporalReusePlane,
    )
    from triton_client_tpu.utils.loadgen import run_streams, synthetic_stream

    n_streams, fps, det_dim = 6, 10.0, 11
    detector_iters = 60  # 128x128 matmul chain: the simulated det cost
    n_frames = max(20, int(duration_s * 10))

    def _window(reuse: bool) -> dict:
        import jax.numpy as jnp

        spec = ModelSpec(
            name="tr_det",
            version="1",
            platform="jax",
            inputs=(
                TensorSpec("detections", (-1, det_dim), "FP32"),
                TensorSpec("valid", (-1,), "BOOL"),
            ),
            outputs=(
                TensorSpec("detections", (-1, det_dim), "FP32"),
                TensorSpec("valid", (-1,), "BOOL"),
            ),
        )
        repo = ModelRepository()

        def _det_fn(inputs):
            return {
                "detections": inputs["detections"],
                "valid": inputs["valid"],
            }

        # the simulated detector cost must be real async-dispatched
        # device work (a jitted device_fn): the ledger's scoreboard
        # window is launch -> execution-ready, so a host sleep would
        # run before dispatch and charge the stream tenant nothing
        eye = jnp.eye(128, dtype=jnp.float32)

        def _det_device_fn(inputs):
            det = inputs["detections"]
            v = jnp.broadcast_to(det.reshape(-1)[:1], (128, 128)) + eye
            for _ in range(detector_iters):
                v = v @ eye
            return {
                "detections": det + v[0, 0] * jnp.float32(1e-30),
                "valid": inputs["valid"],
            }

        repo.register(spec, _det_fn, device_fn=_det_device_fn)
        chan = TPUChannel(repo)
        manager = SessionManager(
            max_sessions=n_streams * 2, ttl_s=300.0,
            tracker=TrackerConfig(max_tracks=32),
        )
        chan.attach_sessions(manager)
        temporal = None
        if reuse:
            temporal = TemporalReusePlane(
                manager,
                config=TemporalReuseConfig(mode="auto", k_max=8),
                channel=chan,
            )
        # metrics on: the DeviceTimeLedger (the row's scoreboard) only
        # exists on the telemetry plane
        server = InferenceServer(
            repo, chan, address="127.0.0.1:0", uds_address="auto",
            max_workers=n_streams + 2, temporal=temporal,
            metrics_port="auto",
        )
        server.start()
        try:
            run_streams(  # compile tracker step + coast outside window
                server.uds_address, spec.name, n_streams=1,
                source=lambda i: synthetic_stream(
                    n_frames=6, fps=100.0, dynamics="static"
                ),
                deadline_s=60.0, stream_id_prefix="warm", realtime=False,
            )
            res = run_streams(
                server.uds_address, spec.name, n_streams=n_streams,
                source=lambda i: synthetic_stream(
                    n_frames=n_frames, fps=fps, n_objects=4, seed=i,
                    dynamics="static",
                ),
                deadline_s=duration_s + 120.0, realtime=False,
            )
            dev_s = 0.0
            if server.device_time is not None:
                dev_s = sum(
                    v
                    for k, v in server.device_time.device_seconds().items()
                    if "|stream:stream-" in k
                )
            summary = res.summary()
            frames = max(1, res.frames_ok)
            dev_per_frame = dev_s / frames
            # fixed-SLO capacity framing: one chip has 1 device-second
            # per wall second; a stream at `fps` consumes
            # dev_per_frame * fps of it
            spc = (
                1.0 / (dev_per_frame * fps) if dev_per_frame > 0 else 0.0
            )
            return {
                "streams_per_chip": spc,
                "device_seconds": dev_s,
                "frames_ok": res.frames_ok,
                "frames_coasted": summary["frames_coasted"],
                "id_switches": summary["id_switches"],
                "fragmentation": summary["fragmentation"],
                "coast_track_drops": summary["coast_track_drops"],
            }
        finally:
            server.stop()

    off = _window(reuse=False)
    on = _window(reuse=True)
    speedup = on["streams_per_chip"] / max(off["streams_per_chip"], 1e-9)
    row = {
        "metric": "temporal_reuse",
        "value": round(on["streams_per_chip"], 2),
        "unit": "streams/chip",
        "streams": n_streams,
        "replay_fps": fps,
        "detector_iters": detector_iters,
        "streams_per_chip_off": round(off["streams_per_chip"], 2),
        "streams_per_chip_on": round(on["streams_per_chip"], 2),
        "temporal_speedup": round(speedup, 3),
        "device_seconds_off": round(off["device_seconds"], 4),
        "device_seconds_on": round(on["device_seconds"], 4),
        "frames_coasted": on["frames_coasted"],
        "id_switches_off": off["id_switches"],
        "id_switches_on": on["id_switches"],
        "id_switch_delta": on["id_switches"] - off["id_switches"],
        "coast_track_drops": on["coast_track_drops"],
        "precision": "f32",
    }
    if on["frames_ok"] == 0 or off["frames_ok"] == 0:
        row["degraded"] = "a replay window completed no frames"
    return row


def _serve_multitenant_row(duration_s: float) -> dict:
    """ISSUE 9 multi-tenant lifecycle under pressure: five synthetic
    models (distinct multipliers, synthetic 100-byte HBM costs) over a
    budget that admits two, split across three tenants with 8/2/1
    shares. Three concurrent closed-loop pools (one per tenant) force
    paging and fair-share arbitration at once; the row reports
    promotion latency quantiles from the lifecycle histogram and
    per-tenant goodput from the scheduler's DRR accounting. Synthetic
    on purpose — the row measures the paging/fair-share machinery, not
    model math."""
    import threading as _threading

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.obs.histogram import quantile_from_snapshot
    from triton_client_tpu.runtime.continuous import (
        ContinuousBatchingChannel,
    )
    from triton_client_tpu.runtime.lifecycle import (
        ModelLifecycleManager,
        TenantPolicy,
        TenantTable,
    )
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.loadgen import run_pool

    repo = ModelRepository()
    models = [("mt_a", 2.0), ("mt_b", 3.0), ("mt_c", 4.0),
              ("mt_d", 5.0), ("mt_e", 6.0)]
    for name, k in models:
        spec = ModelSpec(
            name=name, version="1", max_batch_size=8,
            inputs=(TensorSpec("x", (-1, 64), "FP32"),),
            outputs=(TensorSpec("y", (-1, 64), "FP32"),),
            extra={"param_bytes": 100},
        )
        repo.register(
            spec,
            lambda inputs, k=k: {
                "y": np.asarray(inputs["x"], np.float32) * k
            },
            device_fn=lambda inputs, k=k: {"y": inputs["x"] * k},
        )
    table = TenantTable([
        TenantPolicy(name="gold", share=8, models=("mt_a", "mt_b"),
                     pinned=("mt_a",)),
        TenantPolicy(name="silver", share=2, models=("mt_c",)),
        TenantPolicy(name="bronze", share=1, models=("mt_d", "mt_e")),
    ])
    base = TPUChannel(repo)
    lifecycle = ModelLifecycleManager(repo, budget_bytes=250, tenants=table)
    base.attach_lifecycle(lifecycle)
    batching = ContinuousBatchingChannel(base, max_batch=8)
    batching.attach_tenants(table)
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", metrics_port=0,
        lifecycle=lifecycle, tenants=table,
    )
    server.start()
    try:
        addr = f"127.0.0.1:{server.port}"
        feed = {"x": np.ones((2, 64), np.float32)}
        # one pool per tenant, concurrently: gold/silver/bronze each
        # hammer one of their models; bronze's model set also rotates
        # residency pressure through the 250-byte budget
        results = {}

        def pool(tenant, model):
            results[tenant] = run_pool(
                addr, model, feed, clients=4,
                duration_s=duration_s, deadline_s=60.0,
            )

        threads = [
            _threading.Thread(target=pool, args=(t, m), daemon=True)
            for t, m in (("gold", "mt_a"), ("silver", "mt_c"),
                         ("bronze", "mt_d"))
        ]
        for t in threads:
            t.start()
        # a low-rate scan over every model keeps cold ones promoting
        t_end = time.perf_counter() + duration_s
        scans = 0
        while time.perf_counter() < t_end:
            for name, _ in models:
                try:
                    batching.do_inference(
                        InferRequest(model_name=name, inputs=feed)
                    )
                    scans += 1
                except Exception:
                    pass
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=duration_s + 60.0)
        lc = lifecycle.stats()
        promo = lc["promotion_latency"]
        served = batching.stats().get("tenant_served_frames", {})
        total_fps = sum(
            r.fps for r in results.values() if r is not None
        )
        row = {
            "metric": "multitenant_served_fps",
            "value": round(total_fps, 2),
            "unit": "frames/sec",
            "models_registered": len(models),
            "hbm_budget_bytes": lc["budget_bytes"],
            "hbm_resident_bytes": lc["resident_bytes"],
            "promotions": lc.get("promotions", 0),
            "evictions": lc.get("evictions", 0),
            "promotion_p50_ms": (
                round(quantile_from_snapshot(promo, 0.50) * 1e3, 3)
                if promo.get("count") else None
            ),
            "promotion_p99_ms": (
                round(quantile_from_snapshot(promo, 0.99) * 1e3, 3)
                if promo.get("count") else None
            ),
            "tenant_goodput_fps": {
                t: round(r.fps, 2) for t, r in results.items()
                if r is not None
            },
            "tenant_served_frames": {k: int(v) for k, v in served.items()},
            "tenant_shares": {"gold": 8, "silver": 2, "bronze": 1},
            "scan_requests": scans,
            "precision": "f32",
        }
        if not results:
            row["degraded"] = "no tenant pool completed"
        return row
    finally:
        server.stop()
        batching.close()


def validate_pallas_nms() -> dict:
    """Once per bench session: run the Pallas NMS kernel and the XLA
    loop on the LIVE backend on the same inputs and require identical
    selected-index sequences — a Mosaic lowering regression fails the
    bench run, not a customer (VERDICT r1: interpret-mode tests alone
    never exercised the real TPU lowering)."""
    from triton_client_tpu.ops.nms import _nms_xla
    from triton_client_tpu.ops.pallas_nms import nms_pallas

    rng = np.random.default_rng(7)
    checked = 0
    for n in (128, 512, 1024):
        centers = rng.uniform(0, 512, (n, 2))
        wh = rng.uniform(8, 96, (n, 2))
        boxes = jnp.asarray(
            np.concatenate([centers - wh / 2, centers + wh / 2], axis=1),
            jnp.float32,
        )
        scores = jnp.asarray(rng.uniform(0.01, 1.0, n), jnp.float32)
        for thresh in (0.3, 0.45, 0.6):
            pi, pv = nms_pallas(
                boxes, scores, iou_thresh=thresh, max_det=128, interpret=False
            )
            xi, xv = _nms_xla(boxes, scores, thresh, max_det=128)
            pi, pv, xi, xv = (np.asarray(a) for a in (pi, pv, xi, xv))
            if not (np.array_equal(pv, xv) and np.array_equal(pi[pv], xi[xv])):
                raise AssertionError(
                    f"Pallas NMS diverges from XLA on TPU (n={n}, "
                    f"thresh={thresh}): pallas={pi[pv][:10]} xla={xi[xv][:10]}"
                )
            checked += 1
    return {"pallas_nms_on_tpu": f"identical to XLA loop ({checked} cases)"}


# FRESH-compile warmup cost estimates —
# used only to schedule warmups against the budget; observed actuals
# recalibrate them, so a cache-warm run (~20x cheaper) schedules
# everything and a fresh run sheds the expensive tail first.
WARMUP_EST_S = {
    "yolov5n": 90.0, "yolov5n_bf16": 69.0, "yolov5n_mxu": 79.0,
    "yolov5n_mxu_bf16": 82.0, "yolov5n_b64": 244.0,
    "yolov5n_b64_mxu_bf16": 250.0,
    "pointpillars": 50.0, "pointpillars_uniform": 48.0,
    "second_iou": 46.0, "second_sparse005": 154.0, "centerpoint": 44.0,
}

# shared with the SIGTERM flush: rows already emitted, live configs,
# accumulated results for BENCH_LOCAL.json, phases that failed
_STATE = {
    "configs": [], "provisional": [], "emitted": set(), "failed": [],
    "results": [], "nms_check": None,
}


def _failed(what: str, e: Exception) -> None:
    """A phase failed: say so now, again at the end, and in the exit
    code — later phases still run, the run never ends in 0."""
    print(f"{what} failed: {e}", file=sys.stderr)
    _STATE["failed"].append(f"{what}: {e}")


def _emit_row(row: dict, primary: bool) -> None:
    """Print a metric row the moment it exists (VERDICT r3 #1a): the
    primary owns the one stdout line, secondaries stream to stderr —
    a driver timeout after this point cannot un-capture the row."""
    print(json.dumps(row), file=sys.stdout if primary else sys.stderr,
          flush=True)
    _STATE["emitted"].add(row["metric"])
    _STATE["results"].append(row)


def _write_local() -> None:
    try:  # best-effort: the stdout contract must survive
        with open("BENCH_LOCAL.json", "w") as f:
            json.dump(
                {"nms_check": _STATE["nms_check"],
                 "results": _STATE["results"]},
                f, indent=2,
            )
    except OSError as e:
        print(f"could not write BENCH_LOCAL.json: {e}", file=sys.stderr)


def _flush_rows_on_term(signum, frame):
    """Last-resort row insurance: if the driver's clock fires anyway,
    emit every config that has trial samples from pure numpy (no jax
    calls — a device dispatch inside a signal handler can deadlock
    against the interrupted main thread) and exit."""
    try:
        configs = _STATE["configs"]
        for c in configs + _STATE["provisional"]:
            if c.metric in _STATE["emitted"] or len(c.trial_ms) < 3:
                continue
            try:
                row = c.result(with_latency=False)
                row["provisional"] = "flushed on SIGTERM"
                _emit_row(row, primary=bool(configs) and c is configs[0])
            except Exception:
                pass
        _write_local()
    finally:
        os._exit(1)


def main() -> None:
    device = device_info()
    print(json.dumps({"device": device}), flush=True)
    if device["platform"] != "tpu" or peak_flops("bf16") is None:
        print(
            f"bench.py measures a TPU listed in obs/roofline.DEVICE_PEAKS; "
            f"jax found {device['platform']} ({device['kind']}) "
            f"x{device['count']}", file=sys.stderr,
        )
        sys.exit(2)
    signal.signal(signal.SIGTERM, _flush_rows_on_term)
    nms_check = _STATE["nms_check"] = validate_pallas_nms()
    print(json.dumps(nms_check), file=sys.stderr)

    print(f"budget {BUDGET_S:.0f}s", file=sys.stderr)

    # VALUE order (VERDICT r3 #1c, reworked r5): the primary is
    # mandatory; then the headline winner, the 3D family rows, the b64
    # peak claim (provisional-capable), the reference-grid sparse
    # SECOND, and only then the dtype/layout delta rows — a tight
    # budget sheds the A/Bs, not the
    # family rows or the claims the verdicts asked to see captured.
    factories = [
        ("yolov5n", make_yolov5),
        # fastest b8 config: the two levers stack (base 6.26 ms, mxu
        # 5.21, bf16 5.28, mxu+bf16 4.57 ms = -27%)
        ("yolov5n_mxu_bf16",
         lambda: make_yolov5(mxu=True, dtype=jnp.bfloat16)),
        ("pointpillars", make_pointpillars),
        ("centerpoint", make_centerpoint),
        ("second_iou", make_second),
        # the peak-per-chip claim (README): batch amortizes the small-
        # channel convs' fixed overhead. Ordered DIRECTLY after the
        # family rows (r5): in r4/r5 slow phases it sat behind four
        # delta rows whose warmups ate the budget, so the one row the
        # verdict asked to see driver-captured was always the one
        # shed. When the full protocol no longer fits it degrades to a
        # shortened provisional block instead of shedding silently.
        ("yolov5n_b64_mxu_bf16",
         lambda: make_yolov5(batch=64, mxu=True, dtype=jnp.bfloat16)),
        # the reference-grid sparse SECOND is a family row, not a
        # delta: it outranks the 2D dtype/layout A/Bs
        ("second_sparse005", make_second_sparse),
        # delta rows (dtype/layout/distribution A/Bs already recorded
        # elsewhere): the right things to shed in a slow phase
        ("yolov5n_bf16", lambda: make_yolov5(dtype=jnp.bfloat16)),
        # MXU-shaped layout (s2d stem + 32ch floor): same detection
        # function, losslessly imported weights, measured +16% at b8
        ("yolov5n_mxu", lambda: make_yolov5(mxu=True)),
        # uniform-cloud delta config: same pipeline, r2's input
        # distribution — quantifies what structured scenes changed
        ("pointpillars_uniform",
         lambda: make_pointpillars(structured=False)),
        ("yolov5n_b64", lambda: make_yolov5(batch=64)),
    ]
    # configs whose row may be emitted from a shortened trial block
    # when the full protocol no longer fits the budget. ONLY the peak
    # claim: r5 observed the b64-fp32 delta row taking this path and
    # burning ~400 s (fresh compile through a slow phase) straight out
    # of the serving reserve — a delta row is shed outright, never
    # bought at the serving rows' expense
    PROVISIONAL_OK = {"yolov5n_b64_mxu_bf16"}

    configs = _STATE["configs"]

    def drop(c, stage, e):
        """A secondary failing mid-bench must never cost the primary
        its one-line stdout contract: log, remove, keep going. The
        primary config failing is fatal by design."""
        if configs and c is configs[0]:
            raise e
        _failed(f"{c.name} ({stage}; row dropped)", e)
        configs.remove(c)

    # Build + warm up lazily in value order, scheduling each secondary
    # against the remaining budget (VERDICT r3 #1b): a config we skip
    # costs a stderr line, never the captured rows. The estimate
    # recalibrates from observed actuals so a cache-warm run (compiles
    # ~20x cheaper) keeps everything.
    est_ratio = 1.0
    for label, factory in factories:
        planned = len(configs) + 1
        # what the rest of the run needs if this config joins: trials
        # (~1 s chip work each + host jitter), latency profiles,
        # primary extras, result emission slack — plus the serving
        # stage's reserve for EVERY secondary (r5: when only the b64
        # tails carried the reserve, mid-value delta rows were
        # admitted right through the serving budget and the serving
        # stage starved at 34s left; no secondary may eat the reserve)
        need_after = TRIALS * planned * 1.4 + 3.0 * planned + 45.0 + 30.0
        if configs:
            need_after += SERVING_RESERVE_S
        est = WARMUP_EST_S.get(label, 90.0) * est_ratio
        if configs and _remaining() < est + need_after:
            # Provisional path: a config whose row matters more than
            # protocol uniformity (the b64 peak claims) runs a
            # SHORTENED block — warmup + 3 trials + immediate emission
            # — if at least that fits; the row is labeled provisional
            # so readers know it skipped the interleaved regime.
            # the serving rows outrank BOTH b64 tails: a provisional
            # block is admitted only when the serving reserve survives
            short_need = est + 3 * 1.6 + 8.0 + SERVING_RESERVE_S
            if label in PROVISIONAL_OK and _remaining() >= short_need:
                try:
                    c = factory()
                    # visible to the SIGTERM flush (it runs exactly in
                    # the budget-exhausted regime this block lives in)
                    # but NOT in configs — the main trial loop must not
                    # re-run a provisional config
                    _STATE["provisional"].append(c)
                    t0 = time.perf_counter()
                    c.warmup()
                    est_ratio = max(
                        0.05,
                        0.5 * est_ratio
                        + 0.5 * ((time.perf_counter() - t0)
                                 / WARMUP_EST_S.get(label, 90.0)),
                    )
                    for _ in range(3):
                        c.run_trial()
                    row = c.result(with_latency=False)
                    row["provisional"] = (
                        "shortened 3-trial block (budget); not "
                        "interleaved with the other configs"
                    )
                    _emit_row(row, primary=False)
                except Exception as e:
                    _failed(f"{label} provisional block", e)
                continue
            print(
                f"{label} warmup skipped: {_remaining():.0f}s left < "
                f"{est:.0f}s est warmup + {need_after:.0f}s to finish",
                file=sys.stderr,
            )
            continue
        try:
            c = factory()
        except Exception as e:
            if not configs:
                # the primary failing to BUILD is as fatal as its
                # warmup/trials failing: a secondary must never be
                # silently promoted to the stdout primary row
                raise
            _failed(f"{label} bench setup", e)
            continue
        configs.append(c)
        t0 = time.perf_counter()
        try:
            c.warmup()
        except Exception as e:
            drop(c, "warmup", e)  # raises for the primary
            continue
        took = time.perf_counter() - t0
        # EMA toward the observed fresh/warm ratio: a cache-warm run
        # (~20x under estimate) schedules everything, a contended slow
        # phase (over estimate) sheds the expensive tail sooner
        est_ratio = max(
            0.05,
            0.5 * est_ratio + 0.5 * (took / WARMUP_EST_S.get(label, 90.0)),
        )
        print(
            f"warmup {c.name}: {took:.1f}s "
            f"(flops/call={c.flops_per_call})",
            file=sys.stderr,
        )

    t0 = time.perf_counter()
    done_trials = 0
    for t in range(TRIALS):          # interleaved: A/B/C/D A/B/C/D ...
        for c in list(configs):
            try:
                c.run_trial()
            except Exception as e:
                drop(c, "trial", e)
        done_trials = t + 1
        print(
            f"trial {done_trials}/{TRIALS} done at "
            f"{time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )
        if done_trials >= MIN_TRIALS and _remaining() < (
            3.0 * len(configs) + 30.0 + len(configs) * 1.4
            # the serving stage's reserve survives the trial loop too
            # (r5: admission guarded it but trials ran through it)
            + SERVING_RESERVE_S
        ):
            print(
                f"stopping trials at {done_trials}/{TRIALS}: "
                f"{_remaining():.0f}s left", file=sys.stderr,
            )
            break

    # emit secondaries IMMEDIATELY (VERDICT r3 #1a) — oldest protocol
    # first so a timeout mid-emission still keeps the earlier rows;
    # latency profiling (LAT_CALLS forced readbacks per config, 50 s+
    # across many configs in a slow phase) must not eat the serving
    # reserve — rows degrade to latency-free before serving starves
    for c in list(configs[1:]):
        try:
            _emit_row(
                c.result(
                    with_latency=_remaining() > 20.0 + SERVING_RESERVE_S,
                ),
                primary=False,
            )
        except Exception as e:
            drop(c, "result", e)

    # the primary gets a second block of trials (2x total): its b8
    # config was the noisiest in r2 (trial_spread 0.219) and round-
    # over-round deltas hang off it. The extras stay in the interleaved
    # REGIME by alternating with a spacer config whose extra samples
    # are discarded — solo back-to-back dispatches would measure a
    # different host phase than the protocol every other sample used.
    if configs and configs[0].trial_ms and _remaining() > (
        45.0 + SERVING_RESERVE_S
    ):
        spacer = configs[1] if len(configs) > 1 else None
        # a failure here is the PRIMARY failing: it propagates
        for t in range(TRIALS):
            if _remaining() < 15.0 + SERVING_RESERVE_S:
                print(
                    f"primary extras stopped at {t}/{TRIALS}: "
                    f"{_remaining():.0f}s left", file=sys.stderr,
                )
                break
            configs[0].run_trial()
            if spacer is not None:
                spacer.run_trial()
                spacer.trial_ms.pop()
        else:
            print(f"primary extra trials done ({TRIALS})", file=sys.stderr)

    _emit_row(
        # the primary's 20 forced readbacks are budget spend too: in a
        # stalled phase they degrade to a latency-free row rather than
        # eat the serving reserve (the last unguarded stage, r5)
        configs[0].result(
            with_latency=_remaining() > 20.0 + SERVING_RESERVE_S
        ),
        primary=True,
    )
    _write_local()
    _save_flops_sidecar()

    # serving stage is strictly best-effort after the contract rows:
    # fresh it precompiles every merge size (minutes, uncached),
    # so it only starts with real budget left
    if _remaining() > SERVING_FLOOR_S:
        try:
            # window sized to the leftover budget (post-fix serving
            # runs ~15 fps, so even a minimum window resolves ~20
            # device batches); each transport's row is emitted the
            # moment its window closes, so a cap landing mid-stage
            # keeps the wire row
            measure_serving(
                duration_s=min(
                    SERVING_MAX_WINDOW_S,
                    max(
                        SERVING_MIN_WINDOW_S,
                        (_remaining() - SERVING_TAIL_S) / 5,
                    ),
                ),
                on_row=lambda row: (_emit_row(row, primary=False),
                                    _write_local()),
            )
            print("serving bench done", file=sys.stderr)
        except Exception as e:
            _failed("serving bench", e)
        _write_local()
        # multi-tenant lifecycle row: synthetic and cheap (~10 s), but
        # only with budget left after the real serving windows
        if _remaining() > 40.0:
            try:
                row = _serve_multitenant_row(
                    duration_s=min(10.0, max(5.0, _remaining() - 30.0))
                )
                _emit_row(row, primary=False)
                _write_local()
            except Exception as e:
                _failed("multitenant bench", e)
        else:
            print(
                f"multitenant row skipped: {_remaining():.0f}s left",
                file=sys.stderr,
            )
        # streaming-session replay row (ISSUE 15): synthetic and cheap
        # like the multitenant row — paced replay, so the window IS the
        # duration; last in the serving stage's value order
        if _remaining() > 40.0:
            try:
                row = _serve_streaming_sessions_row(
                    duration_s=min(8.0, max(4.0, _remaining() - 30.0))
                )
                _emit_row(row, primary=False)
                _write_local()
            except Exception as e:
                _failed("streaming sessions bench", e)
        else:
            print(
                f"streaming sessions row skipped: {_remaining():.0f}s "
                "left", file=sys.stderr,
            )
        # quality-plane sidecar row (ISSUE 17): synthetic and cheap —
        # two short paced windows (sampling off/on) on an echo detector
        if _remaining() > 40.0:
            try:
                row = _serve_quality_plane_row(
                    duration_s=min(8.0, max(4.0, _remaining() - 30.0))
                )
                _emit_row(row, primary=False)
                _write_local()
            except Exception as e:
                _failed("quality plane bench", e)
        else:
            print(
                f"quality plane row skipped: {_remaining():.0f}s left",
                file=sys.stderr,
            )
        # temporal-reuse row (ISSUE 19): two synthetic replay windows
        # (reuse off/on) on an echo detector with a simulated device
        # cost — the streams-per-chip scoreboard off the ledger
        if _remaining() > 40.0:
            try:
                row = _serve_temporal_reuse_row(
                    duration_s=min(8.0, max(4.0, _remaining() - 30.0))
                )
                _emit_row(row, primary=False)
                _write_local()
            except Exception as e:
                _failed("temporal reuse bench", e)
        else:
            print(
                f"temporal reuse row skipped: {_remaining():.0f}s left",
                file=sys.stderr,
            )
    else:
        print(
            f"serving stage skipped: {_remaining():.0f}s left of "
            f"{BUDGET_S:.0f}s budget", file=sys.stderr,
        )
    if _STATE["failed"]:
        print(
            f"{len(_STATE['failed'])} phase(s) failed:\n  "
            + "\n  ".join(_STATE["failed"]), file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
