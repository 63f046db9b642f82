#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serve path starts on a TPU.

One process, one chip. It builds a model repository from committed
files (``examples/yolov5_crop``, ``examples/pointpillar_kitti``,
``examples/second_iou`` at their published widths, ``PRNGKey(0)``
weights), stands the server up through the code ``python -m
triton_client_tpu serve -r <repo> --batching --metrics-port auto`` runs
(argv parser -> ``build_server`` -> ``start``), drives it over loopback
with ``GRPCChannel`` as a user would, and holds every response against
the same pipeline's plain XLA route (``fused: off``) evaluated
in-process on the same device and inputs. It then reads the server's
own ``/snapshot`` for the resolved fused stages, the Mosaic kernels in
each launcher and the compile count, drains the server down the SIGTERM
path and prints, as its LAST stdout line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits non-zero — and prints no such line — when jax finds no TPU,
when any phase fails, or when it is run without the repository around
it. A smoke, not a benchmark: the wall times it prints are for a
reader's orientation and are never a result.

    python chip_smoke.py              one chip, as the driver runs it
    python chip_smoke.py --chips 4    ONLY the mesh path: the yolov5
                                      repository behind ``serve --mesh
                                      data=4`` against plain ``serve``
    python chip_smoke.py --rehearse   the same phases at tiny sizes on
                                      whatever backend jax has (CPU:
                                      kernels interpreted); prints
                                      ``"rehearsal": true``, never ok
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import pathlib
import shutil
import signal
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# What ``fused: auto`` resolves to on a TPU (ops/fused.py), per entry.
MODELS = {
    "yolov5_crop": ("2d", ["decode_nms"]),
    "pointpillar_kitti": ("3d", ["decode_nms"]),
    "second_iou": ("3d", ["voxelize_scatter", "decode_nms"]),
}

# Untrained heads score every anchor within a hair of one value
# (yolov5n: 0.2502 +- 0.0001), so at the entries' own thresholds
# (0.3 / 0.1) the 2D model returns nothing and the 3D ones fill
# ``max_det``. The smoke therefore sets the entry format's own score
# threshold key per model, from a permissive pass of the XLA route on
# the device it runs on (Reference.calibrate): high enough that a
# response stays under the ``max_det`` cap, low enough that it holds
# tens of boxes. Measured on CPU at full width, such a gate passes
# 57-63 candidates per lidar scan of which NMS keeps 47-59.
THRESH_KEY = {"2d": "conf_thresh", "3d": "score_thresh"}
PERMISSIVE = 0.05
MIN_DETECTIONS = 4

# Stated float tolerance between the two routes on one device. Boxes:
# BOX_ATOL, in pixels (2D) / metres and radians (3D). Scores: a
# per-model tolerance, 2% of the span of the scores above the gate as
# the calibration pass measured it (untrained yolov5n spreads 300 boxes
# over 1e-4, the lidar heads 128 over 3e-2). Labels must be identical.
# A box whose score lies within that tolerance of the gate may be kept
# by one route alone — the only disagreement allowed — and such boxes
# must stay under ONE_SIDED_SHARE of all boxes compared. The mesh
# comparison (--chips 4) holds two different executables against each
# other, so there a box anywhere in the band may flip, up to
# MESH_ONE_SIDED_SHARE.
# The same goes for the concurrent b4 burst: a b4 request runs alone
# or merged into a b8 launch, neither of which is the executable (or
# the rows) the reference pass used — measured on the v5e, ~15% of
# such a response's boxes flip, while rows routed to the wrong request
# would leave >90% without a partner; BURST_ONE_SIDED_SHARE sits
# between the two.
BOX_ATOL = 2e-2
ONE_SIDED_SHARE = 0.05
MESH_ONE_SIDED_SHARE = 0.10
BURST_ONE_SIDED_SHARE = 0.30


class SmokeFailure(RuntimeError):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(**row) -> None:
    print(json.dumps(row, default=str), flush=True)


# -- the temporary model repository -------------------------------------------


def entry_doc(name: str, rehearse: bool, fused: str | None, thresh: float) -> dict:
    """One committed example entry -> the smoke's config.yaml document:
    same family, widths and batch contract; only the score threshold
    (and, for a rehearsal, the sizes) differ. 3D entries inline their
    dataset yaml (``model:``/``pipeline:`` blocks) because an entry
    that names a ``dataset:`` file takes its thresholds from there."""
    from triton_client_tpu.dataset_config import load_yaml

    doc = load_yaml(str(ROOT / "examples" / name / "config.yaml"))
    kind, _ = MODELS[name]
    if kind == "2d":
        pipeline = dict(doc.get("pipeline", {}))
        pipeline["class_names_file"] = str(ROOT / pipeline["class_names_file"])
        if rehearse:
            doc["model"] = {**doc["model"], "input_hw": [64, 64]}
    else:
        dataset = load_yaml(str(ROOT / doc.pop("dataset")))
        check(dataset.pop("model") == doc["family"], f"{name}: family mismatch")
        pipeline = dict(dataset.pop("pipeline", {}))
        if rehearse:
            dataset["voxel"] = {
                **dataset["voxel"],
                "point_cloud_range": [0.0, -6.4, -3.0, 12.8, 6.4, 1.0],
                "max_voxels": 2048,
            }
            pipeline["point_buckets"] = [4096]
        doc["model"] = dataset
    pipeline[THRESH_KEY[kind]] = thresh
    if fused is not None:
        pipeline["fused"] = fused
    doc["pipeline"] = pipeline
    return doc


def write_repository(root: pathlib.Path, thresholds, rehearse: bool, fused) -> None:
    """``thresholds``: {entry name: score threshold}."""
    import yaml

    for name, thresh in thresholds.items():
        (root / name).mkdir(parents=True)
        with open(root / name / "config.yaml", "w") as f:
            yaml.safe_dump(
                entry_doc(name, rehearse, fused, thresh), f, sort_keys=False
            )


# -- seeded inputs ------------------------------------------------------------


def frame_batches(hw, n: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [
        rng.uniform(0, 255, (8, hw[0], hw[1], 3)).astype(np.float32)
        for _ in range(n)
    ]


def seeded_cloud(voxel: dict, n_points: int, seed: int) -> np.ndarray:
    """~n_points lidar-like returns that occupy at most 60% of the
    entry's ``max_voxels``: the fused voxel route caps occupied cells
    at that budget where the XLA route keeps them all
    (pipelines/detect3d.py), so the comparison stays inside it. Points
    jitter around the centres of a seeded set of cells, a few per
    cell, as returns cluster on surfaces."""
    rng = np.random.default_rng(seed)
    lo = np.asarray(voxel["point_cloud_range"][:3], np.float64)
    hi = np.asarray(voxel["point_cloud_range"][3:], np.float64)
    size = np.asarray(voxel["voxel_size"], np.float64)
    grid = np.round((hi - lo) / size).astype(np.int64)
    n_cells = min(int(0.6 * voxel["max_voxels"]), int(grid.prod()))
    cells = np.stack(
        [rng.integers(0, g, n_cells) for g in grid], axis=1
    )
    pick = rng.integers(0, n_cells, n_points)
    jitter = rng.uniform(-0.3, 0.3, (n_points, 3))
    xyz = lo + (cells[pick] + 0.5 + jitter) * size
    intensity = rng.uniform(0.0, 1.0, (n_points, 1))
    return np.concatenate([xyz, intensity], axis=1).astype(np.float32)


def request_inputs(name: str, rehearse: bool, seed: int) -> list[dict]:
    """Three requests' inputs for an entry: b8 frame batches (the
    reference's served contract) or ~120k-point clouds."""
    doc = entry_doc(name, rehearse, None, PERMISSIVE)
    if MODELS[name][0] == "2d":
        return [
            {"images": frames}
            for frames in frame_batches(doc["model"]["input_hw"], 3, seed)
        ]
    buckets = doc["pipeline"].get("point_buckets", [32768, 65536, 131072])
    n_points = 3000 if rehearse else 120_000
    return [
        cloud_request_inputs(
            seeded_cloud(doc["model"]["voxel"], n_points, seed + i), buckets
        )
        for i in range(3)
    ]


def cloud_request_inputs(points: np.ndarray, buckets) -> dict:
    """The served 3D contract: the cloud padded to its point bucket
    plus the live count (what drivers/driver.channel_infer3d sends)."""
    from triton_client_tpu.ops.voxelize import pad_points

    budget = next(b for b in sorted(buckets) if b >= len(points))
    padded, m = pad_points(points, budget)
    return {"points": padded, "num_points": np.asarray(m, np.int32)}


# -- comparison ---------------------------------------------------------------


def compare_detections(got, want, gate, what: str) -> dict:
    """Served route vs reference route packed rows ``[box..., score,
    label]``: every box of either side has a partner on the other —
    same label, box within BOX_ATOL, score within the gate's tolerance
    — found order-insensitively, because near-tied scores reorder
    candidates between routes; only a box AT the gate may lack one."""
    thresh, tol = gate
    got_d, got_v = (np.asarray(a) for a in got)
    want_d, want_v = (np.asarray(a) for a in want)
    check(got_d.shape == want_d.shape, f"{what}: shape {got_d.shape} vs {want_d.shape}")
    check(np.isfinite(got_d).all(), f"{what}: non-finite detections")
    if got_d.ndim == 2:  # unbatched 3D contract
        got_d, got_v, want_d, want_v = got_d[None], got_v[None], want_d[None], want_v[None]
    stats = {
        "kept": [], "one_sided": 0, "off_gate": [],
        "max_box_err": 0.0, "max_score_err": 0.0,
    }
    for b in range(got_d.shape[0]):
        g, w = got_d[b][got_v[b].astype(bool)], want_d[b][want_v[b].astype(bool)]
        check(len(g) >= MIN_DETECTIONS, f"{what}[{b}]: only {len(g)} detections")
        check(
            len(g) < got_d.shape[1],
            f"{what}[{b}]: {len(g)} detections fill max_det — the cap, "
            "not the gate and NMS, decided the count",
        )
        for rows, others in ((g, w), (w, g)):
            unused = np.ones(len(others), bool)
            for row in rows:
                dist = np.abs(others[:, :-2] - row[:-2]).max(axis=1)
                off = np.abs(others[:, -2] - row[-2])
                fits = (
                    unused & (others[:, -1] == row[-1])
                    & (dist <= BOX_ATOL) & (off <= tol)
                )
                if fits.any():
                    j = int(np.argmin(np.where(fits, dist, np.inf)))
                    unused[j] = False
                    stats["max_box_err"] = max(stats["max_box_err"], float(dist[j]))
                    stats["max_score_err"] = max(stats["max_score_err"], float(off[j]))
                else:
                    stats["one_sided"] += 1
                    if abs(row[-2] - thresh) > tol:
                        stats["off_gate"].append(
                            f"{what}[{b}] box scoring {row[-2]:.8f}"
                        )
        stats["kept"].append(len(g))
    return stats


def summarize(stats: list[dict], what: str, share=ONE_SIDED_SHARE, off_gate_ok=False) -> dict:
    """Fold per-request comparisons and hold them to the stated
    tolerance: boxes without a partner stay under ``share`` of all
    boxes compared and (unless ``off_gate_ok``) all sit at the gate."""
    total = 2 * sum(sum(s["kept"]) for s in stats)
    one_sided = sum(s["one_sided"] for s in stats)
    off_gate = [line for s in stats for line in s["off_gate"]]
    check(
        one_sided <= share * total,
        f"{what}: {one_sided} of {total} boxes have no partner on the other side",
    )
    check(
        off_gate_ok or not off_gate,
        f"{what}: {len(off_gate)} box(es) away from the gate have no partner "
        f"on the other route, e.g. {off_gate[:3]}",
    )
    return {
        "kept": [s["kept"] for s in stats],
        "boxes_compared": total,
        "one_sided": one_sided,
        "one_sided_off_gate": len(off_gate),
        "max_box_err": max(s["max_box_err"] for s in stats),
        "max_score_err": max(s["max_score_err"] for s in stats),
    }


# -- serving ------------------------------------------------------------------


def start_server(repo: pathlib.Path, *extra_argv):
    """``serve``'s own argv parser -> build_server -> start."""
    from triton_client_tpu.cli import serve

    argv = [
        "-r", str(repo), "-a", "127.0.0.1:0", "--batching",
        "--metrics-port", "auto", *extra_argv,
    ]
    print("serve " + " ".join(argv), flush=True)
    args = serve.make_parser().parse_args(argv)
    server = serve.build_server(args)
    server.start()
    print(f"KServe v2 gRPC server listening on port {server.port}", flush=True)
    return server, args


def staged_channel(server):
    """The device channel under the server's batcher."""
    channel = server.channel
    while hasattr(channel, "inner"):
        channel = channel.inner
    return channel


def snapshot(server) -> dict:
    url = f"http://127.0.0.1:{server.metrics_port}/snapshot"
    with urllib.request.urlopen(url, timeout=30.0) as resp:
        return json.load(resp)


def drain(server, args) -> None:
    """Down the SIGTERM path: the handler ``serve`` installs runs on
    this (main) thread and must report a complete drain."""
    from triton_client_tpu.cli import serve

    serve.drain_on_sigterm(server, args.drain_timeout)
    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)  # the handler runs between bytecodes
    sys.stdout.write(captured.getvalue())
    sys.stdout.flush()
    check("drain complete" in captured.getvalue(), "SIGTERM drain did not complete")
    signal.signal(signal.SIGTERM, signal.SIG_DFL)


def infer(client, name: str, inputs: dict):
    from triton_client_tpu.channel.base import InferRequest

    t0 = time.perf_counter()
    out = client.do_inference(InferRequest(name, inputs)).outputs
    return (out["detections"], out["valid"]), time.perf_counter() - t0


@contextlib.contextmanager
def uncached():
    """Keep the smoke's own reference executables out of the persistent
    compile cache: the chip machine caps it (192 MiB, LRU), an
    executable with its weights baked in is ~25 MB, and with the
    references in it a second run found every entry already evicted.
    What stays cached is what the program itself compiles."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


class Reference:
    """One entry on its plain XLA route (``fused: off``), built in this
    process on the same device with the same ``PRNGKey(0)`` weights,
    behind a PERMISSIVE gate — and the served gate applied on the host.

    That is the same function as the XLA route built with the served
    gate: every pipeline gates ``score > thresh`` on the score it
    returns, takes its top-k and runs greedy NMS in descending score
    order, so what survives above a threshold is a prefix of what
    survives above a lower one (as long as neither fills ``max_det``,
    which compare_detections checks). One build and one executable
    per model then serve both the calibration and every comparison."""

    def __init__(self, name: str, rehearse: bool, work: pathlib.Path) -> None:
        from triton_client_tpu.runtime.disk_repository import build_model

        repo = work / "reference"
        write_repository(repo, {name: PERMISSIVE}, rehearse, "off")
        self.name = name
        with uncached():
            self.model = build_model(repo / name)
        check(
            self.model.spec.extra["fused_stages"] == [],
            f"{name}: reference resolved {self.model.spec.extra['fused_stages']}",
        )
        self._passes = {}  # id(first input array) -> (inputs, dets, valid)
        self.gate = None  # (threshold, tolerance), set by calibrate()

    def _infer(self, inputs: dict):
        key = id(next(iter(inputs.values())))
        if key not in self._passes:
            with uncached():
                out = self.model.infer_fn(
                    {k: np.asarray(v) for k, v in inputs.items()}
                )
            # keep ``inputs`` alive: its id is the key
            self._passes[key] = (
                inputs, np.asarray(out["detections"]), np.asarray(out["valid"])
            )
        return self._passes[key][1:]

    def calibrate(self, inputs: dict):
        """(score threshold, score tolerance) on THIS device from one
        permissive pass over the first request: the gate that keeps
        about a quarter of what each image kept (midway between two
        neighbouring scores, so no tie sits on it) and 2% of the score
        span above it."""
        dets, valid = self._infer(inputs)
        if dets.ndim == 2:
            dets, valid = dets[None], valid[None]
        gates, spans = [], []
        for d, v in zip(dets, valid):
            scores = np.sort(d[v.astype(bool)][:, -2])[::-1]
            check(
                len(scores) >= 4 * MIN_DETECTIONS,
                f"{self.name}: {len(scores)} candidates",
            )
            rank = len(scores) // 4
            gates.append(0.5 * (float(scores[rank]) + float(scores[rank + 1])))
            spans.append(float(scores[0]) - float(scores[rank]))
        self.gate = (
            float(np.median(gates)), max(1e-6, 0.02 * float(np.median(spans)))
        )
        return self.gate

    def __call__(self, inputs: dict, rows=slice(None)):
        """(detections, valid) of the XLA route behind the served gate,
        for ``rows`` of a batched input."""
        dets, valid = self._infer(inputs)
        valid = valid.astype(bool) & (dets[..., -2] > np.float32(self.gate[0]))
        return (dets[rows], valid[rows]) if dets.ndim == 3 else (dets, valid)


def concurrently(client, name: str, requests) -> list:
    """Issue the requests at once, one thread each, over the one
    channel; returns their outputs in order (a failed one raises)."""
    outs = [None] * len(requests)

    def call(i):
        outs[i], _ = infer(client, name, requests[i])

    threads = [threading.Thread(target=call, args=(i,)) for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(all(o is not None for o in outs), f"{name}: a concurrent request failed")
    return outs


def drive_2d(client, server, name: str, requests) -> dict:
    """A few b8 frame batches, then concurrent b4 halves until the
    batcher has merged at least one pair into a b8 launch."""
    batches = [r["images"] for r in requests]
    halves = [b[i : i + 4] for b in batches[:2] for i in (0, 4)]
    _, first_call_s = infer(client, name, {"images": batches[0]})
    infer(client, name, {"images": halves[0]})  # the b4 executable
    compiles = snapshot(server)["compile"]["compiles"]
    merges0 = snapshot(server)["batching"]["merges"]

    served, warm_ms = [], []
    for frames in batches:
        out, wall = infer(client, name, {"images": frames})
        served.append((out, {"images": frames}, slice(None)))
        warm_ms.append(wall * 1e3)
    sent = 0
    for _round in range(10):
        results = concurrently(client, name, [{"images": h} for h in halves])
        sent += len(halves)
        groups = snapshot(server)["batching"]["merges"] - merges0 - len(batches)
        if groups < sent:  # fewer launches than requests: a real merge
            break
    check(groups < sent, f"{name}: {sent} concurrent b4 requests never merged")
    # held against the rows of the parent batch's reference pass
    burst = [
        (out, {"images": batches[i // 2]}, slice(4 * (i % 2), 4 * (i % 2) + 4))
        for i, out in enumerate(results)
    ]
    after = snapshot(server)["compile"]["compiles"]
    check(after == compiles, f"{name}: {after - compiles} compile(s) after warm-up")
    return {
        "served": served,
        "burst": burst,
        "first_call_s": first_call_s,
        "warm_ms": warm_ms,
        "merged": f"{sent} b4 requests in {groups} launches",
    }


def drive_3d(client, server, name: str, clouds) -> dict:
    _, first_call_s = infer(client, name, clouds[0])
    compiles = snapshot(server)["compile"]["compiles"]
    served, warm_ms = [], []
    for inputs in clouds:
        out, wall = infer(client, name, inputs)
        served.append((out, inputs, slice(None)))
        warm_ms.append(wall * 1e3)
    # two scans in flight at once through the batcher
    pair = concurrently(client, name, clouds[1:3])
    served += [(out, inputs, slice(None)) for out, inputs in zip(pair, clouds[1:3])]
    after = snapshot(server)["compile"]["compiles"]
    check(after == compiles, f"{name}: {after - compiles} compile(s) after warm-up")
    return {"served": served, "first_call_s": first_call_s, "warm_ms": warm_ms}


def run_kernels(rehearse: bool) -> None:
    """The Pallas kernels no served launcher of this smoke reaches
    (the ``manual`` voxel pipelining form, the standalone NMS kernel,
    the ragged segment-sum), each executed once against a NumPy or XLA
    reference — a kernel that compiles and computes something else
    fails here. Lidar-range coordinates on purpose: an MXU pass that
    rounded its f32 operand to bf16 would be off by centimetres."""
    import jax.numpy as jnp

    from triton_client_tpu.ops import fused
    from triton_client_tpu.ops.nms import _nms_xla
    from triton_client_tpu.ops.pallas_nms import nms_pallas
    from triton_client_tpu.ops.pallas_voxel import (
        POINT_BLOCK,
        sorted_segment_mean_pallas,
    )
    from triton_client_tpu.parallel.ragged_kernels import segment_sum_pallas

    interpret = fused.fused_interpret()
    rng = np.random.default_rng(7)
    n, slots = (4 * POINT_BLOCK, 1500) if rehearse else (131072, 40000)
    ids = np.sort(rng.integers(0, slots, n)).astype(np.int32)
    vals = np.zeros((8, n), np.float32)
    vals[:3] = rng.uniform(-40.0, 70.0, (3, n))
    vals[7] = 1.0  # the count row
    sums = np.zeros((8, slots), np.float64)
    np.add.at(sums.T, ids, vals.T.astype(np.float64))
    want = sums / np.maximum(sums[7:8], 1.0)
    errs = {}
    for form in ("grid", "manual"):
        got = np.asarray(
            sorted_segment_mean_pallas(
                jnp.asarray(vals), jnp.asarray(ids), num_slots=slots,
                interpret=interpret, pipeline=form,
            )
        )[:, :slots]
        errs[f"voxel_mean_{form}"] = float(np.abs(got - want).max())

    # 256 boxes in 4 jittered copies each: NMS has to drop ~3 in 4
    corner = np.repeat(rng.uniform(0, 480, (256, 2)), 4, axis=0)
    extent = np.repeat(rng.uniform(8, 64, (256, 2)), 4, axis=0)
    boxes = np.concatenate([corner, corner + extent], 1) + rng.uniform(-1, 1, (1024, 4))
    boxes = boxes.astype(np.float32)
    scores = rng.uniform(0, 1, 1024).astype(np.float32)
    got_i, got_v = nms_pallas(jnp.asarray(boxes), jnp.asarray(scores), max_det=300, interpret=interpret)
    want_i, want_v = _nms_xla(jnp.asarray(boxes), jnp.asarray(scores), 0.45, max_det=300)
    got_v, want_v = np.asarray(got_v), np.asarray(want_v)
    check(
        np.array_equal(got_v, want_v)
        and np.array_equal(np.asarray(got_i)[got_v], np.asarray(want_i)[want_v]),
        "nms_pallas keeps a different index sequence than the XLA loop",
    )
    check(MIN_DETECTIONS <= got_v.sum() < 300, f"nms_pallas kept {got_v.sum()} of 1024")

    rows = rng.uniform(-1, 1, (1024, 64)).astype(np.float32)
    seg = rng.integers(0, 8, 1024).astype(np.int32)
    want_s = np.zeros((8, 64), np.float64)
    np.add.at(want_s, seg, rows.astype(np.float64))
    got_s = np.asarray(segment_sum_pallas(jnp.asarray(rows), jnp.asarray(seg), 8, interpret=interpret))
    errs["segment_sum"] = float(np.abs(got_s - want_s).max())
    say(kernels=errs, nms_pallas_kept=int(got_v.sum()), interpreted=interpret)
    for what, err in errs.items():
        check(err <= 1e-3, f"{what}: off by {err} from the float64 reference")


def run_one_chip(rehearse: bool, work: pathlib.Path) -> None:
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    run_kernels(rehearse)

    requests = {name: request_inputs(name, rehearse, seed=22) for name in MODELS}
    refs = {name: Reference(name, rehearse, work) for name in MODELS}
    gates = {name: refs[name].calibrate(requests[name][0]) for name in MODELS}
    say(score_gates_and_tolerances=gates)
    served_repo = work / "served"
    # a rehearsal off-TPU has to ask for the kernels (interpreted);
    # on the chip the entries say nothing and ``auto`` decides
    write_repository(
        served_repo, {name: gate[0] for name, gate in gates.items()},
        rehearse, "on" if rehearse else None,
    )

    server, args = start_server(served_repo)
    try:
        client = GRPCChannel(
            f"127.0.0.1:{server.port}", timeout_s=900.0, retries=0
        )
        try:
            say(transport=client.transport)
            driven = {}
            for name, (kind, _) in MODELS.items():
                drive = drive_2d if kind == "2d" else drive_3d
                driven[name] = drive(client, server, name, requests[name])
        finally:
            client.close()

        snap = snapshot(server)
        rows = {m["model"]: m for m in snap["models"]}
        for name, (_, stages) in MODELS.items():
            row = rows[name]
            check(
                row["fused_stages"] == stages,
                f"{name}: fused_stages {row['fused_stages']} != {stages}",
            )
            # every fused stage is at least one Mosaic kernel in the
            # launcher the server compiled; interpreted, it would be 0
            check(
                rehearse or row.get("pallas_kernels", 0) >= len(stages),
                f"{name}: {row.get('pallas_kernels')} tpu_custom_call(s) in "
                f"the launcher for stages {stages} — a kernel ran interpreted",
            )
        check(snap["batching"]["scheduler"] == "continuous", "not the continuous batcher")

        failures = []
        for name in MODELS:

            def compared(entries, what, ref=refs[name]):
                return [
                    compare_detections(
                        out, ref(inputs, rows), ref.gate, f"{name} {what} {i}"
                    )
                    for i, (out, inputs, rows) in enumerate(entries)
                ]

            try:
                stats = summarize(compared(driven[name]["served"], "request"), name)
                if "burst" in driven[name]:
                    burst = summarize(
                        compared(driven[name]["burst"], "burst request"),
                        f"{name} burst",
                        share=BURST_ONE_SIDED_SHARE, off_gate_ok=True,
                    )
                    stats["burst"] = {
                        k: burst[k] for k in ("kept", "boxes_compared", "one_sided")
                    }
            except SmokeFailure as e:  # report every model, fail below
                failures.append(str(e))
                say(model=name, FAILED=str(e))
                continue
            say(
                model=name,
                requests=len(stats["kept"]),
                fused_stages=rows[name]["fused_stages"],
                pallas_kernels=rows[name].get("pallas_kernels"),
                first_call_s=round(driven[name]["first_call_s"], 2),
                warm_ms=[round(v, 2) for v in driven[name]["warm_ms"]],
                merged=driven[name].get("merged"),
                **stats,
                agrees_with="fused: off (plain XLA route; served gate applied on the host), same device",
            )
        say(
            server_device=snap["device"],
            compiles=snap["compile"],
            launched=snap["channel"]["launched"],
            merge_occupancy=snap["batching"].get("merge_occupancy"),
        )
        check(not failures, "; ".join(failures))
    except BaseException:
        server.stop()
        raise
    drain(server, args)


# -- the mesh path (--chips 4) ------------------------------------------------


def run_four_chips(rehearse: bool, work: pathlib.Path) -> None:
    """Only what exists across chips: the yolov5 repository behind
    ``serve --mesh data=4`` (ShardedTPUChannel) against plain ``serve``
    (one device) in the same process, request by request."""
    import jax

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    name = "yolov5_crop"
    check(len(jax.devices()) >= 4, f"--chips 4 with {len(jax.devices())} device(s)")
    batches = [r["images"] for r in request_inputs(name, rehearse, seed=44)]
    gate = Reference(name, rehearse, work).calibrate({"images": batches[0]})
    say(score_gate_and_tolerance=gate)
    repo = work / "served"
    write_repository(repo, {name: gate[0]}, rehearse, "on" if rehearse else None)

    sharded, sharded_args = start_server(repo, "--mesh", "data=4")
    plain, _ = start_server(repo)
    try:
        # the mesh server takes each b8 batch whole (2 rows per device);
        # plain serve takes the same rows as four b2 requests, so both
        # sides run the per-device shape and what is compared is the
        # sharding — placement, order, slice-back — not how two batch
        # sizes round bf16
        outs = {"mesh": [], "plain": []}
        for label, server in (("mesh", sharded), ("plain", plain)):
            client = GRPCChannel(
                f"127.0.0.1:{server.port}", timeout_s=900.0, retries=0
            )
            try:
                for frames in [batches[0], *batches]:  # first call compiles
                    parts = [frames] if label == "mesh" else np.split(frames, 4)
                    got = [infer(client, name, {"images": p})[0] for p in parts]
                    outs[label].append(
                        tuple(np.concatenate([np.asarray(g[k]) for g in got]) for k in (0, 1))
                    )
            finally:
                client.close()
        outs = {label: rows[1:] for label, rows in outs.items()}
        stats = summarize(
            [
                compare_detections(m, p, gate, f"{name} mesh-vs-plain request {i}")
                for i, (m, p) in enumerate(zip(outs["mesh"], outs["plain"]))
            ],
            name, share=MESH_ONE_SIDED_SHARE, off_gate_ok=True,
        )
        bitwise = all(
            np.array_equal(np.asarray(m[0]), np.asarray(p[0]))
            for m, p in zip(outs["mesh"], outs["plain"])
        )

        # where the mesh server's arrays live: stage one request the
        # way the channel does and launch it with its own launcher
        base = staged_channel(sharded)
        model = base._repository.get(name, "")
        inputs, _meta = base._place_inputs(
            model, InferRequest(name, {"images": batches[0]})
        )
        launcher, donate, _ = base._launcher(model)
        out = launcher(
            {k: v for k, v in inputs.items() if k in donate},
            {k: v for k, v in inputs.items() if k not in donate},
        )
        # the device array behind the staged frames (its one leaf where
        # they crossed in their transfer form: channel/staged.py)
        (placed,) = jax.tree_util.tree_leaves(inputs["images"])
        shards = {
            "input": sorted(s.device.id for s in placed.addressable_shards),
            "output": sorted(s.device.id for s in out["detections"].addressable_shards),
        }
        input_rows = [s.data.shape[0] for s in placed.addressable_shards]
        for what, ids in shards.items():
            check(len(set(ids)) == 4, f"{what} shards on devices {ids}, not four")
        check(input_rows == [2, 2, 2, 2], f"b8 split as {input_rows}")
        plain_devices = int(staged_channel(plain).fetch_channel().devices.size)
        check(plain_devices == 1, f"plain serve spans {plain_devices} devices")
        say(
            model=name,
            requests=len(stats["kept"]),
            mesh_agrees_with_plain=True,  # summarize() raised otherwise
            bitwise=bitwise,
            **stats,
            shard_devices=shards,
            rows_per_device=input_rows,
            plain_serve_devices=plain_devices,
            mesh_stats={
                k: snapshot(sharded)["channel"].get(k)
                for k in ("mesh_devices", "data_axis_size")
            },
        )
    except BaseException:
        sharded.stop()
        plain.stop()
        raise
    plain.stop()
    drain(sharded, sharded_args)


# -- entry --------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs ONLY the mesh path and its comparison (builder-run)",
    )
    p.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes, any backend, interpreted kernels off-TPU; "
        "proves control flow only and never prints ok",
    )
    args = p.parse_args(argv)

    from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()  # before the first compile
    import jax

    from triton_client_tpu.obs.roofline import device_info

    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event.endswith("/compilation_cache/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    device = device_info()
    if device["platform"] != "tpu" and not args.rehearse:
        print(
            f"chip_smoke: needs a TPU, jax found {device['platform']} "
            f"({device['kind']}) x{device['count']}",
            file=sys.stderr,
        )
        return 2
    def cache_usage() -> dict:
        files = [
            p for p in pathlib.Path(cache_dir).rglob("*") if p.is_file()
        ] if cache_dir else []
        return {
            "files": len(files),
            "mib": round(sum(p.stat().st_size for p in files) / 2**20, 1),
        }

    say(
        device=device,
        compile_cache_dir=cache_dir or "off",
        cache_at_start=cache_usage(),
        # a cap makes jax evict least-recently-used entries; a smoke
        # that wrote more than the cap would never hit on its next run
        cache_max_bytes=jax.config.jax_compilation_cache_max_size,
    )

    t0 = time.perf_counter()
    work = pathlib.Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.chips == 4:
            run_four_chips(args.rehearse, work)
        else:
            run_one_chip(args.rehearse, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    stats = jax.devices()[0].memory_stats() or {}
    say(
        wall_s=round(time.perf_counter() - t0, 1),
        compile_cache=cache,
        cache_at_end=cache_usage(),
        warm_start=cache["hits"] > cache["misses"],
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
    )
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
