"""Live-driver end-to-end performance (VERDICT r4 #4).

Every bench row measures the DEVICE pipeline; the reference's actual
operating mode is the live hot loop: JPEG-decode -> preprocess ->
infer -> draw -> publish at sensor rate behind a bounded drop-stale
queue (communicator/ros_inference.py:117-175; ros_inference3d.py). This
harness reproduces that loop WITHOUT a ROS master, using only in-tree
pieces: a rosbag of compressed frames / point clouds (io/rosbag.py
writer) replays at its RECORDED rate on a producer thread into the
same drop-oldest bounded queue drivers/ros.py uses; the consumer
decodes, infers, draws, and "publishes" (JPEG-encode / message pack).

Reported per mode: sustained published fps, e2e frame latency
percentiles (capture -> publish, queue wait included), queue-drop
rate, and the device-only fps of the same pipeline for comparison —
the number that shows what the drop-stale overlap design delivers
under a real sensor cadence rather than a saturated pull loop.

device_call_ms separates the device leg from the host legs. Keep the
host idle: a concurrent bench invalidates the decode/draw legs.

Usage:
  python perf/profile_driver_e2e.py 2d [--duration 20] [--sensor-fps 30]
  python perf/profile_driver_e2e.py 3d [--duration 20] [--sensor-fps 10]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import queue
import sys
import tempfile
import threading
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))


def _drop_stale_put(q: queue.Queue, item, dropped: list) -> None:
    """RosDetect2D._callback semantics: drop the OLDEST when full."""
    try:
        q.put_nowait(item)
    except queue.Full:
        try:
            q.get_nowait()
            dropped[0] += 1
        except queue.Empty:
            pass
        q.put_nowait(item)


def _make_image_bag(path: str, n: int, fps: float, hw=(480, 640)) -> None:
    from triton_client_tpu.io import rosbag as rb
    from triton_client_tpu.io.synthdata import synth_detection_frame

    rng = np.random.default_rng(0)
    with rb.BagWriter(path) as w:
        for i in range(n):
            img, _ = synth_detection_frame(rng, hw=hw, num_classes=3)
            w.write(
                "/camera/color/image_raw/compressed",
                rb.numpy_to_compressed_image(img, stamp=i / fps, seq=i),
                t=i / fps,
            )


def _make_cloud_bag(path: str, n: int, fps: float) -> None:
    from triton_client_tpu.io import rosbag as rb
    from triton_client_tpu.io.synthdata import synth_scene_frame

    rng = np.random.default_rng(0)
    with rb.BagWriter(path) as w:
        for i in range(n):
            pts, _ = synth_scene_frame(rng, n_objects=4)
            w.write(
                "/os_cloud_node/points",
                rb.xyzi_to_pointcloud2(pts[:, :4], stamp=i / fps, seq=i),
                t=i / fps,
            )


def _replay(bag_path: str, topic: str, q: queue.Queue, stop: threading.Event,
            emitted: list, dropped: list, rate: float) -> None:
    """Producer: loop the bag at its recorded cadence (scaled by
    ``rate``), pushing (raw message, capture_time) drop-stale."""
    from triton_client_tpu.io import rosbag as rb

    msgs = []
    with rb.BagReader(bag_path) as r:
        for _topic, msg, _t in r.read_messages(topics=[topic]):
            msgs.append(msg)
    period = 1.0 / rate
    t_next = time.perf_counter()
    while not stop.is_set():
        for msg in msgs:
            if stop.is_set():
                return
            now = time.perf_counter()
            if now < t_next:
                time.sleep(t_next - now)
            t_next += period
            _drop_stale_put(q, (msg, time.perf_counter()), dropped)
            emitted[0] += 1


def _consume(q: queue.Queue, stop: threading.Event, decode, infer, publish):
    """RosDetect2D.spin semantics; returns (published, e2e latencies)."""
    lats: list[float] = []
    published = 0
    while not stop.is_set():
        try:
            msg, t_cap = q.get(timeout=0.2)
        except queue.Empty:
            continue
        data = decode(msg)
        result = infer(data)
        publish(data, result)
        lats.append(time.perf_counter() - t_cap)
        published += 1
    return published, lats


def _device_only_fps(infer, data, calls: int = 30) -> float:
    infer(data)  # warm
    t0 = time.perf_counter()
    for _ in range(calls):
        infer(data)
    return calls / (time.perf_counter() - t0)


def run_2d(args) -> dict:
    import cv2

    from triton_client_tpu.io import rosbag as rb
    from triton_client_tpu.io.draw import draw_boxes
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline

    bag = pathlib.Path(tempfile.gettempdir()) / "drive_e2e_2d.bag"
    if not bag.exists():
        _make_image_bag(str(bag), n=90, fps=args.sensor_fps)

    pipeline, _, _ = build_yolov5_pipeline(
        variant="n", num_classes=3, input_hw=(512, 512)
    )

    def decode(msg):
        arr = np.asarray(
            rb.compressed_image_to_numpy(msg), np.uint8
        )
        return np.ascontiguousarray(arr)

    def infer(rgb):
        dets, valid = pipeline.infer(rgb[None])
        return {"detections": np.asarray(dets)[0], "valid": np.asarray(valid)[0]}

    def publish(rgb, result):
        annotated = draw_boxes(
            rgb, result["detections"], result.get("valid"), ("a", "b", "c")
        )
        ok, _ = cv2.imencode(".jpg", annotated[..., ::-1])
        assert ok

    return _run_mode(
        "2d_live", str(bag), "/camera/color/image_raw/compressed",
        decode, infer, publish, args,
    )


def run_3d(args) -> dict:
    from triton_client_tpu.io import rosbag as rb
    from triton_client_tpu.pipelines.detect3d import build_pointpillars_pipeline

    bag = pathlib.Path(tempfile.gettempdir()) / "drive_e2e_3d.bag"
    if not bag.exists():
        _make_cloud_bag(str(bag), n=30, fps=args.sensor_fps)

    pipeline, _, _ = build_pointpillars_pipeline()

    def decode(msg):
        return rb.pointcloud2_to_xyzi(msg)

    def infer(pts):
        out = pipeline.infer(pts)
        return out.result() if hasattr(out, "result") else out

    def publish(pts, result):
        # the reference publishes a detection-array message; the pack
        # cost is the host-side list conversion
        _ = [list(map(float, b)) for b in result["pred_boxes"][:64]]

    return _run_mode(
        "3d_live", str(bag), "/os_cloud_node/points",
        decode, infer, publish, args,
    )


def _run_mode(name, bag, topic, decode, infer, publish, args) -> dict:
    from triton_client_tpu.io import rosbag as rb

    # warm the compile OUTSIDE the timed window (driver.py does the same)
    with rb.BagReader(bag) as r:
        first = next(iter(r.read_messages(topics=[topic])))[1]
    data0 = decode(first)
    infer(data0)

    q: queue.Queue = queue.Queue(maxsize=4)
    stop = threading.Event()
    emitted, dropped = [0], [0]
    producer = threading.Thread(
        target=_replay,
        args=(bag, topic, q, stop, emitted, dropped, args.sensor_fps),
        daemon=True,
    )
    t0 = time.perf_counter()
    producer.start()
    result_box = {}

    def consume():
        result_box["out"] = _consume(q, stop, decode, infer, publish)

    consumer = threading.Thread(target=consume, daemon=True)
    consumer.start()
    time.sleep(args.duration)
    stop.set()
    producer.join(timeout=5)
    consumer.join(timeout=30)
    wall = time.perf_counter() - t0
    published, lats = result_box.get("out", (0, []))

    lat_ms = np.asarray(lats) * 1e3
    dev_fps = _device_only_fps(infer, data0)
    return {
        "mode": name,
        "sensor_fps": args.sensor_fps,
        "duration_s": round(wall, 2),
        "emitted": emitted[0],
        "published": published,
        "published_fps": round(published / wall, 2),
        "dropped": dropped[0],
        "drop_rate": round(dropped[0] / max(emitted[0], 1), 4),
        "e2e_p50_ms": round(float(np.percentile(lat_ms, 50)), 1) if len(lat_ms) else None,
        "e2e_p99_ms": round(float(np.percentile(lat_ms, 99)), 1) if len(lat_ms) else None,
        "device_only_fps": round(dev_fps, 2),
    }


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("mode", choices=("2d", "3d", "both"))
    p.add_argument("--duration", type=float, default=20.0)
    p.add_argument("--sensor-fps", type=float, default=0.0,
                   help="0 = per-mode default (30 for 2d, 10 for 3d)")
    args = p.parse_args(argv)
    modes = ("2d", "3d") if args.mode == "both" else (args.mode,)
    for m in modes:
        a = argparse.Namespace(**vars(args))
        if not a.sensor_fps:
            a.sensor_fps = 30.0 if m == "2d" else 10.0
        row = run_2d(a) if m == "2d" else run_3d(a)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
