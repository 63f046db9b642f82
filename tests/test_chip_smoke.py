"""chip_smoke.py, rehearsed on the CPU.

``--rehearse`` runs the script's own phases — kernel checks, threshold
calibration, ``serve`` stack over loopback gRPC, fused-vs-XLA
comparison, /snapshot asserts, SIGTERM drain — at tiny sizes with the
Pallas kernels interpreted, so a wrong path, argument or control flow
is found here and not on the chip. It proves nothing about the chip:
the rehearsal's last line says ``"rehearsal": true`` and never ``ok``.

Each run is a subprocess: the script owns its process (signal handler,
backend init, XLA_FLAGS read at start-up).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(*argv, devices: int = 1, timeout: float = 900.0):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")
    }
    env["JAX_PLATFORMS"] = "cpu"
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def _json_rows(stdout: str) -> list[dict]:
    return [
        json.loads(line) for line in stdout.splitlines()
        if line.startswith("{")
    ]


def test_plain_run_without_a_tpu_fails_and_names_the_device():
    proc = _run()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU, jax found cpu" in proc.stderr


def test_script_alone_without_the_repository_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={**os.environ, "PYTHONPATH": ""},
        capture_output=True, text=True, timeout=120.0,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_one_chip_rehearsal_runs_every_phase():
    proc = _run("--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    rows = _json_rows(proc.stdout)
    assert rows[-1] == {
        "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert not any("ok" in r for r in rows)
    # phase by phase, in order of appearance
    kernels = next(r for r in rows if "kernels" in r)
    assert set(kernels["kernels"]) == {
        "voxel_mean_grid", "voxel_mean_manual", "segment_sum",
    }
    assert kernels["interpreted"] is True
    assert set(next(r for r in rows if "score_gates_and_tolerances" in r)[
        "score_gates_and_tolerances"
    ]) == {"yolov5_crop", "pointpillar_kitti", "second_iou"}
    assert "serve -r " in proc.stdout and "--batching" in proc.stdout
    assert "device: cpu (cpu) x1; compile cache: off" in proc.stdout
    assert "single-device serving on" in proc.stdout
    assert "micro-batching[continuous]" in proc.stdout
    models = {r["model"]: r for r in rows if "model" in r}
    assert models["yolov5_crop"]["fused_stages"] == ["decode_nms"]
    assert models["pointpillar_kitti"]["fused_stages"] == ["decode_nms"]
    assert models["second_iou"]["fused_stages"] == [
        "voxelize_scatter", "decode_nms",
    ]
    for row in models.values():
        assert row["requests"] >= 3
        assert all(n >= 4 for kept in row["kept"] for n in kept)
        # same backend, interpreted kernels: the routes agree outright
        assert row["one_sided"] == 0
    assert "requests in" in models["yolov5_crop"]["merged"]
    assert len(models["yolov5_crop"]["burst"]["kept"]) == 4
    server = next(r for r in rows if "server_device" in r)
    assert server["server_device"]["platform"] == "cpu"
    assert proc.stdout.index("SIGTERM: draining") < proc.stdout.index(
        "drain complete"
    )


def test_four_chip_rehearsal_on_virtual_devices():
    proc = _run("--rehearse", "--chips", "4", devices=4)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    rows = _json_rows(proc.stdout)
    assert rows[-1]["rehearsal"] is True and rows[-1]["device"]["count"] == 4
    # only the mesh path and what it is compared with
    assert not any("kernels" in r for r in rows)
    assert "mesh serving: 4 devices, data axis 4" in proc.stdout
    assert "single-device serving on" in proc.stdout  # plain serve, said so
    (mesh,) = [r for r in rows if "model" in r]
    assert mesh["model"] == "yolov5_crop" and mesh["mesh_agrees_with_plain"]
    assert mesh["shard_devices"] == {
        "input": [0, 1, 2, 3], "output": [0, 1, 2, 3],
    }
    assert mesh["rows_per_device"] == [2, 2, 2, 2]
    assert mesh["plain_serve_devices"] == 1
    assert "drain complete" in proc.stdout
