"""Closed accuracy loop: prove the stack DETECTS (VERDICT r2 #1).

Real weights stay blocked by zero egress, so the in-environment
accuracy proof is a closed loop over synthetic labeled scenes
(io/synthdata.py): train with the `train` CLI, export to a model
repository, run the FULL detect pipeline (preprocess -> forward ->
decode -> NMS) over a held-out split via the detect CLI's --repo path,
and score mAP through eval/detection_map.py — exercising train,
checkpoint/export, repository loading, pipeline, and eval end to end
(the reference's accuracy-regression role: communicator/
evaluate_inference.py:400-446).

Every stage runs as a subprocess, one after another (a chip belongs
to one process at a time, and this parent never touches jax), so the
CLIs are driven through their real argv surface.

Usage:
  python perf/closed_loop.py 2d [--steps N] [--size S] [--device tpu|cpu]
  python perf/closed_loop.py 3d [--steps N] [--device tpu|cpu] [--vfe auto|grouped]

Targets (VERDICT r2 "Next round" #1): mAP@0.5 >= 0.9 (2D), >= 0.7 (3D).
"""

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
RUNS = REPO_ROOT / "closed_loop_runs"

CPU_PRELUDE = "import jax; jax.config.update('jax_platforms','cpu'); "


def _python(code: str, device: str, log: pathlib.Path) -> None:
    """Run `code` in a fresh interpreter from the repo root (cwd covers
    the import path). CPU mode forces the platform before first jax
    use."""
    prelude = CPU_PRELUDE if device == "cpu" else ""
    t0 = time.time()
    with open(log, "ab") as f:
        f.write(f"\n=== {code[:120]} ===\n".encode())
        f.flush()
        proc = subprocess.run(
            [sys.executable, "-c", prelude + code],
            cwd=REPO_ROOT, stdout=f, stderr=subprocess.STDOUT,
        )
    if proc.returncode:
        tail = log.read_text().splitlines()[-25:]
        raise RuntimeError(
            f"stage failed rc={proc.returncode} ({time.time()-t0:.0f}s):\n"
            + "\n".join(tail)
        )
    print(f"  stage done in {time.time()-t0:.0f}s", flush=True)


def _python_json(code: str, device: str, log: pathlib.Path) -> dict:
    """Like _python but parses the LAST stdout line as JSON."""
    prelude = CPU_PRELUDE if device == "cpu" else ""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-c", prelude + code],
        cwd=REPO_ROOT, capture_output=True, text=True,
    )
    with open(log, "a") as f:
        f.write(f"\n=== {code[:120]} ===\n{proc.stdout}\n{proc.stderr}\n")
    if proc.returncode:
        raise RuntimeError(
            f"stage failed rc={proc.returncode}:\n{proc.stderr[-2000:]}"
        )
    print(f"  stage done in {time.time()-t0:.0f}s", flush=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_2d(args) -> dict:
    work = RUNS / (
        f"2d_s{args.size}_c{args.classes}_n{args.n_train}x{args.n_hold}"
    )
    work.mkdir(parents=True, exist_ok=True)
    log = work / "log.txt"
    train_dir, hold_dir = work / "train", work / "hold"

    if not (train_dir / "gt.jsonl").exists():
        print(f"generating {args.n_train}+{args.n_hold} frames ...", flush=True)
        _python(
            "from triton_client_tpu.io.synthdata import write_detection_dataset;"
            f"write_detection_dataset(r'{train_dir}', {args.n_train}, "
            f"hw=({args.size},{args.size}), num_classes={args.classes}, seed=0);"
            f"write_detection_dataset(r'{hold_dir}', {args.n_hold}, "
            f"hw=({args.size},{args.size}), num_classes={args.classes}, seed=1)",
            "cpu", log,
        )

    repo = work / "repo"
    print(f"training yolov5{args.variant} {args.steps} steps "
          f"@{args.size}px b{args.batch} on {args.device} ...", flush=True)
    _python(
        "from triton_client_tpu.cli.train import main; main("
        f"['-i', r'{train_dir / 'images'}', '--gt', r'{train_dir / 'gt.jsonl'}',"
        f" '--input-size', '{args.size}', '-c', '{args.classes}',"
        f" '--variant', '{args.variant}',"
        f" '-b', '{args.batch}', '--steps', '{args.steps}', '--lr', '{args.lr}',"
        f" '--lr-final', '{args.lr_final}',"
        f" '--checkpoint-dir', r'{work / 'ckpts'}', '--save-every', '500',"
        f" '--export', r'{repo}', '-m', 'loop2d', '--log-every', '50'])",
        args.device, log,
    )

    print("evaluating full pipeline over holdout ...", flush=True)
    report = _python_json(
        "from triton_client_tpu.cli.detect2d import main; main("
        f"['-m', 'loop2d', '--repo', r'{repo}', '-i', r'{hold_dir / 'images'}',"
        f" '--gt', r'{hold_dir / 'gt.jsonl'}', '--conf', '{args.conf}'])",
        args.device, log,
    )
    out = {
        "loop": "2d",
        "model": f"yolov5{args.variant}",
        "steps": args.steps,
        "size": args.size,
        "classes": args.classes,
        "train_frames": args.n_train,
        "holdout_frames": report["eval"]["frames"],
        "map50": round(report["eval"]["map50"], 4),
        "map": round(report["eval"]["map"], 4),
        "precision": round(report["eval"]["precision"], 4),
        "recall": round(report["eval"]["recall"], 4),
        "per_class_ap50": report["eval"]["per_class_ap50"],
        "target_map50": 0.9,
        "pass": report["eval"]["map50"] >= 0.9,
    }
    return out


def run_3d(args) -> dict:
    # workdir encodes the dataset recipe — tag and generator kwargs are
    # built from the SAME dict, so a recipe change can never silently
    # reuse a stale cached dataset. The centerpoint recipe matches the
    # nuScenes 10-sweep contract (nusc_centerpoint_pp_02voxel_two_pfn_
    # 10sweep.py) with moving objects, plus front-biased returns so
    # full-circle yaw is observable (see synth_scene_frame).
    family = args.family
    sweeps = family == "centerpoint"
    recipe = (
        {"n_sweeps": 10, "velocity_max": 3.0, "front_bias": 0.65}
        if sweeps
        else {}
    )
    tag = "".join(
        f"_{k}{v}" for k, v in sorted(recipe.items())
    ).replace(".", "p")
    work = RUNS / f"3d_{family}_n{args.n_train}x{args.n_hold}_road{tag}"
    work.mkdir(parents=True, exist_ok=True)
    log = work / "log.txt"
    train_dir, hold_dir = work / "train", work / "hold"

    if not (train_dir / "gt3d.jsonl").exists():
        print(f"generating {args.n_train}+{args.n_hold} scenes ...", flush=True)
        # road-like yaw: the distribution the reference's axis-aligned
        # anchor config is designed for (KITTI traffic). The extra
        # kwargs come from the same `recipe` dict the cache tag is
        # derived from.
        extra = "".join(f", {k}={v}" for k, v in sorted(recipe.items()))
        _python(
            "from triton_client_tpu.io.synthdata import write_scene_dataset;"
            f"write_scene_dataset(r'{train_dir}', {args.n_train}, seed=0,"
            f" yaw_mode='road'{extra});"
            f"write_scene_dataset(r'{hold_dir}', {args.n_hold}, seed=1,"
            f" yaw_mode='road'{extra})",
            "cpu", log,
        )

    repo = work / "repo"
    config_arg = ""
    if family == "centerpoint":
        config_arg = ", '--config', r'data/kitti_centerpoint.yaml'"
    print(f"training {family} {args.steps} steps b{args.batch} "
          f"on {args.device} ...", flush=True)
    _python(
        "from triton_client_tpu.cli.train import main; main("
        f"['--family', '{family}',"
        f" '-i', r'{train_dir / 'clouds'}', '--gt', r'{train_dir / 'gt3d.jsonl'}',"
        f" '-b', '{args.batch}', '--steps', '{args.steps}', '--lr', '{args.lr}',"
        f" '--lr-final', '{args.lr_final}', '--points', '22000',"
        f" '--checkpoint-dir', r'{work / 'ckpts'}', '--save-every', '500',"
        f" '--export', r'{repo}', '-m', 'loop3d', '--log-every', '50'"
        f"{config_arg}])",
        args.device, log,
    )

    print(f"evaluating full 3D pipeline (vfe={args.vfe}) ...", flush=True)
    report = _python_json(
        "from triton_client_tpu.cli.detect3d import main; main("
        f"['-m', 'loop3d', '--repo', r'{repo}', '-i', r'{hold_dir / 'clouds'}',"
        f" '--gt', r'{hold_dir / 'gt3d.jsonl'}', '--score', '{args.conf}'"
        + (f", '--vfe', '{args.vfe}'" if args.vfe else "")
        + "])",
        args.device, log,
    )
    out = {
        "loop": "3d",
        "model": family,
        "steps": args.steps,
        "vfe": args.vfe or "default",
        "holdout_frames": report["eval"]["frames"],
        "map50": round(report["eval"]["map50"], 4),
        "map": round(report["eval"]["map"], 4),
        "precision": round(report["eval"]["precision"], 4),
        "recall": round(report["eval"]["recall"], 4),
        "per_class_ap50": report["eval"]["per_class_ap50"],
        "target_map50": 0.7,
        "pass": report["eval"]["map50"] >= 0.7,
    }
    if sweeps:
        # END-TO-END velocity proof: decode the served model over the
        # holdout sweeps, match peaks to GT centers, compare |v_err|
        # against the predict-zero baseline |v_gt|
        vel = _python_json(
            "from perf.velocity_probe import main; main("
            f"[r'{repo}', r'{hold_dir}'])",
            args.device, log,
        )
        out["vel_mae"] = vel["vel_mae"]
        out["vel_baseline_mae"] = vel["baseline_mae"]
        out["vel_matched"] = vel["matched"]
        out["vel_pass"] = vel["vel_mae"] < 0.5 * vel["baseline_mae"]
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("loop", choices=("2d", "3d"))
    p.add_argument("--steps", type=int, default=2000)
    p.add_argument("--size", type=int, default=256)
    p.add_argument("--classes", type=int, default=3)
    p.add_argument("--variant", default="n")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--lr-final", type=float, default=0.0,
                   help="cosine-decay the lr to this (0 = constant)")
    p.add_argument("--conf", type=float, default=0.05)
    p.add_argument("--n-train", type=int, default=600)
    p.add_argument("--n-hold", type=int, default=100)
    p.add_argument("--device", default="tpu", choices=("tpu", "cpu"))
    p.add_argument("--vfe", default="", help="3d: vfe mode override")
    p.add_argument("--family", default="pointpillars",
                   choices=("pointpillars", "second_iou", "centerpoint"),
                   help="3d loop family; centerpoint adds 5-sweep "
                   "moving-object scenes + the velocity probe")
    args = p.parse_args()
    run = run_2d if args.loop == "2d" else run_3d
    result = run(args)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
