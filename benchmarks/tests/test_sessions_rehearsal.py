"""CPU rehearsal of a configuration under a ``sessions`` mix kept with
the tests (``data/camera-sessions.json``: 2 callers, streams of 4
requests of 8 frames; in no cell and not under ``traffic/``): the
output check goes through ``loadgen.sessions_sample`` and the real
server, every stream's requests sent in order under one sequence id."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import check_seeds as rehearse  # noqa: E402

MIX = "benchmarks/tests/data/camera-sessions.json"
CELL, CONFIG, CLOSED_MIX = "yolov5n-crop512-bag-replay", "yolov5n-crop512", "bag-replay-b768"


def run(*extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )


def test_sessions_mix_through_the_server():
    out = run("--rehearse", "--traffic-file", MIX)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"], last
    assert last["numbers"]["items"] == 64  # 2 streams x 4 requests x 8 frames, all compared
    assert [l["compared"] for l in lines if "compared" in l] == ["unmatched_share", "score_err_ratio"]


def test_another_mix_is_for_rehearsals_only():
    out = run("--traffic-file", MIX)
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_streams_give_the_numbers_their_requests_give_one_by_one():
    """The streams' 8 requests are the closed mix's 8 requests (the
    same draw), so the check's numbers are the same, digit for digit."""
    streams = rehearse.numbers(CONFIG, MIX, 17)
    single = rehearse.numbers(CONFIG, CLOSED_MIX, 17)
    assert streams["correct"] and streams["items"] == 64
    assert streams == single
