"""CPU rehearsal of ``axk1-ep16-l6`` under ``fleet-rounds`` at its
``rehearsal`` sizes, through the real server: the harness's own set-up
and output check (``run.py --rehearse``), and the control
(``check_control.py``: the same entry with ``model.precision: int8``),
which must fail by ``logit_err_ratio``."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "axk1-ep16-l6-fleet-rounds"


def run(script, *argv):
    out = subprocess.run([sys.executable, str(ROOT / "benchmarks" / script), *argv], capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.strip().splitlines() if line.startswith("{")]


def test_the_cell_rehearses_correct_through_the_server():
    lines = run("run.py", "--workload", CELL, "--seed", "2147483659", "--seconds", "1", "--trace", "0", "--rehearse")
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"], last
    assert last["numbers"]["answers"] == 4 * 9 and last["numbers"]["missing"] == 0
    assert [l["compared"] for l in lines if "compared" in l] == [
        "logit_err_ratio", "beyond_tol_share", "near_tie_share", "beyond_wide_tol_share"]


def test_the_int8_control_fails_and_the_stated_precision_does_not():
    control = run("check_control.py", CELL, "2147483659", "--rehearse")[-1]
    sound = run("check_control.py", CELL, "2147483659", "--rehearse", "--sound")[-1]
    assert control["precision"] == "int8" and not control["correct"]
    assert sound["correct"] and sound["numbers"]["logit_err_ratio"] < 2.0 < 3.0 < control["numbers"]["logit_err_ratio"]
