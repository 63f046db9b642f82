"""CPU rehearsal of every cell's set-up and output check.

Run by hand before a chip call (``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``; some ten minutes): tiny sizes, Pallas kernels
interpreted, no timing taken and no device metric printed. For each
configuration: ``correct`` holds on twelve seeds; it fails when the
served model is built in the precision below the one the configuration
states (the configuration's ``control``), and when one head's output
is pushed beyond tolerance. ``test_cell_through_the_server`` drives
each cell's check through ``run.py --rehearse``: the real ``serve``
entry, the batcher and ``GRPCChannel``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import check_seeds as rehearse  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {}
for _cell in BENCH["workloads"]:  # one traffic mix per configuration is enough here
    CONFIGS.setdefault(_cell["config"], _cell["traffic"])
SEEDS = [3, 17, 101, 999, 4242, 65537, 1234567, 2**31 - 1, 2**31 + 11, 2**31 + 123457, 77, 2024]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_correct_on_a_dozen_seeds(config, seed):
    got = rehearse.numbers(config, CONFIGS[config], seed)
    assert got["correct"], got
    assert got["empty_items"] == 0 and got["full_items"] == 0, got


@pytest.mark.parametrize("seed", SEEDS[:3])
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_lower_precision_is_not_correct(config, seed):
    cfg = json.loads((ROOT / f"benchmarks/configs/{config}.json").read_text())
    got = rehearse.numbers(config, CONFIGS[config], seed, precision=cfg["control"]["serve_precision"])
    assert not got["correct"], got


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_perturbed_head_is_not_correct(config):
    got = rehearse.numbers(config, CONFIGS[config], SEEDS[0], perturb=0.5)
    assert not got["correct"], got


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_through_the_server(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", cell, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"], last


def test_no_accelerator_means_no_result_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cell = BENCH["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300,
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
