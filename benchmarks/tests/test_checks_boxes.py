"""The ``boxes`` check kind through the seam (``checks/boxes.py``)
gives the numbers ``compare.compare`` and ``compare.verdict`` give on
the same rows: a sound pair of row sets, a control's (scores moved by
several roundings) and one with a box at the gate."""

from __future__ import annotations

import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import compare  # noqa: E402
from benchmarks.checks import boxes  # noqa: E402

CFG = json.loads((ROOT / "benchmarks/configs/yolov5n-crop512.json").read_text())
MAX_DET, GATE = CFG["pipeline"]["max_det"], CFG["pipeline"]["conf_thresh"]
SENSITIVITY = 0.004


def reference_rows(rng, items=6):
    """Per item some tens of well-separated boxes ``[x1 y1 x2 y2 score label]``."""
    out = []
    for _ in range(items):
        n = int(rng.integers(20, 36))
        xy = np.stack(np.divmod(rng.permutation(36)[:n], 6), 1) * 80.0  # cells of a 6x6 grid, 80 px apart
        wh = rng.uniform(20, 60, (n, 2))
        score = rng.uniform(GATE + 0.05, 0.95, n)
        out.append(np.concatenate([xy, xy + wh, score[:, None], rng.integers(0, 2, (n, 1))], 1).astype(np.float32))
    return out


def moved(rows, rng, score_std, box_std=0.2):
    """What a served evaluation returns: the same boxes, a little off, in another order."""
    out = []
    for r in rows:
        r = r[rng.permutation(len(r))].copy()
        r[:, :4] += rng.normal(0, box_std, (len(r), 4))
        r[:, 4] += rng.normal(0, score_std, len(r))
        out.append(r.astype(np.float32))
    return out


def responses_of(served, per_response=3):
    """Served rows as the entry answers: ``[b, max_det, 6]`` under a mask."""
    out = []
    for i in range(0, len(served), per_response):
        part = served[i : i + per_response]
        det = np.zeros((len(part), MAX_DET, 6), np.float32)
        valid = np.zeros((len(part), MAX_DET), bool)
        for k, r in enumerate(part):
            det[k, : len(r)], valid[k, : len(r)] = r, True
        out.append(types.SimpleNamespace(outputs={"detections": det, "valid": valid}))
    return out


def cases():
    rng = np.random.default_rng(28)
    want = reference_rows(rng)
    sound = moved(want, rng, SENSITIVITY)
    control = moved(want, rng, 3 * SENSITIVITY)
    at_gate = [r.copy() for r in sound]
    edge = np.asarray([[400.0, 400.0, 430.0, 440.0, GATE + 0.01, 1.0]], np.float32)  # served alone, within gate_band
    at_gate[0] = np.concatenate([at_gate[0], edge])
    return want, {"sound": sound, "control": control, "at_gate": at_gate}


@pytest.mark.parametrize("case", ["sound", "control", "at_gate"])
def test_boxes_through_the_seam_gives_compare_s_numbers(case, tmp_path):
    want, served = cases()
    got = served[case]
    np.savez(tmp_path / "reference.npz", **{f"rows_{i}": r for i, r in enumerate(want)},
             gated=np.asarray([len(r) for r in want]), sensitivity=np.asarray(SENSITIVITY))
    ok, lines, numbers = boxes.served(responses_of(got), tmp_path / "reference.npz", CFG)

    direct = compare.compare(got, want, "boxes", 4, MAX_DET, GATE, CFG["check"], SENSITIVITY)
    direct_ok, direct_lines = compare.verdict(direct, CFG["check"])
    direct.pop("pairs")
    assert numbers == direct and lines == direct_lines and ok == direct_ok
    assert [l["number"] for l in lines] == ["unmatched_share", "score_err_ratio"]
    assert [l["limit"] for l in lines] == [CFG["check"]["max_unmatched_share"], CFG["check"]["max_score_err_ratio"]]
    assert ok == (case != "control")
    assert numbers["at_gate_left_out"] == (1 if case == "at_gate" else 0)
    if case == "at_gate":
        assert numbers["unmatched"] == 0  # the box at the gate is in neither count


def test_streams_are_compared_request_by_request(tmp_path):
    """A sessions mix hands the check streams: lists of responses."""
    want, served = cases()
    np.savez(tmp_path / "reference.npz", **{f"rows_{i}": r for i, r in enumerate(want)},
             gated=np.asarray([len(r) for r in want]), sensitivity=np.asarray(SENSITIVITY))
    flat = responses_of(served["sound"], per_response=2)
    nested = [flat[:2], flat[2:]]
    assert boxes.served(nested, tmp_path / "reference.npz", CFG) == boxes.served(flat, tmp_path / "reference.npz", CFG)


def test_well_formed_and_launch_request():
    (good,) = responses_of(cases()[0][:3])
    assert boxes.well_formed(good, CFG) is None
    short = types.SimpleNamespace(outputs={"detections": good.outputs["detections"][:, :10], "valid": good.outputs["valid"]})
    assert boxes.well_formed(short, CFG).startswith("shapes")
    assert "missing" in boxes.well_formed(types.SimpleNamespace(outputs={}), CFG)
    request = {"images": np.arange(2 * 4 * 4 * 3, dtype=np.uint8).reshape(2, 4, 4, 3)}
    assert boxes.launch_request(request, 0) is request
    wide = boxes.launch_request(request, 5)["images"]
    assert wide.shape == (5, 4, 4, 3) and (wide[2] == request["images"][0]).all()


def test_entry_of_a_rehearsal_shrinks_the_input():
    from benchmarks.server_child import apply_rehearsal, entry_doc

    full = entry_doc(CFG, False, None)
    small = entry_doc(apply_rehearsal(CFG), True, "int8")
    assert full["max_batch_size"] == small["max_batch_size"] == CFG["max_batch_size"]
    assert list(full["model"]["input_hw"]) == [512, 512] and "precision" not in full["model"]
    assert list(small["model"]["input_hw"]) == CFG["rehearsal"]["model"]["input_hw"]
    assert small["model"]["precision"] == "int8" and small["pipeline"]["fused"] == "on"
    assert pathlib.Path(full["pipeline"]["class_names_file"]).is_absolute()
