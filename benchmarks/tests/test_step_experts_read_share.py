"""``step_experts_read_share`` on two hand-made snapshots: the two
counters' growth over the window, in per cent; nothing from a program
that lacks the counters (the parent of the PR that brought them), and
nothing from a window without a step launch."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.layer_metrics import step_experts_read_share  # noqa: E402


def snapshot(**counters):
    return {"sessions": {"models": {"ling3_ep8": counters}}}


def ctx(before, after):
    return {"model": "ling3_ep8", "snapshot_before": before, "snapshot_after": after}


def test_reads_the_growth_between_two_snapshots(capsys):
    before = snapshot(lm_step_launches=80, lm_step_experts_chosen=3_000, lm_step_experts_held=80 * 768)
    after = snapshot(lm_step_launches=300, lm_step_experts_chosen=3_000 + 10_296, lm_step_experts_held=300 * 768)
    assert step_experts_read_share.read(ctx(before, after)) == pytest.approx(100 * 10_296 / (220 * 768))
    assert json.loads(capsys.readouterr().out)["step_experts"] == {
        "lm_step_experts_chosen": 10_296, "lm_step_experts_held": 220 * 768}


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # no session plane at all
    (snapshot(lm_step_launches=80), snapshot(lm_step_launches=300)),  # a program without the counters
    (snapshot(lm_step_experts_chosen=5, lm_step_experts_held=768),
     snapshot(lm_step_experts_chosen=5, lm_step_experts_held=768)),  # no step launch in the window
    ({}, snapshot(lm_step_experts_chosen=5, lm_step_experts_held=768)),  # half of them
], ids=["absent", "parent", "idle", "partial"])
def test_yields_nothing_where_there_is_nothing_to_read(before, after, capsys):
    assert step_experts_read_share.read(ctx(before, after)) is None
    assert step_experts_read_share.read({"model": "ling3_ep8"}) is None
    assert capsys.readouterr().out == ""


def test_no_expert_chosen_reads_zero():
    before = snapshot(lm_step_experts_chosen=0, lm_step_experts_held=0)
    after = snapshot(lm_step_experts_chosen=0, lm_step_experts_held=10 * 40)
    assert step_experts_read_share.read(ctx(before, after)) == 0.0
