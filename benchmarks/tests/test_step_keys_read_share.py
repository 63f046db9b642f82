"""``step_keys_read_share`` on two hand-made snapshots: the two
counters' growth over the window, in per cent; nothing from a program
that lacks the counters (the parent of the PR that brought them), from
one whose step launch does not read by blocks (the counters stay 0) and
from a window without a step launch."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.layer_metrics import step_keys_read_share  # noqa: E402

WHOLE = 8 * 62_720 * 2  # a launch of eight rows, two layers that attend


def snapshot(**counters):
    return {"sessions": {"models": {"ling3_ep8": counters}}}


def ctx(before, after):
    return {"model": "ling3_ep8", "snapshot_before": before, "snapshot_after": after}


def test_reads_the_growth_between_two_snapshots(capsys):
    before = snapshot(lm_step_launches=80, lm_step_keys_fetched=9_000_000, lm_step_keys_whole=80 * WHOLE)
    after = snapshot(lm_step_launches=300, lm_step_keys_fetched=9_000_000 + 41_395_200, lm_step_keys_whole=300 * WHOLE)
    assert step_keys_read_share.read(ctx(before, after)) == pytest.approx(100 * 41_395_200 / (220 * WHOLE))
    assert json.loads(capsys.readouterr().out)["step_keys"] == {
        "lm_step_keys_fetched": 41_395_200, "lm_step_keys_whole": 220 * WHOLE}


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # no session plane at all
    (snapshot(lm_step_launches=80), snapshot(lm_step_launches=300)),  # a program without the counters
    (snapshot(lm_step_launches=80, lm_step_keys_fetched=0, lm_step_keys_whole=0),
     snapshot(lm_step_launches=300, lm_step_keys_fetched=0, lm_step_keys_whole=0)),  # a model that does not read by blocks
    (snapshot(lm_step_keys_fetched=6_272, lm_step_keys_whole=WHOLE),
     snapshot(lm_step_keys_fetched=6_272, lm_step_keys_whole=WHOLE)),  # no step launch in the window
    ({}, snapshot(lm_step_keys_fetched=6_272, lm_step_keys_whole=WHOLE)),  # half of them
], ids=["absent", "parent", "unblocked", "idle", "partial"])
def test_yields_nothing_where_there_is_nothing_to_read(before, after, capsys):
    assert step_keys_read_share.read(ctx(before, after)) is None
    assert step_keys_read_share.read({"model": "ling3_ep8"}) is None
    assert capsys.readouterr().out == ""


def test_a_slot_of_one_block_reads_a_hundred():
    before = snapshot(lm_step_keys_fetched=0, lm_step_keys_whole=0)
    after = snapshot(lm_step_keys_fetched=10 * 16 * 4_352 * 6, lm_step_keys_whole=10 * 16 * 4_352 * 6)
    assert step_keys_read_share.read(ctx(before, after)) == 100.0
