"""``handler_cpu_us`` on two hand-made snapshots: the counters' growth
over the window, microseconds a request; nothing from a program that
lacks the counters (the parent of the PR that brought them), and
nothing from a window in which no request was handled."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.layer_metrics import handler_cpu_us  # noqa: E402


def front(cpu_s, requests, hits=None, misses=None):
    counters = {"handler_cpu_s": cpu_s, "handler_requests": requests}
    if hits is not None:
        counters.update(front_memo_hits=hits, front_memo_misses=misses)
    return {"front_end": counters}


def test_reads_the_growth_between_two_snapshots(capsys):
    ctx = {"snapshot_before": front(1.5, 10_000, 9_000, 1_000), "snapshot_after": front(3.9704, 22_352, 21_160, 1_192)}
    assert handler_cpu_us.read(ctx) == pytest.approx(1e6 * 2.4704 / 12_352)
    logged = json.loads(capsys.readouterr().out)["front_end"]
    assert logged["handler_requests"] == 12_352 and logged["front_memo_misses"] == 192
    assert logged["hit_share"] == pytest.approx(12_160 / 12_352)


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # a program without the counters
    ({"front_end": None}, {"front_end": None}),
    (front(1.0, 5), front(1.0, 5)),  # no request in the window
    ({}, {"front_end": {"handler_requests": 5}}),  # half of them
], ids=["absent", "none", "idle", "partial"])
def test_yields_nothing_where_there_is_nothing_to_read(before, after):
    assert handler_cpu_us.read({"snapshot_before": before, "snapshot_after": after}) is None
    assert handler_cpu_us.read({}) is None


def test_the_counter_alone_reads_without_a_hit_share(capsys):
    """The parent with the two counters laid over it, and no memo."""
    ctx = {"snapshot_before": {}, "snapshot_after": front(0.5, 1_000)}
    assert handler_cpu_us.read(ctx) == pytest.approx(500.0)
    assert json.loads(capsys.readouterr().out)["front_end"]["hit_share"] is None
