"""The two readers of a model whose layers keep rows in two geometries
(``window_keys_share``, ``session_cache_bytes_fill``) and its operation
count (``ops_bytes/smallthinker.py``): what they read from a snapshot,
that a program without the counters (the parent of the PR that brought
them) yields nothing, and the count against a position-by-position sum."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.layer_metrics import session_cache_bytes_fill, window_keys_share  # noqa: E402
from benchmarks.ops_bytes import smallthinker as ops  # noqa: E402

CFG = json.loads((ROOT / "benchmarks/configs/smallthinker21b-ep1-l12.json").read_text())


def _ctx(before: dict | None, after: dict | None, inside=()):
    snap = lambda stats: {"sessions": {"models": {"m": stats}}} if stats is not None else {}
    return {"model": "m", "snapshot_before": snap(before), "snapshot_after": snap(after),
            "snapshots_inside": [snap(s) for s in inside]}


def test_window_keys_share_is_the_counters_growth_over_the_window():
    ctx = _ctx({"lm_keys_read": 100, "lm_keys_visible": 200}, {"lm_keys_read": 756, "lm_keys_visible": 1200})
    assert window_keys_share.read(ctx) == pytest.approx(65.6)


@pytest.mark.parametrize("reader", [window_keys_share, session_cache_bytes_fill])
def test_a_program_without_the_counters_yields_nothing(reader):
    parent = {"lm_keys_visible": 10, "lm_keys_selected": 10, "session_cache_tokens": 5, "session_cache_slots": 8}
    assert reader.read(_ctx(parent, {**parent, "lm_keys_visible": 90})) is None
    assert reader.read(_ctx(None, None)) is None


def test_session_cache_bytes_fill_is_the_mean_over_the_moments_inside_the_window():
    gauge = lambda used: {"session_cache_bytes": 1000, "session_cache_bytes_in_use": used}
    assert session_cache_bytes_fill.read(_ctx(gauge(0), gauge(0), [gauge(200), gauge(400)])) == pytest.approx(30.0)
    assert session_cache_bytes_fill.read(_ctx(gauge(100), gauge(300))) == pytest.approx(20.0)
    assert session_cache_bytes_fill.read(_ctx({"session_cache_bytes": 0}, {"session_cache_bytes": 0})) is None


@pytest.mark.parametrize("tokens, context", [(1024, 0), (2048, 1024), (2048, 3072), (2048, 13312), (100, 4050)])
def test_a_window_layers_pairs_against_the_sum_over_positions(tokens, context):
    window = CFG["model"]["sliding_window_size"]
    want = sum(min(p + 1, window) for p in range(context, context + tokens))
    assert ops._window_pairs(tokens, context, window) == pytest.approx(want)


def test_the_counts_at_this_cells_sizes():
    """A step launch's least bytes are its experts (35 of 64 a layer at 8
    sessions), the head once and the keys a token may see; an extend's
    operations grow with the context in the full layers alone once the
    window is passed."""
    step = ops.count_step(CFG, 8, 5600)
    assert step["experts_touched"] == pytest.approx(64 * (1 - (1 - 6 / 64) ** 8))
    assert 7.0e9 < step["bytes"] < 7.3e9
    near, far = ops.count_extend(CFG, 2048, 5120)["flops"], ops.count_extend(CFG, 2048, 13312)["flops"]
    full_growth = 3 * 2 * 28 * 2048 * (13312 - 5120) * 2 * 128
    assert far - near == pytest.approx(full_growth)
    assert ops.count_prefill is ops.count_extend
