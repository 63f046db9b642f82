"""Readers of the window's gauges on hand-made snapshots. The session
gauges (``session_cache_fill``, ``_sessions.mean_context``): read at
the moments ``run.py`` sampled INSIDE the window, since a window of
whole rounds begins and ends between rounds; and the sampler."""

from __future__ import annotations

import pathlib
import sys
import threading

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402
from benchmarks.layer_metrics import _sessions, session_cache_fill  # noqa: E402


def gauge(tokens, in_use):
    return {"sessions": {"models": {"m": {"session_cache_tokens": tokens, "session_cache_slots_in_use": in_use,
                                          "session_cache_slots": 40, "session_cache_slot_len": 4352}}}}


def test_session_gauges_are_read_inside_the_window_where_it_was_sampled():
    ends = {"model": "m", "snapshot_before": gauge(83_200, 32), "snapshot_after": gauge(20_800, 8)}
    assert session_cache_fill.read(ends) == pytest.approx(100 * (83_200 + 20_800) / 2 / (40 * 4352))
    assert _sessions.mean_context(ends) == pytest.approx(2600.0)
    inside = {**ends, "snapshots_inside": [gauge(100_000, 40), gauge(110_000, 40), {"sessions": {"models": {}}}]}
    assert session_cache_fill.read(inside) == pytest.approx(100 * 105_000 / (40 * 4352))  # the ends no longer count
    assert _sessions.mean_context(inside) == pytest.approx(105_000 / 40)
    assert session_cache_fill.read({"model": "m", "snapshot_before": {}, "snapshot_after": {}}) is None
    assert _sessions.mean_context({"model": "m", "snapshot_before": gauge(0, 0), "snapshot_after": gauge(0, 0)}) is None


def test_the_sampler_asks_until_it_is_stopped(monkeypatch):
    asked = []
    monkeypatch.setattr(run, "http_json", lambda port, path: asked.append((port, path)) or {"n": len(asked)})
    into, stop = [], threading.Event()
    sampler = threading.Thread(target=run.sample_snapshots, args=(7, 0.01, stop, into))
    sampler.start()
    for _ in range(2000):  # ten seconds at most
        if len(into) >= 3:
            break
        stop.wait(0.005)
    stop.set()
    sampler.join(timeout=5.0)
    assert not sampler.is_alive() and len(into) >= 3 and into[0] == {"n": 1}
    assert set(asked) == {(7, "/snapshot")}
    seen = len(into)
    stop.wait(0.05)
    assert len(into) == seen  # nothing after the stop
