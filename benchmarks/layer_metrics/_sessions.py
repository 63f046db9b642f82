"""Helpers shared by the readers of a token model's session counters
(``/snapshot`` -> ``sessions.models.<model>``: runtime/sessions.py
``TokenSessions.stats``) and of its two launch kinds in the device
trace (modules ``jit_mdl_<model>_<version>_lm_step`` and ``_lm_prefill``).
A program without them (the parent of the PR that brought them) yields
nothing, and every reader then reports nothing."""

from __future__ import annotations


def stats(ctx: dict, which: str = "snapshot_after") -> dict | None:
    return ((ctx.get(which) or {}).get("sessions") or {}).get("models", {}).get(ctx["model"])


def delta(ctx: dict, name: str):
    """A counter's growth over the window, or None."""
    before, after = stats(ctx, "snapshot_before"), stats(ctx)
    if before is None or after is None or name not in after:
        return None
    return after[name] - before.get(name, 0)


def kind_rows(ctx: dict, kind: str) -> tuple[int, float]:
    """(launches, device seconds) of the model's ``kind`` modules in the trace."""
    launches = (ctx.get("profile") or {}).get("launches", {})
    rows = [v for k, v in launches.items() if f"mdl_{ctx['model']}_" in k and k.endswith(kind)]
    return sum(r["count"] for r in rows), sum(r["device_s"] for r in rows)


def gauges(ctx: dict) -> list[dict]:
    """The model's session gauges at the moments INSIDE the window that
    ``run.py`` sampled (every two seconds of a traced window), else at
    the window's two ends: a window of whole rounds begins and ends
    between rounds, where the pool holds what the warm-up left."""
    inside = [((s.get("sessions") or {}).get("models") or {}).get(ctx["model"]) for s in ctx.get("snapshots_inside") or []]
    return [s for s in inside if s] or [s for s in (stats(ctx, "snapshot_before"), stats(ctx)) if s]


def mean_context(ctx: dict) -> float | None:
    """Mean positions a live session holds, over ``gauges``."""
    live = [s for s in gauges(ctx) if s.get("session_cache_slots_in_use")]
    if not live:
        return None
    return sum(s["session_cache_tokens"] / s["session_cache_slots_in_use"] for s in live) / len(live)
