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


def mean_context(ctx: dict) -> float | None:
    """Mean positions a live session holds, from the gauges at the
    window's two ends."""
    ends = [s for s in (stats(ctx, "snapshot_before"), stats(ctx)) if s and s.get("session_cache_slots_in_use")]
    if not ends:
        return None
    return sum(s["session_cache_tokens"] / s["session_cache_slots_in_use"] for s in ends) / len(ends)
