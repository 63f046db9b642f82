"""Share of the cache pool's positions that live sessions hold, in
percent: gauge ``session_cache_tokens`` over slots x slot length, the
mean of the window's two ends."""

from ._sessions import stats


def read(ctx):
    ends = [s for s in (stats(ctx, "snapshot_before"), stats(ctx)) if s]
    if not ends:
        return None
    return 100.0 * sum(s["session_cache_tokens"] / (s["session_cache_slots"] * s["session_cache_slot_len"])
                       for s in ends) / len(ends)
