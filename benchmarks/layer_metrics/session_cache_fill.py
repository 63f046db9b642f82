"""Share of the cache pool's positions that live sessions hold, in
percent: gauge ``session_cache_tokens`` over slots x slot length, the
mean over the moments sampled inside the window (``_sessions.gauges``)."""

from ._sessions import gauges


def read(ctx):
    seen = gauges(ctx)
    if not seen:
        return None
    return 100.0 * sum(s["session_cache_tokens"] / (s["session_cache_slots"] * s["session_cache_slot_len"])
                       for s in seen) / len(seen)
