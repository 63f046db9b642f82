"""Share of the session cache's BYTES that live sessions hold, in per
cent, for a model whose layers keep rows in more than one geometry:
gauge ``session_cache_bytes_in_use`` (a live session's rows in each
geometry, a ring's at most the ring) over ``session_cache_bytes`` (what
is allocated), the mean over the moments sampled inside the window
(``_sessions.gauges``). ``session_cache_fill`` counts positions over
``slot_len`` and says nothing of a ring. A program without the gauges
(the parent of the PR that brought them) yields nothing."""

from ._sessions import gauges


def read(ctx):
    seen = [s for s in gauges(ctx) if s.get("session_cache_bytes")]
    if not seen:
        return None
    return 100.0 * sum(s["session_cache_bytes_in_use"] / s["session_cache_bytes"] for s in seen) / len(seen)
