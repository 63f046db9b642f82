"""Device time of the ``..._lm_prefill`` modules of the profiler trace
over the tokens they appended: their count times the window's mean
tokens a prefill launch (counters ``lm_tokens_prefill`` over
``lm_prefill_launches``; the trace is a part of the window and its
launches draw from the same ladder)."""

from ._sessions import delta, kind_rows


def tokens_per_launch(ctx):
    tokens, launches = delta(ctx, "lm_tokens_prefill"), delta(ctx, "lm_prefill_launches")
    return tokens / launches if launches else None


def read(ctx):
    count, seconds = kind_rows(ctx, "lm_prefill")
    per_launch = tokens_per_launch(ctx)
    return 1e6 * seconds / (count * per_launch) if count and per_launch else None
