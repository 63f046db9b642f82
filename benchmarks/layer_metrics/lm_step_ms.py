"""Device time of one step launch: the ``..._lm_step`` modules of the
profiler trace over their count."""

from ._sessions import kind_rows


def read(ctx):
    count, seconds = kind_rows(ctx, "lm_step")
    return 1e3 * seconds / count if count else None
