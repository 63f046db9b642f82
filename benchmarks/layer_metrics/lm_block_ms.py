"""Device time of one block launch (one block of each of its sessions,
denoising and committing rows mixed): the ``..._lm_block`` modules of
the profiler trace over their count. A program without the launch kind
(the parent of the PR that brought it) yields nothing."""

from ._sessions import kind_rows


def read(ctx):
    count, seconds = kind_rows(ctx, "lm_block")
    return 1e3 * seconds / count if count else None
