"""Block rows a committed token over the window: counter
``lm_block_rows`` (one a session a block launch, denoising and
committing alike) over ``lm_tokens_committed``. 0.75 under a schedule of
two denoising passes and a commit a block of four; it moves where a pass
is saved (a commit folded into the next block's first pass), not with
how many sessions a launch carries. A program without the counters
yields nothing."""

from ._sessions import delta


def read(ctx):
    rows, tokens = delta(ctx, "lm_block_rows"), delta(ctx, "lm_tokens_committed")
    return rows / tokens if rows is not None and tokens else None
