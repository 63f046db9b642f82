"""Sessions a block launch over the window: counter ``lm_block_rows``
(sum of sessions over block launches) over ``lm_block_launches``
(``/snapshot`` -> ``sessions.models.<model>``). A program without the
counters yields nothing."""

from ._sessions import delta


def read(ctx):
    rows, launches = delta(ctx, "lm_block_rows"), delta(ctx, "lm_block_launches")
    return rows / launches if rows is not None and launches else None
