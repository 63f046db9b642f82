"""Of the cached positions the window's appended tokens could attend to,
the share their layers' windows leave them to read, in per cent: counter
``lm_keys_read`` over ``lm_keys_visible`` (a token at position p: ``p +
1`` a layer visible; ``min(p + 1, window)`` read in a window layer, ``p
+ 1`` in a full one: runtime/sessions.py from the model's row
geometries). Host arithmetic on what admission knows: what a program
that masks by position NEED read, not what its launches fetched. 100
means no session outgrew its window. A program without the counter (the
parent of the PR that brought it) yields nothing."""

from ._sessions import delta


def read(ctx):
    read_, visible = delta(ctx, "lm_keys_read"), delta(ctx, "lm_keys_visible")
    return 100.0 * read_ / visible if read_ is not None and visible else None
