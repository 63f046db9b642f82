"""Of the cached positions the window's appended tokens could attend to,
the share they read, in percent: counter ``lm_keys_selected`` over
``lm_keys_visible`` (a token at position p, in every layer: ``min(p + 1,
index_topk)`` over ``p + 1``). 100 means the selection never engaged. A
program without the counters yields nothing."""

from ._sessions import delta


def read(ctx):
    selected, visible = delta(ctx, "lm_keys_selected"), delta(ctx, "lm_keys_visible")
    return 100.0 * selected / visible if visible else None
