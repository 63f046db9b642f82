"""Mean ms, over the gaps before launches of session steps or blocks, of
the phase ``restage`` of the cycle, ``[e5, b]``: the group closes, is
held, takes a slot, is staged, transferred and launched (batcher, staged
channel). ``_cycle.py`` has the arithmetic and the log line; nothing on
a program whose requests carry no ``session`` or no ``front``."""

from ._cycle import read as _read


def read(ctx):
    return _read(ctx, "restage")
