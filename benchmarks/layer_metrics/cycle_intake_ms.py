"""Mean ms, over the gaps before launches of session steps or blocks, of
the phase ``intake`` of the cycle, ``[e4, e5]``: the waiting launch's
requests come through the handlers one by one (front end). ``_cycle.py``
has the arithmetic and the log line; nothing on a program whose requests
carry no ``session`` or no ``front``."""

from ._cycle import read as _read


def read(ctx):
    return _read(ctx, "intake")
