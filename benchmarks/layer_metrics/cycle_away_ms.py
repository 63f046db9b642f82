"""Mean ms, over the gaps before launches of session steps or blocks, of
the phase ``away`` of the cycle, ``[e3, e4]``: every answer is out and
no request is in: gRPC both ways and the callers (client + transport).
``_cycle.py`` has the arithmetic and the log line; nothing on a program
whose requests carry no ``session`` or no ``front``."""

from ._cycle import read as _read


def read(ctx):
    return _read(ctx, "away")
