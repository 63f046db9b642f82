"""Mean ms, over the gaps before launches of session steps or blocks, of
the phase ``handback`` of the cycle, ``[a, e2]``: the launch before is
off the device, its answers are copied to the host, split and handed to
their futures (staged channel, batcher). ``_cycle.py`` has the
arithmetic and the log line; nothing on a program whose requests carry
no ``session`` or no ``front``."""

from ._cycle import read as _read


def read(ctx):
    return _read(ctx, "handback")
