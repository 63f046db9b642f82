"""Mean ms, over the gaps before launches of session steps or blocks, of
the phase ``answer`` of the cycle, ``[e2, e3]``: the handlers of the
launch before wake one by one, build and account their answers (front
end). ``_cycle.py`` has the arithmetic and the log line; nothing on a
program whose requests carry no ``session`` or no ``front``."""

from ._cycle import read as _read


def read(ctx):
    return _read(ctx, "answer")
