"""Compilations the server counted during the window (expected 0)."""

from ._spans import counter_delta


def read(ctx):
    after, before = counter_delta(ctx, "compile", "compiles")
    return float(after - before)
