"""The other half of ``test_benchmark_seam.py``: the benchmark's
``tests/test_rehearsal.py`` (every configuration's output check on a
dozen seeds at rehearsal sizes, its control, each cell through the real
server), collected here so that a second worker runs it."""

from __future__ import annotations

from test_benchmark_seam import SPLIT_OFF, adopt

ADOPTED = adopt(globals(), only=SPLIT_OFF)


def test_the_rehearsals_are_here():
    assert {name.split("__")[0] for name in ADOPTED} == set(SPLIT_OFF) and len(ADOPTED) >= 5
