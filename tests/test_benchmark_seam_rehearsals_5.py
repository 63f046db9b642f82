"""One shard of the benchmark's ``tests/test_rehearsal.py`` (every
configuration's output check on a dozen seeds at rehearsal sizes, its
controls, each cell through the real server): which shard, of how many,
and why, ``test_benchmark_seam.py`` says."""

from __future__ import annotations

from test_benchmark_seam import adopt_shard

ADOPTED, SHARD = adopt_shard(globals())


def test_this_shard_is_one_of_several():
    k, n = SHARD
    assert 0 <= k < n and n >= 2 and len(ADOPTED) >= 5
