"""Items (frames, scans) per device launch over the window, from the
batcher's ``merge_occupancy`` counter (items merged -> launches) before
and after. A scan counts as one item."""

from ._spans import counter_delta


def read(ctx):
    after, before = counter_delta(ctx, "batching", "merge_occupancy")
    launches = rows = 0
    for size, count in after.items():
        n = count - before.get(size, 0)
        launches += n
        rows += n * int(size)
    return rows / launches if launches else None
